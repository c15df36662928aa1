"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on XLA's host-platform virtual devices (JAX_PLATFORMS=cpu, x64
on). The chip itself is exercised by chip_smoke.py, never by this suite.
"""

import _thread
import os
import threading

import pytest

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax

jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    # Also registered in pytest.ini; kept here so a stray invocation from
    # another rootdir stays warning-free. The tier-1 command runs
    # `-m 'not slow'`, so `faults` tests — the fault-injection harness
    # suite, including the hang/corrupt kinds — are part of tier-1 by
    # default and selectable alone with `-m faults`.
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "faults: fault-injection/robustness tests, including the "
        "hang/corrupt kinds (runs in tier-1; select alone with "
        "-m faults)")
    config.addinivalue_line(
        "markers",
        "hard_timeout(seconds): outer hard timeout enforced by the "
        "conftest guard — a watchdog BUG in the code under test cannot "
        "hang tier-1")
    config.addinivalue_line(
        "markers",
        "staticcheck: the AST DP-invariant analyzer gate and its "
        "fixtures (always-on tier-1, NOT slow; select alone with "
        "-m staticcheck)")
    config.addinivalue_line(
        "markers",
        "pipeline: the device-resident streaming executor (ingest "
        "thread pool, staging queue, donated accumulator) — "
        "bit-identity, backpressure and fault tests (tier-1, NOT slow; "
        "select alone with -m pipeline)")
    config.addinivalue_line(
        "markers",
        "multihost: multi-controller pod scale-out — process-topology "
        "helpers, process-scoped journals, whole-host loss, and the "
        "spawn-based 2-process jax.distributed CPU dryrun (tier-1, NOT "
        "slow; select alone with -m multihost)")
    config.addinivalue_line(
        "markers",
        "observability: the fleet observability plane — gauges, "
        "Prometheus export, memory watermarks, the privacy-budget "
        "odometer and the cross-process rollup (tier-1, NOT slow; "
        "select alone with -m observability)")
    config.addinivalue_line(
        "markers",
        "service: the resident multi-tenant DP-aggregation service — "
        "concurrent tenants over one backend, persisted tenant budget "
        "ledgers, admission control/load shedding, cross-job "
        "compile-cache reuse (tier-1, NOT slow; select alone with "
        "-m service)")
    config.addinivalue_line(
        "markers",
        "aot: the single-dispatch warm path — AOT executable cache, "
        "fused release kernels, compute/drain overlap: bit-identity, "
        "cache-key correctness, per-job retrace attribution (tier-1, "
        "NOT slow; select alone with -m aot)")
    config.addinivalue_line(
        "markers",
        "batching: megabatched serving — the coalescing tier that runs "
        "identical-spec concurrent jobs as lanes of one vmapped release "
        "launch: per-lane bit-identity vs solo, fallthrough/fallback "
        "paths, ledger reconciliation, launch-count collapse (tier-1, "
        "NOT slow; select alone with -m batching)")
    config.addinivalue_line(
        "markers",
        "fleet: fleet operations — elastic scale-UP, journal-based "
        "job migration, and the zero-loss rolling-restart drill "
        "(tier-1, NOT slow; select alone with -m fleet)")
    config.addinivalue_line(
        "markers",
        "chaos: randomized composed-fault campaigns — seeded schedule "
        "generation, the universal invariant checker (exactly-once "
        "jobs, bit-exact ledgers, bit-identical results), "
        "storage-fault hardening and the delta-debugging schedule "
        "minimizer (tier-1, NOT slow; select alone with -m chaos)")
    config.addinivalue_line(
        "markers",
        "numeric_armor: overflow-safe accumulation, the fail-closed "
        "release sentinel, discrete/snapped noise and the "
        "extreme_values fault kind (tier-1, NOT slow; select alone "
        "with -m numeric_armor)")
    config.addinivalue_line(
        "markers",
        "pld: the PLD fast-composition engine and dual-spend admission "
        "— batched-FFT vs pairwise parity, closed-form/golden "
        "accounting checks, the query fast path, the spectrum cache "
        "and the tenant capacity multiplier (tier-1, NOT slow; select "
        "alone with -m pld)")


@pytest.fixture(autouse=True)
def _hard_timeout_guard(request):
    """Outer safety net for the watchdog/hang tests: if a test marked
    hard_timeout runs past its limit (i.e. the deadline machinery under
    test failed to cancel an injected hang), interrupt the main thread so
    the test FAILS instead of wedging the whole tier-1 run. The injected
    hang hooks sleep in small increments, so KeyboardInterrupt lands
    promptly."""
    marker = request.node.get_closest_marker("hard_timeout")
    if marker is None:
        yield
        return
    limit = float(marker.args[0]) if marker.args else 120.0
    timer = threading.Timer(limit, _thread.interrupt_main)
    timer.daemon = True
    timer.start()
    try:
        yield
    finally:
        timer.cancel()
