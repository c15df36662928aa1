"""Device-mesh helpers.

The framework's parallelism model (SURVEY.md §2.5): rows are data-sharded by
privacy-unit hash over a 1-D mesh axis "shards"; per-partition partial
accumulators are combined with lax.psum over ICI. DCN-reachable multi-host
meshes work the same way — jax.devices() spans all hosts under jax.distributed,
and make_mesh over that global list is the multi-controller entry point:
every process runs the same driver code over the same mesh, each owning only
its locally-addressable slice of the row data. The process-topology helpers
(process_index / process_count / is_fully_addressable / local_devices) are
what the runtime layers key per-process state on (journal file names, health
snapshots, the evacuation decision after a whole-host loss), and
initialize_distributed is the one place the jax.distributed bring-up (with
the CPU gloo collectives the 2-process dryrun rides) is spelled.

This module also owns the shape/padding arithmetic shared by every meshed
stage (round_capacity, per-shard capacities) and the two seams the
collective-reshard transfer discipline rests on:

  * shard_map: jax.shard_map with replication checking off, the one
    spelling every meshed kernel in the package uses.
  * host_fetch: the ONE sanctioned device->host fetch for small control
    tables (O(D^2) reshard counts, O(n_blocks) block offsets — never
    O(rows)). Routing all control-plane fetches through it lets the
    transfer-guard test (tests/test_reshard.py) forbid every other
    device->host materialization and so prove device-resident rows never
    stage through the host.
"""

import contextlib
import logging
import os
import random
import threading
import time
from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec

SHARD_AXIS = "shards"


def make_mesh(devices: Optional[Sequence] = None,
              n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the given (or all) devices, axis name "shards".

    Under jax.distributed (initialize_distributed), jax.devices() is the
    GLOBAL device list spanning every process, so the default mesh of a
    multi-controller job is already the pod-wide mesh: the same sharded
    drivers run unchanged, each process addressing only its local slice.
    """
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (SHARD_AXIS,))  # staticcheck: disable=host-transfer — O(D) device HANDLES at mesh build, not array data


def process_index() -> int:
    """This controller's process index (0 on a single-process mesh)."""
    return int(jax.process_index())


def process_count() -> int:
    """Number of controller processes in the job (1 unless
    jax.distributed is initialized)."""
    return int(jax.process_count())


def device_process(device) -> int:
    """Owning process of a device (0 for objects without the attribute —
    test fakes and single-process CPU devices alike)."""
    return int(getattr(device, "process_index", 0))


def local_devices(mesh: Mesh) -> List:
    """The mesh devices this process can address, in mesh order."""
    me = process_index()
    return [d for d in mesh.devices.flat if device_process(d) == me]


def is_fully_addressable(mesh: Mesh) -> bool:
    """Whether every mesh device belongs to this process (i.e. the mesh
    is single-controller). Multi-controller meshes flip the runtime into
    per-process coordination: journal records gain a process suffix, the
    reshard count exchange stays on device, and a whole-host loss can
    evacuate this controller (runtime/retry.HostEvacuatedError)."""
    return len(local_devices(mesh)) == mesh.devices.size


def mesh_processes(mesh: Mesh) -> List[int]:
    """Sorted process indices participating in the mesh."""
    return sorted({device_process(d) for d in mesh.devices.flat})


def cross_process_fraction(mesh: Mesh) -> float:
    """Fraction of ordered shard pairs whose all_to_all traffic crosses
    processes (DCN rather than ICI) — the geometry factor bench receipts
    multiply into exchange byte counts to estimate cross-host volume."""
    devs = list(mesh.devices.flat)
    d = len(devs)
    if d <= 1:
        return 0.0
    pairs = sum(1 for a in devs for b in devs
                if device_process(a) != device_process(b))
    return pairs / float(d * (d - 1))


def initialize_distributed(coordinator_address: str,
                           num_processes: int,
                           process_id: Optional[int] = None) -> None:
    """Brings up the multi-controller runtime (idempotent).

    Wraps jax.distributed.initialize with the one platform quirk the CPU
    dryrun needs spelled out: the CPU backend's cross-process collectives
    ride the gloo implementation, which must be selected BEFORE the
    backend initializes. process_id=None falls back to the
    JAX_PROCESS_INDEX environment variable (set by the 2-process spawn
    helper) or cluster auto-detection.
    """
    if jax.distributed.is_initialized():
        return  # a re-init would raise — NB: not jax.process_count(),
        # which would initialize the backend as a side effect and make
        # the real initialize below illegal.
    if process_id is None:
        env = os.environ.get("JAX_PROCESS_INDEX")
        process_id = int(env) if env is not None else None
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=int(num_processes),
                               process_id=process_id)


def collective_heartbeat(devices: Sequence) -> set:
    """Default remote-liveness oracle of probe_live_devices: one tiny
    replicated psum over a mesh of the candidate devices. Every surviving
    controller reaches the probe at the same point of the same failure
    (they all observed the same device-fatal dispatch), so the collective
    completes iff the candidate set is live end to end; any failure means
    remote liveness cannot be established and the probe falls back to the
    locally-provable subset."""
    import jax.numpy as jnp
    mesh = make_mesh(devices=list(devices))
    ones = jax.device_put(
        np.ones((len(devices),), np.int32),
        NamedSharding(mesh, PartitionSpec(SHARD_AXIS)))

    def per_shard(x):
        return jax.lax.psum(jnp.sum(x, dtype=x.dtype), SHARD_AXIS)

    fn = shard_map(per_shard, mesh=mesh,
                   in_specs=PartitionSpec(SHARD_AXIS),
                   out_specs=PartitionSpec())
    total = int(host_fetch(fn(ones), max_retries=0))
    if total != len(devices):
        raise RuntimeError(
            f"heartbeat psum returned {total}, expected {len(devices)}")
    return set(devices)


def probe_live_devices(devices: Sequence, heartbeat=None) -> List:
    """Liveness probe backing elastic mesh degradation
    (runtime/retry.run_with_mesh_degradation): which of `devices` can
    still be trusted to carry a rebuilt mesh.

    Locally-addressable devices get the direct proof — a trivial
    put-and-fetch scalar round trip (a dead chip fails it with a runtime
    error). Devices owned by ANOTHER process cannot be probed that way
    (device_put to a non-addressable device is not a thing), so remote
    liveness is learned indirectly: an active fault-injection schedule is
    authoritative (CPU test devices never really die — injected losses,
    including whole-host losses, are exactly what it tracks), and
    otherwise a collective heartbeat over the candidate set
    (collective_heartbeat, injectable for tests) must complete; if it
    cannot, every remote device is conservatively treated as lost and
    the mesh rebuilds over the locally-provable survivors.

    Returns the live devices in their original order, so the rebuilt
    mesh keeps a stable device ordering across shrinks.
    """
    from pipelinedp_tpu.runtime import faults as rt_faults
    lost_ids = rt_faults.injected_lost_device_ids(devices)
    me = process_index()
    remote = [d for d in devices if device_process(d) != me]
    remote_live = set()
    if remote:
        candidates = [d for d in remote
                      if getattr(d, "id", None) not in lost_ids]
        if rt_faults.active() is not None:
            # The schedule is the oracle: whatever it has not marked lost
            # is alive (the dryrun's simulated hosts cannot really die).
            remote_live = set(candidates)
        elif candidates:
            hb = heartbeat if heartbeat is not None else collective_heartbeat
            try:
                remote_live = set(hb(list(devices))) & set(candidates)
            except Exception as e:  # noqa: BLE001 - any heartbeat failure = remote liveness unprovable
                logging.warning(
                    "liveness probe: collective heartbeat over %d devices "
                    "failed (%s: %s) — remote liveness cannot be "
                    "established, treating all %d non-addressable devices "
                    "as lost.", len(devices), type(e).__name__,
                    str(e).splitlines()[0][:160], len(remote))
                remote_live = set()
    live = []
    for d in devices:
        if getattr(d, "id", None) in lost_ids:
            logging.warning(
                "liveness probe: device %s marked lost by the active "
                "fault schedule.", d)
            continue
        if device_process(d) != me:
            if d in remote_live:
                live.append(d)
            continue
        try:
            # max_retries=0: the probe must answer fast — a chip that
            # cannot ack one scalar round trip without retries is not a
            # chip to rebuild the mesh on.
            host_fetch(jax.device_put(np.zeros((1,), np.int32), d),
                       max_retries=0)
        except Exception as e:  # noqa: BLE001 - any failure = dead chip
            logging.warning(
                "liveness probe: device %s failed its probe round trip "
                "(%s: %s) — treating it as lost.", d,
                type(e).__name__, str(e).splitlines()[0][:160])
            continue
        live.append(d)
    return live


def join_candidates(mesh: Mesh, devices: Optional[Sequence] = None,
                    n_devices: Optional[int] = None) -> List:
    """Devices eligible to JOIN `mesh` in an elastic scale-UP.

    Resolves a join announcement (runtime/retry.announce_join) against
    the live mesh: either an explicit device list (devices already in
    the mesh are dropped — re-admitting them is a no-op), or a TARGET
    total of `n_devices`, filled from jax.devices() in enumeration order
    (the stable order every controller of a pod agrees on, so all of
    them resolve the same candidate set from the same announcement).
    Candidates are only nominated here; the elastic runtime still
    probes them (probe_live_devices) before rebuilding the mesh.
    """
    current = {getattr(d, "id", d) for d in mesh.devices.flat}
    if devices is not None:
        return [d for d in devices if getattr(d, "id", d) not in current]
    if n_devices is None:
        return []
    out = []
    for d in jax.devices():
        if len(current) + len(out) >= int(n_devices):
            break
        if getattr(d, "id", d) not in current:
            out.append(d)
    return out


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """jax.shard_map with replication checking off — the one spelling
    every meshed kernel in the package goes through."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def row_sharding(mesh: Mesh) -> NamedSharding:
    """The leading-axis row split every meshed kernel consumes."""
    return NamedSharding(mesh, PartitionSpec(SHARD_AXIS))


def round_capacity(x: int, min_cap: int = 8) -> int:
    """Round up keeping 4 significant bits (<= 1/16 ~ 6.25% slack, 12.5%
    worst-case just above a power of two).

    Bounds the number of distinct padded shapes (so the jit cache stays
    small) without the up-to-2x waste of next-power-of-two padding.
    """
    x = max(int(x), min_cap)
    step = 1 << max((x - 1).bit_length() - 4, 3)
    return -(-x // step) * step


def rows_per_shard(n: int, n_shards: int) -> int:
    """Padded per-shard capacity for an even leading-axis split of n rows:
    ceil(n / n_shards) rounded to a bounded-shape capacity."""
    return round_capacity(-(-max(int(n), 1) // n_shards))


# Thread-local marker read by reshard.forbid_row_fetches so the guard can
# tell a sanctioned control-table fetch from a smuggled row download.
_sanctioned_fetch = threading.local()

# Thread-local override of host_fetch's retry budget, scoped by the
# drivers' runtime entry from the backend's RetryPolicy — so the retry=
# knob governs control-plane fetches too, not just block dispatch.
_fetch_policy = threading.local()
_DEFAULT_FETCH_RETRIES = 2

# Backoff jitter source. Multi-host jobs retry control-plane fetches from
# every host at once; a pure 0.05 * 2**attempt schedule would re-collide
# all of them on the exact same instant, so each delay is scaled by an
# independent uniform [0.5, 1) draw.
_jitter = random.Random()  # staticcheck: disable=host-rng — backoff jitter only: per-process independent seeding is the POINT (de-collides multi-host retries); never touches DP noise or sampling


@contextlib.contextmanager
def fetch_retry_scope(max_retries: Optional[int]):
    """Scopes a retry budget onto every host_fetch on this thread (the
    runtime entry passes the backend RetryPolicy's max_retries; None
    leaves the default in place)."""
    if max_retries is None:
        yield
        return
    prev = getattr(_fetch_policy, "max_retries", None)
    _fetch_policy.max_retries = int(max_retries)
    try:
        yield
    finally:
        _fetch_policy.max_retries = prev


def host_fetch(arr, max_retries: Optional[int] = None) -> np.ndarray:
    """Sanctioned small device->host fetch for meshed control tables.

    Only O(D^2) / O(n_blocks) tables may cross here — never row data. The
    transfer-guard test forbids all other device->host materialization on
    the device-resident path, so any new fetch added outside this helper
    fails that test instead of silently re-introducing host staging.

    Control-table fetches are sync points, so transient runtime failures
    of asynchronously dispatched work surface here; they are
    retried a couple of times before propagating — the table is tiny, the
    re-fetch is cheap, and losing a whole blocked run to one dropped
    control-plane round trip is exactly the failure mode the runtime
    package exists to remove.

    Multi-controller discipline: on a mesh spanning processes, a control
    table is only fetchable when it is fully REPLICATED (every meshed
    kernel producing one reduces it on device — psum/all_gather — before
    it reaches here), because each process can then read its local
    replica without touching another host's memory. A sharded,
    non-addressable array is rejected up front with an actionable
    message instead of np.asarray's generic failure.
    """
    # Imported lazily: mesh is a leaf module most of the package imports.
    from pipelinedp_tpu.runtime import retry as rt_retry
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry
    from pipelinedp_tpu.runtime import trace as rt_trace
    from pipelinedp_tpu.runtime import watchdog as rt_watchdog

    # Control-table fetches are sync points the blocked drivers pass
    # through between dispatch windows: heartbeat the active watchdog so
    # health can report seconds-since-progress even between block guards.
    wd = rt_watchdog.active()
    if wd is not None:
        wd.beat("host_fetch")

    if max_retries is None:
        max_retries = getattr(_fetch_policy, "max_retries", None)
        if max_retries is None:
            max_retries = _DEFAULT_FETCH_RETRIES

    if (isinstance(arr, jax.Array) and not arr.is_fully_addressable and
            not arr.is_fully_replicated):
        raise ValueError(
            f"host_fetch of a sharded, non-addressable array (shape "
            f"{arr.shape}) on a multi-controller mesh — reduce the control "
            f"table on device (psum/all_gather to a replicated layout) so "
            f"each process reads its own replica; this process cannot "
            f"address another host's shards.")

    _sanctioned_fetch.active = True
    try:
        attempt = 0
        while True:
            try:
                # The span carries the transferred byte count so trace
                # summaries can attribute control-plane transfer volume
                # (transfer_bytes) separately from compute.
                with rt_trace.span("host_fetch") as sp:
                    out = np.asarray(arr)
                    sp.set(bytes=int(out.nbytes))
                    rt_telemetry.record("d2h_bytes", int(out.nbytes))
                    return out
            except Exception as e:  # noqa: BLE001 - classified below
                if not rt_retry.is_transient(e) or attempt >= max_retries:
                    raise
                # Spend the job-wide retry budget (threaded by the entry
                # wrapper): composed faults must not turn N cheap
                # re-fetches per seam into an unbounded storm.
                rt_retry.consume_retry_budget("host_fetch")
                # Jittered bounded backoff: the exponential cap keeps the
                # worst case at 1 s, the uniform scale decorrelates the
                # lockstep retries of N hosts re-fetching the same table.
                delay = min(0.05 * 2**attempt, 1.0) * (0.5 +
                                                       0.5 * _jitter.random())
                attempt += 1
                rt_telemetry.record("host_fetch_retries")
                logging.warning(
                    "control-table host fetch failed transiently (%s); "
                    "retry %d/%d in %.2fs", type(e).__name__, attempt,
                    max_retries, delay)
                time.sleep(delay)
    finally:
        _sanctioned_fetch.active = False
