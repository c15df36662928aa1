"""The two metric files of the "mesh" layer read what the program records.

`reshard_ms_per_job` (spans `reshard.collective` + `reshard.host`) and
`reshard_host_fallbacks_per_job` (counter `reshard_host_fallbacks`) are
files under layer_metrics/ that BENCHMARK.json does not list yet: tier-1's
`tests/test_layer_metric_sources.py` demands of every listed span and
counter that one of ITS tiny runs records it, none of those runs is meshed,
and PR 29 (a benchmark PR) may not edit it (PERF.md §7). This is the meshed
tiny run that test needs, on the suite's virtual CPU devices: a dense
ChunkSource job through the engine on a mesh, once sound (the collective
reshard) and once with the `collective` fault `rt_faults` has (the host
fallback, and its counter). The readers then read both files.
"""

import json
import os

import numpy as np
import pytest

from perfbench import readers

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spec(name):
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def meshed_job():
    import jax
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu.parallel import make_mesh

    rng = np.random.default_rng(0)
    n = 8000
    pid, pk = rng.integers(0, 900, n), rng.integers(0, 300, n)
    values = rng.uniform(1, 5, n)
    chunks = [(pid[i:i + 2000], pk[i:i + 2000], values[i:i + 2000])
              for i in range(0, n, 2000)]
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        noise_kind=pdp.NoiseKind.LAPLACE, max_partitions_contributed=2,
        max_contributions_per_partition=1, min_value=1.0, max_value=5.0)
    extractors = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                    partition_extractor=lambda r: r[1],
                                    value_extractor=lambda r: r[2])
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=50.0,
                                           total_delta=1e-6)
    mesh = make_mesh(devices=jax.devices()[:4])
    assert mesh.devices.size == 4, "conftest.py asks for 4 CPU devices"
    engine = pdp.DPEngine(accountant, pdp.TPUBackend(mesh=mesh, noise_seed=1))
    result = engine.aggregate(pdp.ChunkSource(chunks, encode_mode="host"),
                              params, extractors)
    accountant.compute_budgets()
    assert dict(result)


@pytest.fixture(scope="module")
def observed():
    """What a traced run hands the readers, over two meshed jobs."""
    from pipelinedp_tpu.runtime import faults, telemetry, trace

    before = telemetry.snapshot()
    trace.enable()
    try:
        meshed_job()
        collective_only = set(trace.trace_summary()["spans"])
        with faults.inject(faults.FaultSchedule([faults.Fault("collective")])):
            meshed_job()
        return {"jobs": 2, "spans": trace.trace_summary()["spans"],
                "counters": telemetry.delta(before),
                "collective_only": collective_only}
    finally:
        trace.disable()


def test_sound_meshed_job_takes_the_collective(observed):
    assert "reshard.collective" in observed["collective_only"]
    assert "reshard.host" not in observed["collective_only"]


def test_reshard_ms_reads_both_spans(observed):
    metric = spec("reshard_ms_per_job")
    assert sorted(metric["spans"]) == ["reshard.collective", "reshard.host"]
    assert all(name in observed["spans"] for name in metric["spans"])
    assert readers.read(metric, observed) > 0


def test_fallback_counter_reads_one_fallback_in_two_jobs(observed):
    metric = spec("reshard_host_fallbacks_per_job")
    assert readers.read(metric, observed) == 0.5
    assert readers.read(metric, dict(observed, counters={})) is None
