"""Every span and counter a per-layer metric reads is one the program has.

A per-layer metric of the benchmark is a data file
(perfbench/layer_metrics/<metric>.json) that names rt_trace spans
(reader ``span_ms_per_job``) or a telemetry counter (``counter_per_job``).
Nothing ties those names to the program but this test: each span a listed
metric names must be recorded by one of three tiny runs — a dense
ChunkSource job through the engine, a blocked job run once with pass 1
host-staged and once device-resident, a dense job given a host
EncodedData of two value columns whose row count is no power of two (the
dense staging: pad, upload, its slab count) — and each counter must be declared
in telemetry.REGISTRY and, where those runs can reach it, counted by
them. A fourth tiny run, a dense job with two PERCENTILEs over more
partitions than one histogram chunk holds (the lazy descent), counts the
quantile trees and their row passes. A fifth, the dense ChunkSource job
again on a four-device mesh, once sound (the collective reshard) and once
with the `collective` fault rt_faults has (the host fallback), records
the mesh layer's two spans. A sixth, a blocked standalone selection
through `DPEngine.select_partitions`, records the spans and counters the
cell keys1e7-select-blocked reads. A rename in the program then fails
here instead of leaving a null in the ledger.
"""

import json
import os

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu.runtime import telemetry
from pipelinedp_tpu.runtime import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("span_ms_per_job", "counter_per_job")


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _listed_specs():
    """The layer-metric files BENCHMARK.json lists, of the two readers
    that name something inside the program."""
    specs = [_load("perfbench", "layer_metrics", m["name"] + ".json")
             for m in _load("BENCHMARK.json")["per_layer"]]
    return [s for s in specs if s["reader"] in READERS]


SPECS = _listed_specs()


def _dense_chunk_run(mesh=None):
    rng = np.random.default_rng(0)
    n = 8000
    pid, pk = rng.integers(0, 900, n), rng.integers(0, 300, n)
    values = rng.uniform(1, 5, n)
    chunks = [(pid[i:i + 2000], pk[i:i + 2000], values[i:i + 2000])
              for i in range(0, n, 2000)]
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=2,
        max_contributions_per_partition=1,
        min_value=1.0,
        max_value=5.0)
    ex = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                            partition_extractor=lambda r: r[1],
                            value_extractor=lambda r: r[2])
    acc = pdp.NaiveBudgetAccountant(total_epsilon=50.0, total_delta=1e-6)
    engine = pdp.DPEngine(acc, pdp.TPUBackend(mesh=mesh, noise_seed=1))
    result = engine.aggregate(pdp.ChunkSource(chunks, encode_mode="host"),
                              params, ex)
    acc.compute_budgets()
    assert dict(result)


def _meshed_chunk_runs():
    """The dense chunked job on four of the suite's virtual CPU devices
    (perfbench/tests/test_mesh_metric_sources.py's meshed_job): its rows
    are device-resident, so the sound job takes the collective reshard
    (span reshard.collective) and the one whose collective is made to
    fail degrades to the host permutation (span reshard.host, counter
    reshard_host_fallbacks)."""
    import jax
    from pipelinedp_tpu.parallel import make_mesh
    from pipelinedp_tpu.runtime import faults

    mesh = make_mesh(devices=jax.devices()[:4])
    assert mesh.devices.size == 4
    before = telemetry.snapshot()
    _dense_chunk_run(mesh)
    assert "reshard.collective" in trace.trace_summary()["spans"]
    assert "reshard.host" not in trace.trace_summary()["spans"]
    with faults.inject(faults.FaultSchedule([faults.Fault("collective")])):
        _dense_chunk_run(mesh)
    assert "reshard.host" in trace.trace_summary()["spans"]
    assert telemetry.delta(before)["reshard_host_fallbacks"] == 1


def _dense_encoded_run():
    """A host EncodedData, 2 value columns, 3,000 rows (bucket 4,096): the
    dense branch stages it (pipeline.stage_host_rows: dense.pad around the
    bucket-length device buffers, dense.upload around its one slab,
    h2d_bytes, dense_stage_slabs) and counts its columns (value_columns)."""
    from pipelinedp_tpu import columnar

    rng = np.random.default_rng(1)
    n = 3000
    encoded = columnar.encode_columns(
        rng.integers(0, 400, n), rng.integers(0, 5, n),
        rng.uniform(0, 5, (n, 2)), public_partitions=list(range(6)))
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT],
        max_partitions_contributed=3,
        max_contributions_per_partition=2,
        value_columns=[
            pdp.ValueColumn("a", 0.0, 4.0, [pdp.Metrics.SUM]),
            pdp.ValueColumn("b", 1.0, 5.0, [pdp.Metrics.MEAN])])
    acc = pdp.NaiveBudgetAccountant(total_epsilon=50.0, total_delta=0.0)
    engine = pdp.DPEngine(acc, pdp.TPUBackend(noise_seed=3))
    result = engine.aggregate(encoded, params, pdp.DataExtractors(),
                              public_partitions=list(range(6)))
    acc.compute_budgets()
    assert len(dict(result)) == 6


def _dense_percentile_run():
    """Two PERCENTILEs over 600 partitions, more than the 512 whose leaves
    one dense histogram chunk holds: the lazy descent, so
    quantile_row_passes counts its one sort, quantile_node_searches
    2 quantiles x 4 levels x 600 x 15 and quantile_trees the 600."""
    from pipelinedp_tpu import columnar

    rng = np.random.default_rng(4)
    n = 4000
    encoded = columnar.encode_columns(
        rng.integers(0, 500, n), rng.integers(0, 600, n),
        rng.integers(1, 6, n).astype(np.float64))
    assert encoded.n_partitions > 512
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.PERCENTILE(50),
                 pdp.Metrics.PERCENTILE(90)],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=2,
        max_contributions_per_partition=1,
        min_value=1.0,
        max_value=5.0)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=50.0, total_delta=1e-3)
    engine = pdp.DPEngine(acc, pdp.TPUBackend(noise_seed=5))
    before = telemetry.snapshot()
    result = engine.aggregate(encoded, params, pdp.DataExtractors())
    acc.compute_budgets()
    assert dict(result)
    counted = telemetry.delta(before)
    assert counted["quantile_row_passes"] == 1
    assert counted["quantile_node_searches"] == (
        2 * 4 * encoded.n_partitions * 15)
    assert counted["quantile_trees"] == encoded.n_partitions


def _blocked_run(row_chunk):
    """row_chunk below the 3,000 rows: the host-staged pass 1; None: the
    device's own budget, which holds them."""
    import jax
    from pipelinedp_tpu import combiners, executor
    from pipelinedp_tpu.aggregate_params import MechanismType
    from pipelinedp_tpu.ops import selection_ops
    from pipelinedp_tpu.parallel import large_p

    P, n = 1 << 11, 3000
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=2,
        max_contributions_per_partition=3,
        min_value=0.0,
        max_value=5.0)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=100.0, total_delta=1e-6)
    compound = combiners.create_compound_combiner(params, acc)
    budget = acc.request_budget(MechanismType.GENERIC)
    acc.compute_budgets()
    selection = selection_ops.selection_params_from_host(
        params.partition_selection_strategy, budget.eps, budget.delta,
        params.max_partitions_contributed, None)
    cfg = executor.make_kernel_config(params, compound, P,
                                      private_selection=True,
                                      selection_params=selection)
    rng = np.random.default_rng(2)
    large_p.aggregate_blocked(
        rng.integers(0, 150, n).astype(np.int32),
        rng.integers(0, P, n).astype(np.int32), rng.uniform(0, 5, n),
        np.ones(n, bool), *executor.kernel_scalars(params),
        np.asarray(executor.compute_noise_stds(compound, params)),
        jax.random.PRNGKey(5), cfg, block_partitions=1 << 9,
        row_chunk=row_chunk)


def _blocked_select_run():
    """`DPEngine.select_partitions` over 2,048 pre-encoded keys with a
    threshold of 512: the blocked selection route in four blocks of 512.
    What a job of the cell keys1e7-select-blocked records, it records."""
    from pipelinedp_tpu import columnar

    rng = np.random.default_rng(6)
    P, n, users = 1 << 11, 6000, 300
    encoded = columnar.EncodedData(
        pid=rng.integers(0, users, n).astype(np.int32),
        pk=(P * rng.random(n)**3).astype(np.int32),
        values=np.zeros(n, np.float32), partition_vocab=range(P),
        n_privacy_ids=users)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=20.0, total_delta=1e-3)
    engine = pdp.DPEngine(acc, pdp.TPUBackend(
        noise_seed=7, large_partition_threshold=1 << 9,
        block_partitions=1 << 9))
    spans_before = {name: row["count"] for name, row in
                    trace.trace_summary()["spans"].items()}
    before = telemetry.snapshot()
    kept = engine.select_partitions(
        encoded, pdp.SelectPartitionsParams(max_partitions_contributed=4),
        pdp.DataExtractors())
    acc.compute_budgets()
    assert list(kept)
    opened = {name for name, row in trace.trace_summary()["spans"].items()
              if row["count"] > spans_before.get(name, 0)}
    assert {"graph_build", "select_partitions", "contribution_bounding",
            "p1.pad", "p1.upload", "block_offsets", "dispatch",
            "release_wait", "drain", "consume", "p2.wait",
            "post_process"} <= opened, sorted(opened)
    counted = telemetry.delta(before)
    assert 0 < counted["selection_pairs"] <= 4 * users
    assert counted["selection_block_rows"] >= counted["selection_pairs"]
    assert counted["h2d_bytes"] >= n * 9  # pid, pk, valid of every row
    assert counted["d2h_bytes"] > 0
    assert counted["release_dispatches"] == 4 + 1  # four blocks, one drain


# Counters no tiny run is sure to move: a program already built in this
# process fires no compile event.
UNREACHED_COUNTERS = {"backend_compiles"}


@pytest.fixture(scope="module")
def recorded():
    """Names of the spans and counters the tiny runs recorded."""
    telemetry.reset()
    trace.enable()
    try:
        _dense_chunk_run()
        _dense_encoded_run()
        _dense_percentile_run()
        _blocked_run(row_chunk=1000)
        _blocked_run(row_chunk=None)
        _meshed_chunk_runs()
        _blocked_select_run()
        counters = {name for name, n in telemetry.snapshot().items() if n}
        return set(trace.trace_summary()["spans"]) | counters
    finally:
        trace.disable()
        telemetry.reset()


def test_benchmark_lists_metrics_that_read_the_program():
    assert {s["reader"] for s in SPECS} == set(READERS)


@pytest.mark.parametrize("spec", SPECS, ids=[s["name"] for s in SPECS])
def test_metric_reads_what_the_program_records(spec, recorded):
    if spec["reader"] == "span_ms_per_job":
        assert spec["spans"], spec["name"]
        missing = [name for name in spec["spans"] if name not in recorded]
        assert not missing, (
            f"{spec['name']} reads rt_trace spans {missing} that no tiny "
            f"run recorded; recorded: {sorted(recorded)}")
    else:
        metric = telemetry.REGISTRY.get(spec["counter"])
        assert metric is not None and metric.kind == "counter", (
            f"{spec['name']} reads telemetry counter {spec['counter']!r}, "
            f"which telemetry.REGISTRY does not declare as a counter")
        assert (spec["counter"] in recorded or
                spec["counter"] in UNREACHED_COUNTERS), (
            f"{spec['name']} reads telemetry counter {spec['counter']!r}, "
            f"which no tiny run counted; recorded: {sorted(recorded)}")
