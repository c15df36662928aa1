"""Single-dispatch warm path coverage (PR 14).

The contracts under test:

  * **Bit-identity** — the compute/drain overlap (drainer-thread
    consume) and the AOT executable cache are OPTIMIZATIONS: every knob
    combination releases exactly the bytes the default unmeshed serial
    traced run releases, across the dense, meshed (1/4/8 devices) and
    blocked routes, with equal budget-ledger mechanism counts. (That
    the release kernels equal the dense reference forms + np.nonzero is
    tests/test_release_body.py's.)
  * **AOT cache keying** — a distinct spec or row bucket is a miss; an
    identical (spec, shape) is a hit; values never enter the key. A
    second identical-spec service job records 0 aot_cache_misses on
    ITS OWN health record (the cross-tenant zero-retrace proof).
  * **Journal semantics under overlap** — a journaled run consumed on
    the drainer thread writes the same record keys as the serial
    consume loop, and a resume replays them bit-identically.
  * **Async-drain symmetry** — the journaled blocked/sharded consume
    paths run under reshard.forbid_row_fetches: the batched
    copy_to_host_async drain transfers O(kept), never rows.
"""

import numpy as np
import pytest

import jax

import pipelinedp_tpu as pdp
from pipelinedp_tpu import executor
from pipelinedp_tpu.parallel import make_mesh
from pipelinedp_tpu.runtime import aot as rt_aot
from pipelinedp_tpu.runtime import faults as rt_faults
from pipelinedp_tpu.runtime import health as rt_health
from pipelinedp_tpu.runtime import journal as rt_journal
from pipelinedp_tpu.runtime import pipeline as rt_pipeline
from pipelinedp_tpu.runtime import telemetry as rt_telemetry

pytestmark = pytest.mark.aot


@pytest.fixture(autouse=True)
def _aot_epoch():
    rt_aot.enable(False)
    yield
    rt_aot.enable(False)


def _rows(n=3000, n_ids=500, n_parts=8, seed=0):
    rng = np.random.default_rng(seed)
    return [(int(rng.integers(0, n_ids)), int(rng.integers(0, n_parts)),
             float(rng.uniform(0, 5))) for _ in range(n)]


def _exact_rows(n_ids=600, n_parts=12):
    """Integer-valued rows whose contribution bounds (l0=2, linf=3 — the
    _params() bounds) are exactly met: bounding drops nothing, integer
    sums are exact in f64, so engine outputs are a pure function of the
    row multiset — independent of mesh geometry (the multihost identity
    recipe). ONE unmeshed baseline therefore serves every mesh size,
    and equality across geometries is itself part of the assertion."""
    rows = []
    for u in range(n_ids):
        for pk in ((u * 7) % n_parts, (u * 7 + 1) % n_parts):
            for r in range(3):
                rows.append((u, pk, float((u * 3 + pk + r) % 6)))
    return rows


_BASE_CACHE = {}


def _cached(key, fn):
    if key not in _BASE_CACHE:
        _BASE_CACHE[key] = fn()
    return _BASE_CACHE[key]


def _params():
    return pdp.AggregateParams(metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
                               noise_kind=pdp.NoiseKind.LAPLACE,
                               max_partitions_contributed=2,
                               max_contributions_per_partition=3,
                               min_value=0.0,
                               max_value=5.0)


def _extractors():
    return pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                              partition_extractor=lambda r: r[1],
                              value_extractor=lambda r: r[2])


def _run_engine(rows, **backend_kwargs):
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                           total_delta=1e-6)
    engine = pdp.DPEngine(accountant,
                          pdp.TPUBackend(noise_seed=13, **backend_kwargs))
    result = engine.aggregate(rows, _params(), _extractors())
    accountant.compute_budgets()
    out = sorted((k, tuple(v)) for k, v in result)
    return out, accountant.mechanism_count


def _run_select(rows, **backend_kwargs):
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                           total_delta=1e-6)
    engine = pdp.DPEngine(accountant,
                          pdp.TPUBackend(noise_seed=13, **backend_kwargs))
    result = engine.select_partitions(
        rows, pdp.SelectPartitionsParams(max_partitions_contributed=2),
        pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                           partition_extractor=lambda r: r[1]))
    accountant.compute_budgets()
    return sorted(result), accountant.mechanism_count


class TestBitIdentity:
    """Meshed/unmeshed, overlapped/serial and AOT/traced release the
    same bytes on every route."""

    def test_dense_engine(self):
        rows = _rows()
        base, n_base = _run_engine(rows)
        assert base  # a vacuous comparison proves nothing
        got, n = _run_engine(rows, aot=True)
        assert got == base
        assert n == n_base

    @pytest.mark.parametrize("n_devices", [1, 4, 8])
    def test_meshed_engine(self, n_devices):
        # Exactly-met bounds: the UNMESHED traced run is the bitwise
        # baseline for every geometry (computed once, shared across
        # the mesh params) — the meshed release must equal it at 1, 4
        # AND 8 devices: cross-geometry identity in one run per mesh.
        rows = _exact_rows()
        base, n_base = _cached("meshed_base", lambda: _run_engine(rows))
        assert base
        mesh = make_mesh(n_devices=n_devices)
        # AOT executes the same executable jit would dispatch; the
        # 8-device point covers the AOT meshed route.
        kwargs = dict(aot=True) if n_devices == 8 else {}
        meshed, n_m = _run_engine(rows, mesh=mesh, **kwargs)
        assert meshed == base
        assert n_base == n_m

    @pytest.mark.parametrize("mesh_devices", [None, 4])
    def test_blocked_overlap_vs_serial(self, mesh_devices):
        # Exactly-met bounds again: block noise keys are geometry-
        # independent (fold_in(final_key, b)), so the unmeshed SERIAL
        # consume run is the bitwise baseline for the meshed overlapped
        # route too — one baseline, shared across the params.
        rows = _exact_rows()
        kw = dict(large_partition_threshold=4, block_partitions=2)
        serial, n_s = _cached(
            "blocked_base",
            lambda: _run_engine(rows, overlap_drain=False, **kw))
        assert serial
        mesh = (make_mesh(n_devices=mesh_devices)
                if mesh_devices else None)
        # aot=True on the overlapped run: one run covers both the
        # drainer-thread consume and the AOT-dispatched block kernels
        # against the serial traced baseline.
        overlapped, n_o = _run_engine(rows, mesh=mesh, overlap_drain=True,
                                      aot=True, **kw)
        assert overlapped == serial
        assert n_s == n_o

    @pytest.mark.parametrize("n_devices", [None, 8])
    def test_select_routes(self, n_devices):
        # Exact bounds: L0 sampling drops no pairs, counts are integer
        # psums — selection decisions are geometry-independent, so the
        # unmeshed traced run baselines the mesh-8 routes too.
        rows = _exact_rows()
        mesh = make_mesh(n_devices=n_devices) if n_devices else None
        base, _ = _cached("select_base", lambda: _run_select(rows))
        assert base
        dense, _ = _run_select(rows, mesh=mesh, aot=True)
        blocked, _ = _run_select(rows, mesh=mesh,
                                 large_partition_threshold=4,
                                 block_partitions=3,
                                 overlap_drain=True)
        # The serial-consume blocked comparison runs on the cheap
        # unmeshed param only (the drivers share _dispatch_blocks).
        if n_devices is None:
            blocked_serial, _ = _run_select(rows, mesh=mesh,
                                            large_partition_threshold=4,
                                            block_partitions=3,
                                            overlap_drain=False)
            assert blocked_serial == blocked
        assert dense == base
        assert blocked == base

    def test_chunk_source_depths(self):
        """The streamed (batched-append) route at pipeline depths 1/8
        equals the serial row run — the append batching changes
        dispatch counts, never bytes."""
        rows = _rows(n=2500)
        base, n_base = _run_engine(rows, overlap_drain=False)

        def chunks():
            for i in range(0, len(rows), 300):
                chunk = rows[i:i + 300]
                yield (np.array([r[0] for r in chunk]),
                       np.array([r[1] for r in chunk]),
                       np.array([r[2] for r in chunk]))

        for depth in (1, 8):
            got, n = _run_engine(pdp.ChunkSource(chunks()), aot=True,
                                 pipeline_depth=depth, encode_threads=2)
            assert got == base, depth
            assert n == n_base


class TestExecutableCache:

    def test_key_correctness_spec_shape_and_values(self):
        """Distinct spec → miss; distinct row bucket → miss; identical
        (spec, shape) with different VALUES → hit."""
        cache = rt_aot.global_cache()
        cache.clear()
        rt_aot.enable(True)
        n, P = 256, 8
        rng = np.random.default_rng(0)

        def call(linf=3, n_rows=n, seed=1):
            params = _params()
            accountant = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                                   total_delta=1e-6)
            from pipelinedp_tpu import combiners
            compound = combiners.create_compound_combiner(
                params, accountant)
            accountant.compute_budgets()
            cfg = executor.make_kernel_config(
                params, compound, P, private_selection=False,
                selection_params=None)
            import dataclasses
            cfg = dataclasses.replace(cfg, linf=linf)
            stds = executor.compute_noise_stds(compound, params)
            import jax.numpy as jnp
            pid = jnp.asarray(rng.integers(0, 50, n_rows), jnp.int32)
            pk = jnp.asarray(rng.integers(0, P, n_rows), jnp.int32)
            values = jnp.asarray(rng.uniform(0, 5, n_rows))
            valid = jnp.ones(n_rows, bool)
            out = executor.aggregate_release_kernel(
                pid, pk, values, valid, 0.0, 5.0, 0.0, 0.0, 2.5,
                jnp.asarray(stds), jax.random.PRNGKey(seed), cfg)
            jax.block_until_ready(out[0])

        before = rt_telemetry.snapshot()
        call(linf=3)
        call(linf=3, seed=9)  # same spec+shape, different values/key
        d1 = rt_telemetry.delta(before)
        assert d1.get("aot_cache_misses", 0) == 1
        assert d1.get("aot_cache_hits", 0) == 1

        before = rt_telemetry.snapshot()
        call(linf=2)  # distinct spec fingerprint
        call(n_rows=n * 2)  # distinct row bucket
        d2 = rt_telemetry.delta(before)
        assert d2.get("aot_cache_misses", 0) == 2
        assert d2.get("aot_cache_hits", 0) == 0

        stats = cache.stats()
        assert stats["entries"] >= 3
        assert stats["per_entry"]["aggregate_release_kernel"]["misses"] \
            >= 3

    def test_disabled_is_traced_path(self):
        before = rt_telemetry.snapshot()
        _run_engine(_rows(n=400))  # aot knob off
        delta = rt_telemetry.delta(before)
        assert delta.get("aot_cache_misses", 0) == 0
        assert delta.get("aot_cache_hits", 0) == 0

    def test_nested_trace_falls_back_to_jit(self):
        """An aot_probe'd entry called INSIDE another jit trace inlines
        through the traced path (tracers cannot feed an executable)."""
        rt_aot.enable(True)
        calls = {}

        @jax.jit
        def inner(x):
            return x + 1

        wrapped = rt_aot.aot_probe("test_inner", inner)

        @jax.jit
        def outer(x):
            return wrapped(x) * 2

        out = outer(np.arange(4.0))
        np.testing.assert_array_equal(np.asarray(out),
                                      (np.arange(4.0) + 1) * 2)
        del calls

    def test_fingerprint_distinguishes_dtype_and_shape(self):
        import jax.numpy as jnp
        a = {"x": jnp.zeros(4, jnp.int32)}
        b = {"x": jnp.zeros(4, jnp.float32)}
        c = {"x": jnp.zeros(8, jnp.int32)}
        d = {"x": jnp.ones(4, jnp.int32)}  # values don't key
        fa, fb, fc, fd = (rt_aot.fingerprint(v) for v in (a, b, c, d))
        assert fa != fb and fa != fc
        assert fa == fd

    def test_activation_is_thread_scoped(self):
        import threading
        assert not rt_aot.enabled()
        seen = {}

        def worker():
            seen["worker"] = rt_aot.enabled()

        with rt_aot.activate(True):
            assert rt_aot.enabled()
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert not rt_aot.enabled()
        assert seen["worker"] is False  # no cross-thread leak


class TestOverlapSemantics:

    def test_journal_keys_identical_overlap_vs_serial(self, tmp_path):
        rows = _rows()
        j_serial = rt_journal.BlockJournal(str(tmp_path / "serial"))
        j_overlap = rt_journal.BlockJournal(str(tmp_path / "overlap"))
        kw = dict(large_partition_threshold=4, block_partitions=2)
        a, _ = _run_engine(rows, journal=j_serial, job_id="j",
                           overlap_drain=False, **kw)
        b, _ = _run_engine(rows, journal=j_overlap, job_id="j",
                           overlap_drain=True, **kw)
        assert a == b
        assert sorted(j_serial.keys("j")) == sorted(j_overlap.keys("j"))

    def test_resume_replays_overlapped_records(self, tmp_path):
        rows = _rows()
        journal = rt_journal.BlockJournal(str(tmp_path / "j"))
        kw = dict(large_partition_threshold=4, block_partitions=2,
                  journal=journal, job_id="resume-job",
                  overlap_drain=True)
        before = rt_telemetry.snapshot()
        first, n_first = _run_engine(rows, **kw)
        assert rt_telemetry.delta(before).get("journal_replays", 0) == 0
        before = rt_telemetry.snapshot()
        second, n_second = _run_engine(rows, **kw)
        replays = rt_telemetry.delta(before).get("journal_replays", 0)
        block_keys = [k for k in journal.keys("resume-job")
                      if not k.startswith("__")]  # minus the odometer
        assert replays == len(block_keys)
        assert replays > 0
        assert second == first
        assert n_second == n_first  # no duplicate registrations

    @pytest.mark.faults
    def test_transient_consume_fault_under_overlap(self):
        rows = _rows()
        sched = rt_faults.FaultSchedule([
            rt_faults.Fault("consume", block=1),
        ])
        base, n_base = _run_engine(rows, large_partition_threshold=4,
                                   block_partitions=2)
        before = rt_telemetry.snapshot()
        with rt_faults.inject(sched):
            got, n = _run_engine(rows, large_partition_threshold=4,
                                 block_partitions=2, overlap_drain=True)
        delta = rt_telemetry.delta(before)
        assert delta.get("injected_faults", 0) == 1
        assert delta.get("block_retries", 0) >= 1
        assert got == base  # same fold_in key on the retried block
        assert n == n_base

    def test_async_drain_under_forbid_row_fetches(self, tmp_path):
        """Journaled meshed blocked run over device-resident inputs with
        the transfer guard armed: the batched async drain moves O(kept)
        journal records, never rows."""
        from pipelinedp_tpu.parallel import reshard
        rows = _rows(n=1500)
        journal = rt_journal.BlockJournal(str(tmp_path / "j"))

        def chunks():
            for i in range(0, len(rows), 500):
                chunk = rows[i:i + 500]
                yield (np.array([r[0] for r in chunk]),
                       np.array([r[1] for r in chunk]),
                       np.array([r[2] for r in chunk]))

        mesh = make_mesh(n_devices=4)
        kw = dict(mesh=mesh, large_partition_threshold=4,
                  block_partitions=2)
        base, _ = _run_engine(pdp.ChunkSource(chunks()), **kw)
        assert base
        with reshard.forbid_row_fetches():
            got, _ = _run_engine(pdp.ChunkSource(chunks()),
                                 journal=journal, job_id="guarded",
                                 aot=True, overlap_drain=True, **kw)
        assert got == base


class TestServiceReuse:

    def test_second_identical_spec_job_zero_aot_retraces(self):
        from pipelinedp_tpu.service import DPAggregationService, JobSpec
        rt_telemetry.reset()
        rows = [("u%d" % (i % 40), "P%d" % (i % 4), 1.0 + i % 3)
                for i in range(400)]
        spec = lambda seed: JobSpec(params=_params(), epsilon=1.0,
                                    delta=1e-6, noise_seed=seed,
                                    data_extractors=_extractors(),
                                    public_partitions=["P0", "P1", "P2",
                                                       "P3"])
        with DPAggregationService(pdp.TPUBackend(aot=True),
                                  max_concurrent_jobs=1) as svc:
            h1 = svc.submit("tenant-a", spec(3), rows)
            h1.result(timeout=120)
            h2 = svc.submit("tenant-b", spec(4), rows)
            h2.result(timeout=120)
            reuse = svc.compile_reuse()
        (key, stats), = reuse.items()
        assert stats["jobs"] == 2
        second = rt_health.for_job(
            h2.job_id).snapshot()["counters"].get("aot_cache_misses", 0)
        assert second == 0, (
            f"second identical-spec job retraced {second} AOT entries")
        assert stats["aot_cache_hits"] >= 1


class TestAppendBatching:

    @pytest.mark.parametrize("donate", [False, True])
    def test_batched_matches_pad_rows(self, donate):
        from pipelinedp_tpu import columnar
        rng = np.random.default_rng(3)
        sizes = (700, 20, 3000, 5)
        chunks = []
        for i, n in enumerate(sizes):
            chunks.append((rng.integers(0, 50, n).astype(np.int32),
                           rng.integers(0, 9, n).astype(np.int32),
                           rng.uniform(0, 5, n)))
        encoded = columnar.EncodedData(
            pid=np.concatenate([c[0] for c in chunks]),
            pk=np.concatenate([c[1] for c in chunks]),
            values=np.concatenate([c[2] for c in chunks]),
            partition_vocab=list(range(9)), n_privacy_ids=50)
        want = [np.asarray(a) for a in executor.pad_rows(encoded)[:3]]
        acc = rt_pipeline.DeviceRowAccumulator(donate=donate,
                                               batch_rows=1024)
        for i, (pid, pk, values) in enumerate(chunks):
            acc.append(pid, pk, values, len(pid), chunk=i)
        got = [np.asarray(a) for a in acc.finalize()]
        assert acc.n_rows == sum(sizes)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_batching_reduces_append_dispatches(self):
        from pipelinedp_tpu.runtime import trace as rt_trace
        rng = np.random.default_rng(5)
        chunks = [(rng.integers(0, 50, 200).astype(np.int32),
                   rng.integers(0, 9, 200).astype(np.int32),
                   rng.uniform(0, 5, 200)) for _ in range(30)]

        def n_appends(batch_rows):
            rt_trace.reset()
            with rt_trace.scoped():
                acc = rt_pipeline.DeviceRowAccumulator(
                    donate=False, batch_rows=batch_rows)
                for i, (pid, pk, values) in enumerate(chunks):
                    acc.append(pid, pk, values, len(pid), chunk=i)
                acc.finalize()
                spans = rt_trace.trace_summary()["spans"]
            rt_trace.reset()
            return spans.get("pipeline_append", {}).get("count", 0)

        per_chunk = n_appends(0)
        batched = n_appends(2000)
        assert per_chunk == 30
        assert batched <= (30 * 200) // 2000 + 1
