"""Tracing + metrics-registry coverage.

Three contracts:

  * REGISTRY discipline: every ``telemetry.record("...")`` literal in
    the source tree names a declared registry metric, and every declared
    counter is recorded somewhere — the registry and the code cannot
    drift apart in either direction. Since PR 7 this is enforced by
    staticcheck's ``registry-drift`` AST rule (the source-scraping grep
    this file used to carry is gone); the tests here pin the rule's
    verdict on the real tree and prove both drift directions on
    fixtures.
  * Exporter validity: a dumped trace is valid Chrome/Perfetto
    trace-event JSON (json.loads + the required keys on every event),
    and trace_summary's inclusive/exclusive accounting is coherent.
  * Disabled cost: with tracing off, span() must be a near-free bool
    check — the blocked-driver hot path takes two of them per block.
  * Causality: every span has an id and the id of the span that caused
    it, across threads too; the spans of one materialised aggregation
    share the root's ``agg``; the leaf spans cover their parents; the
    same spans appear as ``rt:`` annotations on the profiler's clock.
  * Counters that count with tracing off: backend_compiles, h2d_bytes,
    d2h_bytes.
"""

import functools
import json
import time

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import input_validators, pipeline_backend, staticcheck
from pipelinedp_tpu.runtime import health as rt_health
from pipelinedp_tpu.runtime import telemetry
from pipelinedp_tpu.runtime import trace


@pytest.fixture(autouse=True)
def _trace_epoch():
    """Each test runs in a fresh trace epoch and leaves tracing off."""
    telemetry.reset()
    yield
    trace.disable()
    telemetry.reset()


class TestRegistry:

    @pytest.mark.staticcheck
    def test_registry_and_source_agree_both_directions(self):
        """The analyzer's registry-drift rule over the REAL tree: no
        record() literal without a declaration, no declaration without a
        recording site."""
        tree = staticcheck.load_tree(staticcheck.default_paths())
        found = staticcheck.analyze(
            tree, only_rules=["registry-drift"]).active
        assert found == [], "\n".join(f.render() for f in found)

    @pytest.mark.staticcheck
    def test_recorded_but_undeclared_literal_is_caught(self):
        mods = [
            staticcheck.parse_source(
                "pipelinedp_tpu/runtime/telemetry.py",
                "def _counter(name, help_text):\n"
                "    return (name, 'counter', help_text)\n"
                "REGISTRY = dict(a=_counter('used_counter', 'h'))\n"),
            staticcheck.parse_source(
                "pipelinedp_tpu/fix_user.py",
                "from pipelinedp_tpu.runtime import telemetry\n"
                "def f():\n"
                "    telemetry.record('used_counter')\n"
                "    telemetry.record('undeclared_counter')\n"),
        ]
        found = staticcheck.analyze(
            mods, only_rules=["registry-drift"]).active
        assert len(found) == 1
        assert "undeclared_counter" in found[0].message
        assert found[0].file == "pipelinedp_tpu/fix_user.py"

    @pytest.mark.staticcheck
    def test_declared_but_unrecorded_counter_is_caught(self):
        mods = [
            staticcheck.parse_source(
                "pipelinedp_tpu/runtime/telemetry.py",
                "def _counter(name, help_text):\n"
                "    return (name, 'counter', help_text)\n"
                "REGISTRY = dict(\n"
                "    a=_counter('used_counter', 'h'),\n"
                "    b=_counter('ghost_counter', 'h'))\n"),
            staticcheck.parse_source(
                "pipelinedp_tpu/fix_user.py",
                "from pipelinedp_tpu.runtime import telemetry\n"
                "def f():\n"
                "    telemetry.record('used_counter')\n"),
        ]
        found = staticcheck.analyze(
            mods, only_rules=["registry-drift"]).active
        assert len(found) == 1
        assert "ghost_counter" in found[0].message
        assert found[0].file == "pipelinedp_tpu/runtime/telemetry.py"

    @pytest.mark.staticcheck
    def test_set_gauge_of_undeclared_name_is_caught(self):
        mods = [
            staticcheck.parse_source(
                "pipelinedp_tpu/runtime/telemetry.py",
                "def _gauge(name, help_text):\n"
                "    return (name, 'gauge', help_text)\n"
                "REGISTRY = dict(a=_gauge('used_gauge', 'h'))\n"),
            staticcheck.parse_source(
                "pipelinedp_tpu/fix_user.py",
                "from pipelinedp_tpu.runtime import telemetry\n"
                "def f():\n"
                "    telemetry.set_gauge('used_gauge', 1)\n"
                "    telemetry.set_gauge('undeclared_gauge', 2)\n"),
        ]
        found = staticcheck.analyze(
            mods, only_rules=["registry-drift"]).active
        assert len(found) == 1
        assert "undeclared_gauge" in found[0].message
        assert found[0].file == "pipelinedp_tpu/fix_user.py"

    @pytest.mark.staticcheck
    def test_declared_but_never_set_gauge_is_caught(self):
        mods = [
            staticcheck.parse_source(
                "pipelinedp_tpu/runtime/telemetry.py",
                "def _gauge(name, help_text):\n"
                "    return (name, 'gauge', help_text)\n"
                "REGISTRY = dict(\n"
                "    a=_gauge('used_gauge', 'h'),\n"
                "    b=_gauge('ghost_gauge', 'h'))\n"),
            staticcheck.parse_source(
                "pipelinedp_tpu/fix_user.py",
                "from pipelinedp_tpu.runtime import telemetry\n"
                "def f():\n"
                "    telemetry.set_gauge('used_gauge', 1)\n"),
        ]
        found = staticcheck.analyze(
            mods, only_rules=["registry-drift"]).active
        assert len(found) == 1
        assert "ghost_gauge" in found[0].message
        assert found[0].file == "pipelinedp_tpu/runtime/telemetry.py"

    @pytest.mark.staticcheck
    def test_kind_mismatch_is_caught_both_ways(self):
        mods = [
            staticcheck.parse_source(
                "pipelinedp_tpu/runtime/telemetry.py",
                "def _counter(name, help_text):\n"
                "    return (name, 'counter', help_text)\n"
                "def _gauge(name, help_text):\n"
                "    return (name, 'gauge', help_text)\n"
                "REGISTRY = dict(\n"
                "    a=_counter('a_counter', 'h'),\n"
                "    b=_gauge('a_gauge', 'h'))\n"),
            staticcheck.parse_source(
                "pipelinedp_tpu/fix_user.py",
                "from pipelinedp_tpu.runtime import telemetry\n"
                "def f():\n"
                "    telemetry.record('a_gauge')\n"
                "    telemetry.set_gauge('a_counter', 1)\n"),
        ]
        found = staticcheck.analyze(
            mods, only_rules=["registry-drift"]).active
        messages = "\n".join(f.message for f in found)
        assert "declared as a gauge" in messages
        assert "declared as a counter" in messages

    def test_registry_entries_are_complete(self):
        kinds = set()
        for name, metric in telemetry.REGISTRY.items():
            assert metric.name == name
            assert metric.kind in ("counter", "gauge")
            assert metric.help and isinstance(metric.help, str)
            kinds.add(metric.kind)
        # Both kinds are live in the registry (counters since PR 2,
        # gauges since the observability plane).
        assert kinds == {"counter", "gauge"}

    def test_record_rejects_undeclared_names(self):
        with pytest.raises(ValueError, match="not a declared metric"):
            telemetry.record("totally_made_up_counter")

    def test_record_accepts_declared_names_with_attrs(self):
        telemetry.record("block_retries", block=7)
        assert telemetry.snapshot()["block_retries"] == 1


class TestSnapshotSplit:

    def test_snapshot_is_flat_ints(self):
        telemetry.record("block_retries")
        telemetry.record_duration("phase_y", 0.25)
        snap = telemetry.snapshot()
        assert snap == {"block_retries": 1}
        assert all(isinstance(v, int) for v in snap.values())

    def test_full_snapshot_is_structured(self):
        telemetry.record("block_retries")
        telemetry.record_duration("phase_y", 0.25)
        full = telemetry.full_snapshot()
        assert set(full) == {"counters", "gauges", "timings",
                             "job_timings"}
        assert full["counters"] == {"block_retries": 1}
        assert full["timings"]["phase_y"]["count"] == 1

    def test_delta_never_sees_timings(self):
        before = telemetry.snapshot()
        telemetry.record_duration("phase_y", 1.0)
        assert telemetry.delta(before) == {}
        telemetry.record("block_retries", 2)
        assert telemetry.delta(before) == {"block_retries": 2}


class TestCoordinatedReset:

    def test_reset_clears_counters_timings_health_and_trace(self):
        trace.enable()
        telemetry.record("block_retries")
        telemetry.record_duration("phase_z", 0.5)
        with rt_health.job_scope("reset-job"):
            telemetry.record_duration("phase_z", 0.5)
        with trace.span("s"):
            pass
        assert telemetry.snapshot()
        assert telemetry.timing_snapshot()
        assert rt_health.snapshot_all()
        assert trace.trace_summary()["n_events"] > 0
        telemetry.reset()
        assert telemetry.snapshot() == {}
        assert telemetry.timing_snapshot() == {}
        assert telemetry.job_timing_snapshot() == {}
        assert rt_health.snapshot_all() == {}
        assert trace.trace_summary()["n_events"] == 0


class TestSpans:

    def test_nesting_inclusive_exclusive(self):
        trace.enable()
        with trace.span("outer"):
            time.sleep(0.02)
            with trace.span("inner"):
                time.sleep(0.03)
        s = trace.trace_summary()["spans"]
        assert s["outer"]["count"] == 1
        assert s["inner"]["count"] == 1
        # Inclusive covers the child; exclusive subtracts it.
        assert s["outer"]["inclusive_s"] >= s["inner"]["inclusive_s"]
        assert s["outer"]["exclusive_s"] <= s["outer"]["inclusive_s"]
        # Summary values are rounded to 6 decimals; three roundings can
        # disagree by a few microseconds.
        assert (s["outer"]["exclusive_s"] + s["inner"]["inclusive_s"]
                == pytest.approx(s["outer"]["inclusive_s"], abs=5e-6))
        # The self-times partition the root: generous sleep-based bounds.
        assert s["inner"]["inclusive_s"] >= 0.02
        assert s["outer"]["exclusive_s"] >= 0.01

    def test_span_attrs_and_set(self):
        trace.enable()
        with trace.span("fetch", block=3) as sp:
            sp.set(bytes=4096)
        events = trace.to_trace_events()["traceEvents"]
        span_ev = [e for e in events if e.get("name") == "fetch"][0]
        assert span_ev["args"]["block"] == 3
        assert span_ev["args"]["bytes"] == 4096
        assert trace.trace_summary()["transfer_bytes"] == 4096

    def test_job_scoping(self):
        trace.enable()
        with rt_health.job_scope("job-a"):
            with trace.span("work"):
                pass
        with trace.span("unscoped"):
            pass
        scoped = trace.trace_summary(job_id="job-a")["spans"]
        assert set(scoped) == {"work"}

    def test_id_and_parent_on_nested_spans(self):
        trace.enable()
        with trace.span("outer", agg=41) as outer:
            assert trace.current() is outer
            with trace.span("inner"):
                with trace.span("leaf"):
                    pass
            with trace.span("sibling"):
                pass
        assert trace.current() is None
        args = {e["name"]: e["args"]
                for e in trace.to_trace_events()["traceEvents"]
                if e["ph"] == "X"}
        ids = [a["id"] for a in args.values()]
        assert len(set(ids)) == 4
        assert "parent" not in args["outer"]
        assert args["inner"]["parent"] == args["outer"]["id"]
        assert args["leaf"]["parent"] == args["inner"]["id"]
        assert args["sibling"]["parent"] == args["outer"]["id"]
        # The root's agg reaches every descendant.
        assert {a["agg"] for a in args.values()} == {41}

    def test_parent_token_crosses_threads(self):
        """A span opened on another thread with parent=token names the
        span that caused it and inherits its job and agg, without
        touching the parent's exclusive time."""
        import threading
        trace.enable()

        def work(token):
            with trace.span("child", parent=token):
                time.sleep(0.01)

        with rt_health.job_scope("job-t"):
            with trace.span("root", agg=7):
                t = threading.Thread(target=work, args=(trace.current(),))
                t.start()
                t.join(timeout=10)
                assert not t.is_alive()
        events = {e["name"]: e
                  for e in trace.to_trace_events()["traceEvents"]
                  if e["ph"] == "X"}
        child, root = events["child"], events["root"]
        assert child["tid"] != root["tid"]
        assert child["args"]["parent"] == root["args"]["id"]
        assert child["args"]["agg"] == 7
        assert child["args"]["job"] == "job-t"
        summary = trace.trace_summary()["spans"]
        assert summary["root"]["exclusive_s"] == pytest.approx(
            summary["root"]["inclusive_s"], abs=5e-6)

    def test_current_is_none_when_disabled(self):
        with trace.span("ghost"):
            assert trace.current() is None

    def test_instants_from_counters(self):
        trace.enable()
        telemetry.record("journal_replays", block=5)
        summary = trace.trace_summary()
        assert summary["instants"].get("journal_replays") == 1

    def test_buffer_limit_counts_drops(self):
        trace.enable(buffer_limit=10)
        for _ in range(25):
            trace.instant("tick")
        summary = trace.trace_summary()
        assert summary["n_events"] == 10
        assert summary["dropped_events"] == 15

    def test_disabled_records_nothing(self):
        with trace.span("ghost"):
            trace.instant("ghost_tick")
        trace.enable()
        assert trace.trace_summary()["n_events"] == 0


class TestDisabledOverhead:
    """Disabled tracing must add no measurable per-span overhead: the
    blocked drivers take two span() calls per block, and the acceptance
    bar is < 2% driver throughput regression with tracing off."""

    def test_disabled_span_is_near_free(self):
        assert not trace.enabled()
        n = 200_000
        start = time.perf_counter()
        for _ in range(n):
            with trace.span("hot"):
                pass
        elapsed = time.perf_counter() - start
        # ~100-300ns/span on this class of hardware; 5µs/span is two
        # orders of magnitude of headroom against CI noise while still
        # catching an accidental allocation/lock on the disabled path.
        assert elapsed / n < 5e-6, (
            f"disabled span() costs {elapsed / n * 1e9:.0f}ns — the "
            f"disabled path must stay a bool check")
        assert trace.trace_summary()["n_events"] == 0


class TestExporter:

    def test_dump_is_valid_chrome_trace_json(self, tmp_path):
        trace.enable()
        with trace.span("outer", rows=4):
            with trace.span("inner"):
                pass
            trace.instant("incident", block=1)
        path = trace.dump(str(tmp_path / "trace.json"))
        with open(path) as f:
            payload = json.load(f)
        assert set(payload) >= {"traceEvents", "displayTimeUnit"}
        events = payload["traceEvents"]
        assert isinstance(events, list) and len(events) == 4  # M + 2X + i
        for ev in events:
            assert {"name", "ph", "pid", "tid", "ts"} <= set(ev), ev
            assert ev["ph"] in ("X", "i", "M")
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
            if ev["ph"] == "i":
                assert ev["s"] == "t"
        names = {e["name"] for e in events}
        assert {"outer", "inner", "incident"} <= names

    def test_dump_filters_by_job(self, tmp_path):
        trace.enable()
        with rt_health.job_scope("job-x"):
            with trace.span("mine"):
                pass
        with trace.span("theirs"):
            pass
        path = trace.dump(str(tmp_path / "trace.json"), job_id="job-x")
        with open(path) as f:
            names = {e["name"] for e in json.load(f)["traceEvents"]}
        assert "mine" in names and "theirs" not in names


class TestJitProbe:

    def test_compile_miss_and_hit_attribution(self):
        import jax
        import jax.numpy as jnp
        probed = trace.probe_jit("probe_target",
                                 jax.jit(lambda x: x * 2 + 1))
        trace.enable()
        x = jnp.ones(16)
        np.testing.assert_allclose(np.asarray(probed(x)), 3.0)
        probed(x)  # cache hit: no new compile
        stats = trace.compile_stats()
        assert stats["probe_target"]["misses"] == 1
        assert stats["probe_target"]["compile_s"] > 0
        probed(jnp.ones(32))  # new shape: second compile
        assert trace.compile_stats()["probe_target"]["misses"] == 2
        summary = trace.trace_summary()
        assert summary["spans"]["jit:probe_target"]["count"] == 3
        assert summary["instants"]["jit_compile:probe_target"] == 2
        assert telemetry.snapshot()["jit_cache_misses"] == 2

    def test_untraced_calls_skip_attribution(self):
        import jax
        import jax.numpy as jnp
        probed = trace.probe_jit("probe_quiet", jax.jit(lambda x: x + 1))
        probed(jnp.ones(8))
        assert trace.compile_stats() == {}


def _chunk_job(noise_seed, n=40_000, n_chunks=4, **backend_kw):
    """A small ChunkSource(encode_mode="host") aggregation through the
    engine; returns (release dict, rows, number of released columns)."""
    rng = np.random.default_rng(noise_seed)
    pid = rng.integers(0, 6000, n)
    pk = rng.integers(0, 6000, n)
    values = rng.uniform(1, 5, n)
    step = n // n_chunks
    chunks = [(pid[i:i + step], pk[i:i + step], values[i:i + step])
              for i in range(0, n, step)]
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
    engine = pdp.DPEngine(
        acc, pdp.TPUBackend(noise_seed=noise_seed, **backend_kw))
    result = engine.aggregate(pdp.ChunkSource(chunks, encode_mode="host"),
                              params, ex)
    acc.compute_budgets()
    return dict(result), n, 2


def _blocked_args(n=4000, P=1 << 12, epsilon=1.0):
    """Arguments of large_p.aggregate_blocked for a small COUNT+SUM job."""
    import jax
    from pipelinedp_tpu import combiners, executor
    from pipelinedp_tpu.aggregate_params import MechanismType
    from pipelinedp_tpu.ops import selection_ops

    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=2,
        max_contributions_per_partition=3,
        min_value=0.0,
        max_value=5.0)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=epsilon,
                                    total_delta=1e-6)
    compound = combiners.create_compound_combiner(params, acc)
    budget = acc.request_budget(MechanismType.GENERIC)
    acc.compute_budgets()
    selection = selection_ops.selection_params_from_host(
        params.partition_selection_strategy, budget.eps, budget.delta,
        params.max_partitions_contributed, None)
    cfg = executor.make_kernel_config(params, compound, P,
                                      private_selection=True,
                                      selection_params=selection)
    stds = executor.compute_noise_stds(compound, params)
    scalars = executor.kernel_scalars(params)
    rng = np.random.default_rng(3)
    pid = rng.integers(0, 200, n).astype(np.int32)
    pk = rng.integers(0, P, n).astype(np.int32)
    values = rng.uniform(0, 5, n)
    valid = np.ones(n, bool)
    return (pid, pk, values, valid, *scalars, np.asarray(stds),
            jax.random.PRNGKey(11), cfg)


def _span_events():
    return [e for e in trace.to_trace_events()["traceEvents"]
            if e["ph"] == "X"]


def _assert_pass2_waits(events, dispatched):
    """Each p2.wait is a child of its block's consume, lies inside no
    release_wait, and every dispatched block's consume opened one (a
    journal-less serial run)."""
    by_id = {e["args"]["id"]: e for e in events}
    waits = [e for e in events if e["name"] == "p2.wait"]
    assert len(waits) >= dispatched, (len(waits), dispatched)
    release_waits = [e for e in events if e["name"] == "release_wait"]
    slack = 0.01  # ts/dur are microseconds rounded to 3 places
    for wait in waits:
        consume = by_id[wait["args"]["parent"]]
        assert consume["name"] == "consume", consume
        assert consume["args"]["block"] == wait["args"]["block"]
        for rw in release_waits:
            assert (wait["ts"] >= rw["ts"] + rw["dur"] - slack or
                    wait["ts"] + wait["dur"] <= rw["ts"] + slack), (wait,
                                                                    rw)


class TestBackendIntegration:

    def test_trace_knob_validation(self):
        with pytest.raises(ValueError, match="trace"):
            pipeline_backend.TPUBackend(trace="yes")
        with pytest.raises(ValueError, match="trace"):
            input_validators.validate_trace("/tmp/trace.json", "T")

    def test_backend_trace_enables_and_dumps(self, tmp_path):
        backend = pdp.TPUBackend(noise_seed=5, trace=True)
        assert trace.enabled()
        rng = np.random.default_rng(1)
        rows = list(
            zip(rng.integers(0, 40, 800).tolist(),
                rng.integers(0, 20, 800).tolist(),
                rng.uniform(0, 5, 800).tolist()))
        ex = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                partition_extractor=lambda r: r[1],
                                value_extractor=lambda r: r[2])
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=4,
            max_contributions_per_partition=8,
            min_value=0.0,
            max_value=5.0)
        # High epsilon: partition selection keeps the dense partitions
        # with probability ~1, so the decode/post-process spans run.
        acc = pdp.NaiveBudgetAccountant(total_epsilon=100.0,
                                        total_delta=1e-6)
        engine = pdp.DPEngine(acc, backend)
        result = engine.aggregate(rows, params, ex)
        acc.compute_budgets()
        assert dict(result)
        summary = backend.trace_summary()
        # The stage spans of the fused path, plus ledger instants.
        for expected in ("graph_build", "encode", "dispatch", "drain",
                         "post_process"):
            assert expected in summary["spans"], (expected,
                                                  sorted(summary["spans"]))
        assert summary["instants"].get("budget_registrations", 0) >= 1
        path = backend.dump_trace(str(tmp_path / "engine_trace.json"))
        with open(path) as f:
            payload = json.load(f)
        assert len(payload["traceEvents"]) > 5

    @pytest.mark.parametrize("row_chunk", [1 << 24, 1000],
                             ids=["device_resident", "host_staged"])
    def test_blocked_driver_spans_and_phase_partition(self, row_chunk):
        """A blocked run's spans decompose its wall time: per-block
        dispatch/drain spans exist and no span's exclusive time is
        negative or exceeds the driver's entry span. With row_chunk
        below the row count pass 1 takes the host-staged branch, whose
        p1.* leaf spans tile contribution_bounding: each names it as
        parent, lies inside it, and none overlaps another. (Structure,
        not ratios of wall times: five other workers share the host.)"""
        from pipelinedp_tpu.parallel import large_p

        args = _blocked_args()
        run = functools.partial(large_p.aggregate_blocked, *args,
                                block_partitions=1 << 10,
                                row_chunk=row_chunk)
        run()  # warm
        resident_before = telemetry.snapshot().get("pass1_device_resident",
                                                   0)
        trace.enable()
        # Serial consume loop (overlap=False): the one-thread timeline
        # whose exclusive span times partition the root span by
        # construction. The overlapped drainer records the SAME spans
        # on its own thread — they overlap the dispatch timeline, so
        # only presence (not partition) is asserted for it below.
        run(overlap=False)
        resident = (telemetry.snapshot().get("pass1_device_resident", 0) -
                    resident_before)
        spans = trace.trace_summary()["spans"]
        for expected in ("aggregate_blocked", "contribution_bounding",
                         "p1.chunk", "p1.pad", "block_offsets", "dispatch",
                         "drain", "release_wait", "consume", "p2.wait"):
            assert expected in spans, (expected, sorted(spans))
        assert spans["dispatch"]["count"] >= 2  # several blocks
        root = spans["aggregate_blocked"]["inclusive_s"]
        for name, stats in spans.items():
            assert 0.0 <= stats["exclusive_s"] <= root + 1e-6, (
                name, stats, root)
        json.dumps(trace.to_trace_events())  # every attribute exports
        # One p1.pad in each p1.chunk: the chunk's four columns written
        # out to its capacity (pid, pk, value, valid).
        events = _span_events()
        by_id = {e["args"]["id"]: e for e in events}
        row_bytes = 9 + np.dtype(large_p.executor._ftype()).itemsize
        chunks = [e for e in events if e["name"] == "p1.chunk"]
        pads = [e for e in events if e["name"] == "p1.pad"]
        assert sorted(e["args"]["parent"] for e in pads) == sorted(
            e["args"]["id"] for e in chunks)
        for pad in pads:
            chunk = by_id[pad["args"]["parent"]]
            assert (pad["args"]["rows"], pad["args"]["cap"]) == (
                chunk["args"]["rows"], chunk["args"]["cap"])
            assert pad["args"]["bytes"] == row_bytes * chunk["args"]["cap"]
        _assert_pass2_waits(events, spans["dispatch"]["count"])
        # The last staged copies come down under a drain of the driver's
        # own, counted in no release_dispatches.
        (last,) = [e for e in events
                   if e["name"] == "drain" and "block" not in e["args"]]
        assert by_id[last["args"]["parent"]]["name"] == "aggregate_blocked"
        staged = {e["args"]["staged"] for e in _span_events()
                  if e["name"] == "contribution_bounding"}
        if row_chunk < len(args[0]):
            assert staged == {"host"}
            assert resident == 0
            leaves = ("p1.host_sort", "p1.chunk", "p1.chunk_wait",
                      "p1.fetch", "p1.merge", "p1.upload")
            for name in leaves:
                assert name in spans, (name, sorted(spans))
            assert spans["p1.chunk"]["count"] >= 2  # several chunks
            pass1, = (e for e in _span_events()
                      if e["name"] == "contribution_bounding")
            p1 = sorted((e for e in _span_events() if e["name"] in leaves),
                        key=lambda e: e["ts"])
            # ts/dur are microseconds rounded to 3 places.
            slack = 0.01
            end = pass1["ts"]
            for e in p1:
                assert e["args"]["parent"] == pass1["args"]["id"], e
                assert e["ts"] >= end - slack, (e, end)  # no overlap
                end = e["ts"] + e["dur"]
            assert end <= pass1["ts"] + pass1["dur"] + slack, (end, pass1)
        else:
            assert staged == {"device"}
            assert resident == 1  # counted once per call, not per block
            assert spans["p1.chunk"]["count"] == 1
            # The host columns' one explicit copy up, inside the chunk.
            assert spans["p1.upload"]["count"] == 1
            upload = next(e for e in _span_events()
                          if e["name"] == "p1.upload")
            (pad,) = pads
            assert pad["ts"] + pad["dur"] <= upload["ts"] + 0.01
            # pid + pk + value (f64 under the tests' x64) + valid, padded.
            row_bytes = 9 + np.dtype(large_p.executor._ftype()).itemsize
            assert upload["args"]["bytes"] == row_bytes * \
                large_p.round_capacity(len(args[0]))
            for name in ("p1.host_sort", "p1.chunk_wait", "p1.fetch",
                         "p1.merge"):
                assert name not in spans, name
        trace.reset()
        run(overlap=True)
        spans_overlapped = trace.trace_summary()["spans"]
        for expected in ("aggregate_blocked", "contribution_bounding",
                         "dispatch", "drain", "consume", "p2.wait"):
            assert expected in spans_overlapped, (
                expected, sorted(spans_overlapped))
        # The drainer thread's spans name the driver's span as their
        # cause, so they carry the job the dispatch thread ran under.
        by_id = {e["args"]["id"]: e for e in _span_events()}
        drainer = next(e for e in by_id.values() if e["name"] == "drainer")
        driver = by_id[drainer["args"]["parent"]]
        assert driver["tid"] != drainer["tid"]
        # Each block's drain on the drainer; the driver's last one (no
        # block) on its own thread, after the drainer has joined.
        drains = [e for e in by_id.values()
                  if e["name"] == "drain" and "block" in e["args"]]
        assert drains and all(e["args"]["parent"] == drainer["args"]["id"]
                              and e["tid"] == drainer["tid"]
                              for e in drains)

    def test_blocked_selection_pads_and_waits_where_they_happen(self):
        """select_partitions_blocked in four blocks: p1.pad is a child of
        contribution_bounding that ends before its sibling p1.upload
        begins, with the three padded columns' bytes; each p2.wait sits
        in its block's consume, outside every release_wait."""
        import jax
        from pipelinedp_tpu.ops import selection_ops
        from pipelinedp_tpu.parallel import large_p

        P, n, l0 = 1 << 11, 6000, 4
        rng = np.random.default_rng(6)
        pid = rng.integers(0, 300, n).astype(np.int32)
        pk = (P * rng.random(n)**3).astype(np.int32)
        valid = np.ones(n, bool)
        selection = selection_ops.selection_params_from_host(
            pdp.PartitionSelectionStrategy.TRUNCATED_GEOMETRIC, 20.0, 1e-3,
            l0, None)
        run = functools.partial(large_p.select_partitions_blocked, pid, pk,
                                valid, jax.random.PRNGKey(7), l0, P,
                                selection, block_partitions=1 << 9)
        run()  # warm
        trace.enable()
        assert len(run()) > 0
        events = _span_events()
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)
        (bounding,) = by_name["contribution_bounding"]
        (pad,) = by_name["p1.pad"]
        (upload,) = by_name["p1.upload"]
        assert pad["args"]["parent"] == bounding["args"]["id"]
        assert upload["args"]["parent"] == bounding["args"]["id"]
        assert pad["ts"] + pad["dur"] <= upload["ts"] + 0.01
        cap = large_p.round_capacity(n)
        assert (pad["args"]["rows"], pad["args"]["cap"],
                pad["args"]["bytes"]) == (n, cap, 9 * cap)
        assert len(by_name["dispatch"]) == 4
        _assert_pass2_waits(events, len(by_name["dispatch"]))

    def test_elastic_floor_pads_under_p1_pad(self):
        """The meshed selection's single-device floor pads its columns
        under p1.pad, as the blocked drivers do."""
        import jax
        from pipelinedp_tpu.ops import selection_ops
        from pipelinedp_tpu.parallel import large_p, sharded

        P, n, l0 = 64, 300, 2
        rng = np.random.default_rng(8)
        selection = selection_ops.selection_params_from_host(
            pdp.PartitionSelectionStrategy.TRUNCATED_GEOMETRIC, 20.0, 1e-3,
            l0, None)
        args = (None, rng.integers(0, 40, n).astype(np.int32),
                rng.integers(0, P, n).astype(np.int32), np.ones(n, bool),
                jax.random.PRNGKey(9), l0, P, selection)
        trace.enable()
        sharded._fallback_select_partitions(args, {}, "floor")
        (pad,) = [e for e in _span_events() if e["name"] == "p1.pad"]
        cap = large_p.round_capacity(n)
        assert (pad["args"]["rows"], pad["args"]["cap"],
                pad["args"]["bytes"]) == (n, cap, 9 * cap)

    def test_chunk_aggregate_spans_share_one_root(self):
        """A ChunkSource aggregation records the ingest and release-wait
        leaf spans under ONE `aggregate` root: every span of the job
        carries the root's agg (the pool threads' pipeline_encode spans
        too, whose parent is the ingest span), and the root's direct
        children cover its wall time within 10%."""
        _chunk_job(3)  # warm: compile outside the traced job
        trace.enable()
        released, n, _ = _chunk_job(4)
        assert released
        events = _span_events()
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)
        for expected in ("aggregate", "ingest", "ingest.wait",
                         "ingest.merge", "ingest.stage", "ingest.finalize",
                         "pipeline_encode", "pipeline_append", "dispatch",
                         "post_process", "release_wait", "drain"):
            assert expected in by_name, (expected, sorted(by_name))
        (root,) = by_name["aggregate"]
        assert root["args"]["route"] == "dense"
        assert root["args"]["rows"] >= n
        agg = root["args"]["agg"]
        # graph_build ran at graph time, before the root opened.
        in_job = [e for e in events if e["name"] != "graph_build"]
        assert {e["args"].get("agg") for e in in_job} == {agg}
        (ingest,) = by_name["ingest"]
        assert ingest["args"]["parent"] == root["args"]["id"]
        assert len(by_name["pipeline_encode"]) == 4
        for e in by_name["pipeline_encode"]:
            assert e["args"]["parent"] == ingest["args"]["id"]
            assert e["tid"] != ingest["tid"]  # a pool thread
        for name in ("ingest.wait", "ingest.merge"):
            assert all(e["args"]["parent"] == ingest["args"]["id"]
                       for e in by_name[name])
        assert {e["args"]["what"] for e in by_name["release_wait"]} == {
            "sentinel", "n_kept"}
        children = sum(e["dur"] for e in events
                       if e["args"].get("parent") == root["args"]["id"]
                       and e["tid"] == root["tid"])
        assert abs(children - root["dur"]) <= 0.1 * root["dur"], (
            children, root["dur"])
        # A second aggregation is a second request.
        trace.reset()
        _chunk_job(4)
        (root2,) = [e for e in _span_events() if e["name"] == "aggregate"]
        assert root2["args"]["agg"] == agg + 1

    def test_rt_spans_are_profiler_annotations_on_one_clock(self, tmp_path):
        """While tracing is on every span is also a
        jax.profiler.TraceAnnotation("rt:<name>"): a profiler trace taken
        around a tiny job holds rt:ingest on the host plane, at the start
        the rt_trace export gives it (mapped by one anchor) within 1 ms,
        with the span's id and agg as the event's stats."""
        import glob

        import jax
        from pipelinedp_tpu.parallel import large_p
        blocked = functools.partial(large_p.aggregate_blocked,
                                    *_blocked_args(),
                                    block_partitions=1 << 10)
        _chunk_job(5)  # warm
        blocked()
        trace.enable()
        jax.profiler.start_trace(str(tmp_path))
        try:
            trace.instant("anchor")
            with jax.profiler.TraceAnnotation("test_anchor"):
                pass
            _chunk_job(6)
            blocked()  # a tiny blocked job: its pad and its pass-2 waits
        finally:
            jax.profiler.stop_trace()
        (xplane,) = glob.glob(
            str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        annotations = {}
        for plane in jax.profiler.ProfileData.from_file(xplane).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("rt:") or e.name == "test_anchor":
                        annotations.setdefault(e.name, []).append(
                            (e.start_ns, dict(e.stats)))
        exported = {e["name"]: e
                    for e in trace.to_trace_events()["traceEvents"]
                    if e["name"] in ("anchor", "ingest")}
        (anchor_ns, _), = annotations["test_anchor"]
        (ingest_ns, stats), = annotations["rt:ingest"]
        on_profiler_us = (ingest_ns - anchor_ns) / 1e3
        on_rt_trace_us = exported["ingest"]["ts"] - exported["anchor"]["ts"]
        assert abs(on_profiler_us - on_rt_trace_us) < 1e3, (
            on_profiler_us, on_rt_trace_us)
        assert stats["id"] == exported["ingest"]["args"]["id"]
        assert stats["agg"] == exported["ingest"]["args"]["agg"]
        for name in ("rt:aggregate", "rt:ingest.wait", "rt:release_wait",
                     "rt:p1.pad", "rt:p2.wait"):
            assert name in annotations, (name, sorted(annotations))


class TestUntracedCounters:
    """The counters an operator has with rt_trace off."""

    def test_backend_compiles_counts_a_fresh_jit_untraced(self):
        import jax
        import jax.numpy as jnp
        assert not trace.enabled()
        telemetry.install_compile_listener()
        telemetry.install_compile_listener()  # idempotent: one listener
        fresh = jax.jit(lambda x: x * 3 + 11)
        x = jnp.ones(24)  # its own small program: built before the count
        before = telemetry.snapshot()
        fresh(x).block_until_ready()
        built = telemetry.delta(before)
        assert built["backend_compiles"] == 1
        assert all(isinstance(v, int) for v in telemetry.snapshot().values())
        before = telemetry.snapshot()
        fresh(x).block_until_ready()  # a dispatch, no compile
        assert "backend_compiles" not in telemetry.delta(before)
        assert "jit_cache_misses" not in telemetry.snapshot()

    def test_dense_chunk_job_bytes_follow_its_shapes(self):
        """h2d_bytes is the encoded rows the accumulator uploaded, d2h_bytes
        the kept ids and released columns the decode fetched: both from
        the run's shapes alone, with tracing off."""
        from pipelinedp_tpu import executor
        _chunk_job(8)  # warm
        assert not trace.enabled()
        before = telemetry.snapshot()
        released, n, n_columns = _chunk_job(9)
        moved = telemetry.delta(before)
        from pipelinedp_tpu.runtime import pipeline
        f = np.dtype(executor._ftype()).itemsize
        assert moved["h2d_bytes"] == n * (4 + 4 + f)
        # More than DRAIN_MIN_ROWS partitions: the decode slices
        # to the kept count's bucket on the device and fetches exactly
        # that; the counter holds what crossed.
        bucket = pipeline.drain_bucket(len(released), 5900)
        assert len(released) < bucket < 5900  # of some 6,000 partitions
        assert moved["drain_bucket_rows"] == bucket
        assert moved["d2h_bytes"] == bucket * (4 + n_columns * f)

    def test_blocked_host_staged_bytes_follow_its_shapes(self):
        from pipelinedp_tpu import executor
        from pipelinedp_tpu.parallel import large_p
        from pipelinedp_tpu.runtime import pipeline
        args = _blocked_args(epsilon=200.0)
        run = functools.partial(large_p.aggregate_blocked, *args,
                                block_partitions=1 << 10, row_chunk=1000)
        run()  # warm
        trace.enable()  # for the chunk capacities and the fetch sizes
        before = telemetry.snapshot()
        kept, outputs = run()
        moved = telemetry.delta(before)
        assert len(kept) > 0
        f = np.dtype(executor._ftype()).itemsize
        events = _span_events()
        caps = [e["args"]["cap"] for e in events if e["name"] == "p1.chunk"]
        fetches = [e["args"] for e in events if e["name"] == "p1.fetch"]
        survivors = sum(a["rows"] for a in fetches)
        survivor_bytes = survivors * (4 + 1 + f)  # spk, pair flag, sum
        # What a fetch brings down is its survivor count's bucket of the
        # chunk's capacity, not the count.
        fetched_bytes = sum(a["bytes"] for a in fetches)
        assert fetched_bytes == sum(
            pipeline.drain_bucket(a["rows"], cap) * (4 + 1 + f)
            for a, cap in zip(fetches, caps))
        control = sum(e["args"]["bytes"] for e in events
                      if e["name"] == "host_fetch")
        # Up: each chunk padded to its capacity (pid, pk, value, valid),
        # then the merged survivors once more.
        assert moved["h2d_bytes"] == sum(caps) * (4 + 4 + f + 1) + \
            survivor_bytes
        # Down: the survivors' buckets, the block-offset table, and per
        # block its kept partitions' bucket of ids and released columns
        # (blocks of 1 << 10 partitions: such a column crosses whole).
        drained = moved["drain_bucket_rows"] - sum(
            pipeline.drain_bucket(a["rows"], cap)
            for a, cap in zip(fetches, caps))
        assert len(kept) <= drained and drained % (1 << 10) == 0
        assert moved["d2h_bytes"] == fetched_bytes + control + \
            drained * (4 + len(outputs) * f)


def _all_rows_survive(n=4000, P=1 << 12):
    """_blocked_args whose rows all survive the bounding (l0 = 2, linf =
    3): each privacy id holds two rows, in two partitions."""
    args = list(_blocked_args(n=n, P=P))
    rng = np.random.default_rng(4)
    args[0] = (np.arange(n) // 2).astype(np.int32)
    pk = rng.integers(0, P // 2, n)
    pk[1::2] += P // 2  # an id's second row lies in another partition
    args[1] = pk.astype(np.int32)
    return args


class TestPass2Counters:
    """pass2_rows and pass2_block_rows count with tracing off."""

    @pytest.mark.parametrize("row_chunk", [None, 1000],
                             ids=["device_resident", "host_staged"])
    def test_counters_follow_the_block_offsets(self, row_chunk):
        """pass2_rows is the last block offset — here every row, since
        every row survives — and pass2_block_rows the shared row capacity
        times the blocks dispatched; the last drain dispatches nothing."""
        from pipelinedp_tpu.parallel import large_p
        args = _all_rows_survive()
        n, C = len(args[0]), 1 << 10
        run = functools.partial(large_p.aggregate_blocked, *args,
                                block_partitions=C, row_chunk=row_chunk)
        run()  # warm
        assert not trace.enabled()
        before = telemetry.snapshot()
        run()
        counted = telemetry.delta(before)
        per_block = np.bincount(args[1] // C, minlength=4)
        dispatched = int(np.count_nonzero(per_block))
        assert counted["pass2_rows"] == n
        assert counted["release_dispatches"] == dispatched
        assert counted["pass2_block_rows"] == dispatched * \
            large_p.round_capacity(int(per_block.max()))

    def test_meshed_counters_sum_over_shards(self, monkeypatch):
        from pipelinedp_tpu.parallel import large_p, make_mesh
        mesh = make_mesh(n_devices=4)
        args = _all_rows_survive()
        caps = []
        range_row_cap = large_p._range_row_cap

        def spy(starts):
            caps.append(range_row_cap(starts))
            return caps[-1]

        monkeypatch.setattr(large_p, "_range_row_cap", spy)
        before = telemetry.snapshot()
        large_p.aggregate_blocked_sharded(mesh, *args,
                                          block_partitions=1 << 10)
        counted = telemetry.delta(before)
        (row_cap,) = caps
        assert counted["pass2_rows"] == len(args[0])
        assert counted["pass2_block_rows"] == \
            4 * row_cap * counted["release_dispatches"]
