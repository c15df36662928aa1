"""Multi-chip sharding tests on the 8-device virtual CPU mesh."""

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu.parallel import make_mesh, shard_rows_by_pid

HUGE_EPS = 1e7

ROWS = [("u%d" % (i % 50), "pk%d" % (i % 7), float(i % 5))
        for i in range(1000)]

EXTRACTORS = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                partition_extractor=lambda r: r[1],
                                value_extractor=lambda r: r[2])


def _serve_the_scatter_form(monkeypatch):
    """Puts the lazy quantile descent's reference, the scatter form served
    until PR 38 (tests/test_executor_quantiles.py), in the served form's
    place; the list returned fills as the reference is traced."""
    from pipelinedp_tpu import executor
    from tests.test_executor_quantiles import (
        scatter_form_lazy_quantile_outputs)
    traced = []

    def reference_form(*args, **kwargs):
        traced.append(True)
        return scatter_form_lazy_quantile_outputs(*args, **kwargs)

    monkeypatch.setattr(executor, "_lazy_quantile_outputs", reference_form)
    return traced


def _aggregate(backend, rows, params, public=None, eps=HUGE_EPS):
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=eps,
                                           total_delta=1e-5)
    engine = pdp.DPEngine(accountant, backend)
    result = engine.aggregate(rows, params, EXTRACTORS, public)
    accountant.compute_budgets()
    return dict(result)


class TestShardRows:

    def test_shard_rows_by_pid_colocates_and_pads(self):
        pid = np.arange(100, dtype=np.int32)
        pk = np.zeros(100, dtype=np.int32)
        values = np.ones(100)
        valid = np.ones(100, dtype=bool)
        spid, spk, svalues, svalid = shard_rows_by_pid(
            pid, pk, values, valid, 8)
        assert len(spid) % 8 == 0
        per_shard = len(spid) // 8
        # Every privacy id's rows land on exactly one shard.
        shard_of = {}
        for s in range(8):
            block_pid = spid[s * per_shard:(s + 1) * per_shard]
            block_valid = svalid[s * per_shard:(s + 1) * per_shard]
            for p in block_pid[block_valid]:
                assert shard_of.setdefault(int(p), s) == s
        assert svalid.sum() == 100
        assert svalues[svalid].sum() == 100

    def test_all_rows_one_pid(self):
        pid = np.zeros(10, dtype=np.int32)
        spid, spk, sval, svalid = shard_rows_by_pid(pid, pid, pid.astype(
            float), np.ones(10, bool), 4)
        assert svalid.sum() == 10

    def test_skewed_pids_bounded_padding(self):
        # Zipf-ish skew: a few very hot ids plus a long tail. The two-phase
        # balancing (greedy LPT for heavy ids, serpentine tail) must keep
        # total padded size < 1.2x the ideal equal-split layout (the old
        # pid%n scheme + pow2 rounding could inflate this past 2x).
        rng = np.random.default_rng(0)
        n_ids = 2000
        counts = (rng.zipf(1.5, n_ids) % 500 + 1)
        pid = np.repeat(np.arange(n_ids, dtype=np.int32), counts)
        n = len(pid)
        pk = rng.integers(0, 16, n).astype(np.int32)
        spid, _, _, svalid = shard_rows_by_pid(pid, pk, np.ones(n),
                                               np.ones(n, bool), 8)
        ideal = 8 * (-(-n // 8))
        assert len(spid) < 1.2 * ideal, (len(spid), ideal)
        assert svalid.sum() == n

    def test_one_dominant_pid_padding(self):
        # One id holds half the rows; its shard is irreducibly hot, but the
        # other shards must share the remainder evenly.
        n_tail = 7000
        pid = np.concatenate([
            np.zeros(7000, dtype=np.int32),
            np.arange(1, 1 + n_tail, dtype=np.int32)
        ])
        n = len(pid)
        spid, _, _, svalid = shard_rows_by_pid(pid, pid, np.ones(n),
                                               np.ones(n, bool), 8)
        # Capacity is set by the hot shard (7000 rows) with <=12.5% slack.
        assert len(spid) <= 8 * 7000 * 1.125
        assert svalid.sum() == n


class TestShardedEngineParity:

    @pytest.mark.parametrize("n_devices", [1, 4, 8])
    def test_count_sum_matches_local(self, n_devices):
        mesh = make_mesh(n_devices=n_devices)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM,
                     pdp.Metrics.PRIVACY_ID_COUNT],
            max_partitions_contributed=7,
            max_contributions_per_partition=30,
            min_value=0.0,
            max_value=5.0)
        public = ["pk%d" % i for i in range(7)]
        expected = _aggregate(pdp.LocalBackend(seed=0), ROWS, params, public)
        actual = _aggregate(pdp.TPUBackend(mesh=mesh, noise_seed=0), ROWS,
                            params, public)
        assert set(actual) == set(expected)
        for pk in expected:
            assert actual[pk].count == pytest.approx(expected[pk].count,
                                                     abs=0.05)
            assert actual[pk].sum == pytest.approx(expected[pk].sum, abs=0.05)
            assert actual[pk].privacy_id_count == pytest.approx(
                expected[pk].privacy_id_count, abs=0.05)

    def test_private_selection_sharded(self):
        mesh = make_mesh(n_devices=8)
        rows = [(f"u{i}", "big", 1.0) for i in range(2000)]
        rows += [("solo", "tiny", 1.0)]
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                                     max_partitions_contributed=1,
                                     max_contributions_per_partition=1)
        result = _aggregate(pdp.TPUBackend(mesh=mesh, noise_seed=1), rows,
                            params)
        assert "big" in result
        assert "tiny" not in result
        assert result["big"].count == pytest.approx(2000, abs=0.1)

    def test_l0_bounding_across_shards(self):
        # One privacy id with rows in many partitions: bounding must treat
        # them globally (all rows co-located on one shard).
        mesh = make_mesh(n_devices=8)
        rows = [("hot_user", f"pk{i}", 1.0) for i in range(16)]
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                                     max_partitions_contributed=4,
                                     max_contributions_per_partition=1)
        public = [f"pk{i}" for i in range(16)]
        result = _aggregate(pdp.TPUBackend(mesh=mesh, noise_seed=2), rows,
                            params, public)
        total = sum(result[pk].count for pk in public)
        assert total == pytest.approx(4, abs=0.05)

    def test_mean_sharded(self):
        mesh = make_mesh(n_devices=4)
        params = pdp.AggregateParams(metrics=[pdp.Metrics.MEAN],
                                     max_partitions_contributed=7,
                                     max_contributions_per_partition=30,
                                     min_value=0.0,
                                     max_value=5.0)
        public = ["pk%d" % i for i in range(7)]
        expected = _aggregate(pdp.LocalBackend(seed=0), ROWS, params, public)
        actual = _aggregate(pdp.TPUBackend(mesh=mesh, noise_seed=3), ROWS,
                            params, public)
        for pk in expected:
            assert actual[pk].mean == pytest.approx(expected[pk].mean,
                                                    abs=0.01)

    def test_variance_sharded(self):
        mesh = make_mesh(n_devices=4)
        params = pdp.AggregateParams(metrics=[pdp.Metrics.VARIANCE,
                                              pdp.Metrics.MEAN],
                                     max_partitions_contributed=7,
                                     max_contributions_per_partition=30,
                                     min_value=0.0,
                                     max_value=5.0)
        public = ["pk%d" % i for i in range(7)]
        expected = _aggregate(pdp.LocalBackend(seed=0), ROWS, params, public)
        actual = _aggregate(pdp.TPUBackend(mesh=mesh, noise_seed=3), ROWS,
                            params, public)
        for pk in expected:
            assert actual[pk].variance == pytest.approx(
                expected[pk].variance, abs=0.05)
            assert actual[pk].mean == pytest.approx(expected[pk].mean,
                                                    abs=0.01)

    def test_secure_release_sharded(self):
        # Secure (snapped discrete) release must survive the psum'd
        # multi-chip path with the same huge-eps values as LocalBackend.
        mesh = make_mesh(n_devices=4)
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT,
                                              pdp.Metrics.SUM],
                                     noise_kind=pdp.NoiseKind.LAPLACE,
                                     max_partitions_contributed=7,
                                     max_contributions_per_partition=30,
                                     min_value=0.0,
                                     max_value=5.0)
        public = ["pk%d" % i for i in range(7)]
        expected = _aggregate(pdp.LocalBackend(seed=0), ROWS, params, public)
        actual = _aggregate(
            pdp.TPUBackend(mesh=mesh, noise_seed=5, secure_noise=True), ROWS,
            params, public)
        for pk in expected:
            assert actual[pk].count == pytest.approx(expected[pk].count,
                                                     abs=0.05)
            assert actual[pk].sum == pytest.approx(expected[pk].sum,
                                                   abs=0.05)

    def test_percentile_sharded(self):
        # Values spread across shards must merge into one global tree.
        mesh = make_mesh(n_devices=8)
        rows = [("u%d" % i, "A", float(i % 100)) for i in range(800)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(25),
                     pdp.Metrics.PERCENTILE(75)],
            max_partitions_contributed=1,
            max_contributions_per_partition=1,
            min_value=0.0,
            max_value=100.0)
        result = _aggregate(pdp.TPUBackend(mesh=mesh, noise_seed=5), rows,
                            params, ["A"])
        assert result["A"].percentile_25 == pytest.approx(25.0, abs=2.0)
        assert result["A"].percentile_75 == pytest.approx(75.0, abs=2.0)

    def test_percentile_sharded_multichunk(self, monkeypatch):
        # Forces quantile_chunk=2 so quantile_outputs dispatches to the
        # LAZY descent (executor._lazy_quantile_outputs) under shard_map —
        # its per-level psum of [P, B] child counts is the collective that
        # would otherwise only be exercised on real meshes.
        import dataclasses
        from pipelinedp_tpu import executor
        orig = executor.make_kernel_config

        def forced_chunk(*a, **kw):
            cfg = orig(*a, **kw)
            return dataclasses.replace(cfg, quantile_chunk=2)

        monkeypatch.setattr(executor, "make_kernel_config", forced_chunk)
        mesh = make_mesh(n_devices=8)
        rows = [("u%d" % i, "pk%d" % (i % 5), float(i % 100))
                for i in range(1000)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(50)],
            max_partitions_contributed=1,
            max_contributions_per_partition=1,
            min_value=0.0,
            max_value=100.0)
        public = ["pk%d" % i for i in range(5)]
        result = _aggregate(pdp.TPUBackend(mesh=mesh, noise_seed=6), rows,
                            params, public)
        assert set(result) == set(public)
        for pk in public:
            assert 30.0 <= result[pk].percentile_50 <= 70.0

    def test_lazy_descent_sharded_releases_what_the_scatter_form_did(
            self, monkeypatch):
        # The job of test_percentile_sharded_multichunk, noise ON (eps 1),
        # twice under one noise seed: as served (every shard sorts its own
        # rows by (partition, leaf) and searches them; the [P, B] counts
        # psum'd) and with the scatter form that was served before put in
        # its place. The released percentiles are the same bytes.
        import dataclasses
        import jax
        from pipelinedp_tpu import executor
        orig = executor.make_kernel_config

        def forced_chunk(*a, **kw):
            return dataclasses.replace(orig(*a, **kw), quantile_chunk=2)

        monkeypatch.setattr(executor, "make_kernel_config", forced_chunk)
        mesh = make_mesh(n_devices=8)
        rows = [("u%d" % i, "pk%d" % (i % 5), float((i * 37) % 100))
                for i in range(1000)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.PERCENTILE(50), pdp.Metrics.PERCENTILE(90)],
            max_partitions_contributed=1,
            max_contributions_per_partition=1,
            min_value=0.0,
            max_value=100.0)
        public = ["pk%d" % i for i in range(5)]

        def release():
            jax.clear_caches()  # the form is read when the body is traced
            result = _aggregate(pdp.TPUBackend(mesh=mesh, noise_seed=6),
                                rows, params, public, eps=1.0)
            return {pk: (m.percentile_50, m.percentile_90)
                    for pk, m in result.items()}

        served = release()
        traced = _serve_the_scatter_form(monkeypatch)
        reference = release()
        jax.clear_caches()
        assert traced  # the second release did run the reference
        assert set(served) == set(public)
        assert served == reference
        # The noise is on: at eps 1 the answers are not the true quantiles'.
        assert len({v for pair in served.values() for v in pair}) > 2

    def test_vector_sum_sharded(self):
        mesh = make_mesh(n_devices=8)
        rows = [("u%d" % (i % 50), "pk%d" % (i % 3),
                 np.array([float(i % 5), 1.0])) for i in range(300)]
        params = pdp.AggregateParams(metrics=[pdp.Metrics.VECTOR_SUM],
                                     max_partitions_contributed=3,
                                     max_contributions_per_partition=100,
                                     vector_norm_kind=pdp.NormKind.Linf,
                                     vector_max_norm=1000.0,
                                     vector_size=2)
        public = ["pk0", "pk1", "pk2"]
        expected = _aggregate(pdp.LocalBackend(seed=0), rows, params, public)
        actual = _aggregate(pdp.TPUBackend(mesh=mesh, noise_seed=4), rows,
                            params, public)
        for pk in public:
            np.testing.assert_allclose(actual[pk].vector_sum,
                                       expected[pk].vector_sum, atol=0.1)


class TestMultiProcBackend:

    def test_engine_e2e_on_multiproc(self):
        backend = pdp.MultiProcLocalBackend(n_jobs=2)
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                                     max_partitions_contributed=2,
                                     max_contributions_per_partition=2)
        rows = [("u1", "A", 1.0), ("u2", "A", 1.0), ("u1", "B", 1.0)]
        accountant = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                               total_delta=1e-5)
        engine = pdp.DPEngine(accountant, backend)
        result = engine.aggregate(rows, params, EXTRACTORS, ["A", "B"])
        accountant.compute_budgets()
        result = dict(result)
        assert result["A"].count == pytest.approx(2, abs=0.01)
        assert result["B"].count == pytest.approx(1, abs=0.01)


class TestMaxPartitionsKnob:

    def test_max_partitions_pads_and_decodes(self):
        backend = pdp.TPUBackend(max_partitions=64, noise_seed=0)
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                                     max_partitions_contributed=2,
                                     max_contributions_per_partition=2)
        rows = [("u1", "A", 1.0), ("u2", "B", 1.0)]
        result = _aggregate(backend, rows, params, ["A", "B"])
        assert set(result) == {"A", "B"}

    def test_max_partitions_too_small_raises(self):
        backend = pdp.TPUBackend(max_partitions=1, noise_seed=0)
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                                     max_partitions_contributed=2,
                                     max_contributions_per_partition=2)
        rows = [("u1", "A", 1.0), ("u2", "B", 1.0)]
        with pytest.raises(ValueError, match="max_partitions"):
            _aggregate(backend, rows, params, ["A", "B"])


class TestShardedSelectPartitions:

    @staticmethod
    def _select(backend, rows, l0=30):
        accountant = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                               total_delta=1e-5)
        engine = pdp.DPEngine(accountant, backend)
        params = pdp.SelectPartitionsParams(max_partitions_contributed=l0)
        result = engine.select_partitions(rows, params, EXTRACTORS)
        accountant.compute_budgets()
        return set(result)

    def test_select_partitions_mesh_matches_local(self):
        # Every partition has many distinct users and l0 does not bind, so
        # huge-eps selection is deterministic on every path.
        rng = np.random.default_rng(11)
        rows = [(f"u{i % 120}", f"pk{k}", 0.0)
                for i, k in enumerate(rng.integers(0, 20, size=4000))]
        mesh = make_mesh(n_devices=8)
        expected = self._select(pdp.LocalBackend(seed=0), rows)
        assert self._select(pdp.TPUBackend(mesh=mesh, noise_seed=3),
                            rows) == expected
        assert len(expected) == 20

    def test_select_partitions_mesh_drops_small(self):
        mesh = make_mesh(n_devices=4)
        rows = [(f"u{i}", "big", 0.0) for i in range(2000)]
        rows += [("solo", "tiny", 0.0)]
        got = self._select(pdp.TPUBackend(mesh=mesh, noise_seed=5), rows,
                           l0=2)
        assert got == {"big"}

    def test_sharded_counts_match_single_device(self):
        # Count-stage parity: psum of shard-local counts == single-device
        # counts when l0 does not bind (no sampling randomness involved).
        import jax
        from pipelinedp_tpu import executor
        from pipelinedp_tpu.parallel import sharded
        from pipelinedp_tpu.ops import selection_ops

        rng = np.random.default_rng(7)
        n, P = 5000, 40
        pid = rng.integers(0, 200, n).astype(np.int32)
        pk = rng.integers(0, P, n).astype(np.int32)
        valid = np.ones(n, bool)
        selection = selection_ops.SelectionParams(kind=1, pre_shift=0,
                                                  threshold=10.5,
                                                  scale=1e-12)
        mesh = make_mesh(n_devices=8)
        n_kept, ids = sharded.sharded_select_partitions(
            mesh, pid, pk, valid, jax.random.PRNGKey(0), P, P, selection)
        kept_mesh = np.asarray(ids)[:int(n_kept)]
        keep_single = np.asarray(
            executor.select_partitions_kernel(pid, pk, valid,
                                              jax.random.PRNGKey(0), P, P,
                                              selection))
        # Deterministic threshold selection: both reduce to count >= 10.5.
        expected = np.array([
            len({p for p, k in zip(pid, pk) if k == j}) >= 11
            for j in range(P)
        ])
        assert np.array_equal(kept_mesh, np.flatnonzero(expected))
        assert (keep_single == expected).all()


class TestShardedBlockedLargeP:
    """Mesh-sharded blocked large-P path (aggregate_blocked_sharded)."""

    @staticmethod
    def _spec(P, **kw):
        from tests.test_large_p import _spec
        return _spec(P, **kw)

    @staticmethod
    def _data(n, n_ids, P, seed=0):
        rng = np.random.default_rng(seed)
        pid = rng.integers(0, n_ids, n).astype(np.int32)
        pk = rng.integers(0, P, n).astype(np.int32)
        values = rng.uniform(0, 5, n)
        return pid, pk, values, np.ones(n, bool)

    @pytest.mark.parametrize("n_devices", [1, 8])
    def test_public_noise_free_exact_parity(self, n_devices):
        # Multiple blocks, no selection, zero noise: the sharded blocked
        # result must EXACTLY match the single-device blocked path and the
        # raw numpy aggregate.
        import jax
        from pipelinedp_tpu.parallel import large_p
        mesh = make_mesh(n_devices=n_devices)
        P = 1000
        cfg, stds, (min_v, max_v, min_s, max_s, mid) = self._spec(
            P, private=False, l0=P, linf=64)
        stds = np.zeros_like(np.asarray(stds))
        pid, pk, values, valid = self._data(20_000, 500, P)
        key = jax.random.PRNGKey(0)
        kept, outputs = large_p.aggregate_blocked_sharded(
            mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
            stds, key, cfg, block_partitions=128)
        ref_kept, ref_outputs = large_p.aggregate_blocked(
            pid, pk, values, valid, min_v, max_v, min_s, max_s, mid, stds,
            key, cfg, block_partitions=128)
        assert list(kept) == list(range(P))
        assert list(ref_kept) == list(kept)
        expected_count = np.bincount(pk, minlength=P)
        expected_sum = np.bincount(pk, weights=np.clip(values, 0, 5),
                                   minlength=P)
        np.testing.assert_allclose(outputs["count"], expected_count,
                                   atol=1e-4)
        np.testing.assert_allclose(outputs["sum"], expected_sum, rtol=1e-5)
        np.testing.assert_allclose(outputs["sum"], ref_outputs["sum"],
                                   rtol=1e-5)

    def test_private_selection_across_blocks(self):
        # Dense partitions in first/middle/last block kept, single-id
        # partitions dropped — decisions deterministic at huge eps, so the
        # kept set must equal the single-device blocked path's.
        import jax
        from pipelinedp_tpu.parallel import large_p
        mesh = make_mesh(n_devices=8)
        P = 300
        cfg, stds, (min_v, max_v, min_s, max_s, mid) = self._spec(
            P, l0=20, linf=4, eps=30)
        stds = np.zeros_like(np.asarray(stds))
        rows = []
        for p in list(range(10)) + [150] + list(range(290, 300)):
            for u in range(200):
                rows.append((u * 100_003 + p, p))
        for i, p in enumerate(range(20, 280, 13)):
            rows.append((50_000_000 + i, p))
        pid = np.array([r[0] for r in rows], np.int64)
        pk = np.array([r[1] for r in rows], np.int32)
        values = np.ones(len(rows))
        valid = np.ones(len(rows), bool)
        key = jax.random.PRNGKey(3)
        kept, outputs = large_p.aggregate_blocked_sharded(
            mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
            stds, key, cfg, block_partitions=64)
        ref_kept, _ = large_p.aggregate_blocked(
            pid, pk, values, valid, min_v, max_v, min_s, max_s, mid, stds,
            key, cfg, block_partitions=64)
        expected = set(list(range(10)) + [150] + list(range(290, 300)))
        assert set(kept.tolist()) == expected
        assert set(ref_kept.tolist()) == expected
        # Noise-free counts: l0=20 does not bind (each id hits one
        # partition), so kept counts equal the raw per-partition bincount
        # (partition 150 also catches one sparse row: 201).
        truth = np.bincount(pk, minlength=P)
        np.testing.assert_allclose(outputs["count"], truth[kept], atol=1e-4)

    def test_percentile_blocked_sharded_equals_the_scatter_form(
            self, monkeypatch):
        # One blocked job with percentiles over the mesh, noise ON and a
        # fixed key: three blocks of 512 trees, each on the lazy descent
        # (quantile_chunk 64), as served and with the scatter form that
        # was served before in its place: the same kept ids and the same
        # bytes in every released column.
        import dataclasses
        import jax
        from pipelinedp_tpu.parallel import large_p
        mesh = make_mesh(n_devices=8)
        P = 1500
        metrics = [pdp.Metrics.COUNT, pdp.Metrics.PERCENTILE(50),
                   pdp.Metrics.PERCENTILE(90)]
        cfg, stds, (min_v, max_v, min_s, max_s, mid) = self._spec(
            P, private=False, metrics_list=metrics, l0=P, linf=64)
        cfg = dataclasses.replace(cfg, quantile_chunk=64)
        stds = np.full_like(np.asarray(stds), 3.0)
        pid, pk, values, valid = self._data(6_000, 300, P, seed=8)

        def release():
            jax.clear_caches()  # the form is read when the body is traced
            return large_p.aggregate_blocked_sharded(
                mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s,
                mid, stds, jax.random.PRNGKey(4), cfg, block_partitions=512)

        kept, served = release()
        traced = _serve_the_scatter_form(monkeypatch)
        ref_kept, reference = release()
        jax.clear_caches()
        assert traced  # the second release did run the reference
        assert list(kept) == list(ref_kept) == list(range(P))
        assert sorted(served) == sorted(reference)
        for name in served:
            np.testing.assert_array_equal(served[name], reference[name],
                                          err_msg=name)
        assert len(np.unique(served["percentile_50"])) > 100  # noise is on

    @pytest.mark.slow
    def test_percentile_blocked_sharded(self):
        # Per-block lazy quantile descent over the mesh: the [C, B]
        # child-count psum inside quantile_outputs is the collective under
        # test. Noise-free medians must land within leaf width of numpy.
        # `slow`: ~4 min of wall alone on the CPU tier-1 box — the
        # descent's per-level dispatches dominate; the same collective
        # is covered fast by test_percentile_sharded (dense route) and
        # test_percentile_blocked_matches_dense (blocked, single
        # device), so tier-1 keeps both halves of the composition.
        import jax
        from pipelinedp_tpu.parallel import large_p
        mesh = make_mesh(n_devices=8)
        P = 3000
        metrics = [pdp.Metrics.COUNT, pdp.Metrics.PERCENTILE(50)]
        cfg, stds, (min_v, max_v, min_s, max_s, mid) = self._spec(
            P, private=False, metrics_list=metrics, l0=P, linf=64)
        stds = np.zeros_like(np.asarray(stds))
        rng = np.random.default_rng(5)
        n = 30_000
        pid = rng.integers(0, 400, n).astype(np.int32)
        pk = rng.integers(0, 40, n).astype(np.int32) * 75  # spread blocks
        values = rng.uniform(0, 5, n)
        valid = np.ones(n, bool)
        kept, outputs = large_p.aggregate_blocked_sharded(
            mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
            stds, jax.random.PRNGKey(2), cfg, block_partitions=256)
        leaf = (max_v - min_v) / (cfg.branching**cfg.tree_height)
        kept_list = kept.tolist()
        for p in range(0, 3000, 75):
            j = kept_list.index(p)
            true_median = np.quantile(values[pk == p], 0.5,
                                      method="inverted_cdf")
            assert abs(outputs["percentile_50"][j] -
                       true_median) < 3 * leaf + 0.05

    def test_mean_variance_engine_meshed_blocked(self):
        # MEAN/VARIANCE children (count+sum+sum-of-squares columns) through
        # the meshed blocked route vs LocalBackend at huge eps.
        mesh = make_mesh(n_devices=8)
        params = pdp.AggregateParams(metrics=[pdp.Metrics.MEAN,
                                              pdp.Metrics.VARIANCE],
                                     max_partitions_contributed=7,
                                     max_contributions_per_partition=30,
                                     min_value=0.0,
                                     max_value=5.0)
        public = ["pk%d" % i for i in range(7)]
        expected = _aggregate(pdp.LocalBackend(seed=0), ROWS, params, public)
        actual = _aggregate(
            pdp.TPUBackend(mesh=mesh, noise_seed=3,
                           large_partition_threshold=4), ROWS, params,
            public)
        for pk in expected:
            assert actual[pk].mean == pytest.approx(expected[pk].mean,
                                                    abs=0.01)
            assert actual[pk].variance == pytest.approx(
                expected[pk].variance, abs=0.05)

    # `slow`: ~30s whole-path sweep. Exact-parity coverage stays in
    # tier-1 via test_public_noise_free_exact_parity[1|8] and the
    # single-device blocked parity tests; this adds the probabilistic-
    # eps L0-not-binding regime on top.
    @pytest.mark.slow
    def test_exact_parity_when_l0_not_binding(self):
        # Whole-path equivalence at probabilistic eps: when L0 sampling
        # never binds (the only per-shard randomness), per-partition
        # counts are identical across paths, so the shared per-block
        # selection keys must give the EXACT same kept set, counts and
        # sums — even where individual keep decisions are coin flips.
        # (Multi-block with skipped empty blocks; the same property was
        # hand-verified at P=10^7 — scale does not change it.)
        import jax
        from pipelinedp_tpu.parallel import large_p
        mesh = make_mesh(n_devices=8)
        P = 100_000
        cfg, stds, (min_v, max_v, min_s, max_s, mid) = self._spec(
            P, l0=64, linf=8, eps=30)
        stds = np.zeros_like(np.asarray(stds))
        rng = np.random.default_rng(1)
        n = 50_000
        pid = rng.integers(0, 10_000, n).astype(np.int64)
        pk = (np.power(rng.random(n), 6.0) * P).astype(np.int32)
        valid = np.ones(n, bool)
        values = rng.uniform(0, 5, n)
        key = jax.random.PRNGKey(2)
        kept, outputs = large_p.aggregate_blocked_sharded(
            mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
            stds, key, cfg, block_partitions=1 << 14)
        ref_kept, ref_out = large_p.aggregate_blocked(
            pid, pk, values, valid, min_v, max_v, min_s, max_s, mid, stds,
            key, cfg, block_partitions=1 << 14)
        assert len(kept) > 0
        assert np.array_equal(kept, ref_kept)
        np.testing.assert_allclose(outputs["count"], ref_out["count"],
                                   atol=1e-3)
        np.testing.assert_allclose(outputs["sum"], ref_out["sum"],
                                   rtol=1e-4)

    def test_streamed_ingest_through_meshed_blocked(self):
        # Device-resident EncodedData (streamed ingest) through the
        # meshed blocked engine route: columns reshard on device (the
        # collective all_to_all path, tests/test_reshard.py) and the
        # result must match the row-input LocalBackend path.
        from pipelinedp_tpu import ingest
        rows = ROWS
        chunks = [(np.array([r[0] for r in rows[i:i + 300]], object),
                   np.array([r[1] for r in rows[i:i + 300]], object),
                   np.array([r[2] for r in rows[i:i + 300]]))
                  for i in range(0, len(rows), 300)]
        encoded = ingest.stream_encode_columns(iter(chunks))
        mesh = make_mesh(n_devices=8)
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT,
                                              pdp.Metrics.SUM],
                                     max_partitions_contributed=7,
                                     max_contributions_per_partition=30,
                                     min_value=0.0,
                                     max_value=5.0)
        expected = _aggregate(pdp.LocalBackend(seed=0), rows, params)
        actual = _aggregate(
            pdp.TPUBackend(mesh=mesh, noise_seed=0,
                           large_partition_threshold=4), encoded, params)
        assert set(actual) == set(expected)
        for pk in expected:
            assert actual[pk].count == pytest.approx(expected[pk].count,
                                                     abs=0.05)
            assert actual[pk].sum == pytest.approx(expected[pk].sum,
                                                   abs=0.05)

    def test_vector_sum_engine_meshed_blocked(self):
        # VECTOR_SUM through the meshed blocked route (per-dim scalar
        # columns ride the pass-1 payload sort; the [C]-block reduce keeps
        # vector_size).
        mesh = make_mesh(n_devices=8)
        rows = [("u%d" % (i % 50), "pk%d" % (i % 3),
                 np.array([float(i % 5), 1.0])) for i in range(300)]
        params = pdp.AggregateParams(metrics=[pdp.Metrics.VECTOR_SUM],
                                     max_partitions_contributed=3,
                                     max_contributions_per_partition=100,
                                     vector_norm_kind=pdp.NormKind.Linf,
                                     vector_max_norm=1000.0,
                                     vector_size=2)
        public = ["pk0", "pk1", "pk2"]
        expected = _aggregate(pdp.LocalBackend(seed=0), rows, params, public)
        actual = _aggregate(
            pdp.TPUBackend(mesh=mesh, noise_seed=4,
                           large_partition_threshold=1), rows, params,
            public)
        for pk in public:
            np.testing.assert_allclose(actual[pk].vector_sum,
                                       expected[pk].vector_sum, atol=0.1)

    def test_secure_blocked_sharded(self):
        # Secure snapped release through the MESHED blocked path: outputs
        # on the secure grid, equal to the single-device blocked secure
        # outputs' grid, matching the raw aggregate to grid resolution.
        import dataclasses as dc
        import jax
        import jax.numpy as jnp
        from pipelinedp_tpu import executor
        from pipelinedp_tpu.ops import secure_noise
        from pipelinedp_tpu.parallel import large_p
        mesh = make_mesh(n_devices=4)
        P = 300
        cfg, stds, (min_v, max_v, min_s, max_s,
                    mid), params, compound = self._spec(P, private=False,
                                                        l0=P, linf=64,
                                                        eps=1e6, full=True)
        cfg = dc.replace(cfg, secure=True)
        sens = executor.compute_noise_sensitivities(compound, params)
        thr_hi, thr_lo, gran = secure_noise.build_tables(
            np.asarray(stds), pdp.NoiseKind.LAPLACE, sensitivities=sens)
        tables = (jnp.asarray(thr_hi), jnp.asarray(thr_lo),
                  jnp.asarray(gran))
        rng = np.random.default_rng(6)
        n = 10_000
        pid = rng.integers(0, 300, n).astype(np.int32)
        pk = rng.integers(0, P, n).astype(np.int32)
        values = rng.uniform(0, 5, n)
        valid = np.ones(n, bool)
        kept, outputs = large_p.aggregate_blocked_sharded(
            mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
            np.asarray(stds), jax.random.PRNGKey(3), cfg,
            block_partitions=128, secure_tables=tables)
        expected = np.bincount(pk, minlength=P)
        np.testing.assert_allclose(outputs["count"], expected, atol=0.5)
        g = float(gran[0])
        ratios = outputs["count"] / g
        np.testing.assert_allclose(ratios, np.round(ratios), atol=1e-3)

    def test_select_partitions_blocked_sharded_matches_single(self):
        # Mesh + blocked standalone selection: kept set must equal the
        # single-device blocked path's at huge eps (deterministic
        # decisions), across block boundaries.
        import jax
        from pipelinedp_tpu.ops import selection_ops
        from pipelinedp_tpu.parallel import large_p
        mesh = make_mesh(n_devices=8)
        P, l0 = 300, 30
        rows = []
        for p in list(range(10)) + [150] + list(range(290, 300)):
            for u in range(60):
                rows.append((u * 100_003 + p, p))
        for i, p in enumerate(range(21, 280, 13)):
            rows.append((50_000_000 + i, p))
        pid = np.array([r[0] for r in rows], np.int64)
        pk = np.array([r[1] for r in rows], np.int32)
        valid = np.ones(len(rows), bool)
        sel = selection_ops.selection_params_from_host(
            pdp.PartitionSelectionStrategy.TRUNCATED_GEOMETRIC, 1e7, 1e-5,
            l0, None)
        key = jax.random.PRNGKey(5)
        kept = large_p.select_partitions_blocked_sharded(
            mesh, pid, pk, valid, key, l0, P, sel, block_partitions=64)
        ref = large_p.select_partitions_blocked(pid, pk, valid, key, l0, P,
                                                sel, block_partitions=64)
        expected = sorted(list(range(10)) + [150] + list(range(290, 300)))
        assert kept.tolist() == expected
        assert ref.tolist() == expected

    def test_select_partitions_engine_meshed_blocked_route(self):
        # TPUBackend(mesh, threshold below P): standalone selection must
        # route through the sharded blocked path and match LocalBackend.
        rng = np.random.default_rng(11)
        rows = [(f"u{i % 120}", f"pk{k}", 0.0)
                for i, k in enumerate(rng.integers(0, 20, size=4000))]
        mesh = make_mesh(n_devices=8)

        def run(backend):
            accountant = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                                   total_delta=1e-5)
            engine = pdp.DPEngine(accountant, backend)
            params = pdp.SelectPartitionsParams(max_partitions_contributed=30)
            result = engine.select_partitions(rows, params, EXTRACTORS)
            accountant.compute_budgets()
            return set(result)

        expected = run(pdp.LocalBackend(seed=0))
        assert run(
            pdp.TPUBackend(mesh=mesh, noise_seed=3,
                           large_partition_threshold=8)) == expected
        assert len(expected) == 20

    def test_engine_routes_meshed_blocked(self):
        # TPUBackend(mesh, large_partition_threshold below P) must route
        # through the sharded blocked path and agree with LocalBackend.
        mesh = make_mesh(n_devices=8)
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT,
                                              pdp.Metrics.SUM],
                                     max_partitions_contributed=7,
                                     max_contributions_per_partition=30,
                                     min_value=0.0,
                                     max_value=5.0)
        public = ["pk%d" % i for i in range(7)]
        expected = _aggregate(pdp.LocalBackend(seed=0), ROWS, params, public)
        actual = _aggregate(
            pdp.TPUBackend(mesh=mesh, noise_seed=0,
                           large_partition_threshold=4), ROWS, params,
            public)
        assert set(actual) == set(expected)
        for pk in expected:
            assert actual[pk].count == pytest.approx(expected[pk].count,
                                                     abs=0.05)
            assert actual[pk].sum == pytest.approx(expected[pk].sum,
                                                   abs=0.05)
