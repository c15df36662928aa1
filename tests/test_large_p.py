"""Tests for the blocked large-partition-space path (parallel/large_p.py)."""

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import combiners, executor
from pipelinedp_tpu.aggregate_params import MechanismType
from pipelinedp_tpu.ops import selection_ops
from pipelinedp_tpu.parallel import large_p
from pipelinedp_tpu.runtime import telemetry

import jax


def _spec(n_partitions, private=True, metrics_list=None, l0=4, linf=8,
          eps=1.0, full=False):
    params = pdp.AggregateParams(
        metrics=metrics_list or [pdp.Metrics.COUNT, pdp.Metrics.SUM],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=l0,
        max_contributions_per_partition=linf,
        min_value=0.0,
        max_value=5.0)
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=eps,
                                           total_delta=1e-6)
    compound = combiners.create_compound_combiner(params, accountant)
    selection = None
    if private:
        budget = accountant.request_budget(MechanismType.GENERIC)
    accountant.compute_budgets()
    if private:
        selection = selection_ops.selection_params_from_host(
            params.partition_selection_strategy, budget.eps, budget.delta,
            params.max_partitions_contributed, None)
    cfg = executor.make_kernel_config(params, compound, n_partitions,
                                      private_selection=private,
                                      selection_params=selection)
    stds = executor.compute_noise_stds(compound, params)
    scalars = executor.kernel_scalars(params)
    if full:
        return cfg, stds, scalars, params, compound
    return cfg, stds, scalars


class TestRoundCapacity:

    def test_slack_bounded(self):
        for x in [1, 7, 8, 9, 100, 1000, 12345, 1 << 20, (1 << 20) + 1]:
            cap = large_p.round_capacity(x)
            assert cap >= max(x, 8)
            assert cap <= max(x, 8) * 1.125 + 8


class TestBlockedAggregation:

    def _data(self, n, n_ids, P, seed=0):
        rng = np.random.default_rng(seed)
        pid = rng.integers(0, n_ids, n).astype(np.int32)
        pk = rng.integers(0, P, n).astype(np.int32)
        values = rng.uniform(0, 5, n)
        valid = np.ones(n, dtype=bool)
        return pid, pk, values, valid

    @pytest.mark.parametrize("block_partitions", [128, 32])
    def test_matches_dense_kernel_public_noise_free(self, block_partitions):
        # Public (no selection), zero noise, loose bounds -> blocked result
        # must EXACTLY match the dense kernel and the raw aggregate.
        # block_partitions=32 -> 32 blocks >> the 8-block dispatch window,
        # so _StagedDrain must flush older block groups mid-loop (bounding
        # staged HBM residency) without disturbing per-target append order.
        P = 1000
        cfg, stds, (min_v, max_v, min_s, max_s, mid) = _spec(P,
                                                            private=False,
                                                            l0=P,
                                                            linf=64)
        stds = np.zeros_like(np.asarray(stds))
        pid, pk, values, valid = self._data(20_000, 500, P)
        key = jax.random.PRNGKey(0)
        kept, outputs = large_p.aggregate_blocked(pid,
                                                  pk,
                                                  values,
                                                  valid,
                                                  min_v,
                                                  max_v,
                                                  min_s,
                                                  max_s,
                                                  mid,
                                                  stds,
                                                  key,
                                                  cfg,
                                                  block_partitions=block_partitions,
                                                  row_chunk=4096)
        assert list(kept) == list(range(P))
        expected_count = np.bincount(pk, minlength=P)
        expected_sum = np.bincount(pk,
                                   weights=np.clip(values, 0, 5),
                                   minlength=P)
        np.testing.assert_allclose(outputs["count"], expected_count,
                                   atol=1e-4)
        np.testing.assert_allclose(outputs["sum"], expected_sum, rtol=1e-5)

    def test_private_selection_blocked(self):
        # Partitions with many ids are kept, single-id partitions dropped —
        # across block boundaries.
        P = 300
        cfg, stds, (min_v, max_v, min_s, max_s, mid) = _spec(P, l0=20,
                                                             linf=4, eps=30)
        stds = np.zeros_like(np.asarray(stds))
        # Dense partitions 0..9 and 290..299 (first and last block); sparse
        # singles elsewhere.
        rows = []
        for p in list(range(10)) + list(range(290, 300)):
            for u in range(200):
                rows.append((u, p))
        for p in range(100, 110):
            rows.append((10_000 + p, p))
        pid = np.array([r[0] for r in rows], dtype=np.int32)
        pk = np.array([r[1] for r in rows], dtype=np.int32)
        values = np.ones(len(rows))
        kept, outputs = large_p.aggregate_blocked(pid,
                                                  pk,
                                                  values,
                                                  np.ones(len(rows), bool),
                                                  min_v,
                                                  max_v,
                                                  min_s,
                                                  max_s,
                                                  mid,
                                                  stds,
                                                  jax.random.PRNGKey(1),
                                                  cfg,
                                                  block_partitions=64,
                                                  row_chunk=2048)
        kept = set(kept.tolist())
        assert set(range(10)).issubset(kept)
        assert set(range(290, 300)).issubset(kept)
        assert not kept & set(range(100, 110))

    def test_bounding_is_global_across_blocks(self):
        # One privacy id contributing to many partitions must be l0-bounded
        # globally even though its partitions land in different blocks.
        P = 256
        cfg, stds, (min_v, max_v, min_s, max_s, mid) = _spec(
            P, private=False, l0=4, linf=1, metrics_list=[pdp.Metrics.COUNT])
        stds = np.zeros_like(np.asarray(stds))
        pid = np.zeros(P, dtype=np.int32)
        pk = np.arange(P, dtype=np.int32)
        kept, outputs = large_p.aggregate_blocked(pid,
                                                  pk,
                                                  np.ones(P),
                                                  np.ones(P, bool),
                                                  min_v,
                                                  max_v,
                                                  min_s,
                                                  max_s,
                                                  mid,
                                                  stds,
                                                  jax.random.PRNGKey(2),
                                                  cfg,
                                                  block_partitions=32,
                                                  row_chunk=10_000)
        assert outputs["count"].sum() == pytest.approx(4.0, abs=1e-6)

    def test_ten_million_partitions_smoke(self):
        # P = 10^7 with tiny blocks of data: bounded memory, only kept
        # partitions returned.
        P = 10_000_000
        cfg, stds, (min_v, max_v, min_s, max_s, mid) = _spec(P, l0=20,
                                                             linf=8, eps=30)
        rng = np.random.default_rng(7)
        n = 50_000
        pid = rng.integers(0, 2000, n).astype(np.int32)
        # Rows concentrated on 20 partitions spread across the huge space.
        hot = rng.integers(0, P, 20)
        pk = hot[rng.integers(0, 20, n)].astype(np.int32)
        kept, outputs = large_p.aggregate_blocked(pid,
                                                  pk,
                                                  rng.uniform(0, 5, n),
                                                  np.ones(n, bool),
                                                  min_v,
                                                  max_v,
                                                  min_s,
                                                  max_s,
                                                  mid,
                                                  np.asarray(stds),
                                                  jax.random.PRNGKey(3),
                                                  cfg,
                                                  block_partitions=1 << 20)
        assert set(kept.tolist()).issubset(set(hot.tolist()))
        assert len(kept) > 0
        assert len(outputs["count"]) == len(kept)

    def test_mean_variance_blocked(self):
        # MEAN/VARIANCE exercise the nsum/nsum2 reduce columns through the
        # blocked path; noise-free public run must match the dense kernel.
        P = 500
        cfg, stds, scalars = _spec(P,
                                   private=False,
                                   metrics_list=[
                                       pdp.Metrics.MEAN, pdp.Metrics.VARIANCE
                                   ],
                                   l0=P,
                                   linf=64)
        min_v, max_v, min_s, max_s, mid = scalars
        stds = np.zeros_like(np.asarray(stds))
        pid, pk, values, valid = self._data(30_000, 400, P, seed=5)
        import jax.numpy as jnp
        kept, outputs = large_p.aggregate_blocked(pid,
                                                  pk,
                                                  values,
                                                  valid,
                                                  min_v,
                                                  max_v,
                                                  min_s,
                                                  max_s,
                                                  mid,
                                                  stds,
                                                  jax.random.PRNGKey(2),
                                                  cfg,
                                                  block_partitions=128,
                                                  row_chunk=8192)
        ref_outputs, ref_keep, _ = executor.aggregate_kernel(
            jnp.asarray(pid), jnp.asarray(pk), jnp.asarray(values),
            jnp.asarray(valid), min_v, max_v, min_s, max_s, mid,
            jnp.asarray(stds), jax.random.PRNGKey(2), cfg)
        for name in ("mean", "variance"):
            np.testing.assert_allclose(outputs[name],
                                       np.asarray(ref_outputs[name]),
                                       rtol=1e-5,
                                       atol=1e-6,
                                       err_msg=name)

    def test_secure_blocked(self):
        # Secure snapped release through the blocked path: outputs live on
        # the secure grid and match the raw aggregate to grid resolution.
        from pipelinedp_tpu.ops import secure_noise
        import dataclasses as dc
        import jax.numpy as jnp
        P = 300
        cfg, stds, (min_v, max_v, min_s, max_s,
                    mid), params, compound = _spec(P,
                                                   private=False,
                                                   l0=P,
                                                   linf=64,
                                                   eps=1e6,
                                                   full=True)
        cfg = dc.replace(cfg, secure=True)
        sens = executor.compute_noise_sensitivities(compound, params)
        thr_hi, thr_lo, gran = secure_noise.build_tables(
            np.asarray(stds), pdp.NoiseKind.LAPLACE, sensitivities=sens)
        tables = (jnp.asarray(thr_hi), jnp.asarray(thr_lo),
                  jnp.asarray(gran))
        pid, pk, values, valid = self._data(10_000, 300, P, seed=6)
        kept, outputs = large_p.aggregate_blocked(pid,
                                                  pk,
                                                  values,
                                                  valid,
                                                  min_v,
                                                  max_v,
                                                  min_s,
                                                  max_s,
                                                  mid,
                                                  np.asarray(stds),
                                                  jax.random.PRNGKey(3),
                                                  cfg,
                                                  block_partitions=128,
                                                  secure_tables=tables)
        expected = np.bincount(pk, minlength=P)
        np.testing.assert_allclose(outputs["count"], expected, atol=0.5)
        g = float(gran[0])
        ratios = outputs["count"] / g
        np.testing.assert_allclose(ratios, np.round(ratios), atol=1e-3)

    def test_empty_input(self):
        # Zero rows (e.g. everything filtered upstream) must return empty
        # results, not crash on undiscovered metric columns.
        P = 300
        cfg, stds, (min_v, max_v, min_s, max_s, mid) = _spec(P)
        kept, outputs = large_p.aggregate_blocked(
            np.zeros(0, np.int32), np.zeros(0, np.int32), np.zeros(0),
            np.zeros(0, bool), min_v, max_v, min_s, max_s, mid,
            np.asarray(stds), jax.random.PRNGKey(0), cfg,
            block_partitions=64)
        assert len(kept) == 0
        assert len(outputs["count"]) == 0
        assert len(outputs["sum"]) == 0

    def test_sparse_blocks_skipped_private(self):
        # Only blocks containing rows run device kernels in private mode.
        P = 1 << 22
        cfg, stds, (min_v, max_v, min_s, max_s, mid) = _spec(P, l0=2,
                                                             linf=4, eps=30)
        pid = np.repeat(np.arange(500, dtype=np.int32), 2)
        pk = np.where(np.arange(1000) % 2 == 0, 7, P - 3).astype(np.int32)
        kept, outputs = large_p.aggregate_blocked(
            pid, pk, np.ones(1000), np.ones(1000, bool), min_v, max_v,
            min_s, max_s, mid,
            np.zeros_like(np.asarray(stds)), jax.random.PRNGKey(1), cfg,
            block_partitions=1 << 16)
        assert set(kept.tolist()) == {7, P - 3}
        assert outputs["count"].sum() == pytest.approx(1000, abs=1e-6)

    def test_percentile_blocked_matches_dense(self):
        # Noise-free percentiles: the blocked path (multiple blocks, lazy
        # per-block descent) must agree with the dense kernel's quantiles.
        P = 3000
        metrics = [
            pdp.Metrics.COUNT,
            pdp.Metrics.PERCENTILE(25),
            pdp.Metrics.PERCENTILE(90),
        ]
        cfg, stds, (min_v, max_v, min_s, max_s, mid) = _spec(
            P, private=False, metrics_list=metrics, l0=P, linf=64)
        stds = np.zeros_like(np.asarray(stds))
        pid, pk, values, valid = self._data(30_000, 400, P, seed=5)
        kept, outputs = large_p.aggregate_blocked(pid,
                                                  pk,
                                                  values,
                                                  valid,
                                                  min_v,
                                                  max_v,
                                                  min_s,
                                                  max_s,
                                                  mid,
                                                  stds,
                                                  jax.random.PRNGKey(2),
                                                  cfg,
                                                  block_partitions=256)
        dense_out, dense_keep, _ = executor.aggregate_kernel(
            pid, pk, values, valid, min_v, max_v, min_s, max_s, mid, stds,
            jax.random.PRNGKey(7), cfg)
        assert list(kept) == list(range(P))
        for name in ("percentile_25", "percentile_90"):
            np.testing.assert_allclose(outputs[name],
                                       np.asarray(dense_out[name]),
                                       atol=(max_v - min_v) / 1e4)

    # `slow`: ~23s scale exercise. Blocked-percentile correctness stays
    # in tier-1 via test_percentile_blocked_matches_dense; this adds the
    # P=10^7 bounded-memory regime on top.
    @pytest.mark.slow
    def test_percentile_blocked_huge_p_bounded_memory(self):
        # P = 10^7 with rows concentrated in a few partitions: only
        # row-bearing blocks run; percentile values stay close to the true
        # per-partition quantiles at zero noise.
        P = 10_000_000
        metrics = [pdp.Metrics.PERCENTILE(50)]
        cfg, stds, (min_v, max_v, min_s, max_s, mid) = _spec(
            P, private=True, metrics_list=metrics, l0=4, linf=64, eps=30)
        stds = np.zeros_like(np.asarray(stds))
        rng = np.random.default_rng(9)
        n = 4000
        pid = np.arange(n, dtype=np.int32) % 997
        # Two populated partitions far apart in the space.
        pk = np.where(np.arange(n) % 2 == 0, 12345, P - 77).astype(np.int32)
        values = rng.uniform(0, 5, n)
        kept, outputs = large_p.aggregate_blocked(pid,
                                                  pk,
                                                  values,
                                                  valid := np.ones(n, bool),
                                                  min_v,
                                                  max_v,
                                                  min_s,
                                                  max_s,
                                                  mid,
                                                  stds,
                                                  jax.random.PRNGKey(4),
                                                  cfg,
                                                  block_partitions=1 << 20)
        assert set(kept.tolist()) == {12345, P - 77}
        for j, pk_id in enumerate(kept.tolist()):
            true_median = np.median(values[pk == pk_id])
            # Tree quantiles quantize to leaf width; tolerance is a couple
            # of leaves.
            leaf = (max_v - min_v) / (cfg.branching**cfg.tree_height)
            assert abs(outputs["percentile_50"][j] -
                       true_median) < 3 * leaf + 0.05

class _FakeDevice:
    """A device whose memory_stats() reports the given limit (None: the
    platform reports no stats, as the CPU does)."""

    def __init__(self, bytes_limit):
        self._bytes_limit = bytes_limit

    def memory_stats(self):
        if self._bytes_limit is None:
            return None
        return {"bytes_limit": self._bytes_limit}


class TestStagingRegimesAgree:

    LOG_ROWS = 21_011_340  # the AOL log of the keys-1e7 deployment

    @pytest.mark.parametrize("case", [
        "log_fits_16gb", "log_over_a_small_limit", "no_memory_stats",
        "percentiles_cost_rows"
    ])
    def test_row_budget_follows_device_memory(self, case):
        """row_chunk=None asks _pass1_row_budget: a share of the device's
        memory limit over the pass-1 program's bytes per row of this
        cfg; 2^24 rows where the platform reports no memory stats."""
        cfg, _, _ = _spec(10_154_742)

        def budget(cfg, bytes_limit):
            return large_p._pass1_row_budget(cfg, _FakeDevice(bytes_limit))

        if case == "log_fits_16gb":
            # Device-resident with room to spare, and still a bound.
            assert 2 * self.LOG_ROWS < budget(cfg, 16 << 30) < 1 << 31
        elif case == "log_over_a_small_limit":
            # 2 GiB cannot hold the log's pass 1 in its share: host-staged.
            assert 1 << 20 < budget(cfg, 2 << 30) < self.LOG_ROWS
        elif case == "no_memory_stats":
            assert budget(cfg, None) == 1 << 24
            assert large_p._pass1_row_budget(cfg,
                                             jax.local_devices()[0]) == 1 << 24
        else:
            cfg_pct, _, _ = _spec(10_154_742,
                                  metrics_list=[
                                      pdp.Metrics.COUNT, pdp.Metrics.SUM,
                                      pdp.Metrics.PERCENTILE(50)
                                  ])
            cfg_count, _, _ = _spec(10_154_742,
                                    metrics_list=[pdp.Metrics.COUNT])
            assert (budget(cfg_pct, 16 << 30) < budget(cfg, 16 << 30) <
                    budget(cfg_count, 16 << 30))
            # Proportional to the limit: one rule, no size classes.
            assert budget(cfg, 16 << 30) == pytest.approx(
                8 * budget(cfg, 2 << 30), rel=1e-6)

    @pytest.mark.parametrize("chosen", [False, True],
                             ids=["explicit_row_chunk", "row_chunk_none"])
    def test_device_resident_and_host_staged_agree(self, chosen,
                                                   monkeypatch):
        """The two row-staging regimes (rows fit one chunk vs chunked host
        staging) must produce the same kept set and noise-free values on
        bounded data at huge epsilon — per-chunk RNG folding differs, so
        agreement must come from determinism of the bounded computation,
        not shared draws. With row_chunk=None the regime follows the
        device's memory limit: none reported (the CPU) keeps these rows
        on the device, a limit too small for them stages them."""
        rng = np.random.default_rng(2)
        P = 1 << 12
        # Bounded by construction: each user in exactly l0=4 partitions,
        # 2 <= linf rows per pair; plus lone 1-user partitions that private
        # selection must deterministically drop.
        pid, pk, values = [], [], []
        for u in range(600):
            for j in range(4):
                target = (u % 30) * 4 + j
                for r in range(2):
                    pid.append(u)
                    pk.append(target)
                    values.append(float((u + j + r) % 5))
        for j in range(4):
            pid.append(601)
            pk.append(3000 + j)
            values.append(1.0)
        pid = np.asarray(pid, np.int32)
        pk = np.asarray(pk, np.int32)
        values = np.asarray(values)
        valid = np.ones(len(pid), bool)

        cfg, stds, (min_v, max_v, min_s, max_s, mid) = _spec(
            P,
            eps=1e7,
            metrics_list=[
                pdp.Metrics.COUNT, pdp.Metrics.SUM,
                pdp.Metrics.PERCENTILE(50)
            ])

        def run(row_chunk):
            return large_p.aggregate_blocked(pid, pk, values, valid, min_v,
                                             max_v, min_s, max_s, mid,
                                             np.asarray(stds),
                                             jax.random.PRNGKey(3), cfg,
                                             block_partitions=1 << 10,
                                             row_chunk=row_chunk)

        def resident_calls():
            return telemetry.snapshot().get("pass1_device_resident", 0)

        before = resident_calls()
        if chosen:
            kept_fast, outs_fast = run(None)
            assert resident_calls() == before + 1
            # A device whose share holds about 1,000 of the 4,804 rows.
            small = int(1000 * large_p._pass1_bytes_per_row(cfg) /
                        large_p._PASS1_MEMORY_SHARE)
            monkeypatch.setattr(large_p.rt_observability,
                                "device_bytes_limit",
                                lambda devices=None: small)
            kept_host, outs_host = run(None)
        else:
            kept_fast, outs_fast = run(1 << 20)
            assert resident_calls() == before + 1
            kept_host, outs_host = run(1024)
        assert resident_calls() == before + 1  # the host regime: no count
        assert np.array_equal(kept_fast, kept_host)
        assert len(kept_fast) == 120  # the 30*4 dense partitions
        assert np.all(np.diff(kept_fast) > 0)
        np.testing.assert_allclose(outs_fast["count"], outs_host["count"],
                                   atol=1e-2)
        np.testing.assert_allclose(outs_fast["sum"], outs_host["sum"],
                                   atol=1e-1)
        # Percentiles: leaf staging must survive the host-staged merge;
        # values are leaf-quantized and noise is negligible at huge eps.
        np.testing.assert_allclose(outs_fast["percentile_50"],
                                   outs_host["percentile_50"],
                                   atol=1e-2)


class TestPresortedReduceContract:

    def test_presorted_matches_sorted_reduce(self):
        """reduce_rows_to_partitions(presorted=True) must equal the sorting
        variant whenever rows arrive (kept-first, spk-ascending) — the
        exact order _bounded_compact_kernel emits."""
        import jax.numpy as jnp
        rng = np.random.default_rng(4)
        n, P = 4096, 64
        spk = np.sort(rng.integers(0, P, n)).astype(np.int32)
        keep = np.ones(n, bool)
        # Tail of dropped rows, as the compact kernel produces.
        keep[-128:] = False
        spk[-128:] = np.iinfo(np.int32).max
        pair = rng.random(n) < 0.3
        cols = {"sum": rng.random(n).astype(np.float32)}
        args = (jnp.asarray(spk), jnp.asarray(keep), jnp.asarray(pair),
                {k: jnp.asarray(v) for k, v in cols.items()})
        ref = executor.reduce_rows_to_partitions(*args, P, 0)
        fast = executor.reduce_rows_to_partitions(*args, P, 0,
                                                  presorted=True)
        for name in ref:
            np.testing.assert_allclose(np.asarray(fast[name]),
                                       np.asarray(ref[name]), atol=1e-5)


class TestBlockedSelection:
    """O(kept) standalone selection (large_p.select_partitions_blocked)."""

    def _mixed_data(self, P, dense_parts, n_users=60, l0=30, seed=0):
        # Dense partitions get n_users distinct ids each; every 7th other
        # partition gets exactly one id -> huge-eps selection decisions are
        # deterministic (keep prob 1 vs <= delta), so the blocked path's
        # different per-block RNG stream cannot change the outcome.
        rows = []
        for p in dense_parts:
            for u in range(n_users):
                rows.append((u * 100_003 + p, p))
        sparse = [p for p in range(P) if p not in set(dense_parts)][::7]
        for i, p in enumerate(sparse):
            rows.append((10_000_000 + i, p))
        pid = np.array([r[0] for r in rows], np.int64)
        pk = np.array([r[1] for r in rows], np.int32)
        valid = np.ones(len(rows), bool)
        return pid, pk, valid

    def _selection(self, l0):
        return selection_ops.selection_params_from_host(
            pdp.PartitionSelectionStrategy.TRUNCATED_GEOMETRIC, 1e7, 1e-5,
            l0, None)

    def test_matches_dense_kernel_across_blocks(self):
        import jax.numpy as jnp
        P, l0 = 300, 30
        dense_parts = list(range(10)) + [150] + list(range(290, 300))
        pid, pk, valid = self._mixed_data(P, dense_parts, l0=l0)
        sel = self._selection(l0)
        key = jax.random.PRNGKey(5)
        dense_keep = np.asarray(
            executor.select_partitions_kernel(jnp.asarray(pid), jnp.asarray(
                pk), jnp.asarray(valid), key, l0, P, sel))
        kept = large_p.select_partitions_blocked(pid,
                                                 pk,
                                                 valid,
                                                 key,
                                                 l0,
                                                 P,
                                                 sel,
                                                 block_partitions=64)
        np.testing.assert_array_equal(kept, np.nonzero(dense_keep)[0])
        assert kept.dtype == np.int64
        # 19 blocks >> the 8-block window: the staged-drain flush path
        # must leave the kept set and ascending order unchanged.
        kept_small = large_p.select_partitions_blocked(pid,
                                                       pk,
                                                       valid,
                                                       key,
                                                       l0,
                                                       P,
                                                       sel,
                                                       block_partitions=16)
        np.testing.assert_array_equal(kept_small, np.nonzero(dense_keep)[0])

    def test_single_block_and_empty(self):
        P, l0 = 50, 10
        sel = self._selection(l0)
        key = jax.random.PRNGKey(9)
        pid, pk, valid = self._mixed_data(P, [3, 40], l0=l0)
        kept = large_p.select_partitions_blocked(pid, pk, valid, key, l0, P,
                                                 sel)
        assert set(kept) == {3, 40}
        # All rows invalid -> every block is empty and skipped.
        kept = large_p.select_partitions_blocked(pid, pk,
                                                 np.zeros_like(valid), key,
                                                 l0, P, sel)
        assert len(kept) == 0

    def test_l0_sampling_binds(self):
        # One privacy id spread over every partition with l0=2: at most 2
        # pair contributions survive, none reach keep-probability 1, and
        # with delta tiny every partition must be dropped.
        P = 96
        pid = np.zeros(P, np.int32)
        pk = np.arange(P, dtype=np.int32)
        valid = np.ones(P, bool)
        sel = self._selection(l0=2)
        kept = large_p.select_partitions_blocked(pid, pk, valid,
                                                 jax.random.PRNGKey(1), 2, P,
                                                 sel, block_partitions=32)
        assert len(kept) == 0
