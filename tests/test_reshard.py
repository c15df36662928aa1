"""On-device all_to_all reshard (parallel/reshard.py) on the 8-device
virtual CPU mesh: co-location, host/device path parity on every meshed
route, and the transfer guard proving device-resident inputs never stage
rows through the host."""

import logging

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu.parallel import make_mesh
from pipelinedp_tpu.parallel import reshard
from tests.test_release_body import kept_release


def _data(n=10_000, n_ids=700, n_pk=50, seed=0, invalid_frac=0.1):
    rng = np.random.default_rng(seed)
    pid = rng.integers(0, n_ids, n).astype(np.int32)
    pk = rng.integers(0, n_pk, n).astype(np.int32)
    values = rng.uniform(0, 5, n).astype(np.float32)
    valid = rng.random(n) >= invalid_frac
    return pid, pk, values, valid


def _device(*cols):
    import jax.numpy as jnp
    return tuple(jnp.asarray(c) for c in cols)


def _spec(P, l0=50, linf=64, eps=1.0):
    from pipelinedp_tpu import combiners, executor
    from pipelinedp_tpu.aggregate_params import MechanismType
    from pipelinedp_tpu.ops import selection_ops
    params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT,
                                          pdp.Metrics.SUM],
                                 noise_kind=pdp.NoiseKind.LAPLACE,
                                 max_partitions_contributed=l0,
                                 max_contributions_per_partition=linf,
                                 min_value=0.0,
                                 max_value=5.0)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=eps, total_delta=1e-6)
    compound = combiners.create_compound_combiner(params, acc)
    budget = acc.request_budget(MechanismType.GENERIC)
    acc.compute_budgets()
    selection = selection_ops.selection_params_from_host(
        params.partition_selection_strategy, budget.eps, budget.delta,
        params.max_partitions_contributed, None)
    cfg = executor.make_kernel_config(params, compound, P,
                                      private_selection=True,
                                      selection_params=selection)
    stds = np.zeros_like(executor.compute_noise_stds(compound, params))
    return cfg, selection, stds, executor.kernel_scalars(params)


class TestDeviceReshard:

    @pytest.mark.parametrize("n_devices", [1, 4, 8])
    def test_colocates_and_preserves_rows(self, n_devices):
        mesh = make_mesh(n_devices=n_devices)
        pid, pk, values, valid = _data()
        rp, rk, rv, rva = map(
            np.asarray,
            reshard.device_reshard_rows_by_pid(
                mesh, *_device(pid, pk, values, valid)))
        assert len(rp) % n_devices == 0
        per = len(rp) // n_devices
        shard_of = {}
        for s in range(n_devices):
            sl = slice(s * per, (s + 1) * per)
            for p in rp[sl][rva[sl]]:
                assert shard_of.setdefault(int(p), s) == s
        # The exchanged row multiset is exactly the valid input rows.
        a = sorted(zip(pid[valid].tolist(), pk[valid].tolist(),
                       values[valid].tolist()))
        b = sorted(zip(rp[rva].tolist(), rk[rva].tolist(),
                       rv[rva].tolist()))
        assert a == b

    def test_bounded_padding_near_uniform(self):
        # Near-uniform ids: hash bucketing must land within the documented
        # bound — out_cap <= ~9/8 of the max shard load, and total padded
        # size within 2x of ideal even under hash imbalance.
        mesh = make_mesh(n_devices=8)
        pid, pk, values, valid = _data(n=40_000, n_ids=8000,
                                       invalid_frac=0.0)
        rp, _, _, rva = map(
            np.asarray,
            reshard.device_reshard_rows_by_pid(
                mesh, *_device(pid, pk, values, valid)))
        assert rva.sum() == 40_000
        assert len(rp) < 2.0 * 40_000

    def test_dominant_pid_warns_on_skew(self, caplog):
        # One id holding half the rows breaks the hash-balance assumption;
        # the reshard must say so instead of silently padding 8x.
        mesh = make_mesh(n_devices=8)
        n_tail = 7000
        pid = np.concatenate([
            np.zeros(7000, dtype=np.int32),
            np.arange(1, 1 + n_tail, dtype=np.int32)
        ])
        n = len(pid)
        cols = _device(pid, pid, np.ones(n, np.float32), np.ones(n, bool))
        with caplog.at_level(logging.WARNING):
            _, _, _, rva = map(
                np.asarray,
                reshard.device_reshard_rows_by_pid(mesh, *cols))
        assert rva.sum() == n
        assert any("hash" in r.message for r in caplog.records)

    def test_empty_and_zero_width_values(self):
        import jax.numpy as jnp
        mesh = make_mesh(n_devices=8)
        rp, _, rv, rva = map(
            np.asarray,
            reshard.device_reshard_rows_by_pid(
                mesh, jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.int32),
                jnp.zeros((0, 0), jnp.float32), jnp.zeros(0, bool)))
        assert rva.sum() == 0 and rv.shape[1] == 0
        # Zero-width values column (the selection path) with real rows.
        pid, pk, _, valid = _data(n=4000)
        rp, _, rv, rva = map(
            np.asarray,
            reshard.device_reshard_rows_by_pid(
                mesh, *_device(pid, pk,
                               np.zeros((len(pid), 0), np.float32), valid)))
        assert rva.sum() == valid.sum() and rv.shape[1] == 0

    def test_vector_values_column(self):
        mesh = make_mesh(n_devices=4)
        pid, pk, _, valid = _data(n=3000)
        vec = np.stack([pid.astype(np.float32),
                        np.ones(len(pid), np.float32)], axis=1)
        rp, _, rv, rva = map(
            np.asarray,
            reshard.device_reshard_rows_by_pid(
                mesh, *_device(pid, pk, vec, valid)))
        assert rv.shape[1] == 2
        # Each row's vector rode the exchange with its pid.
        np.testing.assert_allclose(rv[rva, 0], rp[rva].astype(np.float32))

    def test_stage_rows_rejects_bad_mode(self):
        mesh = make_mesh(n_devices=4)
        pid, pk, values, valid = _data(n=100)
        with pytest.raises(ValueError, match="reshard"):
            reshard.stage_rows_to_mesh(mesh, pid, pk, values, valid,
                                       "bogus")

    def test_backend_rejects_bad_mode(self):
        with pytest.raises(ValueError, match="reshard"):
            pdp.TPUBackend(reshard="bogus")


class TestTransferGuard:

    def test_guard_catches_row_fetch(self):
        import jax.numpy as jnp
        big = jnp.zeros(1 << 13)
        with reshard.forbid_row_fetches():
            with pytest.raises(AssertionError, match="device->host"):
                np.asarray(big)

    def test_guard_allows_control_tables_and_host_arrays(self):
        import jax.numpy as jnp
        from pipelinedp_tpu.parallel import mesh as mesh_lib
        with reshard.forbid_row_fetches():
            np.asarray(jnp.zeros(64))  # control-table sized: fine
            np.asarray(np.zeros(1 << 20))  # host numpy: not a transfer
            mesh_lib.host_fetch(jnp.zeros(1 << 13))  # sanctioned

    def test_device_inputs_never_stage_through_host(self):
        # The tentpole guarantee: a device-resident aggregation performs
        # ZERO O(rows) device->host fetches through reshard + kernels.
        import jax
        from pipelinedp_tpu.parallel import sharded
        mesh = make_mesh(n_devices=8)
        P = 50
        cfg, _, stds, (min_v, max_v, min_s, max_s, mid) = _spec(P)
        pid, pk, values, valid = _data()
        cols = _device(pid, pk, values, valid)
        key = jax.random.PRNGKey(0)
        with reshard.forbid_row_fetches():
            n_kept, ids, outputs, _ = sharded.sharded_aggregate_arrays(
                mesh, *cols, min_v, max_v, min_s, max_s, mid, stds, key,
                cfg)
        assert np.asarray(ids).shape == (P,)

    def test_host_inputs_would_fail_the_guard(self):
        # Sanity that the guard scope is meaningful: forcing the HOST
        # permutation on device-resident inputs downloads the rows and
        # must trip the guard.
        mesh = make_mesh(n_devices=8)
        pid, pk, values, valid = _data()
        cols = _device(pid, pk, values, valid)
        with reshard.forbid_row_fetches():
            with pytest.raises(AssertionError, match="device->host"):
                reshard.stage_rows_to_mesh(mesh, *cols, reshard="host")


class TestMeshedRouteParity:
    """Host-staged vs collective reshard must give identical results on
    every meshed route (noise-free; bounds non-binding so placement
    cannot change sampling)."""

    def test_dense_sharded_aggregate(self):
        import jax
        from pipelinedp_tpu.parallel import sharded
        mesh = make_mesh(n_devices=8)
        P = 50
        cfg, _, stds, (min_v, max_v, min_s, max_s, mid) = _spec(P,
                                                               eps=1e7)
        pid, pk, values, valid = _data()
        key = jax.random.PRNGKey(0)
        kept_h, out_h = kept_release(sharded.sharded_aggregate_arrays(
            mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
            stds, key, cfg))
        with reshard.forbid_row_fetches():
            release_d = sharded.sharded_aggregate_arrays(
                mesh, *_device(pid, pk, values, valid), min_v, max_v,
                min_s, max_s, mid, stds, key, cfg)
        kept_d, out_d = kept_release(release_d)
        assert np.array_equal(kept_h, kept_d)
        assert len(kept_h) > 0
        np.testing.assert_allclose(out_h["count"], out_d["count"],
                                   atol=1e-3)
        np.testing.assert_allclose(out_h["sum"], out_d["sum"], rtol=1e-4,
                                   atol=1e-3)

    def test_reshard_mode_escape_hatches(self):
        import jax
        from pipelinedp_tpu.parallel import sharded
        mesh = make_mesh(n_devices=8)
        P = 50
        cfg, _, stds, (min_v, max_v, min_s, max_s, mid) = _spec(P)
        pid, pk, values, valid = _data()
        key = jax.random.PRNGKey(0)
        kept_ref, _ = kept_release(sharded.sharded_aggregate_arrays(
            mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
            stds, key, cfg))
        # host mode on device inputs, device mode on host inputs.
        kept_h, _ = kept_release(sharded.sharded_aggregate_arrays(
            mesh, *_device(pid, pk, values, valid), min_v, max_v, min_s,
            max_s, mid, stds, key, cfg, reshard="host"))
        kept_d, _ = kept_release(sharded.sharded_aggregate_arrays(
            mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
            stds, key, cfg, reshard="device"))
        assert np.array_equal(kept_ref, kept_h)
        assert np.array_equal(kept_ref, kept_d)

    def test_sharded_select_partitions(self):
        import jax
        from pipelinedp_tpu.parallel import sharded
        mesh = make_mesh(n_devices=8)
        P = 50
        _, selection, _, _ = _spec(P, eps=1e7)
        pid, pk, _, valid = _data()
        key = jax.random.PRNGKey(1)
        n_h, ids_h = sharded.sharded_select_partitions(
            mesh, pid, pk, valid, key, 50, P, selection)
        with reshard.forbid_row_fetches():
            n_d, ids_d = sharded.sharded_select_partitions(
                mesh, *_device(pid, pk, valid), key, 50, P, selection)
        assert int(n_h) == int(n_d) > 0
        assert np.array_equal(np.asarray(ids_h)[:int(n_h)],
                              np.asarray(ids_d)[:int(n_d)])

    def test_blocked_aggregate(self):
        import jax
        import jax.numpy as jnp
        from pipelinedp_tpu.parallel import large_p
        mesh = make_mesh(n_devices=8)
        P = 100_000
        cfg, _, stds, (min_v, max_v, min_s, max_s, mid) = _spec(
            P, l0=64, linf=8, eps=30)
        rng = np.random.default_rng(1)
        n = 30_000
        pid = rng.integers(0, 3000, n).astype(np.int64)
        pk = (np.power(rng.random(n), 6.0) * P).astype(np.int32)
        values = rng.uniform(0, 5, n).astype(np.float32)
        valid = np.ones(n, bool)
        key = jax.random.PRNGKey(2)
        kept_h, out_h = large_p.aggregate_blocked_sharded(
            mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
            stds, key, cfg, block_partitions=1 << 14)
        with reshard.forbid_row_fetches():
            kept_d, out_d = large_p.aggregate_blocked_sharded(
                mesh, jnp.asarray(pid), jnp.asarray(pk),
                jnp.asarray(values), jnp.asarray(valid), min_v, max_v,
                min_s, max_s, mid, stds, key, cfg,
                block_partitions=1 << 14)
        assert len(kept_h) > 0
        assert np.array_equal(kept_h, kept_d)
        np.testing.assert_allclose(out_h["count"], out_d["count"],
                                   atol=1e-3)
        np.testing.assert_allclose(out_h["sum"], out_d["sum"], rtol=1e-4,
                                   atol=1e-3)

    def test_blocked_select_partitions(self):
        import jax
        from pipelinedp_tpu.parallel import large_p
        mesh = make_mesh(n_devices=8)
        P, l0 = 100_000, 30
        _, selection, _, _ = _spec(P, l0=l0, eps=1e7)
        rows = []
        for p in (5, 50_000, 99_999):
            for u in range(80):
                rows.append((u * 100_003 + p, p))
        pid = np.array([r[0] for r in rows], np.int64)
        pk = np.array([r[1] for r in rows], np.int32)
        valid = np.ones(len(rows), bool)
        key = jax.random.PRNGKey(5)
        kept_h = large_p.select_partitions_blocked_sharded(
            mesh, pid, pk, valid, key, l0, P, selection,
            block_partitions=1 << 14)
        with reshard.forbid_row_fetches():
            kept_d = large_p.select_partitions_blocked_sharded(
                mesh, *_device(pid, pk, valid), key, l0, P, selection,
                block_partitions=1 << 14)
        assert kept_h.tolist() == [5, 50_000, 99_999]
        assert np.array_equal(kept_h, kept_d)

    def test_engine_streamed_ingest_device_resident(self):
        # End to end: streamed-ingest EncodedData through the meshed
        # engine keeps its columns device-resident (auto -> collective
        # reshard) and must match LocalBackend.
        from pipelinedp_tpu import ingest
        rows = [("u%d" % (i % 50), "pk%d" % (i % 7), float(i % 5))
                for i in range(1000)]
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
        ex = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                partition_extractor=lambda r: r[1],
                                value_extractor=lambda r: r[2])

        def agg(backend, data):
            acc = pdp.NaiveBudgetAccountant(total_epsilon=1e7,
                                            total_delta=1e-5)
            engine = pdp.DPEngine(acc, backend)
            result = engine.aggregate(data, params, ex)
            acc.compute_budgets()
            return dict(result)

        expected = agg(pdp.LocalBackend(seed=0), rows)
        actual = agg(pdp.TPUBackend(mesh=mesh, noise_seed=0), encoded)
        assert set(actual) == set(expected)
        for pk in expected:
            assert actual[pk].count == pytest.approx(expected[pk].count,
                                                     abs=0.05)
            assert actual[pk].sum == pytest.approx(expected[pk].sum,
                                                   abs=0.05)
