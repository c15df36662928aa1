"""On-device all_to_all reshard (parallel/reshard.py) on the 8-device
virtual CPU mesh: co-location, host/device path parity on every meshed
route, and the transfer guard proving device-resident inputs never stage
rows through the host."""

import functools
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PSpec

import pipelinedp_tpu as pdp
from pipelinedp_tpu.parallel import make_mesh
from pipelinedp_tpu.parallel import reshard
from pipelinedp_tpu.parallel.mesh import SHARD_AXIS, row_sharding, shard_map
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


def _spec(P, l0=50, linf=64, eps=1.0, noisy=False):
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
    stds = np.asarray(executor.compute_noise_stds(compound, params))
    if not noisy:
        stds = np.zeros_like(stds)
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


@functools.partial(jax.jit, static_argnames=("n_shards", "salt", "mesh"))
def _gather_form_stats(pid, valid, n_shards, salt, mesh):
    """The count-stats program as the tree had it before PR 36: a per-row
    scatter-add into D + 1 bins. Kept HERE as the reference the served
    `_count_stats_kernel` is held to; never in the package."""

    def per_shard(pid_s, valid_s):
        dest = reshard._dest_shard(pid_s, n_shards, salt)
        idx = jnp.where(valid_s, dest, n_shards)
        counts = jnp.zeros((n_shards + 1,), jnp.int32).at[idx].add(
            1)[:n_shards]
        recv = jax.lax.psum(counts, SHARD_AXIS)
        max_send = jax.lax.pmax(counts.max(), SHARD_AXIS)
        return jnp.stack([max_send, recv.max(), recv.sum()])

    return shard_map(per_shard, mesh=mesh,
                     in_specs=(PSpec(SHARD_AXIS), PSpec(SHARD_AXIS)),
                     out_specs=PSpec())(pid, valid)


@functools.partial(jax.jit,
                   static_argnames=("cap_send", "out_cap", "n_shards",
                                    "salt", "mesh"))
def _gather_form_exchange(pid, pk, values, valid, cap_send, out_cap,
                          n_shards, salt, mesh):
    """The exchange as the tree had it before PR 36 (argsort by
    destination, `col[take]` into the buckets, a second argsort and
    `[keep_first]` to compact): nine per-row gathers. The reference for
    the served `_exchange_kernel`'s rows AND their order."""

    def per_shard(pid_s, pk_s, values_s, valid_s):
        n_local = pid_s.shape[0]
        dest = jnp.where(valid_s,
                         reshard._dest_shard(pid_s, n_shards, salt),
                         n_shards)
        order = jnp.argsort(dest, stable=True)
        starts = jnp.searchsorted(dest[order],
                                  jnp.arange(n_shards + 1, dtype=jnp.int32))
        j = jnp.arange(cap_send, dtype=jnp.int32)
        slot = starts[:-1, None] + j[None, :]
        slot_valid = slot < starts[1:, None]
        take = order[jnp.minimum(slot, n_local - 1)]

        def exchange(col, fill):
            bucket = jnp.where(
                slot_valid.reshape(slot_valid.shape + (1,) *
                                   (col.ndim - 1)), col[take],
                jnp.asarray(fill, col.dtype))
            return jax.lax.all_to_all(bucket, SHARD_AXIS, 0, 0, tiled=True)

        r_valid = jax.lax.all_to_all(slot_valid, SHARD_AXIS, 0, 0,
                                     tiled=True)
        r_pid = exchange(pid_s, 0)
        r_pk = exchange(pk_s, -1)
        r_val = exchange(values_s, 0)

        def flat(x):
            return x.reshape((n_shards * cap_send,) + x.shape[2:])

        fvalid = flat(r_valid)
        keep_first = jnp.argsort(~fvalid, stable=True)[:out_cap]
        return (flat(r_pid)[keep_first], flat(r_pk)[keep_first],
                flat(r_val)[keep_first], fvalid[keep_first])

    fn = shard_map(per_shard, mesh=mesh, in_specs=(PSpec(SHARD_AXIS),) * 4,
                   out_specs=(PSpec(SHARD_AXIS),) * 4)
    return fn(pid, pk, values, valid)


def _exchange_case(n_shards, per_in, values_shape=(), values_dtype="f4",
                   pid_dtype="i4", invalid_frac=0.1, heavy=0.0,
                   cap_send=None, out_cap=None, salt=0):
    """One geometry of the exchange: D shards of `per_in` rows each (the
    layout `_pad_and_shard` hands both programs). `cap_send` / `out_cap`
    None take the capacities `device_reshard_rows_by_pid` would derive
    from the stats."""
    return dict(n_shards=n_shards, per_in=per_in,
                values_shape=tuple(values_shape), values_dtype=values_dtype,
                pid_dtype=pid_dtype, invalid_frac=invalid_frac, heavy=heavy,
                cap_send=cap_send, out_cap=out_cap, salt=salt)


_EXCHANGE_CASES = {
    # D in {2, 4, 8}, the one-column float32 job.
    "d2": _exchange_case(2, 640),
    "d4": _exchange_case(4, 640),
    "d8": _exchange_case(8, 320, salt=7),
    # The value columns the callers pass: select_partitions' [n, 0] and
    # dummy [n], a one-column [n, 1], Q1's [n, 5], the tests' float64,
    # the blocked route's int64 pids.
    "values_n0": _exchange_case(4, 640, values_shape=(0,)),
    "values_n1": _exchange_case(4, 640, values_shape=(1,)),
    "values_n5": _exchange_case(4, 640, values_shape=(5,)),
    "values_n5_d8": _exchange_case(8, 176, values_shape=(5,)),
    "values_f64": _exchange_case(4, 640, values_dtype="f8"),
    "values_n2_f64_pid_i64": _exchange_case(
        2, 640, values_shape=(2,), values_dtype="f8", pid_dtype="i8"),
    "every_row_invalid": _exchange_case(4, 640, invalid_frac=1.0),
    "no_row_invalid": _exchange_case(4, 640, invalid_frac=0.0),
    # One pid holds 60 % of the rows: its bucket is near cap_send on
    # every shard, the others are thin or empty.
    "heavy_pid": _exchange_case(4, 640, heavy=0.6),
    "heavy_pid_only": _exchange_case(4, 640, heavy=1.0, invalid_frac=0.0),
    "heavy_pid_d8_n5": _exchange_case(8, 176, values_shape=(5,),
                                      heavy=0.6),
    # cap_send at and above the rows a shard holds.
    "cap_send_is_per_in": _exchange_case(4, 640, cap_send=640),
    "cap_send_above_per_in": _exchange_case(4, 640, cap_send=1024),
    # out_cap smaller than D * cap_send, larger (the output is then
    # D * cap_send long, as the tree's slice gave) and equal (the
    # benchmark's cell: 18,874,368 = 4 x 4,718,592).
    "out_cap_below_d_cap_send": _exchange_case(4, 640, cap_send=640,
                                               out_cap=640),
    "out_cap_above_d_cap_send": _exchange_case(4, 640, cap_send=256,
                                               out_cap=4096),
    "out_cap_is_d_cap_send": _exchange_case(2, 640, cap_send=512,
                                            out_cap=1024),
    # A stale cached capacity that no longer fits (the optimistic
    # dispatch): both forms truncate alike; the caller re-dispatches.
    "cap_send_too_small": _exchange_case(4, 640, heavy=0.6, cap_send=64,
                                         out_cap=192),
    "out_cap_too_small": _exchange_case(4, 640, out_cap=96),
    # An empty input: `rows_per_shard(0, D)` rows of padding a shard.
    "empty": _exchange_case(4, 8, invalid_frac=1.0),
}


class TestExchangeMovesRowsWithoutRandomAccess:
    """PR 36: the exchange carries rows as sort payloads and contiguous
    copies and counts with masked sums. Same rows out, in the same order,
    bit for bit, as the gather form above."""

    @staticmethod
    def _columns(case, mesh):
        n = case["n_shards"] * case["per_in"]
        rng = np.random.default_rng(0)
        pid = rng.integers(1, 500, n)
        pid[rng.random(n) < case["heavy"]] = 77
        pid = pid.astype(case["pid_dtype"])
        pk = rng.integers(0, 50, n).astype(np.int32)
        values = rng.uniform(0.5, 5, (n,) + case["values_shape"]).astype(
            case["values_dtype"])
        valid = rng.random(n) >= case["invalid_frac"]
        sharding = row_sharding(mesh)
        return tuple(jax.device_put(c, sharding)
                     for c in (pid, pk, values, valid))

    @pytest.mark.parametrize("name", sorted(_EXCHANGE_CASES))
    def test_same_rows_same_order_as_the_gather_form(self, name):
        case = _EXCHANGE_CASES[name]
        n_shards, salt = case["n_shards"], case["salt"]
        mesh = make_mesh(n_devices=n_shards)
        pid, pk, values, valid = self._columns(case, mesh)

        stats = np.asarray(
            reshard._count_stats_kernel(pid, valid, n_shards, salt, mesh))
        want_stats = np.asarray(
            _gather_form_stats(pid, valid, n_shards, salt, mesh))
        assert stats.dtype == want_stats.dtype and stats.shape == (3,)
        assert np.array_equal(stats, want_stats)
        assert int(stats[2]) == int(np.asarray(valid).sum())

        cap_send = case["cap_send"] or reshard.round_capacity(int(stats[0]))
        out_cap = case["out_cap"] or reshard.round_capacity(int(stats[1]))
        got = reshard._exchange_kernel(pid, pk, values, valid, cap_send,
                                       out_cap, n_shards, salt, mesh)
        want = _gather_form_exchange(pid, pk, values, valid, cap_send,
                                     out_cap, n_shards, salt, mesh)
        for g, w in zip(got, want):
            assert g.shape == w.shape and g.dtype == w.dtype
            assert g.sharding == w.sharding
            assert np.array_equal(np.asarray(g), np.asarray(w))
        if int(stats[0]) <= cap_send and int(stats[1]) <= out_cap:
            assert int(np.asarray(got[3]).sum()) == int(stats[2])

    def test_compiled_programs_hold_no_gather_and_no_scatter(self):
        # The check that would have caught the nine passes: the compiled
        # exchange and stats programs move rows by sort, slice and
        # all-to-all alone.
        mesh = make_mesh(n_devices=4)
        n = 4 * 640

        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=row_sharding(mesh))

        pid, pk = arg((n,), jnp.int32), arg((n,), jnp.int32)
        valid = arg((n,), jnp.bool_)
        texts = [
            reshard._count_stats_kernel.lower(pid, valid, 4, 0,
                                              mesh).compile().as_text()
        ]
        for values in (arg((n,), jnp.float32), arg((n, 5), jnp.float32),
                       arg((n, 0), jnp.float32)):
            texts.append(
                reshard._exchange_kernel.lower(pid, pk, values, valid, 256,
                                               768, 4, 0,
                                               mesh).compile().as_text())
        assert "all-reduce" in texts[0]
        for text in texts[1:]:
            assert "all-to-all" in text
            assert " sort(" in text
        for text in texts:
            assert "gather(" not in text
            assert "scatter(" not in text
        # ... and the gather form does hold them (the assertion can fail).
        case = _EXCHANGE_CASES["d4"]
        cols = TestExchangeMovesRowsWithoutRandomAccess._columns(case, mesh)
        old = _gather_form_exchange.lower(*cols, 256, 768, 4, 0,
                                          mesh).compile().as_text()
        assert "gather(" in old


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

    @pytest.mark.parametrize("route", ["dense_aggregate",
                                       "select_partitions",
                                       "blocked_aggregate"])
    def test_releases_what_the_gather_form_released(self, route,
                                                    monkeypatch):
        # PR 36: with the noise ON, the bounds binding (so the sample
        # depends on the rows' order) and a fixed key, each meshed route
        # releases byte for byte what it released through the parent's
        # two programs, put back in the served ones' place.
        from pipelinedp_tpu.parallel import large_p, sharded
        mesh = make_mesh(n_devices=4)

        def release():
            reshard.reset_capacity_cache()
            if route == "blocked_aggregate":
                n_parts = 100_000
                cfg, _, stds, scalars = _spec(n_parts, l0=3, linf=2,
                                              eps=30, noisy=True)
                rng = np.random.default_rng(1)
                n = 30_000
                cols = _device(
                    rng.integers(0, 3000, n).astype(np.int64),
                    (np.power(rng.random(n), 6.0) * n_parts).astype(
                        np.int32),
                    rng.uniform(0, 5, n).astype(np.float32),
                    np.ones(n, bool))
                kept, out = large_p.aggregate_blocked_sharded(
                    mesh, *cols, *scalars, stds, jax.random.PRNGKey(2),
                    cfg, block_partitions=1 << 14)
                return [np.asarray(kept), np.asarray(out["count"]),
                        np.asarray(out["sum"])]
            n_parts = 50
            cfg, selection, stds, scalars = _spec(n_parts, l0=2, linf=1,
                                                  eps=5.0, noisy=True)
            pid, pk, values, valid = _data()
            if route == "select_partitions":
                n_kept, ids = sharded.sharded_select_partitions(
                    mesh, *_device(pid, pk, valid), jax.random.PRNGKey(1),
                    2, n_parts, selection)
                return [np.asarray(ids)[:int(n_kept)]]
            kept, out = kept_release(sharded.sharded_aggregate_arrays(
                mesh, *_device(pid, pk, values, valid), *scalars, stds,
                jax.random.PRNGKey(0), cfg))
            return [kept, out["count"], out["sum"]]

        served = release()
        monkeypatch.setattr(reshard, "_exchange_kernel",
                            _gather_form_exchange)
        monkeypatch.setattr(reshard, "_count_stats_kernel",
                            _gather_form_stats)
        parent = release()
        assert len(served[0]) > 0
        for got, want in zip(served, parent):
            assert got.dtype == want.dtype
            assert got.tobytes() == want.tobytes()

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
