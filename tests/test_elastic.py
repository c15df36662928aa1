"""Elastic mesh degradation: device-loss tolerance for the meshed paths.

Covers the device-fatal failure class (runtime/retry.is_device_fatal),
the mesh re-plan loop (run_with_mesh_degradation) driven through all
four meshed drivers, the degradation floor (D=1 unsharded fallback, the
min_devices error), and the privacy invariant the whole design rests
on: block noise/selection keys are fold_in(final_key, b) — pure
functions of the run key and block index, independent of mesh size D —
so a run degraded onto fewer devices releases bit-identical noise.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pipelinedp_tpu as pdp
from pipelinedp_tpu import combiners, executor
from pipelinedp_tpu.aggregate_params import MechanismType
from pipelinedp_tpu.ops import selection_ops
from pipelinedp_tpu.parallel import large_p, make_mesh, sharded
from pipelinedp_tpu.parallel import mesh as mesh_lib
from pipelinedp_tpu.runtime import BlockJournal
from pipelinedp_tpu.runtime import faults
from pipelinedp_tpu.runtime import health as health_lib
from pipelinedp_tpu.runtime import retry as retry_lib
from pipelinedp_tpu.runtime import telemetry
from tests.test_release_body import kept_release

pytestmark = pytest.mark.faults

P = 1 << 12
BLOCK = 1 << 10  # 4 blocks
L0 = 2
FAST = retry_lib.RetryPolicy(max_retries=2, base_delay=0.0, max_delay=0.0)


def _spec(noise_free=False):
    params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT,
                                          pdp.Metrics.SUM],
                                 noise_kind=pdp.NoiseKind.LAPLACE,
                                 max_partitions_contributed=L0,
                                 max_contributions_per_partition=3,
                                 min_value=0.0,
                                 max_value=5.0)
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                           total_delta=1e-6)
    compound = combiners.create_compound_combiner(params, accountant)
    budget = accountant.request_budget(MechanismType.GENERIC)
    accountant.compute_budgets()
    selection = selection_ops.selection_params_from_host(
        params.partition_selection_strategy, budget.eps, budget.delta, L0,
        None)
    cfg = executor.make_kernel_config(params, compound, P,
                                      private_selection=True,
                                      selection_params=selection)
    stds = np.asarray(executor.compute_noise_stds(compound, params))
    if noise_free:
        stds = np.zeros_like(stds)
    return cfg, stds, executor.kernel_scalars(params), selection


def _data():
    """Placement-independent rows: every privacy id holds exactly ONE row
    in ONE partition (L0/Linf bounding can never drop anything, so which
    shard an id lands on — a function of mesh size D — cannot change the
    aggregate), and INTEGER values, so per-shard partial sums are exact
    in floating point and reduce ordering across different D cannot
    perturb a bit. 12 dense partitions with 120 ids each (keep
    probability ~1) + 5 single-id partitions (~0)."""
    dense_parts = (np.arange(12, dtype=np.int64) * 239 + 57) % P
    n_per = 120
    pid = (np.repeat(np.arange(n_per), 12) * 1_000_003 +
           np.tile(np.arange(12), n_per)).astype(np.int32)
    pk = np.tile(dense_parts, n_per).astype(np.int32)
    rng = np.random.default_rng(7)
    values = rng.integers(0, 6, len(pk)).astype(np.float64)
    pid = np.concatenate([pid,
                          2_000_000_000 + np.arange(5, dtype=np.int32)])
    sparse_parts = (np.arange(5, dtype=np.int64) * 911 + 13) % P
    pk = np.concatenate([pk, sparse_parts.astype(np.int32)])
    values = np.concatenate([values, np.ones(5)])
    return pid, pk, values, np.ones(len(pid), bool), np.sort(dense_parts)


class TestDeviceFatalClassification:

    def test_injected_and_markers(self):
        assert retry_lib.is_device_fatal(
            faults.InjectedDeviceLossError("x"))
        assert retry_lib.is_device_fatal(
            RuntimeError("INTERNAL: DEVICE_LOST: core dumped"))
        assert retry_lib.is_device_fatal(
            RuntimeError("UNAVAILABLE: device is lost"))
        assert not retry_lib.is_device_fatal(
            RuntimeError("UNAVAILABLE: socket closed"))
        assert not retry_lib.is_device_fatal(faults.InjectedOOMError("x"))

    def test_device_fatal_is_neither_transient_nor_oom(self):
        # Device-loss status text often carries UNAVAILABLE — the
        # device-fatal class must win, or the runtime would retry the
        # same program onto a dead chip.
        lost = RuntimeError("UNAVAILABLE: device is lost (chip 3)")
        assert not retry_lib.is_transient(lost)
        assert not retry_lib.is_oom(lost)
        assert not retry_lib.is_transient(
            faults.InjectedDeviceLossError("x"))

    def test_device_loss_fault_point_validation(self):
        faults.Fault("device_loss", point="dispatch")
        faults.Fault("device_loss", point="collective")
        with pytest.raises(ValueError):
            faults.Fault("device_loss", point="drain")

    def test_schedule_assigns_losses_sticky(self):
        sched = faults.FaultSchedule(
            [faults.Fault("device_loss", times=2)])
        sched.note_device_loss(faults.Fault("device_loss"))
        assert sched.assign_lost([0, 1, 2, 3]) == {3}
        # A later probe of the shrunken set agrees and extends.
        sched.note_device_loss(faults.Fault("device_loss"))
        assert sched.assign_lost([0, 1, 2]) == {2}
        assert sched.assign_lost([0, 1, 2, 3]) == {2, 3}


class TestBlockKeyGeometryInvariance:
    """The privacy invariant elastic degradation relies on, pinned:
    fold_in(final_key, b) block keys — and therefore the released noise
    and selection decisions — are independent of the mesh size D. With
    placement-independent inputs (one row per id per partition, integer
    values: see _data) the FULL driver outputs, noise included, must be
    bit-identical on D=1/2/4 CPU meshes and on the unsharded driver."""

    def test_blocked_aggregate_bit_identical_across_mesh_sizes(self):
        cfg, stds, (min_v, max_v, min_s, max_s, mid), _ = _spec()
        pid, pk, values, valid, expected_kept = _data()
        key = jax.random.PRNGKey(5)
        ref_kept, ref_out = large_p.aggregate_blocked(
            pid, pk, values, valid, min_v, max_v, min_s, max_s, mid, stds,
            key, cfg, block_partitions=BLOCK)
        assert np.array_equal(ref_kept, expected_kept)
        for d in (1, 2, 4):
            kept, out = large_p.aggregate_blocked_sharded(
                make_mesh(n_devices=d), pid, pk, values, valid, min_v,
                max_v, min_s, max_s, mid, stds, key, cfg,
                block_partitions=BLOCK)
            assert np.array_equal(ref_kept, kept), f"D={d}"
            for name in ("count", "sum"):
                assert np.array_equal(np.asarray(ref_out[name]),
                                      np.asarray(out[name])), \
                    f"{name} not bit-identical at D={d}"

    def test_blocked_select_bit_identical_across_mesh_sizes(self):
        _, _, _, selection = _spec()
        pid, pk, values, valid, _ = _data()
        key = jax.random.PRNGKey(9)
        ref = large_p.select_partitions_blocked(
            pid, pk, valid, key, L0, P, selection, block_partitions=BLOCK)
        for d in (1, 2, 4):
            kept = large_p.select_partitions_blocked_sharded(
                make_mesh(n_devices=d), pid, pk, valid, key, L0, P,
                selection, block_partitions=BLOCK)
            assert np.array_equal(ref, kept), f"D={d}"

    def test_dense_aggregate_noise_identical_across_mesh_sizes(self):
        cfg, stds, (min_v, max_v, min_s, max_s, mid), _ = _spec()
        pid, pk, values, valid, _ = _data()
        key = jax.random.PRNGKey(11)
        ref = None
        for d in (1, 2, 4):
            kept, out = kept_release(sharded.sharded_aggregate_arrays(
                make_mesh(n_devices=d), pid, pk, values, valid, min_v,
                max_v, min_s, max_s, mid, stds, key, cfg))
            got = (kept, out["count"], out["sum"])
            if ref is None:
                ref = got
                continue
            assert np.array_equal(ref[0], got[0]), f"kept ids differ at D={d}"
            assert np.array_equal(ref[1], got[1]), f"count differs at D={d}"
            assert np.array_equal(ref[2], got[2]), f"sum differs at D={d}"


def _blocked_agg_runner(mesh, key, journal=None, **kwargs):
    cfg, stds, (min_v, max_v, min_s, max_s, mid), _ = _spec()
    pid, pk, values, valid, _ = _data()
    kept, out = large_p.aggregate_blocked_sharded(
        mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
        stds, key, cfg, block_partitions=BLOCK, journal=journal, **kwargs)
    return kept, np.asarray(out["sum"])


def _blocked_select_runner(mesh, key, journal=None, **kwargs):
    _, _, _, selection = _spec()
    pid, pk, values, valid, _ = _data()
    kept = large_p.select_partitions_blocked_sharded(
        mesh, pid, pk, valid, key, L0, P, selection,
        block_partitions=BLOCK, journal=journal, **kwargs)
    return kept, kept


def _dense_agg_runner(mesh, key, journal=None, **kwargs):
    assert journal is None
    cfg, stds, (min_v, max_v, min_s, max_s, mid), _ = _spec()
    pid, pk, values, valid, _ = _data()
    kept, out = kept_release(sharded.sharded_aggregate_arrays(
        mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
        stds, key, cfg, **kwargs))
    return kept, out["sum"]


def _dense_select_runner(mesh, key, journal=None, **kwargs):
    assert journal is None
    _, _, _, selection = _spec()
    pid, pk, values, valid, _ = _data()
    n_kept, ids = sharded.sharded_select_partitions(
        mesh, pid, pk, valid, key, L0, P, selection, **kwargs)
    kept = np.asarray(ids)[:int(n_kept)]
    return kept, kept


# (runner, supports_journal) for each of the four meshed drivers.
DRIVERS = [
    ("blocked_aggregate", _blocked_agg_runner, True),
    ("blocked_select", _blocked_select_runner, True),
    ("dense_aggregate", _dense_agg_runner, False),
    ("dense_select", _dense_select_runner, False),
]


class TestElasticRecovery:

    @pytest.mark.parametrize("name,runner,_j",
                             DRIVERS,
                             ids=[d[0] for d in DRIVERS])
    def test_device_loss_shrinks_mesh_and_preserves_outputs(
            self, name, runner, _j):
        key = jax.random.PRNGKey(21)
        base = runner(make_mesh(n_devices=4), key)
        sched = faults.FaultSchedule(
            [faults.Fault("device_loss", point="dispatch")])
        before = telemetry.snapshot()
        job = f"elastic-{name}"
        with faults.inject(sched):
            got = runner(make_mesh(n_devices=4), key, retry=FAST,
                         elastic=True, job_id=job)
        assert sched.pending() == 0
        assert np.array_equal(base[0], got[0])
        assert np.array_equal(base[1], got[1])
        delta = telemetry.delta(before)
        assert delta.get("device_losses") == 1, delta
        assert delta.get("mesh_degradations") == 1, delta
        snap = health_lib.for_job(job).snapshot()
        assert snap["state"] == "DEGRADED", snap
        assert snap["planned_devices"] == 4, snap
        assert snap["live_devices"] == 3, snap

    def test_journaled_blocks_replay_on_degraded_mesh(self, tmp_path):
        """A device lost at block 2 must not re-dispatch blocks 0-1: they
        were consumed (and journaled) before the loss, so the degraded
        re-entry replays them from the host record."""
        key = jax.random.PRNGKey(23)
        base = _blocked_agg_runner(make_mesh(n_devices=4), key)
        journal = BlockJournal(str(tmp_path))
        sched = faults.FaultSchedule(
            [faults.Fault("device_loss", block=2, point="dispatch")])
        before = telemetry.snapshot()
        with faults.inject(sched):
            got = _blocked_agg_runner(make_mesh(n_devices=4), key,
                                      journal=journal, retry=FAST,
                                      elastic=True, job_id="elastic-replay")
        assert sched.pending() == 0
        assert np.array_equal(base[0], got[0])
        assert np.array_equal(base[1], got[1])
        delta = telemetry.delta(before)
        assert delta.get("journal_replays", 0) >= 1, delta

    def test_collective_point_loss_recovers(self):
        """A device lost during the all_to_all reshard is NOT a
        collective failure the host permutation can absorb — the mesh
        must shrink and the permutation rebuild for the new geometry."""
        cfg, stds, (min_v, max_v, min_s, max_s, mid), _ = _spec()
        pid, pk, values, valid, _ = _data()
        key = jax.random.PRNGKey(29)
        mesh = make_mesh(n_devices=4)
        base_kept, base_out = large_p.aggregate_blocked_sharded(
            mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
            stds, key, cfg, block_partitions=BLOCK)
        dev_cols = (jnp.asarray(pid), jnp.asarray(pk), jnp.asarray(values),
                    jnp.asarray(valid))
        sched = faults.FaultSchedule(
            [faults.Fault("device_loss", point="collective")])
        before = telemetry.snapshot()
        with faults.inject(sched):
            kept, out = large_p.aggregate_blocked_sharded(
                mesh, *dev_cols, min_v, max_v, min_s, max_s, mid, stds,
                key, cfg, block_partitions=BLOCK, retry=FAST, elastic=True)
        assert sched.pending() == 0
        assert np.array_equal(base_kept, kept)
        assert np.array_equal(np.asarray(base_out["sum"]),
                              np.asarray(out["sum"]))
        delta = telemetry.delta(before)
        assert delta.get("mesh_degradations") == 1, delta
        # The loss propagated to the elastic loop, not the host-fallback
        # path: a dead chip in the mesh cannot be routed around by
        # staging rows through the host.
        assert "reshard_host_fallbacks" not in delta, delta

    def test_repeated_losses_keep_degrading(self):
        key = jax.random.PRNGKey(31)
        base = _blocked_agg_runner(make_mesh(n_devices=4), key)
        sched = faults.FaultSchedule(
            [faults.Fault("device_loss", point="dispatch", times=2)])
        before = telemetry.snapshot()
        with faults.inject(sched):
            got = _blocked_agg_runner(make_mesh(n_devices=4), key,
                                      retry=FAST, elastic=True,
                                      job_id="elastic-twice")
        assert sched.pending() == 0
        assert np.array_equal(base[0], got[0])
        assert np.array_equal(base[1], got[1])
        delta = telemetry.delta(before)
        assert delta.get("device_losses") == 2, delta
        assert delta.get("mesh_degradations") == 2, delta
        snap = health_lib.for_job("elastic-twice").snapshot()
        assert snap["live_devices"] == 2, snap

    def test_without_elastic_device_loss_is_fatal(self):
        key = jax.random.PRNGKey(33)
        sched = faults.FaultSchedule(
            [faults.Fault("device_loss", point="dispatch")])
        with faults.inject(sched):
            with pytest.raises(faults.InjectedDeviceLossError):
                _blocked_agg_runner(make_mesh(n_devices=4), key,
                                    retry=FAST, job_id="elastic-off")
        snap = health_lib.for_job("elastic-off").snapshot()
        assert snap["state"] == "FAILED", snap


class TestDegradationFloor:

    @pytest.mark.parametrize("name,runner,_j",
                             DRIVERS,
                             ids=[d[0] for d in DRIVERS])
    def test_one_device_mesh_takes_unsharded_fallback(
            self, name, runner, _j, caplog):
        key = jax.random.PRNGKey(41)
        base = runner(make_mesh(n_devices=2), key)
        with caplog.at_level(logging.WARNING):
            got = runner(make_mesh(n_devices=1), key, elastic=True)
        assert np.array_equal(base[0], got[0])
        assert np.array_equal(base[1], got[1])
        warnings = [r for r in caplog.records
                    if "unsharded driver" in r.getMessage()]
        assert len(warnings) == 1, (
            f"expected exactly one clear fallback warning, got "
            f"{[r.getMessage() for r in warnings]}")

    @pytest.mark.parametrize("name,runner,supports_journal",
                             DRIVERS,
                             ids=[d[0] for d in DRIVERS])
    def test_losses_past_min_devices_raise_actionable_error(
            self, name, runner, supports_journal, tmp_path):
        key = jax.random.PRNGKey(43)
        job = f"floor-{name}"
        journal = BlockJournal(str(tmp_path)) if supports_journal else None
        kwargs = dict(retry=FAST, elastic=True, min_devices=2, job_id=job)
        if supports_journal:
            kwargs["journal"] = journal
        sched = faults.FaultSchedule(
            [faults.Fault("device_loss", point="dispatch")])
        with faults.inject(sched):
            with pytest.raises(retry_lib.MeshDegradationError) as err:
                runner(make_mesh(n_devices=2), key, **kwargs)
        msg = str(err.value)
        assert job in msg, msg
        if supports_journal:
            assert str(tmp_path) in msg, msg
        else:
            assert "no journal configured" in msg, msg
        snap = health_lib.for_job(job).snapshot()
        assert snap["state"] == "FAILED", snap

    def test_losing_the_last_device_exhausts_the_floor(self):
        """A device_loss that fires inside the unsharded fallback means
        the final surviving device died: unrecoverable by design."""
        key = jax.random.PRNGKey(47)
        sched = faults.FaultSchedule(
            [faults.Fault("device_loss", point="dispatch", times=2)])
        with faults.inject(sched):
            with pytest.raises(retry_lib.MeshDegradationError):
                _blocked_agg_runner(make_mesh(n_devices=2), key,
                                    retry=FAST, elastic=True,
                                    job_id="floor-last")


class TestHostFetchRetryKnobs:
    """Satellite: host_fetch backoff is jittered (multi-host retries must
    not fire in lockstep) and its budget threads from the backend's
    RetryPolicy instead of the hardcoded default."""

    class _Flaky:
        def __init__(self, failures):
            self.left = failures
            self.calls = 0

        def __array__(self, dtype=None, copy=None):
            self.calls += 1
            if self.left > 0:
                self.left -= 1
                raise RuntimeError("UNAVAILABLE: transient hiccup")
            return np.zeros(1)

    def test_fetch_retry_scope_threads_budget(self, monkeypatch):
        monkeypatch.setattr(mesh_lib.time, "sleep", lambda _: None)
        flaky = self._Flaky(failures=4)
        with pytest.raises(RuntimeError):
            mesh_lib.host_fetch(self._Flaky(failures=4))  # default: 2
        with mesh_lib.fetch_retry_scope(6):
            assert mesh_lib.host_fetch(flaky) is not None
        assert flaky.calls == 5

    def test_backoff_is_jittered(self, monkeypatch):
        delays = []
        monkeypatch.setattr(mesh_lib.time, "sleep", delays.append)
        with mesh_lib.fetch_retry_scope(6):
            mesh_lib.host_fetch(self._Flaky(failures=6))
        assert len(delays) == 6
        pure = [min(0.05 * 2**a, 1.0) for a in range(6)]
        # Every delay sits in [0.5, 1.0) x the pure exponential value,
        # and at least one differs from it (the lockstep schedule).
        for d, p in zip(delays, pure):
            assert 0.5 * p <= d < p + 1e-12, (d, p)
        assert any(abs(d - p) > 1e-9 for d, p in zip(delays, pure))


class TestJobScopedTimings:
    """Satellite: timing stats are scoped by job the same way counter
    forwarding is, so a receipt's per-job snapshot cannot mix phases
    from two jobs run in the same process."""

    def test_per_job_snapshots_do_not_mix(self):
        with health_lib.job_scope("timing-job-a"):
            telemetry.record_duration("phase_one", 1.0)
        with health_lib.job_scope("timing-job-b"):
            telemetry.record_duration("phase_one", 3.0)
            telemetry.record_duration("phase_two", 0.5)
        a = telemetry.timing_snapshot("timing-job-a")
        b = telemetry.timing_snapshot("timing-job-b")
        assert a["phase_one"]["count"] == 1 and a["phase_one"]["sum"] == 1.0
        assert "phase_two" not in a
        assert b["phase_one"]["sum"] == 3.0
        assert b["phase_two"]["count"] == 1
        by_job = telemetry.job_timing_snapshot()
        assert by_job["timing-job-a"] == a
        assert by_job["timing-job-b"] == b
        # The process-wide aggregate still merges everything.
        merged = telemetry.timing_snapshot()
        assert merged["phase_one"]["count"] >= 2
