"""Numeric armor: overflow-safe accumulation, the fail-closed release
sentinel, floating-point-safe discrete noise, and the extreme_values
fault kind.

The contracts under test:

  * **The release sentinel** — every released column is scanned on
    device (one scalar reduction) for NaN/Inf/saturation before any
    decode or journal write; a trip raises a typed
    ReleaseIntegrityError (NumericOverflowError for overflow in safe
    mode), records release_sentinel_trips, and releases NOTHING.
    Unkept slots never trip it.
  * **Compensated accumulation** — numeric_mode="safe" runs the fused
    segment sums through a TwoSum (hi/lo) associative scan: exact for
    integer-valued f32 workloads far past the 2**24 naive-f32 cliff,
    matching a float64 oracle bit-for-bit; "fast" (the default) keeps
    the historical bit-identical path and the two modes agree wherever
    f32 was already exact.
  * **Extreme inputs through the drivers** — clip-bound-magnitude
    values (~3e38) overflow the f32 prefix sums and fail CLOSED with a
    typed error on the dense, meshed and blocked drivers; denormal
    inputs (1e-40) release finite values without tripping anything.
  * **Fail-closed budget discipline** — an overflow abort registers no
    new mechanisms (the two-phase budget protocol already froze the
    graph) and yields zero released partitions.
  * **The extreme_values fault kind** — validated modes (nan |
    magnitude), one-partition poisoning at every driver ingest seam,
    pinned trials proving the sentinel trips and the service converts
    the abort into a typed shed.
  * **Discrete/snapped mechanisms** — geometric noise for counts is
    exactly integer-valued; snapped Laplace/Gaussian land exactly on
    their declared power-of-two grid with the Delta + g widened
    calibration; threefry-keyed draws replay bit-identically;
    distribution parity (moments + CDF) against the continuous
    mechanisms within grid tolerance.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pipelinedp_tpu as pdp
from pipelinedp_tpu import budget_accounting as ba
from pipelinedp_tpu import dp_computations as dp
from pipelinedp_tpu import numeric as rt_numeric
from pipelinedp_tpu import pipeline_backend
from pipelinedp_tpu.aggregate_params import MechanismType
from pipelinedp_tpu.ops import segment_ops
from pipelinedp_tpu.runtime import faults
from pipelinedp_tpu.runtime import telemetry
from pipelinedp_tpu.parallel import make_mesh
from pipelinedp_tpu.service import DPAggregationService, JobSpec, JobStatus

pytestmark = pytest.mark.numeric_armor

F32_SAT = rt_numeric.SATURATION_LIMIT  # finfo(f32).max / 2


@pytest.fixture
def f32_compute():
    """Run the engine at TPU-native f32 precision.

    The test harness forces jax_enable_x64 on (tests/conftest.py), which
    widens executor._ftype() to f64 — the very cliff/overflow behavior
    this PR armors against disappears. These tests flip the flag off for
    their duration so the accumulators behave exactly as on device."""
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", old)


def _cols(**arrays):
    return {k: jnp.asarray(v, dtype=jnp.float32) for k, v in arrays.items()}


class TestReleaseSentinel:

    def test_clean_columns_pass_both_modes(self):
        cols = _cols(count=[1.0, 2.0, 3.0, 0.0])
        for mode in ("fast", "safe"):
            rt_numeric.check_release(cols, n_kept=jnp.int32(3),
                                     numeric_mode=mode)

    def test_nan_in_kept_rows_trips_fast_mode(self):
        cols = _cols(count=[1.0, np.nan, 3.0, 0.0])
        before = telemetry.snapshot()
        with pytest.raises(rt_numeric.ReleaseIntegrityError, match="NaN"):
            rt_numeric.check_release(cols, n_kept=jnp.int32(3),
                                     numeric_mode="fast")
        assert telemetry.delta(before).get("release_sentinel_trips") == 1

    def test_nan_in_unkept_rows_is_ignored(self):
        cols = _cols(count=[1.0, 2.0, np.nan, np.nan])
        rt_numeric.check_release(cols, n_kept=jnp.int32(2),
                                 numeric_mode="safe")

    def test_overflow_is_typed_in_safe_mode_advisory_in_fast(self):
        """Inf (and finite saturation) without NaN classifies as
        NumericOverflowError in safe mode; fast mode treats finite
        saturation as advisory (no raise — bit-identity preserved) but
        still refuses Inf."""
        sat = _cols(s=[F32_SAT * 1.5, 1.0])
        rt_numeric.check_release(sat, n_kept=jnp.int32(2),
                                 numeric_mode="fast")  # advisory only
        before = telemetry.snapshot()
        with pytest.raises(rt_numeric.NumericOverflowError):
            rt_numeric.check_release(sat, n_kept=jnp.int32(2),
                                     numeric_mode="safe")
        d = telemetry.delta(before)
        assert d.get("numeric_overflows") == 1
        assert d.get("release_sentinel_trips") == 1
        inf = _cols(s=[np.inf, 1.0])
        with pytest.raises(rt_numeric.ReleaseIntegrityError):
            rt_numeric.check_release(inf, n_kept=jnp.int32(2),
                                     numeric_mode="fast")

    def test_overflow_error_is_a_release_integrity_error(self):
        assert issubclass(rt_numeric.NumericOverflowError,
                          rt_numeric.ReleaseIntegrityError)

    def test_integer_columns_are_exempt(self):
        cols = {"ids": jnp.asarray([2**30, 5], dtype=jnp.int32)}
        rt_numeric.check_release(cols, n_kept=jnp.int32(2),
                                 numeric_mode="safe")

    def test_2d_columns_gate_on_rows(self):
        col = np.ones((4, 3), np.float32)
        col[3, 1] = np.nan
        rt_numeric.check_release({"q": jnp.asarray(col)},
                                 n_kept=jnp.int32(3), numeric_mode="safe")
        with pytest.raises(rt_numeric.ReleaseIntegrityError):
            rt_numeric.check_release({"q": jnp.asarray(col)},
                                     n_kept=jnp.int32(4),
                                     numeric_mode="safe")


# An integer-valued f32 stream a naive f32 cumsum gets WRONG: after the
# 2**24 prefix, +1.0 increments vanish (f32 spacing there is 2.0).
_CLIFF = float(1 << 24)


class TestCompensatedAccumulation:

    def test_compensated_scan_matches_f64_oracle_past_the_cliff(self):
        x = np.ones(64, np.float32)
        x[0] = _CLIFF
        hi, lo = segment_ops.compensated_cumsum(jnp.asarray(x))
        starts = jnp.asarray([0, 64], dtype=jnp.int32)
        safe = np.asarray(segment_ops.compensated_segment_diff(
            hi, lo, starts))
        oracle = np.cumsum(x.astype(np.float64))[-1]
        # Correctly rounded: the f32 nearest to the exact f64 sum
        # (2**24 + 63 itself is odd, below f32 resolution there).
        assert float(safe[0]) == float(np.float32(oracle))
        naive = float(np.asarray(jnp.cumsum(jnp.asarray(x),
                                            dtype=jnp.float32))[-1])
        assert naive != float(np.float32(oracle))  # the cliff is real

    def test_integer_and_f64_inputs_pass_through_exactly(self):
        xi = jnp.asarray([5, 7, 9], dtype=jnp.int32)
        hi, lo = segment_ops.compensated_cumsum(xi)
        assert np.array_equal(np.asarray(hi), [5, 12, 21])
        assert not np.asarray(lo).any()

    def test_kernel_config_numeric_mode_is_static_and_defaults_fast(self):
        from pipelinedp_tpu import combiners, executor
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT], max_partitions_contributed=1,
            max_contributions_per_partition=1, min_value=0.0,
            max_value=1.0)
        accountant = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                               total_delta=1e-6)
        compound = combiners.create_compound_combiner(params, accountant)
        cfg = executor.make_kernel_config(params, compound, 8, False, None)
        assert cfg.numeric_mode == "fast"
        cfg2 = executor.make_kernel_config(params, compound, 8, False,
                                           None, numeric_mode="safe")
        assert cfg2.numeric_mode == "safe"


# Engine-level workloads. Epsilon 1e12 makes the Laplace noise scale
# sub-integer for the released magnitudes below, so round() recovers
# the exact aggregate regardless of whether the residual host-side f64
# noise survives the release dtype.
_EXACT_EPS = 1e12


def _run_engine(backend, rows, params, public, total_epsilon=_EXACT_EPS):
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=total_epsilon,
                                           total_delta=1e-5)
    engine = pdp.DPEngine(accountant, backend)
    ext = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                             partition_extractor=lambda r: r[1],
                             value_extractor=lambda r: r[2])
    result = engine.aggregate(rows, params, ext, public)
    accountant.compute_budgets()
    return dict(result), accountant


def _cliff_params():
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        max_partitions_contributed=1,
        max_contributions_per_partition=3,
        min_value=0.0, max_value=_CLIFF)


# One partition whose exact sum (2**24 + 2) is unreachable by a naive
# f32 accumulation (it rounds to 2**24).
_CLIFF_ROWS = [("u1", "A", _CLIFF), ("u2", "A", 1.0), ("u3", "A", 1.0)]
_CLIFF_ORACLE = _CLIFF + 2.0


def _backends(numeric_mode):
    """The driver matrix: dense solo, dense meshed, blocked solo,
    blocked meshed."""
    mesh = make_mesh(n_devices=8)
    return {
        "dense": pdp.TPUBackend(noise_seed=5, numeric_mode=numeric_mode),
        "meshed": pdp.TPUBackend(noise_seed=5, mesh=mesh,
                                 numeric_mode=numeric_mode),
        "blocked": pdp.TPUBackend(noise_seed=5,
                                  large_partition_threshold=1,
                                  block_partitions=8,
                                  numeric_mode=numeric_mode),
        "blocked-meshed": pdp.TPUBackend(noise_seed=5, mesh=mesh,
                                         large_partition_threshold=1,
                                         block_partitions=8,
                                         numeric_mode=numeric_mode),
    }


class TestNumericModeThroughDrivers:

    @pytest.mark.parametrize("driver", ["dense", "meshed", "blocked",
                                        "blocked-meshed"])
    def test_safe_mode_matches_f64_oracle_on_integer_workload(
            self, driver, f32_compute):
        backend = _backends("safe")[driver]
        result, _ = _run_engine(backend, _CLIFF_ROWS, _cliff_params(),
                                ["A"])
        assert round(result["A"].sum) == _CLIFF_ORACLE
        assert round(result["A"].count) == 3

    @pytest.mark.parametrize("driver", ["dense", "blocked"])
    def test_fast_mode_documents_the_f32_error(self, driver, f32_compute):
        """The historical path loses the +2 past the cliff — the exact
        error class safe mode exists to remove."""
        backend = _backends("fast")[driver]
        result, _ = _run_engine(backend, _CLIFF_ROWS, _cliff_params(),
                                ["A"])
        assert round(result["A"].sum) == _CLIFF  # wrong by exactly 2
        assert round(result["A"].count) == 3

    @pytest.mark.parametrize("driver", ["dense", "meshed", "blocked",
                                        "blocked-meshed"])
    def test_fast_and_safe_agree_where_f32_is_exact(self, driver):
        rows = [("u1", "A", 3.0), ("u2", "A", 1.0), ("u2", "B", 2.0),
                ("u3", "B", 4.0)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=2,
            max_contributions_per_partition=2,
            min_value=0.0, max_value=5.0)
        fast, _ = _run_engine(_backends("fast")[driver], rows, params,
                              ["A", "B"])
        safe, _ = _run_engine(_backends("safe")[driver], rows, params,
                              ["A", "B"])
        for p in ("A", "B"):
            assert fast[p].count == safe[p].count
            assert fast[p].sum == safe[p].sum

    def test_default_mode_releases_are_bit_stable(self):
        """numeric_mode never entered KernelConfig before this PR; the
        default must compile the identical program — two default-mode
        runs (and an explicit fast run) release identical bits."""
        params = _cliff_params()
        a, _ = _run_engine(pdp.TPUBackend(noise_seed=5), _CLIFF_ROWS,
                           params, ["A"])
        b, _ = _run_engine(pdp.TPUBackend(noise_seed=5), _CLIFF_ROWS,
                           params, ["A"])
        c, _ = _run_engine(pdp.TPUBackend(noise_seed=5,
                                          numeric_mode="fast"),
                           _CLIFF_ROWS, params, ["A"])
        assert a["A"].sum == b["A"].sum == c["A"].sum
        assert a["A"].count == b["A"].count == c["A"].count


_F32_MAX = float(np.finfo(np.float32).max)


class TestExtremeInputs:

    @pytest.mark.parametrize("driver", ["dense", "meshed", "blocked",
                                        "blocked-meshed"])
    def test_clip_bound_magnitude_inputs_fail_closed(self, driver,
                                                     f32_compute):
        """Rows at ~3e38 under a clip bound that admits them: the f32
        prefix sums overflow, and every driver refuses the release with
        a typed error instead of publishing Inf/NaN."""
        rows = [(f"u{i}", "A" if i % 2 else "B", 3e38) for i in range(12)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM],
            max_partitions_contributed=1,
            max_contributions_per_partition=1,
            min_value=0.0, max_value=_F32_MAX)
        backend = _backends("safe")[driver]
        before = telemetry.snapshot()
        with pytest.raises(rt_numeric.ReleaseIntegrityError):
            _run_engine(backend, rows, params, ["A", "B"])
        assert telemetry.delta(before).get("release_sentinel_trips",
                                           0) >= 1

    def test_overflow_in_safe_mode_is_numeric_overflow_no_partial_release(
            self, f32_compute):
        """Safe mode classifies the trip as NumericOverflowError; zero
        partitions are released and zero mechanisms register beyond the
        graph-time set (no duplicate budget registrations)."""
        rows = [("u1", "A", 3e38), ("u2", "A", 3e38), ("u3", "A", 3e38)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM],
            max_partitions_contributed=1,
            max_contributions_per_partition=1,
            min_value=0.0, max_value=_F32_MAX)
        backend = pdp.TPUBackend(noise_seed=5, numeric_mode="safe")
        accountant = pdp.NaiveBudgetAccountant(total_epsilon=_EXACT_EPS,
                                               total_delta=1e-5)
        engine = pdp.DPEngine(accountant, backend)
        ext = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                 partition_extractor=lambda r: r[1],
                                 value_extractor=lambda r: r[2])
        result = engine.aggregate(rows, params, ext, ["A"])
        accountant.compute_budgets()
        registered = accountant.mechanism_count
        released = []
        before = telemetry.snapshot()
        with pytest.raises(rt_numeric.NumericOverflowError):
            for item in result:
                released.append(item)
        assert released == []  # fail closed: nothing escaped
        assert accountant.mechanism_count == registered
        d = telemetry.delta(before)
        assert d.get("numeric_overflows") == 1
        assert d.get("release_sentinel_trips") == 1

    def test_denormal_inputs_release_finite_values(self, f32_compute):
        rows = [("u1", "A", 1e-40), ("u2", "A", 1e-40)]
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=1,
            max_contributions_per_partition=1,
            min_value=0.0, max_value=1.0)
        for mode in ("fast", "safe"):
            result, _ = _run_engine(
                pdp.TPUBackend(noise_seed=5, numeric_mode=mode), rows,
                params, ["A"])
            assert math.isfinite(result["A"].sum)
            assert abs(result["A"].sum) < 1e-6  # denormals don't explode
            assert round(result["A"].count) == 2


class TestExtremeValuesFaultKind:

    def test_mode_vocabulary_is_validated(self):
        assert faults.Fault("extreme_values").mode == "nan"
        assert faults.Fault("extreme_values",
                            mode="magnitude").mode == "magnitude"
        with pytest.raises(ValueError, match="mode"):
            faults.Fault("extreme_values", mode="truncate")
        with pytest.raises(ValueError, match="mode"):
            faults.Fault("corrupt", mode="nan")

    def test_maybe_extreme_rows_poisons_one_partition(self):
        values = np.ones(16, np.float64)
        pk = np.array([3, 7] * 8, np.int32)
        assert faults.maybe_extreme_rows(values, pk) is None  # no schedule
        sched = faults.FaultSchedule([faults.Fault("extreme_values")])
        before = telemetry.snapshot()
        with faults.inject(sched):
            poisoned = faults.maybe_extreme_rows(values, pk)
            again = faults.maybe_extreme_rows(values, pk)
        assert again is None  # one firing, consumed
        assert telemetry.delta(before).get("injected_faults") == 1
        nan_rows = np.isnan(poisoned)
        assert nan_rows[pk == 3].all() and not nan_rows[pk == 7].any()
        assert (values == 1.0).all()  # caller's array untouched

    def test_pinned_driver_trial_magnitude_trips_the_sentinel(
            self, f32_compute):
        """The reproducer trial: an extreme_values magnitude fault at
        the blocked driver's ingest, wide clip bounds so the pattern
        survives bounding — the poisoned block must die PRE-JOURNAL
        with a typed error, never become a durable record."""
        from pipelinedp_tpu import combiners, executor
        from pipelinedp_tpu.parallel import large_p
        P, n = 64, 4096
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.SUM],
            noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=4,
            max_contributions_per_partition=8,
            min_value=-_F32_MAX, max_value=_F32_MAX)
        accountant = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                               total_delta=1e-6)
        compound = combiners.create_compound_combiner(params, accountant)
        accountant.compute_budgets()
        cfg = executor.make_kernel_config(params, compound, P, False, None)
        stds = np.asarray(executor.compute_noise_stds(compound, params))
        rng = np.random.default_rng(11)
        pid = rng.integers(0, 128, n).astype(np.int32)
        pk = rng.integers(0, P, n).astype(np.int32)
        values = rng.uniform(0, 5, n)
        min_v, max_v, min_s, max_s, mid = executor.kernel_scalars(params)
        sched = faults.FaultSchedule(
            [faults.Fault("extreme_values", mode="magnitude")])
        before = telemetry.snapshot()
        with faults.inject(sched):
            with pytest.raises(rt_numeric.ReleaseIntegrityError):
                large_p.aggregate_blocked(
                    pid, pk, values, np.ones(n, bool), min_v, max_v,
                    min_s, max_s, mid, stds, jax.random.PRNGKey(23),
                    cfg, block_partitions=16)
        d = telemetry.delta(before)
        assert d.get("release_sentinel_trips", 0) >= 1
        assert d.get("injected_faults") == 1

    def test_pinned_service_trial_sheds_with_typed_error(self):
        """The service half: a NaN-mode extreme_values fault during a
        job's run converts into a typed SHED (not a wedged worker, not
        a silent FAILED) and counts service_jobs_shed."""
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=1,
            max_contributions_per_partition=1,
            min_value=0.0, max_value=5.0)
        ext = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                 partition_extractor=lambda r: r[1],
                                 value_extractor=lambda r: r[2])
        spec = JobSpec(params=params, epsilon=1.0, delta=1e-6,
                       data_extractors=ext, noise_seed=29,
                       public_partitions=["A"])
        rows = [("u1", "A", 1.0), ("u2", "A", 2.0)]
        sched = faults.FaultSchedule([faults.Fault("extreme_values")])
        before = telemetry.snapshot()
        with faults.inject(sched, scope="process"):
            with DPAggregationService(pdp.TPUBackend()) as svc:
                handle = svc.submit("tenant-nx", spec, rows)
                with pytest.raises(rt_numeric.ReleaseIntegrityError):
                    handle.result(timeout=120)
                assert handle.status == JobStatus.SHED
        d = telemetry.delta(before)
        assert d.get("service_jobs_shed") == 1
        assert d.get("release_sentinel_trips", 0) >= 1


KEY = jax.random.PRNGKey(77)


class TestDiscreteMechanisms:

    def test_geometric_releases_are_integers_and_deterministic(self):
        a = dp.GeometricMechanism(0.7, 2, key=KEY)
        b = dp.GeometricMechanism(0.7, 2, key=KEY)
        draws_a = [a.add_noise(10) for _ in range(32)]
        draws_b = [b.add_noise(10) for _ in range(32)]
        assert draws_a == draws_b
        assert all(v == int(v) for v in draws_a)
        assert len(set(draws_a)) > 1  # the counter advances per draw

    def test_geometric_moment_parity_with_laplace(self):
        """The discrete Laplace tracks the continuous one: mean ~0 and
        std within a grid-step tolerance of the declared std."""
        m = dp.GeometricMechanism(0.4, 1, key=KEY)
        draws = np.array([m.add_noise(0) for _ in range(4000)])
        assert abs(draws.mean()) < 4 * m.std / math.sqrt(len(draws))
        assert abs(draws.std() - m.std) < 0.1 * m.std + 1.0

    @pytest.mark.parametrize("mech_cls,args", [
        (dp.SnappedLaplaceMechanism, (1.0, 4.0)),
        (dp.SnappedGaussianMechanism, (1.0, 1e-6, 4.0)),
    ])
    def test_snapped_releases_land_exactly_on_the_grid(self, mech_cls,
                                                       args):
        m = mech_cls(*args, snap_grid_bits=-6, key=KEY)
        g = m.grid
        assert g >= 2.0 ** -6 and math.log2(g) == int(math.log2(g))
        for i in range(64):
            v = m.add_noise(100.0 + i / 7.0)
            assert v == round(v / g) * g  # exactly on the grid

    def test_snap_widens_sensitivity_never_budget(self):
        m = dp.SnappedLaplaceMechanism(2.0, 8.0, key=KEY)
        assert m.sensitivity == 8.0 + m.grid
        assert m.epsilon == 2.0  # the granted budget is unchanged
        # Widened scale: b = (Delta + g) / eps > Delta / eps.
        assert m.noise_parameter == m.sensitivity / 2.0

    def test_snapped_cdf_parity_with_continuous(self):
        """KS-style check: snapped Laplace draws against the continuous
        Laplace CDF, tolerance one grid step plus sampling error."""
        m = dp.SnappedLaplaceMechanism(1.0, 1.0, key=KEY)
        n = 4000
        draws = np.sort([m.add_noise(0.0) for _ in range(n)])
        b = m.noise_parameter
        cdf = np.where(draws < 0, 0.5 * np.exp(draws / b),
                       1.0 - 0.5 * np.exp(-draws / b))
        empirical = (np.arange(n) + 0.5) / n
        ks = np.max(np.abs(cdf - empirical))
        assert ks < 1.7 / math.sqrt(n) + m.grid / b

    def test_create_discrete_mechanism_dispatch(self):
        sens = dp.Sensitivities(l0=2, linf=3.0)
        lap = ba.MechanismSpec(MechanismType.LAPLACE)
        lap.set_eps_delta(1.0, 0.0)
        gau = ba.MechanismSpec(MechanismType.GAUSSIAN)
        gau.set_eps_delta(1.0, 1e-6)
        m = dp.create_discrete_mechanism(lap, sens, value_is_integer=True,
                                         key=KEY)
        assert isinstance(m, dp.GeometricMechanism)
        m = dp.create_discrete_mechanism(lap, sens, key=KEY)
        assert isinstance(m, dp.SnappedLaplaceMechanism)
        m = dp.create_discrete_mechanism(gau, sens, snap_grid_bits=-4,
                                         key=KEY)
        assert isinstance(m, dp.SnappedGaussianMechanism)
        assert m.grid >= 2.0 ** -4

    def test_discrete_draws_record_snapped_releases(self):
        before = telemetry.snapshot()
        dp.GeometricMechanism(1.0, 1, key=KEY).add_noise(3)
        dp.SnappedLaplaceMechanism(1.0, 1.0, key=KEY).add_noise(3.0)
        assert telemetry.delta(before).get("snapped_releases") == 2

    def test_snap_grid_bits_floors_the_secure_noise_tables(self):
        from pipelinedp_tpu.aggregate_params import NoiseKind
        from pipelinedp_tpu.ops import secure_noise
        _, _, g_default = secure_noise.build_table(2.0, NoiseKind.LAPLACE,
                                                   sensitivity=1.0)
        _, _, g_floored = secure_noise.build_table(
            2.0, NoiseKind.LAPLACE, sensitivity=1.0, grid_floor=0.25)
        assert g_floored >= 0.25 >= g_default
        assert math.log2(g_floored) == int(math.log2(g_floored))


class TestKnobs:

    def test_backend_rejects_bad_numeric_knobs(self):
        with pytest.raises(ValueError, match="numeric_mode"):
            pipeline_backend.TPUBackend(numeric_mode="fancy")
        with pytest.raises(ValueError, match="snap_grid_bits"):
            pipeline_backend.TPUBackend(snap_grid_bits=1.5)
        with pytest.raises(ValueError, match="snap_grid_bits"):
            pipeline_backend.TPUBackend(snap_grid_bits=65)
        with pytest.raises(ValueError, match="snap_grid_bits"):
            pipeline_backend.TPUBackend(snap_grid_bits=True)

    def test_boundary_values_are_accepted_and_threaded(self):
        b = pipeline_backend.TPUBackend(numeric_mode="safe",
                                        snap_grid_bits=-64)
        view = b.for_job(job_id="j1")
        assert view.numeric_mode == "safe"
        assert view.snap_grid_bits == -64
        assert pipeline_backend.TPUBackend().numeric_mode == "fast"
        assert pipeline_backend.TPUBackend().snap_grid_bits is None
