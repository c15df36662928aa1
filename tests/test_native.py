"""Tests for the native C++ DP primitives (pipelinedp_tpu/native).

Follows the reference's statistical-test strategy (SURVEY.md §4.4): large
sample draws checked for mean/std and distributional closeness (KS) against
the floating-point reference distributions, plus exact parity checks of the
calibration / partition-selection closed forms against the Python
implementations they mirror.
"""

import math

import numpy as np
import pytest
from scipy import stats

from pipelinedp_tpu import dp_computations
from pipelinedp_tpu import native
from pipelinedp_tpu import partition_selection
from pipelinedp_tpu.aggregate_params import PartitionSelectionStrategy

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")

N = 200_000


@pytest.fixture(autouse=True)
def deterministic_rng():
    native.seed_test_rng(12345)
    yield
    native.use_secure_rng()


class TestSecureNoiseDistributions:

    def test_discrete_laplace_matches_continuous(self):
        # DLap with scale t/s = 1000/1 ≈ continuous Laplace(1000).
        samples = native.discrete_laplace(1000, 1, N).astype(np.float64)
        ks = stats.kstest(samples / 1000.0, stats.laplace(scale=1).cdf)
        assert ks.statistic < 0.01, ks

    def test_discrete_gaussian_matches_continuous(self):
        # sigma^2 = 1e6 → sigma = 1000 ≫ 1 grid step.
        samples = native.discrete_gaussian(1_000_000, 1, N).astype(np.float64)
        ks = stats.kstest(samples / 1000.0, stats.norm(scale=1).cdf)
        assert ks.statistic < 0.01, ks

    def test_secure_laplace_add_moments(self):
        scale = 2.5
        out = native.secure_laplace_add(np.zeros(N), scale)
        assert abs(out.mean()) < 0.05
        assert out.std() == pytest.approx(scale * math.sqrt(2), rel=0.02)
        ks = stats.kstest(out, stats.laplace(scale=scale).cdf)
        assert ks.statistic < 0.01, ks

    def test_secure_gaussian_add_moments(self):
        sigma = 3.0
        out = native.secure_gaussian_add(np.zeros(N), sigma)
        assert abs(out.mean()) < 0.05
        assert out.std() == pytest.approx(sigma, rel=0.02)
        ks = stats.kstest(out, stats.norm(scale=sigma).cdf)
        assert ks.statistic < 0.01, ks

    def test_snapping_granularity(self):
        # All outputs must lie on the power-of-two granularity grid.
        scale = 2.5
        out = native.secure_laplace_add(np.full(100, 17.3), scale)
        g = 2.0**(math.ceil(math.log2(scale)) - 40)
        on_grid = np.abs(out / g - np.round(out / g))
        assert np.all(on_grid < 1e-6)

    def test_values_are_shifted(self):
        out = native.secure_laplace_add(np.full(1000, 100.0), 1.0)
        assert out.mean() == pytest.approx(100.0, abs=0.2)

    def test_deterministic_under_test_seed(self):
        native.seed_test_rng(7)
        a = native.discrete_laplace(100, 1, 100)
        native.seed_test_rng(7)
        b = native.discrete_laplace(100, 1, 100)
        np.testing.assert_array_equal(a, b)


class TestGaussianCalibrationParity:

    @pytest.mark.parametrize("eps,delta,l2", [
        (1.0, 1e-6, 1.0),
        (0.1, 1e-10, 3.5),
        (10.0, 1e-5, 1.0),
        (5.0, 1e-12, math.sqrt(7)),
    ])
    def test_sigma_matches_python(self, eps, delta, l2):
        assert native.gaussian_sigma(eps, delta, l2) == pytest.approx(
            dp_computations.gaussian_sigma(eps, delta, l2), rel=1e-9)

    @pytest.mark.parametrize("sigma,eps,l2", [
        (1.0, 1.0, 1.0),
        (4.0, 0.5, 2.0),
        (0.5, 30.0, 1.0),
    ])
    def test_delta_matches_python(self, sigma, eps, l2):
        assert native.gaussian_delta(sigma, eps, l2) == pytest.approx(
            dp_computations.gaussian_delta(sigma, eps, l2), rel=1e-9)


class TestPartitionSelectionParity:

    COUNTS = np.concatenate([
        np.arange(0, 50, dtype=np.int64),
        np.array([100, 1000, 100000, 10**7], dtype=np.int64)
    ])

    @pytest.mark.parametrize("pre_threshold", [None, 10])
    @pytest.mark.parametrize("eps,delta,l0", [
        (1.0, 1e-5, 1),
        (0.5, 1e-8, 3),
        (20.0, 1e-4, 2),
    ])
    def test_truncated_geometric(self, eps, delta, l0, pre_threshold):
        selector = partition_selection.create_partition_selection_strategy(
            PartitionSelectionStrategy.TRUNCATED_GEOMETRIC, eps, delta, l0,
            pre_threshold)
        want = selector.probability_of_keep_vec(self.COUNTS)
        got = native.truncated_geometric_prob_keep(eps, delta, l0,
                                                   pre_threshold, self.COUNTS)
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-300)

    @pytest.mark.parametrize("eps,delta,l0", [
        (1.0, 1e-5, 1),
        (0.5, 1e-8, 3),
    ])
    def test_laplace_thresholding(self, eps, delta, l0):
        selector = partition_selection.create_partition_selection_strategy(
            PartitionSelectionStrategy.LAPLACE_THRESHOLDING, eps, delta, l0,
            None)
        want = selector.probability_of_keep_vec(self.COUNTS)
        got = native.laplace_prob_keep(eps, delta, l0, None, self.COUNTS)
        np.testing.assert_allclose(got, want, rtol=1e-9)
        assert native.laplace_threshold(eps, delta,
                                        l0) == pytest.approx(
                                            selector.threshold, rel=1e-12)

    @pytest.mark.parametrize("eps,delta,l0", [
        (1.0, 1e-5, 1),
        (0.5, 1e-8, 3),
    ])
    def test_gaussian_thresholding(self, eps, delta, l0):
        selector = partition_selection.create_partition_selection_strategy(
            PartitionSelectionStrategy.GAUSSIAN_THRESHOLDING, eps, delta, l0,
            None)
        want = selector.probability_of_keep_vec(self.COUNTS)
        got = native.gaussian_prob_keep(eps, delta, l0, None, self.COUNTS)
        np.testing.assert_allclose(got, want, rtol=1e-7)
        sigma, threshold = native.gaussian_thresholding_params(eps, delta, l0)
        assert sigma == pytest.approx(selector.sigma, rel=1e-9)
        assert threshold == pytest.approx(selector.threshold, rel=1e-7)

    def test_sample_keep_frequencies(self):
        probs = np.full(N, 0.25)
        kept = native.sample_keep(probs)
        assert kept.mean() == pytest.approx(0.25, abs=0.01)

    @pytest.mark.parametrize("strategy", [
        PartitionSelectionStrategy.TRUNCATED_GEOMETRIC,
        PartitionSelectionStrategy.LAPLACE_THRESHOLDING,
        PartitionSelectionStrategy.GAUSSIAN_THRESHOLDING,
    ])
    def test_probability_of_keep_warning_clean(self, strategy):
        # The privacy path must be warning-clean even at extreme counts:
        # np.where evaluates both branches, so an unclamped exp in the
        # dead branch overflows at large n (the Laplace survival function
        # regression this test pins). Escalate every warning to an error.
        import warnings
        selector = partition_selection.create_partition_selection_strategy(
            strategy, 1.0, 1e-8, 2, None)
        counts = np.concatenate([
            self.COUNTS,
            np.array([10**9, 10**12, 10**15], dtype=np.int64)
        ])
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            probs = selector.probability_of_keep_vec(counts)
            scalar = [selector.probability_of_keep(int(c))
                      for c in (0, 1, 10**9)]
        assert np.all((probs >= 0.0) & (probs <= 1.0))
        assert probs[-1] == pytest.approx(1.0)
        assert scalar[0] == 0.0 and scalar[-1] == pytest.approx(1.0)


class TestSecureNoiseMechanismIntegration:

    def test_use_secure_noise_laplace(self):
        dp_computations.use_secure_noise(True)
        try:
            mech = dp_computations.LaplaceMechanism.create_from_epsilon(
                1.0, 1.0)
            vals = np.array([mech.add_noise(10.0) for _ in range(2000)])
            assert vals.mean() == pytest.approx(10.0, abs=0.2)
            assert vals.std() == pytest.approx(math.sqrt(2), rel=0.15)
            g = 2.0**(-40)  # scale 1.0 → granularity 2^-40
            on_grid = np.abs(vals / g - np.round(vals / g))
            assert np.all(on_grid < 1e-3)
        finally:
            dp_computations.use_secure_noise(False)

    def test_apply_mechanisms_covered_by_secure_mode(self):
        # VARIANCE / VECTOR_SUM noise flows through apply_*_mechanism — the
        # secure gate must cover those too, not just the mechanism classes.
        dp_computations.use_secure_noise(True)
        try:
            v = dp_computations.apply_laplace_mechanism(7.0, 1.0, 1.0)
            g = 2.0**(-40)  # b = 1.0 → granularity 2^-40
            assert abs(v / g - round(v / g)) < 1e-3
            v2 = dp_computations.apply_gaussian_mechanism(7.0, 1.0, 1e-6, 1.0)
            assert v2 != 7.0  # noised
        finally:
            dp_computations.use_secure_noise(False)

    def test_use_secure_noise_gaussian(self):
        dp_computations.use_secure_noise(True)
        try:
            mech = (dp_computations.GaussianMechanism
                    .create_from_epsilon_delta(1.0, 1e-6, 1.0))
            vals = np.array([mech.add_noise(5.0) for _ in range(2000)])
            assert vals.mean() == pytest.approx(5.0, abs=0.5)
            assert vals.std() == pytest.approx(mech.std, rel=0.15)
        finally:
            dp_computations.use_secure_noise(False)


class TestVocabEncode:
    """Native open-addressing vocabulary encoder (the ingest fallback when
    pandas is absent; must agree with pandas.factorize exactly)."""

    @pytest.mark.parametrize("make", [
        lambda rng: np.char.add("key_",
                                rng.integers(0, 500, 20_000).astype(str)),
        lambda rng: rng.integers(-1000, 1000, 20_000),
        lambda rng: rng.random(20_000).round(2),
        lambda rng: np.char.add("k", rng.integers(0, 3, 17).astype(str)),
    ])
    def test_matches_pandas_factorize(self, make):
        import pandas as pd
        from pipelinedp_tpu import native
        if not native.available():
            pytest.skip("native library unavailable")
        rng = np.random.default_rng(11)
        arr = make(rng)
        encoded = native.vocab_encode(arr)
        assert encoded is not None
        codes, first_rows = encoded
        ref_codes, ref_uniques = pd.factorize(arr, use_na_sentinel=False)
        np.testing.assert_array_equal(codes, ref_codes)
        np.testing.assert_array_equal(arr[first_rows],
                                      np.asarray(ref_uniques))

    def test_rejects_object_dtype(self):
        from pipelinedp_tpu import native
        if not native.available():
            pytest.skip("native library unavailable")
        arr = np.array([("a", 1), ("b", 2), ("a", 1)], dtype=object)
        assert native.vocab_encode(np.asarray(arr)) is None

    def test_empty(self):
        from pipelinedp_tpu import native
        if not native.available():
            pytest.skip("native library unavailable")
        codes, first = native.vocab_encode(np.zeros(0, dtype=np.int64))
        assert len(codes) == 0 and len(first) == 0

    def test_factorize_without_pandas(self, monkeypatch):
        # The columnar path must route through the native encoder when
        # pandas is unavailable.
        from pipelinedp_tpu import columnar, native
        if not native.available():
            pytest.skip("native library unavailable")
        monkeypatch.setattr(columnar, "_pd", None)
        arr = np.char.add("pk", np.arange(1000).astype(str))[
            np.random.default_rng(0).integers(0, 1000, 5000)]
        codes, vocab = columnar.factorize(arr)
        assert (np.asarray(vocab)[codes] == arr).all()
        # first-occurrence order preserved (native path, not sorted unique)
        assert vocab[codes[0]] == arr[0]

    def test_factorize_object_array_with_nan(self, monkeypatch):
        # np.unique's sort-adjacency dedup breaks when NaN sits among
        # object keys (equal regular keys can land non-adjacent and get
        # TWO codes); factorize must detect this and take the dict path,
        # with all NaN keys sharing one code.
        from pipelinedp_tpu import columnar
        monkeypatch.setattr(columnar, "_pd", None)
        arr = columnar._as_key_array([1, float("nan"), 1, np.nan, 2])
        codes, vocab = columnar.factorize(arr)
        np.testing.assert_array_equal(codes, [0, 1, 0, 1, 2])
        assert vocab[0] == 1 and np.isnan(vocab[1]) and vocab[2] == 2

    def test_negative_zero_unified(self):
        from pipelinedp_tpu import native
        if not native.available():
            pytest.skip("native library unavailable")
        codes, first = native.vocab_encode(np.array([0.0, -0.0, 0.0, -0.0]))
        assert list(codes) == [0, 0, 0, 0]

    def test_nan_float_keys_fall_back(self):
        from pipelinedp_tpu import native
        if not native.available():
            pytest.skip("native library unavailable")
        assert native.vocab_encode(np.array([1.0, np.nan, 1.0])) is None


class TestBuildFreshness:

    def test_stale_binary_is_rebuilt_not_loaded(self, tmp_path, monkeypatch):
        """git ignores the binary, so the source can move under it: a
        binary older than dp_primitives.cc is rebuilt, never loaded."""
        import ctypes
        import os
        import shutil

        source = tmp_path / native._SRC_NAME
        shutil.copy(os.path.join(native._dir, native._SRC_NAME), source)
        stale = tmp_path / native._LIB_NAME
        stale.write_bytes(b"not a library")
        older = os.path.getmtime(source) - 100
        os.utime(stale, (older, older))
        monkeypatch.setattr(native, "_dir", str(tmp_path))

        assert native._needs_build()
        assert native._try_build()
        assert not native._needs_build()
        assert ctypes.CDLL(str(stale)).dpn_gaussian_sigma is not None
        assert sorted(os.listdir(tmp_path)) == sorted(
            [native._LIB_NAME, native._SRC_NAME])  # no build litter

    def test_status_reports_the_loaded_library(self):
        status = native.status()
        assert status["in_use"] and not status["build_failed"]
