"""Tests for budget accounting (naive + PLD) and the native PLD library.

Modeled on /root/reference/tests/budget_accounting_test.py patterns: split
proportions, scope normalization, restriction enforcement, PLD binary search.
"""

import math

import numpy as np
import pytest
import threadpoolctl

import pipelinedp_tpu as pdp
from pipelinedp_tpu.accounting import pld as pldlib
from pipelinedp_tpu.aggregate_params import MechanismType


class TestMechanismSpec:

    def test_lazy_access_raises(self):
        spec = pdp.MechanismSpec(mechanism_type=MechanismType.LAPLACE)
        with pytest.raises(AssertionError):
            _ = spec.eps
        with pytest.raises(AssertionError):
            _ = spec.noise_standard_deviation

    def test_set_and_get(self):
        spec = pdp.MechanismSpec(mechanism_type=MechanismType.GAUSSIAN)
        spec.set_eps_delta(0.5, 1e-8)
        assert spec.eps == 0.5
        assert spec.delta == 1e-8
        assert spec.use_delta()

    def test_laplace_does_not_use_delta(self):
        spec = pdp.MechanismSpec(mechanism_type=MechanismType.LAPLACE)
        assert not spec.use_delta()


class TestNaiveBudgetAccountant:

    def test_equal_split_laplace(self):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1, total_delta=0)
        s1 = acc.request_budget(MechanismType.LAPLACE)
        s2 = acc.request_budget(MechanismType.LAPLACE)
        acc.compute_budgets()
        assert s1.eps == pytest.approx(0.5)
        assert s2.eps == pytest.approx(0.5)
        assert s1.delta == 0

    def test_weighted_split(self):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1, total_delta=1e-6)
        s1 = acc.request_budget(MechanismType.LAPLACE, weight=3)
        s2 = acc.request_budget(MechanismType.GAUSSIAN, weight=1)
        acc.compute_budgets()
        assert s1.eps == pytest.approx(0.75)
        assert s2.eps == pytest.approx(0.25)
        # Only the Gaussian mechanism consumes delta.
        assert s2.delta == pytest.approx(1e-6)

    def test_count_multiplies_weight(self):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1, total_delta=0)
        s1 = acc.request_budget(MechanismType.LAPLACE, count=3)
        s2 = acc.request_budget(MechanismType.LAPLACE)
        acc.compute_budgets()
        assert s1.eps == pytest.approx(0.25)
        assert s2.eps == pytest.approx(0.25)

    def test_gaussian_without_delta_raises(self):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1, total_delta=0)
        with pytest.raises(ValueError, match="Gaussian"):
            acc.request_budget(MechanismType.GAUSSIAN)

    def test_request_after_compute_raises(self):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1, total_delta=0)
        acc.request_budget(MechanismType.LAPLACE)
        acc.compute_budgets()
        with pytest.raises(Exception, match="after compute_budgets"):
            acc.request_budget(MechanismType.LAPLACE)

    def test_compute_twice_raises(self):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1, total_delta=0)
        acc.request_budget(MechanismType.LAPLACE)
        acc.compute_budgets()
        with pytest.raises(Exception, match="twice"):
            acc.compute_budgets()

    def test_scope_normalizes_weights(self):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1, total_delta=0)
        with acc.scope(weight=0.5):
            s1 = acc.request_budget(MechanismType.LAPLACE)
            s2 = acc.request_budget(MechanismType.LAPLACE)
        with acc.scope(weight=0.5):
            s3 = acc.request_budget(MechanismType.LAPLACE)
        acc.compute_budgets()
        assert s1.eps == pytest.approx(0.25)
        assert s2.eps == pytest.approx(0.25)
        assert s3.eps == pytest.approx(0.5)

    def test_num_aggregations_enforced(self):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1,
                                        total_delta=0,
                                        num_aggregations=2)
        acc._compute_budget_for_aggregation(1)
        acc.request_budget(MechanismType.LAPLACE)
        with pytest.raises(ValueError, match="num_aggregations"):
            acc.compute_budgets()

    def test_num_aggregations_and_weights_conflict(self):
        with pytest.raises(ValueError):
            pdp.NaiveBudgetAccountant(total_epsilon=1,
                                      total_delta=0,
                                      num_aggregations=2,
                                      aggregation_weights=[1, 2])

    def test_aggregation_weights_split_and_enforcement(self):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                        total_delta=0,
                                        aggregation_weights=[1, 3])
        with acc.scope(weight=1):
            s1 = acc.request_budget(MechanismType.LAPLACE)
        acc._compute_budget_for_aggregation(1)
        with acc.scope(weight=3):
            s2 = acc.request_budget(MechanismType.LAPLACE)
        acc._compute_budget_for_aggregation(3)
        acc.compute_budgets()
        # eps split proportionally to declared aggregation weights.
        assert s1.eps == pytest.approx(0.25)
        assert s2.eps == pytest.approx(0.75)

    def test_aggregation_weights_count_mismatch_raises(self):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                        total_delta=0,
                                        aggregation_weights=[1, 3])
        with acc.scope(weight=1):
            acc.request_budget(MechanismType.LAPLACE)
        acc._compute_budget_for_aggregation(1)
        with pytest.raises(ValueError, match="aggregation_weights"):
            acc.compute_budgets()

    def test_aggregation_weights_value_mismatch_raises(self):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                        total_delta=0,
                                        aggregation_weights=[1, 3])
        with acc.scope(weight=1):
            acc.request_budget(MechanismType.LAPLACE)
        acc._compute_budget_for_aggregation(1)
        with acc.scope(weight=2):  # declared 3, actual 2
            acc.request_budget(MechanismType.LAPLACE)
        acc._compute_budget_for_aggregation(2)
        with pytest.raises(ValueError):
            acc.compute_budgets()

    def test_num_aggregations_requires_unit_weights(self):
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                        total_delta=0,
                                        num_aggregations=1)
        with acc.scope(weight=2):
            acc.request_budget(MechanismType.LAPLACE)
        acc._compute_budget_for_aggregation(2)
        with pytest.raises(ValueError, match="weights have to be 1"):
            acc.compute_budgets()


class TestPld:

    def test_gaussian_epsilon_matches_analytic_shape(self):
        # For sigma=2, delta=1e-6: epsilon from PLD must be finite, positive
        # and close to the analytic Gaussian mechanism's calibration.
        pld = pldlib.from_gaussian_mechanism(2.0,
                                             value_discretization_interval=1e-3)
        eps = pld.get_epsilon_for_delta(1e-6)
        assert 0 < eps < 10
        # More noise -> smaller epsilon.
        pld2 = pldlib.from_gaussian_mechanism(
            4.0, value_discretization_interval=1e-3)
        assert pld2.get_epsilon_for_delta(1e-6) < eps

    def test_laplace_pure_dp(self):
        # Laplace(b) is (1/b, 0)-DP: epsilon at delta=0 is 1/b (up to the
        # pessimistic discretization error).
        b = 2.0
        pld = pldlib.from_laplace_mechanism(b,
                                            value_discretization_interval=1e-4)
        eps = pld.get_epsilon_for_delta(0)
        assert eps == pytest.approx(1 / b, abs=1e-3)

    def test_composition_additivity_upper_bound(self):
        # eps of the composition is between the single-mechanism eps and the
        # naive sum of epsilons.
        pld = pldlib.from_laplace_mechanism(1.0,
                                            value_discretization_interval=1e-4)
        composed = pld.compose(pld)
        eps1 = pld.get_epsilon_for_delta(1e-9)
        eps2 = composed.get_epsilon_for_delta(1e-9)
        assert eps1 < eps2 <= 2 * eps1 + 1e-3

    def test_self_compose_matches_compose(self):
        pld = pldlib.from_gaussian_mechanism(3.0,
                                             value_discretization_interval=1e-3)
        a = pld.compose(pld).compose(pld)
        b = pld.self_compose(3)
        assert a.get_epsilon_for_delta(1e-6) == pytest.approx(
            b.get_epsilon_for_delta(1e-6), rel=1e-6)

    def test_delta_monotone_in_epsilon(self):
        pld = pldlib.from_gaussian_mechanism(1.0,
                                             value_discretization_interval=1e-3)
        deltas = [pld.get_delta_for_epsilon(e) for e in (0.0, 0.5, 1.0, 2.0)]
        assert all(d1 >= d2 for d1, d2 in zip(deltas, deltas[1:]))

    def test_from_privacy_parameters(self):
        pld = pldlib.from_privacy_parameters(
            1.0, 1e-6, value_discretization_interval=1e-4)
        eps = pld.get_epsilon_for_delta(1e-6)
        assert eps == pytest.approx(1.0, abs=1e-3)


class TestPldGoldenValues:
    """Cross-validation of the native PLD against independent references.

    dp_accounting (the reference's PLD library) is not installable here, so
    the golden values are derived from methods independent of the FFT/
    discretization pipeline under test:

      * Gaussian, any k: k-fold composition of Gaussian mechanisms is
        EXACTLY the Gaussian mechanism with sigma/sqrt(k) (the privacy loss
        is N(mu, 2mu) with mu additive under composition), and its
        delta(eps) is the Balle-Wang analytic formula
            delta = Phi(1/(2s) - eps*s) - e^eps * Phi(-1/(2s) - eps*s).
      * Laplace, k=1: hockey-stick integral evaluated with scipy.quad.
      * Laplace, k=2: exact atom/continuous decomposition of the loss
        convolution (atoms at +-1/b, interior density e^{-(1-bl)/(2b)}/4),
        integrated with scipy quad/dblquad.
      * Generic (eps0, delta0): three-point loss distribution closed form
            delta(eps) = delta0 + (1-delta0) e^eps0/(1+e^eps0) (1-e^(eps-eps0)).

    Every pinned value was recomputed with those formulas (see the
    derivations above); the PLD must match within pessimistic tolerance:
    never below the exact value, and within rel_tol above it.
    """

    # (sigma, k, delta) -> exact composed epsilon (Balle-Wang closed form).
    GAUSSIAN_GOLDEN = [
        (1.0, 1, 1e-5, 4.377178),
        (2.0, 1, 1e-6, 2.254085),
        (1.0, 10, 1e-5, 17.856587),
        (0.5, 4, 1e-6, 26.356964),
        (3.0, 30, 1e-5, 8.940357),
    ]

    @pytest.mark.parametrize("sigma,k,delta,exact_eps", GAUSSIAN_GOLDEN)
    def test_gaussian_composition_golden(self, sigma, k, delta, exact_eps):
        pld = pldlib.from_gaussian_mechanism(sigma)
        if k > 1:
            pld = pld.self_compose(k)
        eps = pld.get_epsilon_for_delta(delta)
        assert eps >= exact_eps - 1e-5  # pessimistic rounding: never below
        assert eps == pytest.approx(exact_eps, rel=5e-4)

    # (b, k, delta) -> exact composed epsilon (quad integration).
    LAPLACE_GOLDEN = [
        (1.0, 1, 1e-5, 0.999980),
        (0.5, 1, 1e-3, 1.997999),
        (2.0, 1, 1e-6, 0.499998),
        (1.0, 1, 1e-2, 0.979899),
        (1.0, 2, 1e-5, 1.999960),
        (2.0, 2, 1e-6, 0.999996),
    ]

    @pytest.mark.parametrize("b,k,delta,exact_eps", LAPLACE_GOLDEN)
    def test_laplace_golden(self, b, k, delta, exact_eps):
        pld = pldlib.from_laplace_mechanism(b)
        if k > 1:
            pld = pld.self_compose(k)
        eps = pld.get_epsilon_for_delta(delta)
        assert eps >= exact_eps - 1e-5
        assert eps == pytest.approx(exact_eps, rel=1e-4)

    # (eps0, delta0, delta) -> exact epsilon (three-point closed form).
    GENERIC_GOLDEN = [
        (1.0, 1e-6, 1e-4, 0.999865),
        (0.3, 0.0, 1e-3, 0.298258),
    ]

    @pytest.mark.parametrize("eps0,delta0,delta,exact_eps", GENERIC_GOLDEN)
    def test_generic_golden(self, eps0, delta0, delta, exact_eps):
        pld = pldlib.from_privacy_parameters(eps0, delta0)
        eps = pld.get_epsilon_for_delta(delta)
        assert eps >= exact_eps - 1e-5
        assert eps == pytest.approx(exact_eps, rel=1e-4)

    def test_heterogeneous_composition_golden(self):
        # Gaussian(s=2) o Laplace(b=1) o Generic(0.5, 1e-8) at delta=1e-5,
        # pinned from this library at 1e-4 discretization and sanity-bounded
        # by the naive sum of epsilons (upper) and each component (lower).
        pld = (pldlib.from_gaussian_mechanism(2.0).compose(
            pldlib.from_laplace_mechanism(1.0)).compose(
                pldlib.from_privacy_parameters(0.5, 1e-8)))
        eps = pld.get_epsilon_for_delta(1e-5)
        assert eps == pytest.approx(3.355885, rel=1e-3)
        naive_sum = (pldlib.from_gaussian_mechanism(2.0).get_epsilon_for_delta(
            1e-5) + 1.0 + 0.5)
        assert eps < naive_sum

    def test_gaussian_delta_for_epsilon_golden(self):
        # Balle-Wang at sigma=1, eps=1: delta = Phi(-0.5) - e * Phi(-1.5)
        #                                     = 0.12693674 (exact).
        pld = pldlib.from_gaussian_mechanism(1.0)
        assert pld.get_delta_for_epsilon(1.0) == pytest.approx(0.12693674,
                                                               rel=1e-3)


class TestPldIndependentCrossChecks:
    """Cross-validation against implementations NOT sharing code with the
    production PLD pipeline.

    Google's dp_accounting (the reference's library,
    /root/reference/pipeline_dp/budget_accounting.py:579-619) cannot be
    installed in this environment (no package index access), so its golden
    outputs cannot be generated here. These checks substitute two fully
    independent derivations:

      * An RDP (Renyi) accountant bound for composed Gaussians — a different
        accounting formalism entirely. PLD is exact, RDP is an upper bound,
        so eps_PLD <= eps_RDP must hold (and eps_PLD >= the Balle-Wang exact
        value, asserted in TestPldGoldenValues).
      * A from-scratch dense-convolution PLD for composed Laplace mechanisms
        written in ~20 lines of numpy here in the test: the exact loss
        distribution (two atoms + interior density) discretized with ceil
        rounding and composed with np.convolve — no FFT, no shared
        discretization code with accounting/pld.py.
    """

    @pytest.mark.parametrize("sigma,k,delta", [(1.0, 1, 1e-5), (2.0, 4, 1e-6),
                                               (1.0, 16, 1e-5),
                                               (3.0, 30, 1e-5)])
    def test_gaussian_below_rdp_bound(self, sigma, k, delta):
        pld = pldlib.from_gaussian_mechanism(sigma)
        if k > 1:
            pld = pld.self_compose(k)
        eps_pld = pld.get_epsilon_for_delta(delta)
        # RDP of k Gaussians: rdp(alpha) = k * alpha / (2 sigma^2); convert
        # with the improved bound (Balle et al. 2020):
        #   eps = min_a rdp(a) + log1p(-1/a) - log(delta * a) / (a - 1).
        alphas = np.linspace(1.0 + 1e-3, 200.0, 20000)
        rdp = k * alphas / (2.0 * sigma**2)
        eps_rdp = np.min(rdp + np.log1p(-1.0 / alphas) -
                         (np.log(delta) + np.log(alphas)) / (alphas - 1.0))
        assert eps_pld <= eps_rdp + 1e-3

    @staticmethod
    def _laplace_loss_pmf(b: float, grid: float):
        """Pessimistically discretized privacy-loss PMF of Laplace(b),
        sensitivity 1: atoms at +-1/b, interior density e^{-(1-bl)/(2b)}/4."""
        n_bins = int(np.ceil(1.0 / (b * grid)))
        losses = (np.arange(-n_bins, n_bins + 1)) * grid
        pmf = np.zeros_like(losses)
        # Interior mass of bin (l-grid, l] assigned to its UPPER edge (ceil
        # rounding = pessimistic, losses only rounded up).
        edges = np.clip(losses, -1.0 / b, 1.0 / b)
        cdf = lambda l: 0.5 * (np.exp((b * l - 1.0) / (2.0 * b)) - np.exp(
            -1.0 / b))  # integral of interior density from -1/b to l
        pmf[1:] = cdf(edges[1:]) - cdf(edges[:-1])
        pmf[-1] += 0.5  # atom at +1/b: P(x < 0)
        pmf[0] += np.exp(-1.0 / b) / 2.0  # atom at -1/b: P(x > 1)
        return losses, pmf

    @pytest.mark.parametrize("b,k,delta", [(1.0, 4, 1e-5), (0.8, 3, 1e-4),
                                           (2.0, 6, 1e-6)])
    def test_laplace_matches_dense_convolution(self, b, k, delta):
        grid = 1e-4
        losses, pmf = self._laplace_loss_pmf(b, grid)
        composed = pmf
        # np.convolve is one BLAS dot per output element, 60,000 and more of
        # them: with BLAS threads on, each wakes a pool that has to share the
        # cores with five other xdist workers' XLA threads, and the 3 s this
        # takes alone became 238-623 s under the driver's command (PR 35).
        with threadpoolctl.threadpool_limits(limits=1, user_api="blas"):
            for _ in range(k - 1):
                composed = np.convolve(composed, pmf)
        n = (len(losses) - 1) // 2
        composed_losses = np.arange(-k * n, k * n + 1) * grid
        # Hockey-stick divergence at eps from the composed PMF.
        eps_grid = np.linspace(0.0, k / b, 4000)
        deltas = np.array([
            np.sum(
                np.where(composed_losses > e,
                         composed * -np.expm1(e - composed_losses), 0.0))
            for e in eps_grid
        ])
        eps_ref = float(np.interp(-delta, -deltas, eps_grid))
        eps_pld = pldlib.from_laplace_mechanism(b).self_compose(
            k).get_epsilon_for_delta(delta)
        # Both are pessimistic discretizations of the same exact object on
        # unrelated grids; they must agree to grid resolution.
        assert eps_pld == pytest.approx(eps_ref, rel=2e-3, abs=2e-3)


class TestPLDBudgetAccountant:

    def test_delta_zero_closed_form(self):
        acc = pdp.PLDBudgetAccountant(total_epsilon=1, total_delta=0)
        s1 = acc.request_budget(MechanismType.LAPLACE)
        s2 = acc.request_budget(MechanismType.LAPLACE)
        acc.compute_budgets()
        assert acc.minimum_noise_std == pytest.approx(2 * math.sqrt(2))
        assert s1.noise_standard_deviation == pytest.approx(2 * math.sqrt(2))
        assert s2.noise_standard_deviation == pytest.approx(2 * math.sqrt(2))

    def test_binary_search_satisfies_budget(self):
        total_eps, total_delta = 1.0, 1e-6
        acc = pdp.PLDBudgetAccountant(total_epsilon=total_eps,
                                      total_delta=total_delta,
                                      pld_discretization=1e-3)
        acc.request_budget(MechanismType.GAUSSIAN)
        acc.request_budget(MechanismType.GAUSSIAN)
        acc.compute_budgets()
        std = acc.minimum_noise_std
        assert std > 0
        # Verify the composed PLD at the found noise std fits in the budget.
        pld = pldlib.from_gaussian_mechanism(
            std, value_discretization_interval=1e-3).self_compose(2)
        assert pld.get_epsilon_for_delta(total_delta) <= total_eps * 1.01

    def test_pld_beats_naive_for_many_mechanisms(self):
        # PLD composition should allow strictly less noise than naive
        # accounting for >2 Gaussian mechanisms.
        total_eps, total_delta = 1.0, 1e-6
        n = 4
        acc = pdp.PLDBudgetAccountant(total_epsilon=total_eps,
                                      total_delta=total_delta,
                                      pld_discretization=1e-3)
        specs = [acc.request_budget(MechanismType.GAUSSIAN) for _ in range(n)]
        acc.compute_budgets()
        from pipelinedp_tpu import dp_computations
        naive_std = dp_computations.gaussian_sigma(total_eps / n,
                                                   total_delta / n, 1.0)
        assert specs[0].noise_standard_deviation < naive_std

    def test_huge_eps_naive_fallback(self):
        # Beyond the PLD finite-loss cap the accountant splits naively so
        # the huge-eps determinism trick still works; mixed mechanism kinds
        # each get their exact single-mechanism calibration.
        acc = pdp.PLDBudgetAccountant(total_epsilon=1e5, total_delta=1e-6)
        lap = acc.request_budget(MechanismType.LAPLACE)
        gau = acc.request_budget(MechanismType.GAUSSIAN)
        gen = acc.request_budget(MechanismType.GENERIC)
        acc.compute_budgets()
        eps_i = 1e5 / 3
        assert lap.noise_standard_deviation == pytest.approx(
            math.sqrt(2) / eps_i)
        assert gau.noise_standard_deviation < 0.01
        assert gen.eps == pytest.approx(eps_i)
        assert gen.delta == pytest.approx(0.5e-6)

    def test_generic_mechanism_gets_eps_delta(self):
        acc = pdp.PLDBudgetAccountant(total_epsilon=1,
                                      total_delta=1e-6,
                                      pld_discretization=1e-3)
        s = acc.request_budget(MechanismType.GENERIC)
        acc.compute_budgets()
        assert s.eps > 0
        assert s.delta > 0
