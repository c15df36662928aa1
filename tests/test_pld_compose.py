"""The PLD fast-composition engine and dual-spend admission
(pipelinedp_tpu/accounting/compose.py + the pld.py query fast path).

The contracts under test:

  * **Query fast path** — the suffix-tail-sum ``get_delta_for_epsilon``
    is EXACTLY equivalent (to float64 ulp) to the full-grid mask+sum
    scan it replaced, across Laplace/Gaussian/generic/composed PLDs
    and across the fallback boundaries (huge epsilon, exp-saturated
    loss cells).
  * **Batched composition parity** — the one-shot frequency-domain
    compose matches the sequential pairwise ``compose`` chain within
    1e-9 (acceptance bar; measured slack is orders tighter), matches
    closed-form Gaussian self-composition, and reproduces the pinned
    golden accounting values. The device (jnp.fft) path matches the
    host path within 1e-9 — the host float64 path stays ledger-facing.
  * **Spectrum cache** — hits/misses counted, LRU-bounded, keyed so
    distinct (kind, scale, sensitivity, discretization) never collide.
  * **Evolving-discretization coarsening** — rebucketing conserves
    mass and only ever moves loss UP (pessimistic, sound).
  * **Dual-spend ledger** — the naive sum stays the bit-exact ledger
    of record in BOTH accounting modes; pld mode admits >= 2x the jobs
    on the same lifetime budget at k >= 100 Gaussian jobs; the rebuilt
    spend survives a journal reload.
"""

import math

import numpy as np
import pytest

from pipelinedp_tpu import dp_computations as dpc
from pipelinedp_tpu import input_validators
from pipelinedp_tpu.aggregate_params import MechanismType
from pipelinedp_tpu.accounting import compose as eng
from pipelinedp_tpu.accounting import pld as pldlib
from pipelinedp_tpu.budget_accounting import PLDBudgetAccountant
from pipelinedp_tpu.runtime import observability as obs
from pipelinedp_tpu.runtime import telemetry
from pipelinedp_tpu.runtime.journal import BlockJournal
from pipelinedp_tpu.service.errors import TenantBudgetExceededError
from pipelinedp_tpu.service.ledger import TenantLedger

pytestmark = pytest.mark.pld

# Coarse grids keep every composition in this suite fast; parity and
# equivalence claims are grid-exact, so resolution is not load-bearing.
_D = 1e-3


def _sample_plds():
    """A spread of mechanism PLDs covering every from_* constructor."""
    return [
        pldlib.from_gaussian_mechanism(1.0, _D),
        pldlib.from_gaussian_mechanism(4.0, _D),
        pldlib.from_laplace_mechanism(1.0, _D),
        pldlib.from_laplace_mechanism(0.5, _D),
        pldlib.from_privacy_parameters(0.5, 1e-7, _D),
        pldlib.from_gaussian_mechanism(2.0, _D).compose(
            pldlib.from_laplace_mechanism(1.5, _D)),
    ]


class TestQueryFastPath:
    """get_delta_for_epsilon's suffix-sum path vs the scan it replaced."""

    @pytest.mark.parametrize("idx", range(6))
    def test_fast_equals_scan(self, idx):
        pld = _sample_plds()[idx]
        lo = float(pld.losses[0]) if len(pld.probs) else 0.0
        hi = float(pld.losses[-1]) if len(pld.probs) else 1.0
        grid = np.concatenate([
            np.linspace(lo - 1.0, hi + 1.0, 301),
            pld.losses[:: max(1, len(pld.probs) // 50)],  # exact cell edges
            [0.0, lo, hi],
        ])
        for eps in grid:
            fast = pld.get_delta_for_epsilon(float(eps))
            scan = pld._get_delta_for_epsilon_scan(float(eps))
            assert fast == pytest.approx(scan, abs=1e-12), eps

    def test_huge_epsilon_falls_back_and_agrees(self):
        pld = pldlib.from_laplace_mechanism(1e-4, 1e-2)  # losses ~ 1e4
        for eps in (10999.0, 11001.0, 2e4):
            assert pld.get_delta_for_epsilon(eps) == pytest.approx(
                pld._get_delta_for_epsilon_scan(eps), abs=1e-12)

    def test_epsilon_for_delta_round_trip(self):
        for pld in _sample_plds():
            eps = pld.get_epsilon_for_delta(1e-6)
            # The bisection's answer must actually achieve the delta.
            assert pld.get_delta_for_epsilon(eps) <= 1e-6 + 1e-12

    def test_delta_monotone_nonincreasing(self):
        pld = _sample_plds()[0]
        grid = np.linspace(-2.0, 8.0, 200)
        deltas = [pld.get_delta_for_epsilon(float(e)) for e in grid]
        assert all(a >= b - 1e-12 for a, b in zip(deltas, deltas[1:]))


class TestBatchedComposition:
    """One-shot frequency-domain compose vs the pairwise chain."""

    def test_matches_pairwise_within_1e9(self):
        plds = _sample_plds()[:4]
        counts = [3, 2, 2, 1]
        batched = eng.compose_plds(plds, counts)
        seq = None
        for p, c in zip(plds, counts):
            for _ in range(c):
                seq = p if seq is None else seq.compose(p)
        assert len(batched.probs) == len(seq.probs)
        assert np.max(np.abs(batched.probs - seq.probs)) <= 1e-9
        assert batched.infinity_mass == pytest.approx(seq.infinity_mass,
                                                      abs=1e-9)
        for delta in (1e-4, 1e-6, 1e-8):
            assert batched.get_epsilon_for_delta(delta) == pytest.approx(
                seq.get_epsilon_for_delta(delta), rel=1e-9)

    def test_spectrum_powers_equal_repeated_entries(self):
        one = pldlib.from_gaussian_mechanism(2.0, _D)
        powered = eng.compose_plds([one], [6])
        repeated = eng.compose_plds([one] * 6)
        np.testing.assert_allclose(powered.probs, repeated.probs,
                                   atol=1e-15)

    def test_matches_closed_form_gaussian(self):
        # k-fold Gaussian(sigma) IS Gaussian(sigma/sqrt(k)); both sides
        # go through the discretizer, so agreement is tight but not
        # exact (different grids).
        k, sigma = 16, 4.0
        kfold = eng.compose_plds([pldlib.from_gaussian_mechanism(sigma, _D)],
                                 [k])
        single = pldlib.from_gaussian_mechanism(sigma / math.sqrt(k), _D)
        for delta in (1e-6, 1e-8):
            assert kfold.get_epsilon_for_delta(delta) == pytest.approx(
                single.get_epsilon_for_delta(delta), rel=2e-3)

    def test_device_path_matches_host(self):
        # Documented tolerance: the jnp.fft path is the throughput path
        # and must stay within 1e-9 of the ledger-facing host path
        # (measured slack is ~1e-18 on CPU; the bound leaves room for
        # accelerator FFT reassociation).
        plds = _sample_plds()[:4]
        counts = [2, 3, 1, 2]
        host = eng.compose_plds(plds, counts)
        dev = eng.compose_plds(plds, counts, device=True)
        assert np.max(np.abs(host.probs - dev.probs)) <= 1e-9
        assert dev.get_epsilon_for_delta(1e-6) == pytest.approx(
            host.get_epsilon_for_delta(1e-6), abs=1e-9)

    def test_infinity_mass_composes(self):
        p = pldlib.from_privacy_parameters(0.3, 1e-3, _D)
        composed = eng.compose_plds([p], [10])
        assert composed.infinity_mass == pytest.approx(
            -math.expm1(10 * math.log1p(-p.infinity_mass)), rel=1e-12)

    def test_rejects_bad_inputs(self):
        one = pldlib.from_gaussian_mechanism(1.0, _D)
        with pytest.raises(ValueError, match="at least one"):
            eng.compose_plds([])
        with pytest.raises(ValueError, match="counts"):
            eng.compose_plds([one], [0])
        with pytest.raises(ValueError, match="counts"):
            eng.compose_plds([one], [1, 2])
        other = pldlib.from_gaussian_mechanism(1.0, 2 * _D)
        with pytest.raises(ValueError, match="intervals"):
            eng.compose_plds([one, other])


class TestGoldenValues:
    """The batched engine against pinned reference epsilons (the same
    independently-derived closed-form/quadrature values the pairwise
    golden suite pins — see test_budget_accounting.py for the
    derivations)."""

    GOLDEN = [
        ("gaussian", 1.0, 1, 1e-5, 4.377178),
        ("gaussian", 3.0, 30, 1e-5, 8.940357),
        ("laplace", 1.0, 2, 1e-5, 1.999960),
    ]

    @pytest.mark.parametrize("kind,scale,k,delta,exact_eps", GOLDEN)
    def test_batched_golden(self, kind, scale, k, delta, exact_eps):
        build = (pldlib.from_gaussian_mechanism if kind == "gaussian"
                 else pldlib.from_laplace_mechanism)
        composed = eng.compose_plds([build(scale)], [k])
        eps = composed.get_epsilon_for_delta(delta)
        assert eps >= exact_eps - 1e-5  # pessimistic: never below exact
        assert eps == pytest.approx(exact_eps, rel=5e-4)


class TestCoarsening:
    """Evolving-discretization rebucketing: sound and mass-conserving."""

    def test_mass_conserved_and_pessimistic(self):
        pld = pldlib.from_gaussian_mechanism(1.0, _D)
        coarse = eng.coarsen_pld(pld, 4)
        assert coarse.interval == pytest.approx(4 * _D)
        assert np.sum(coarse.probs) == pytest.approx(np.sum(pld.probs),
                                                     abs=1e-12)
        # Ceiling rebucketing only moves loss UP, so delta at any eps
        # can only grow (a sound upper bound can loosen, never tighten).
        for eps in (0.0, 1.0, 3.0):
            assert (coarse.get_delta_for_epsilon(eps) >=
                    pld.get_delta_for_epsilon(eps) - 1e-12)

    def test_max_grid_triggers_coarsening(self):
        pld = pldlib.from_gaussian_mechanism(1.0, _D)
        small = eng.compose_plds([pld], [64], max_grid=1 << 12)
        big = eng.compose_plds([pld], [64])
        assert len(small.probs) <= 1 << 12
        assert small.interval > big.interval
        # Still a sound bound: coarse epsilon >= fine epsilon.
        assert (small.get_epsilon_for_delta(1e-6) >=
                big.get_epsilon_for_delta(1e-6) - 1e-9)


class TestSpectrumCache:

    def test_hits_misses_and_reuse(self):
        cache = eng.SpectrumCache()
        before = telemetry.snapshot()
        a = cache.get("MechanismType.GAUSSIAN", 2.0, 1.0, _D)
        b = cache.get("MechanismType.GAUSSIAN", 2.0, 1.0, _D)
        assert a is b
        c = cache.get("MechanismType.GAUSSIAN", 3.0, 1.0, _D)
        assert c is not a
        diff = telemetry.delta(before)
        assert diff.get("pld_cache_hits", 0) == 1
        assert diff.get("pld_cache_misses", 0) == 2

    def test_distinct_keys_never_collide(self):
        cache = eng.SpectrumCache()
        variants = [
            ("MechanismType.GAUSSIAN", 2.0, 1.0, _D),
            ("MechanismType.LAPLACE", 2.0, 1.0, _D),
            ("MechanismType.GAUSSIAN", 2.0, 1.0, 2 * _D),
            ("MechanismType.GAUSSIAN", 2.0, 2.0, _D),
        ]
        built = [cache.get(*v) for v in variants]
        assert len(cache) == len(variants)
        assert len({id(p) for p in built}) == len(variants)

    def test_lru_eviction_bounds_entries(self):
        cache = eng.SpectrumCache(max_entries=3)
        for scale in (1.0, 2.0, 3.0, 4.0, 5.0):
            cache.get("MechanismType.LAPLACE", scale, 1.0, 1e-2)
        assert len(cache) == 3

    def test_generic_kind_builds_dominating_pld(self):
        cache = eng.SpectrumCache()
        pld = cache.get("job_failed", (0.5, 1e-6), 1.0, _D)
        # The three-point PLD of an (eps0, delta0) guarantee: its
        # epsilon at delta0 is eps0 (up to grid rounding above).
        assert pld.get_epsilon_for_delta(1e-6) == pytest.approx(0.5,
                                                                rel=1e-2)


class TestAccountantRewire:
    """PLDBudgetAccountant through the cache + batched engine."""

    def test_budget_still_satisfied(self):
        accountant = PLDBudgetAccountant(1.0, 1e-6,
                                         pld_discretization=1e-3)
        specs = [accountant.request_budget(MechanismType.GAUSSIAN)
                 for _ in range(4)]
        accountant.compute_budgets()
        composed = accountant._compose_distributions(
            accountant.minimum_noise_std)
        assert composed.get_epsilon_for_delta(1e-6) <= 1.0 + 1e-6
        assert all(s.noise_standard_deviation ==
                   specs[0].noise_standard_deviation for s in specs)

    def test_rejects_bad_discretization(self):
        with pytest.raises(ValueError, match="pld_discretization"):
            PLDBudgetAccountant(1.0, 1e-6, pld_discretization=-1e-4)
        with pytest.raises(ValueError, match="pld_discretization"):
            PLDBudgetAccountant(1.0, 1e-6, pld_discretization=0.9)


def _gaussian_record(eps, delta):
    std = dpc.gaussian_sigma(eps, delta, 1.0)
    return {
        "seq": 0, "job_id": None, "metric": "count",
        "mechanism_kind": "MechanismType.GAUSSIAN", "weight": 1.0,
        "sensitivity": 1.0, "count": 1, "process_index": 0,
        "eps": eps, "delta": delta, "noise_std": std,
    }


def _admit_until_refused(ledger, eps, delta, cap):
    n = 0
    while n < cap:
        job = f"{ledger.tenant_id}--j{n + 1}"
        try:
            ledger.reserve(job, eps)
        except TenantBudgetExceededError:
            break
        ledger.charge(job, [_gaussian_record(eps, delta)])
        n += 1
    return n


class TestDualSpendLedger:

    def test_naive_mode_unchanged_and_bit_exact(self):
        led = TenantLedger("acct-a", 1.0, BlockJournal(None))
        n = _admit_until_refused(led, 0.1, 1e-8, cap=50)
        assert n == 10
        # Bit-exact (not approx) against THE named spend fold.
        assert led.spent_epsilon() == obs.fold_spend([0.1] * n)
        snap = led.snapshot()
        assert snap["accounting_mode"] == "naive"
        assert snap["admission_spent_epsilon"] == snap["spent_epsilon"]

    def test_pld_mode_capacity_multiplier(self):
        """The acceptance bar: >= 2x jobs admitted on one fixed budget
        at k >= 100 Gaussian jobs, with the naive ledger-of-record sum
        still bit-exact."""
        eps, delta, budget = 0.1, 1e-8, 5.0
        naive_led = TenantLedger("acct-n", budget, BlockJournal(None),
                                 pld_discretization=_D)
        n_naive = _admit_until_refused(naive_led, eps, delta, cap=200)
        assert n_naive == 50

        pld_led = TenantLedger("acct-p", budget, BlockJournal(None),
                               accounting_mode="pld",
                               pld_discretization=_D)
        cap = max(2 * n_naive, 100) + 10
        n_pld = _admit_until_refused(pld_led, eps, delta, cap=cap)
        assert n_pld >= max(2 * n_naive, 100)
        # The ledger of record is untouched by the admission mode.
        assert pld_led.spent_epsilon() == obs.fold_spend([eps] * n_pld)
        snap = pld_led.snapshot()
        assert snap["accounting_mode"] == "pld"
        assert snap["pld_spent_epsilon"] < snap["spent_epsilon"]
        assert snap["admission_spent_epsilon"] <= snap["spent_epsilon"]
        # The saved-epsilon gauge reflects the last rebuild.
        saved = telemetry.gauge_snapshot().get(
            "tenant_pld_epsilon_saved", {}).get("acct-p")
        assert saved == pytest.approx(
            snap["spent_epsilon"] - snap["pld_spent_epsilon"], abs=1e-9)

    @pytest.mark.parametrize("as_type", [float, np.float64],
                             ids=["float", "np.float64"])
    def test_spend_fold_is_one_algorithm_for_both_types(self, as_type):
        """Builtin sum() is compensated for exact floats and plain for
        np.float64 on Python >= 3.12; the ledger, the accountant and
        the odometer must fold identically whichever type a share
        arrives as."""
        shares = [as_type(0.1)] * 10
        expected = 0.0
        for share in shares:
            expected += share  # the left-to-right float64 fold
        assert obs.fold_spend(shares) == expected == 0.9999999999999999
        assert type(obs.fold_spend(iter(shares))) in (float, np.float64)
        led = TenantLedger("acct-fold", 10.0, BlockJournal(None))
        led.charge("acct-fold--j1",
                   [_gaussian_record(share, 1e-8) for share in shares])
        assert led.spent_epsilon() == expected
        assert led.job_spent_epsilon("acct-fold--j1") == expected
        assert led.snapshot()["spent_epsilon"] == expected

    def test_pld_admission_never_looser_than_budget(self):
        # Even in pld mode a request that exceeds the remaining budget
        # under the COMPOSED spend is refused.
        led = TenantLedger("acct-r", 0.5, BlockJournal(None),
                           accounting_mode="pld", pld_discretization=_D)
        led.reserve("acct-r--j1", 0.4)
        with pytest.raises(TenantBudgetExceededError):
            led.reserve("acct-r--j2", 0.2)

    def test_pld_spend_survives_reload(self, tmp_path):
        journal = BlockJournal(str(tmp_path))
        led = TenantLedger("acct-d", 2.0, journal, accounting_mode="pld",
                           pld_discretization=_D)
        for i in range(5):
            job = f"acct-d--j{i + 1}"
            led.reserve(job, 0.1)
            led.charge(job, [_gaussian_record(0.1, 1e-8)])
        reloaded = TenantLedger("acct-d", 2.0, BlockJournal(str(tmp_path)),
                                accounting_mode="pld",
                                pld_discretization=_D)
        assert reloaded.spent_epsilon() == led.spent_epsilon()
        assert reloaded.pld_spent_epsilon() == pytest.approx(
            led.pld_spent_epsilon(), abs=1e-12)

    def test_pending_records_skipped_like_naive(self):
        rec = _gaussian_record(0.1, 1e-8)
        pending = dict(rec, eps=None, delta=None, noise_std=None)
        eps, _ = eng.composed_epsilon_from_records([rec, pending, rec],
                                                   discretization=_D)
        only = eng.composed_epsilon_from_records([rec, rec],
                                                 discretization=_D)[0]
        assert eps == pytest.approx(only, abs=1e-12)

    def test_rejects_bad_knobs(self):
        with pytest.raises(ValueError, match="tenant_accounting"):
            TenantLedger("acct-x", 1.0, BlockJournal(None),
                         accounting_mode="exact")
        with pytest.raises(ValueError, match="pld_discretization"):
            TenantLedger("acct-x", 1.0, BlockJournal(None),
                         pld_discretization=float("nan"))


class TestOdometerNoiseStd:

    def test_round_trips_through_journal(self, tmp_path):
        journal = BlockJournal(str(tmp_path))
        rows = [_gaussian_record(0.2, 1e-7)]
        obs.persist_odometer(journal, "acct-o", records=rows)
        loaded = obs.load_odometer(journal, "acct-o")
        assert loaded[0]["noise_std"] == rows[0]["noise_std"]

    def test_legacy_trail_without_column_loads_none(self, tmp_path):
        from pipelinedp_tpu.runtime.journal import BlockRecord
        journal = BlockJournal(str(tmp_path))
        journal.put("acct-o", obs.ODOMETER_KEY, BlockRecord(
            ids=np.asarray([0], dtype=np.int64),
            outputs={
                "eps": np.asarray([0.1]), "delta": np.asarray([1e-8]),
                "weight": np.asarray([1.0]),
                "sensitivity": np.asarray([1.0]),
                "count": np.asarray([1], dtype=np.int64),
                "process_index": np.asarray([0], dtype=np.int32),
                "job_id": np.asarray([""], dtype=np.str_),
                "metric": np.asarray([""], dtype=np.str_),
                "mechanism_kind": np.asarray(["MechanismType.GAUSSIAN"],
                                             dtype=np.str_),
            }))
        loaded = obs.load_odometer(journal, "acct-o")
        assert loaded[0]["noise_std"] is None
        # And the spend rebuild still works off the (eps, delta) share.
        eps, _ = eng.composed_epsilon_from_records(loaded,
                                                   discretization=_D)
        assert math.isfinite(eps) and eps > 0


class TestMetricsExport:

    def test_pld_metrics_render_and_parse_strict(self):
        eng.compose_plds([pldlib.from_gaussian_mechanism(1.0, _D)], [2])
        telemetry.set_gauge("tenant_pld_epsilon_saved", 0.25,
                            job_id="acct-m")
        text = obs.render_prometheus()
        names = ("pdp_pld_compositions", "pdp_pld_cache_hits",
                 "pdp_pld_cache_misses", "pdp_tenant_pld_epsilon_saved")
        for name in names:
            assert any(line.startswith(name) for line in text.splitlines())
        parsed = obs.parse_prometheus(text)  # strict grammar must hold
        assert parsed["pdp_pld_compositions"]["type"] == "counter"


class TestValidators:

    @pytest.mark.parametrize("bad", ["exact", "", None, 1, True])
    def test_tenant_accounting_rejects(self, bad):
        with pytest.raises(ValueError, match="tenant_accounting"):
            input_validators.validate_tenant_accounting(bad, "t")

    @pytest.mark.parametrize("ok", ["naive", "pld"])
    def test_tenant_accounting_accepts(self, ok):
        input_validators.validate_tenant_accounting(ok, "t")

    @pytest.mark.parametrize(
        "bad", [0.0, -1e-4, 1e-8, 0.6, float("nan"), float("inf"), True,
                "fine"])
    def test_pld_discretization_rejects(self, bad):
        with pytest.raises(ValueError, match="pld_discretization"):
            input_validators.validate_pld_discretization(bad, "t")

    @pytest.mark.parametrize("ok", [1e-7, 1e-4, 0.5])
    def test_pld_discretization_accepts(self, ok):
        input_validators.validate_pld_discretization(ok, "t")
