"""Executes the Beam adapter stack over the in-memory fake runner.

Run with PYTHONPATH including tests/fake_runners (so `import apache_beam`
resolves to the fake) and the repo root. Exercises the REAL adapter code —
pipeline_backend.BeamBackend, private_beam's PTransforms, label uniqueness,
DPEngine on Beam collections, and the distributed utility-analysis path —
none of which can execute under the plain test suite (apache_beam is not
installable here).
"""

import os
import sys

import apache_beam as beam
assert "fake_runners" in beam.__file__, beam.__file__

import pipelinedp_tpu as pdp
from pipelinedp_tpu import pipeline_backend, private_beam
from pipelinedp_tpu import private_collection

ROWS = [(f"u{i % 30}", f"pk{i % 4}", float(i % 5)) for i in range(400)]
HUGE_EPS = 1e6


def check(name, condition, detail=""):
    if not condition:
        print(f"FAILED: {name} {detail}", file=sys.stderr)
        sys.exit(1)
    print(f"ok: {name}")


def raw_counts():
    counts = {}
    for _, pk, _ in ROWS:
        counts[pk] = counts.get(pk, 0) + 1
    return counts


_create_counter = [0]


def pcol_of(pipeline, data):
    _create_counter[0] += 1
    return pipeline | f"create input {_create_counter[0]}" >> beam.Create(
        data)


def test_backend_ops_match_local():
    backend = pipeline_backend.BeamBackend()
    local = pdp.LocalBackend()
    pipeline = beam.Pipeline()
    kv = [("a", 1), ("b", 2), ("a", 3), ("c", 4)]

    def run_both(op, *args):
        got = list(op(backend)(pcol_of(pipeline, kv), *args))
        want = list(op(local)(iter(kv), *args))
        return got, want

    got, want = run_both(lambda b: lambda c: b.map(c, lambda x:
                                                   (x[0], x[1] * 10), "m"))
    check("map", sorted(got) == sorted(want))
    got, want = run_both(
        lambda b: lambda c: b.map_tuple(c, lambda k, v: (k, v + 1), "mt"))
    check("map_tuple", sorted(got) == sorted(want))
    got, want = run_both(
        lambda b: lambda c: b.map_values(c, lambda v: -v, "mv"))
    check("map_values", sorted(got) == sorted(want))
    got, want = run_both(
        lambda b: lambda c: b.filter(c, lambda x: x[1] > 1, "f"))
    check("filter", sorted(got) == sorted(want))
    got, want = run_both(lambda b: lambda c: b.keys(c, "k"))
    check("keys", sorted(got) == sorted(want))
    got, want = run_both(lambda b: lambda c: b.values(c, "v"))
    check("values", sorted(got) == sorted(want))
    got, want = run_both(lambda b: lambda c: b.distinct(c, "d"))
    check("distinct", sorted(got) == sorted(want))
    got, want = run_both(lambda b: lambda c: b.sum_per_key(c, "s"))
    check("sum_per_key", sorted(got) == sorted(want))
    got, want = run_both(lambda b: lambda c: b.count_per_element(c, "ce"))
    check("count_per_element", sorted(got) == sorted(want))
    got = {
        k: sorted(v)
        for k, v in pipeline_backend.BeamBackend().group_by_key(
            pcol_of(pipeline, kv), "g")
    }
    check("group_by_key", got == {"a": [1, 3], "b": [2], "c": [4]})
    got = sorted(
        backend.filter_by_key(pcol_of(pipeline, kv), ["a", "c"], "fbk"))
    check("filter_by_key(list)", got == [("a", 1), ("a", 3), ("c", 4)])
    keys_pcol = pipeline | "keys pcol" >> beam.Create(["b"])
    got = sorted(backend.filter_by_key(pcol_of(pipeline, kv), keys_pcol,
                                       "fbk2"))
    check("filter_by_key(pcol)", got == [("b", 2)])
    got = sorted(
        backend.flatten((pcol_of(pipeline, kv),
                         pipeline | "more" >> beam.Create([("z", 9)])),
                        "fl"))
    check("flatten", got == sorted(kv + [("z", 9)]))
    got = list(backend.to_list(pcol_of(pipeline, kv), "tl"))
    check("to_list", len(got) == 1 and sorted(got[0]) == sorted(kv))
    got = list(
        backend.map_with_side_inputs(pcol_of(pipeline, [1, 2]),
                                     lambda x, side: x + sum(side),
                                     [pipeline | "side" >> beam.Create(
                                         [10, 20])], "msi"))
    check("map_with_side_inputs", sorted(got) == [31, 32])
    got = sorted(
        backend.sample_fixed_per_key(pcol_of(pipeline, kv), 1, "sfpk"))
    check("sample_fixed_per_key",
          [k for k, _ in got] == ["a", "b", "c"] and all(
              len(v) == 1 for _, v in got))


def test_duplicate_labels_raise():
    pipeline = beam.Pipeline()
    pcol = pipeline | "input" >> beam.Create([1, 2])
    _ = pcol | "stage" >> beam.Map(lambda x: x)
    try:
        _ = pcol | "stage" >> beam.Map(lambda x: x)
    except RuntimeError as e:
        check("duplicate label raises", "already exists" in str(e))
    else:
        check("duplicate label raises", False)


def test_dp_engine_on_beam():
    backend = pipeline_backend.BeamBackend()
    pipeline = beam.Pipeline()
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                           total_delta=1e-6)
    engine = pdp.DPEngine(accountant, backend)
    params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
                                 max_partitions_contributed=4,
                                 max_contributions_per_partition=20,
                                 min_value=0.0,
                                 max_value=5.0)
    extractors = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                    partition_extractor=lambda r: r[1],
                                    value_extractor=lambda r: r[2])
    result = engine.aggregate(pcol_of(pipeline, ROWS), params, extractors,
                              [f"pk{i}" for i in range(4)])
    accountant.compute_budgets()
    got = dict(result)
    for pk, want in raw_counts().items():
        assert abs(got[pk].count - want) < 0.5, (pk, got[pk].count, want)
    check("DPEngine.aggregate on BeamBackend", True)


def test_private_beam_transforms():
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                           total_delta=1e-6)
    with beam.Pipeline() as pipeline:
        pcol = pipeline | "read" >> beam.Create(ROWS)
        private = pcol | private_beam.MakePrivate(
            budget_accountant=accountant,
            privacy_id_extractor=lambda r: r[0])
        mapped = private | private_beam.Map(lambda r: (r[1], r[2]))
        count = mapped | private_beam.Count(
            pdp.CountParams(noise_kind=pdp.NoiseKind.LAPLACE,
                            max_partitions_contributed=4,
                            max_contributions_per_partition=20,
                            partition_extractor=lambda r: r[0]),
            public_partitions=[f"pk{i}" for i in range(4)])
        sums = mapped | private_beam.Sum(
            pdp.SumParams(noise_kind=pdp.NoiseKind.LAPLACE,
                          max_partitions_contributed=4,
                          max_contributions_per_partition=20,
                          min_value=0.0,
                          max_value=5.0,
                          partition_extractor=lambda r: r[0],
                          value_extractor=lambda r: r[1]),
            public_partitions=[f"pk{i}" for i in range(4)])
        selected = (private | private_beam.SelectPartitions(
            pdp.SelectPartitionsParams(max_partitions_contributed=4),
            partition_extractor=lambda r: r[1]))
        accountant.compute_budgets()
        got_counts = dict(count)
        for pk, want in raw_counts().items():
            assert abs(got_counts[pk] - want) < 0.5, (pk, got_counts[pk])
        got_sums = dict(sums)
        check("private_beam Count/Sum",
              set(got_sums) == set(raw_counts()))
        check("private_beam SelectPartitions",
              set(selected) == set(raw_counts()))


def test_private_beam_mean_variance_pid_count():
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                           total_delta=1e-6)
    raw_vals = {}
    for _, pk, v in ROWS:
        raw_vals.setdefault(pk, []).append(v)
    with beam.Pipeline() as pipeline:
        pcol = pipeline | "read mv" >> beam.Create(ROWS)
        private = pcol | "mp mv" >> private_beam.MakePrivate(
            budget_accountant=accountant,
            privacy_id_extractor=lambda r: r[0])
        flat = private | private_beam.FlatMap(lambda r: [(r[1], r[2])] * 2)
        mean = flat | private_beam.Mean(
            pdp.MeanParams(noise_kind=pdp.NoiseKind.GAUSSIAN,
                           max_partitions_contributed=4,
                           max_contributions_per_partition=40,
                           min_value=0.0,
                           max_value=5.0,
                           partition_extractor=lambda r: r[0],
                           value_extractor=lambda r: r[1]),
            public_partitions=[f"pk{i}" for i in range(4)])
        var = flat | private_beam.Variance(
            pdp.VarianceParams(noise_kind=pdp.NoiseKind.GAUSSIAN,
                               max_partitions_contributed=4,
                               max_contributions_per_partition=40,
                               min_value=0.0,
                               max_value=5.0,
                               partition_extractor=lambda r: r[0],
                               value_extractor=lambda r: r[1]),
            public_partitions=[f"pk{i}" for i in range(4)])
        pid_count = private | private_beam.PrivacyIdCount(
            pdp.PrivacyIdCountParams(noise_kind=pdp.NoiseKind.GAUSSIAN,
                                     max_partitions_contributed=4,
                                     partition_extractor=lambda r: r[1]),
            public_partitions=[f"pk{i}" for i in range(4)])
        accountant.compute_budgets()
        import numpy as _np
        got_mean = dict(mean)
        got_var = dict(var)
        ok_mean = all(
            abs(got_mean[pk] - _np.mean(vs)) < 0.05
            for pk, vs in raw_vals.items())
        # FlatMap duplicated every value, which leaves mean/variance of the
        # duplicated stream identical to the raw one.
        ok_var = all(
            abs(got_var[pk] - _np.var(vs)) < 0.1
            for pk, vs in raw_vals.items())
        check("private_beam FlatMap + Mean", ok_mean)
        check("private_beam Variance", ok_var)
        got_pid = dict(pid_count)
        raw_pids = {}
        for pid, pk, _ in ROWS:
            raw_pids.setdefault(pk, set()).add(pid)
        check("private_beam PrivacyIdCount",
              all(abs(got_pid[pk] - len(pids)) < 0.5
                  for pk, pids in raw_pids.items()))


def test_private_beam_combine_per_key():

    class _SumCombineFn(private_collection.PrivateCombineFn):

        def create_accumulator(self):
            return 0.0

        def add_input_for_private_output(self, accumulator, value):
            return accumulator + min(max(value, 0.0), 5.0)

        def merge_accumulators(self, accumulators):
            return sum(accumulators)

        def extract_private_output(self, accumulator, budget,
                                   aggregate_params):
            assert budget.eps > 0
            return accumulator

        def request_budget(self, budget_accountant):
            return budget_accountant.request_budget(
                pdp.MechanismType.LAPLACE)

    accountant = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                           total_delta=1e-6)
    with beam.Pipeline() as pipeline:
        pcol = pipeline | "cpk read" >> beam.Create(ROWS)
        private = pcol | private_beam.MakePrivate(
            budget_accountant=accountant,
            privacy_id_extractor=lambda r: r[0])
        keyed = private | private_beam.Map(lambda r: (r[1], r[2]))
        combined = keyed | private_beam.CombinePerKey(
            _SumCombineFn(),
            private_collection.CombinePerKeyParams(
                max_partitions_contributed=4,
                max_contributions_per_partition=20))
        accountant.compute_budgets()
        got = dict(combined)
        check("private_beam CombinePerKey", len(got) == 4)


def test_private_contribution_bounds_on_beam():
    # Reference parity: DP L0-bound calculation runs on Beam
    # (/root/reference/tests/dp_engine_test.py
    # test_calculate_private_contribution_works_on_beam).
    backend = pipeline_backend.BeamBackend()
    pipeline = beam.Pipeline()
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                           total_delta=1e-6)
    engine = pdp.DPEngine(accountant, backend)
    params = pdp.CalculatePrivateContributionBoundsParams(
        aggregation_noise_kind=pdp.NoiseKind.LAPLACE,
        aggregation_eps=1.0,
        aggregation_delta=0.0,
        calculation_eps=1.0,
        max_partitions_contributed_upper_bound=8)
    extractors = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                    partition_extractor=lambda r: r[1],
                                    value_extractor=lambda r: r[2])
    partitions = pipeline | "bounds partitions" >> beam.Create(
        [f"pk{i}" for i in range(4)])
    result = engine.calculate_private_contribution_bounds(
        pcol_of(pipeline, ROWS), params, extractors, partitions)
    bounds = list(result)[0]
    check("calculate_private_contribution_bounds on BeamBackend",
          1 <= bounds.max_partitions_contributed <= 8)


def test_utility_analysis_on_beam():
    from pipelinedp_tpu import analysis
    from pipelinedp_tpu.analysis import data_structures
    backend = pipeline_backend.BeamBackend()
    pipeline = beam.Pipeline()
    options = data_structures.UtilityAnalysisOptions(
        epsilon=10,
        delta=1e-5,
        aggregate_params=pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT],
            noise_kind=pdp.NoiseKind.GAUSSIAN,
            max_partitions_contributed=2,
            max_contributions_per_partition=5))
    extractors = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                    partition_extractor=lambda r: r[1],
                                    value_extractor=lambda r: r[2])
    reports, per_partition = analysis.perform_utility_analysis(
        pcol_of(pipeline, ROWS), backend, options, extractors)
    reports = sorted(reports, key=lambda r: r.configuration_index)
    check("utility analysis on BeamBackend",
          len(reports) == 1 and
          reports[0].partitions_info.num_dataset_partitions == 4)
    check("per-partition output on BeamBackend",
          len(list(per_partition)) == 4)


def test_worker_serialization_boundary():
    """The fake runner ships every closure through cloudpickle; prove the
    boundary is real: unserializable closures fail, and workers operate on
    COPIES of captured driver objects (so driver-side mutation after the
    ship is invisible — the reason compute_budgets() must precede run())."""
    import threading
    pipeline = beam.Pipeline()
    lock = threading.Lock()  # not serializable, even by cloudpickle
    pcol = pcol_of(pipeline, [1, 2, 3])
    bad = pcol | "capture lock" >> beam.Map(lambda x: (lock, x)[1])
    try:
        list(bad._data)
        check("unserializable closure rejected at the worker boundary",
              False)
    except TypeError:
        check("unserializable closure rejected at the worker boundary",
              True)

    pipeline2 = beam.Pipeline()
    driver_side = []  # captured by the closure; workers get a copy
    pcol2 = pcol_of(pipeline2, [1, 2, 3])
    out = pcol2 | "append" >> beam.Map(
        lambda x: (driver_side.append(x), x)[1])
    result = list(out._data)
    check("workers mutate a shipped COPY, not the driver object",
          result == [1, 2, 3] and driver_side == [])


def test_grouped_values_are_lazy_reiterables():
    """GroupByKey/CombinePerKey values must behave like a real shuffle's
    lazy iterables: re-iterable, but len()/indexing raise TypeError (the
    bug class a DirectRunner list hides)."""
    pipeline = beam.Pipeline()
    pcol = pcol_of(pipeline, [("a", 1), ("a", 2), ("b", 3)])
    grouped = pcol | "gbk strict" >> beam.GroupByKey()
    items = dict(grouped._data)
    vs = items["a"]
    check("grouped values are re-iterable",
          sorted(vs) == [1, 2] and sorted(vs) == [1, 2])
    for op, fn in (("len", lambda: len(vs)), ("index", lambda: vs[0]),
                   ("bool", lambda: bool(vs))):
        try:
            fn()
            check(f"grouped values reject {op}()", False)
        except TypeError:
            check(f"grouped values reject {op}()", True)
    combined = pcol | "combine strict" >> beam.CombinePerKey(
        lambda values: sum(values))
    check("CombinePerKey fn receives an iterable (sum works)",
          dict(combined._data) == {"a": 3, "b": 3})

    pipeline2 = beam.Pipeline()
    pcol2 = pcol_of(pipeline2, [("a", 1)])
    try:
        _ = pcol2 | "combine list op" >> beam.CombinePerKey(
            lambda values: values[0])
        list(_._data)
        check("CombinePerKey fn indexing grouped values rejected", False)
    except TypeError:
        check("CombinePerKey fn indexing grouped values rejected", True)


def test_windowing_rejected():
    """The eager fake must refuse windowed pipelines rather than silently
    run them in one global window."""
    try:
        beam.WindowInto(object())
        check("WindowInto rejected", False)
    except NotImplementedError:
        check("WindowInto rejected", True)
    try:
        beam.window.FixedWindows(60)
        check("window.FixedWindows rejected", False)
    except NotImplementedError:
        check("window.FixedWindows rejected", True)


if __name__ == "__main__":
    test_backend_ops_match_local()
    test_duplicate_labels_raise()
    test_dp_engine_on_beam()
    test_private_beam_transforms()
    test_private_beam_mean_variance_pid_count()
    test_private_beam_combine_per_key()
    test_private_contribution_bounds_on_beam()
    test_utility_analysis_on_beam()
    test_worker_serialization_boundary()
    test_grouped_values_are_lazy_reiterables()
    test_windowing_rejected()
    print("BEAM_CHECKS_PASSED")
