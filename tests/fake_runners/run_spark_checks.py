"""Executes the Spark adapter stack over the in-memory fake runner.

Run with PYTHONPATH including tests/fake_runners (so `import pyspark`
resolves to the fake) and the repo root. Exercises the REAL adapter code —
pipeline_backend.SparkRDDBackend, private_spark's PrivateRDD, DPEngine on
RDDs, and the distributed utility-analysis path.
"""

import os
import sys

import pyspark
assert "fake_runners" in pyspark.__file__, pyspark.__file__

import pipelinedp_tpu as pdp
from pipelinedp_tpu import pipeline_backend, private_spark

ROWS = [(f"u{i % 30}", f"pk{i % 4}", float(i % 5)) for i in range(400)]
HUGE_EPS = 1e6
SC = pyspark.SparkContext()


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


def test_backend_ops_match_local():
    backend = pipeline_backend.SparkRDDBackend(SC)
    local = pdp.LocalBackend()
    kv = [("a", 1), ("b", 2), ("a", 3), ("c", 4)]

    def run_both(op):
        got = list(op(backend)(SC.parallelize(kv)).collect())
        want = list(op(local)(iter(kv)))
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
        for k, v in backend.group_by_key(SC.parallelize(kv), "g").collect()
    }
    check("group_by_key", got == {"a": [1, 3], "b": [2], "c": [4]})
    got = sorted(
        backend.filter_by_key(SC.parallelize(kv), ["a", "c"],
                              "fbk").collect())
    check("filter_by_key(list)", got == [("a", 1), ("a", 3), ("c", 4)])
    got = sorted(
        backend.filter_by_key(SC.parallelize(kv), SC.parallelize(["b"]),
                              "fbk2").collect())
    check("filter_by_key(rdd)", got == [("b", 2)])
    got = sorted(
        backend.flatten(
            (SC.parallelize(kv), SC.parallelize([("z", 9)])), "fl").collect())
    check("flatten", got == sorted(kv + [("z", 9)]))
    got = sorted(
        backend.sample_fixed_per_key(SC.parallelize(kv), 1,
                                     "sfpk").collect())
    check("sample_fixed_per_key",
          [k for k, _ in got] == ["a", "b", "c"] and all(
              len(v) == 1 for _, v in got))


def test_dp_engine_on_spark():
    backend = pipeline_backend.SparkRDDBackend(SC)
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
    result = engine.aggregate(SC.parallelize(ROWS), params, extractors,
                              [f"pk{i}" for i in range(4)])
    accountant.compute_budgets()
    got = dict(result.collect())
    for pk, want in raw_counts().items():
        assert abs(got[pk].count - want) < 0.5, (pk, got[pk].count, want)
    check("DPEngine.aggregate on SparkRDDBackend", True)


def test_private_rdd():
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                           total_delta=1e-6)
    private = private_spark.make_private(SC.parallelize(ROWS), accountant,
                                         lambda r: r[0])
    mapped = private.map(lambda r: (r[1], r[2]))
    count = mapped.count(
        pdp.CountParams(noise_kind=pdp.NoiseKind.LAPLACE,
                        max_partitions_contributed=4,
                        max_contributions_per_partition=20,
                        partition_extractor=lambda r: r[0]),
        public_partitions=[f"pk{i}" for i in range(4)])
    sums = mapped.sum(
        pdp.SumParams(noise_kind=pdp.NoiseKind.LAPLACE,
                      max_partitions_contributed=4,
                      max_contributions_per_partition=20,
                      min_value=0.0,
                      max_value=5.0,
                      partition_extractor=lambda r: r[0],
                      value_extractor=lambda r: r[1]),
        public_partitions=[f"pk{i}" for i in range(4)])
    selected = private.select_partitions(
        pdp.SelectPartitionsParams(max_partitions_contributed=4),
        partition_extractor=lambda r: r[1])
    flat = private.flat_map(lambda r: [r[2], r[2]])
    pid_count = flat.privacy_id_count(
        pdp.PrivacyIdCountParams(noise_kind=pdp.NoiseKind.LAPLACE,
                                 max_partitions_contributed=1,
                                 partition_extractor=lambda v: "all"),
        public_partitions=["all"])
    accountant.compute_budgets()
    got_counts = dict(count.collect())
    for pk, want in raw_counts().items():
        assert abs(got_counts[pk] - want) < 0.5, (pk, got_counts[pk])
    check("PrivateRDD count/sum", len(dict(sums.collect())) == 4)
    check("PrivateRDD select_partitions",
          set(selected.collect()) == set(raw_counts()))
    got_pid = dict(pid_count.collect())
    check("PrivateRDD flat_map + privacy_id_count",
          abs(got_pid["all"] - 30) < 0.5)


def test_private_rdd_mean_variance():
    import numpy as _np
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                           total_delta=1e-6)
    private = private_spark.make_private(SC.parallelize(ROWS), accountant,
                                         lambda r: r[0])
    mapped = private.map(lambda r: (r[1], r[2]))
    mean = mapped.mean(
        pdp.MeanParams(noise_kind=pdp.NoiseKind.GAUSSIAN,
                       max_partitions_contributed=4,
                       max_contributions_per_partition=20,
                       min_value=0.0,
                       max_value=5.0,
                       partition_extractor=lambda r: r[0],
                       value_extractor=lambda r: r[1]),
        public_partitions=[f"pk{i}" for i in range(4)])
    var = mapped.variance(
        pdp.VarianceParams(noise_kind=pdp.NoiseKind.GAUSSIAN,
                           max_partitions_contributed=4,
                           max_contributions_per_partition=20,
                           min_value=0.0,
                           max_value=5.0,
                           partition_extractor=lambda r: r[0],
                           value_extractor=lambda r: r[1]),
        public_partitions=[f"pk{i}" for i in range(4)])
    accountant.compute_budgets()
    raw_vals = {}
    for _, pk, v in ROWS:
        raw_vals.setdefault(pk, []).append(v)
    got_mean = dict(mean.collect())
    got_var = dict(var.collect())
    check("PrivateRDD mean",
          all(abs(got_mean[pk] - _np.mean(vs)) < 0.05
              for pk, vs in raw_vals.items()))
    check("PrivateRDD variance",
          all(abs(got_var[pk] - _np.var(vs)) < 0.1
              for pk, vs in raw_vals.items()))


def test_utility_analysis_on_spark():
    from pipelinedp_tpu import analysis
    from pipelinedp_tpu.analysis import data_structures
    backend = pipeline_backend.SparkRDDBackend(SC)
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
        SC.parallelize(ROWS), backend, options, extractors)
    reports = sorted(reports.collect(), key=lambda r: r.configuration_index)
    check("utility analysis on SparkRDDBackend",
          len(reports) == 1 and
          reports[0].partitions_info.num_dataset_partitions == 4)
    check("per-partition output on SparkRDDBackend",
          len(per_partition.collect()) == 4)


def test_executor_serialization_boundary():
    """Closures ship through cloudpickle: unserializable ones fail, and
    executors operate on copies of captured driver objects."""
    import threading
    lock = threading.Lock()
    bad = SC.parallelize([1, 2, 3]).map(lambda x: (lock, x)[1])
    try:
        bad.collect()
        check("unserializable closure rejected at the executor boundary",
              False)
    except TypeError:
        check("unserializable closure rejected at the executor boundary",
              True)

    driver_side = []
    out = SC.parallelize([1, 2, 3]).map(
        lambda x: (driver_side.append(x), x)[1]).collect()
    check("executors mutate a shipped COPY, not the driver object",
          out == [1, 2, 3] and driver_side == [])


if __name__ == "__main__":
    test_backend_ops_match_local()
    test_dp_engine_on_spark()
    test_private_rdd()
    test_private_rdd_mean_variance()
    test_utility_analysis_on_spark()
    test_executor_serialization_boundary()
    print("SPARK_CHECKS_PASSED")
