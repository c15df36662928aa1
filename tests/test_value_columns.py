"""Several value columns bounded in ONE pass (AggregateParams.value_columns).

  (a) at a huge epsilon, with bounds that do not bind, every released field
      equals a numpy group-by, on every TPU route and on LocalBackend;
  (b) with l-infinity and a clamp that bind, both backends' releases over
      30 noise seeds pass the plain reference's comparison (the law
      perfbench/laws/columns_laplace_public.py at the Q1 cell's own
      limits: its pooled spread and noise are in standard errors, so one
      limit holds a window of any job count) and each of its breaks fails
      it;
  (c) one column expressed the new way releases what the old one-column job
      releases, bit for bit under a fixed noise seed, on the dense, blocked
      and meshed routes;
  (d) the routes that refuse several columns do so before any budget is
      requested.
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import columnar, combiners, executor
from pipelinedp_tpu.parallel import make_mesh

from perfbench import reference
from perfbench.generators import tpch_lineitem_columns
from perfbench.laws import columns_laplace_public as law

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
M = pdp.Metrics
EXTRACTORS = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                partition_extractor=lambda r: r[1],
                                value_extractor=lambda r: r[2])


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _mesh():
    return make_mesh(devices=jax.devices()[:4])


BACKENDS = {
    "local": lambda seed: pdp.LocalBackend(),
    "dense": lambda seed: pdp.TPUBackend(noise_seed=seed),
    "blocked": lambda seed: pdp.TPUBackend(
        noise_seed=seed, large_partition_threshold=2, block_partitions=2),
    "mesh4": lambda seed: pdp.TPUBackend(noise_seed=seed, mesh=_mesh()),
    "blocked_mesh4": lambda seed: pdp.TPUBackend(
        noise_seed=seed, mesh=_mesh(), large_partition_threshold=2,
        block_partitions=2),
}


def _release(backend, rows, params, publics, epsilon, extractors=EXTRACTORS):
    accountant = pdp.NaiveBudgetAccountant(
        total_epsilon=epsilon, total_delta=0.0 if publics else 1e-6)
    engine = pdp.DPEngine(accountant, backend)
    result = engine.aggregate(rows, params, extractors,
                              public_partitions=publics)
    accountant.compute_budgets()
    return dict(result)


# ---- (a) every field equals a group-by -----------------------------------

COLUMNS = [pdp.ValueColumn("qty", 1, 50, [M.SUM, M.MEAN]),
           pdp.ValueColumn("price", 0, 1000, [M.SUM, M.MEAN]),
           pdp.ValueColumn("net", 0, 1000, [M.SUM]),
           pdp.ValueColumn("gross", 0, 1100, [M.SUM]),
           pdp.ValueColumn("disc", 0, 0.1, [M.MEAN])]
FIELDS = ("qty_mean", "count", "qty_sum", "price_mean", "price_sum",
          "net_sum", "gross_sum", "disc_mean")


def _loose_rows(seed=0, ids=200, partitions=3):
    """Every id in every partition with 1-6 rows: l0 = 3 and linf = 6 do
    not bind, and no value leaves its column's range."""
    rng = np.random.default_rng(seed)
    per_pair = rng.integers(1, 7, (ids, partitions))
    pid = np.repeat(np.repeat(np.arange(ids), partitions), per_pair.ravel())
    pk = np.repeat(np.tile(np.arange(partitions), ids), per_pair.ravel())
    n = len(pid)
    qty = rng.integers(1, 51, n).astype(np.float64)
    price = rng.uniform(1, 1000, n)
    disc = rng.integers(0, 11, n) / 100.0
    values = np.stack([qty, price, price * (1 - disc),
                       price * (1 - disc) * 1.08, disc], axis=1)
    return pid, pk, values


def _group_by(pk, values, partitions):
    out = {}
    for p in range(partitions):
        v = values[pk == p]
        out[p] = dict(count=len(v), qty_sum=v[:, 0].sum(),
                      qty_mean=v[:, 0].mean(), price_sum=v[:, 1].sum(),
                      price_mean=v[:, 1].mean(), net_sum=v[:, 2].sum(),
                      gross_sum=v[:, 3].sum(), disc_mean=v[:, 4].mean())
    return out


@pytest.mark.parametrize("route", list(BACKENDS))
def test_every_field_equals_a_group_by(route):
    pid, pk, values = _loose_rows()
    rows = [(int(a), int(b), tuple(v)) for a, b, v in zip(pid, pk, values)]
    params = pdp.AggregateParams(metrics=[M.COUNT],
                                 max_partitions_contributed=3,
                                 max_contributions_per_partition=6,
                                 value_columns=COLUMNS)
    got = _release(BACKENDS[route](11), rows, params, [0, 1, 2, 3], 1e6)
    want = _group_by(pk, values, 3)
    assert sorted(got) == [0, 1, 2, 3]
    for p in range(3):
        assert got[p]._fields == FIELDS
        for field in FIELDS:
            assert getattr(got[p], field) == pytest.approx(
                want[p][field], rel=1e-5), (p, field)
    # The empty public partition: noise about 0 in every linear field.
    for field in ("count", "net_sum", "gross_sum"):
        assert abs(getattr(got[3], field)) < 1.0


def test_encoded_columns_equal_extracted_rows():
    """EncodedData.values [n, d] and rows whose extractor yields d values
    are the same job: the same release under one noise seed."""
    pid, pk, values = _loose_rows(seed=3)
    rows = [(int(a), int(b), tuple(v)) for a, b, v in zip(pid, pk, values)]
    params = pdp.AggregateParams(metrics=[M.COUNT],
                                 max_partitions_contributed=3,
                                 max_contributions_per_partition=2,
                                 value_columns=COLUMNS)
    encoded = columnar.encode_columns(pid, pk, values,
                                      public_partitions=[0, 1, 2])
    a = _release(pdp.TPUBackend(noise_seed=5), rows, params, [0, 1, 2], 2.0)
    b = _release(pdp.TPUBackend(noise_seed=5), encoded, params, [0, 1, 2],
                 2.0, pdp.DataExtractors())
    assert a == b


# ---- (b) the law holds both backends, and each break fails -----------------

JOBS = 30


@pytest.fixture(scope="module")
def binding():
    """Q1's guarantees with bounds that bind hard at a test's size: rows by
    the configuration's generator, linf 8 of about 18 rows a pair, the
    price clamps at 60,000, and an epsilon at which the clamp shows."""
    config = _load("perfbench", "configs", "q1-fewgroups.json")
    g = json.loads(json.dumps(config["guarantees"]))
    g.update(epsilon=2000.0, linf=8)
    for column in g["columns"][1:4]:
        column["max_value"] = 60000.0
    columns = tpch_lineitem_columns.generate(rows=12000, seed=7,
                                             customers=300, parts=200000)
    limits = _load("perfbench", "workloads", "q1-sf10-encoded.json")["limits"]
    return g, columns, law.expectations(*columns, g), limits


def _params_of(g):
    metrics = {"sum": M.SUM, "mean": M.MEAN}
    return pdp.AggregateParams(
        metrics=[M.COUNT], max_partitions_contributed=g["l0"],
        max_contributions_per_partition=g["linf"],
        value_columns=[pdp.ValueColumn(c["name"], c["min_value"],
                                       c["max_value"],
                                       [metrics[m] for m in c["metrics"]])
                       for c in g["columns"]])


@pytest.mark.parametrize("route", ["dense", "local"])
def test_releases_pass_the_law(binding, route):
    g, (pid, pk, values), expect, limits = binding
    publics = list(range(g["public_partitions"]))
    if route == "local":
        inside = pk >= 0  # LocalBackend drops the others itself, slowly
        rows = [(int(a), int(b), tuple(v)) for a, b, v in
                zip(pid[inside], pk[inside], values[inside])]
        extractors = EXTRACTORS
    else:
        rows = columnar.encode_columns(pid, pk, values,
                                       public_partitions=publics)
        extractors = pdp.DataExtractors()
    releases = []
    for seed in range(JOBS):
        got = _release(BACKENDS[route](seed), rows, _params_of(g), publics,
                       g["epsilon"], extractors)
        releases.append((np.array(list(got)), np.array(
            [[getattr(m, name) for name in g["released"]]
             for m in got.values()])))
    correct, table = reference.decide(law.compare(expect, releases), limits)
    assert correct, {k: v for k, v in table.items() if not v["ok"]}


@pytest.mark.parametrize("broken", [None, "linf_off", "clamp_off",
                                    "noise_half", "swap_columns"])
def test_each_break_fails_the_law(binding, broken):
    g, columns, expect, limits = binding
    pairs = law.Pairs(*columns, g)
    rng = np.random.default_rng(1)
    releases = [law.simulate_release(pairs, g, rng, broken)
                for _ in range(JOBS)]
    correct, table = reference.decide(law.compare(expect, releases), limits)
    assert correct == (broken is None), table


def test_law_counts_the_row_it_reads():
    g = _load("perfbench", "configs", "q1-fewgroups.json")["guarantees"]
    assert law.min_bytes(100, 6, g) == 100 * (9 + 4 * 5) + 6 * 8 * 4
    assert law.budgets(g)["mechanisms"] == 8


# ---- (c) one column the new way is the old job, bit for bit ---------------


def _one_column_rows(seed=4, n=3000):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 150, n), rng.integers(0, 6, n),
            rng.uniform(-1, 7, n))


@pytest.mark.parametrize("form", ["rows", "encoded"])
@pytest.mark.parametrize("route", ["dense", "blocked", "mesh4"])
def test_one_column_is_the_old_job_bit_for_bit(route, form):
    pid, pk, value = _one_column_rows()
    bounds = dict(max_partitions_contributed=2,
                  max_contributions_per_partition=3)
    old = pdp.AggregateParams(metrics=[M.COUNT, M.SUM, M.MEAN], min_value=0,
                              max_value=5, **bounds)
    new = pdp.AggregateParams(
        metrics=[M.COUNT], value_columns=[
            pdp.ValueColumn("v", 0, 5, [M.SUM, M.MEAN])], **bounds)
    if form == "rows":
        old_in = [(int(a), int(b), float(v))
                  for a, b, v in zip(pid, pk, value)]
        new_in = [(a, b, (v,)) for a, b, v in old_in]
        extractors = EXTRACTORS
    else:
        old_in = columnar.encode_columns(pid, pk, value)
        new_in = dataclasses.replace(old_in, values=old_in.values[:, None])
        extractors = pdp.DataExtractors()
    want = _release(BACKENDS[route](9), old_in, old, None, 3.0, extractors)
    got = _release(BACKENDS[route](9), new_in, new, None, 3.0, extractors)
    assert want and sorted(got) == sorted(want)
    for key, metrics in want.items():
        assert got[key]._fields == ("v_mean", "count", "v_sum")
        assert tuple(got[key]) == tuple(metrics)  # mean, count, sum


# ---- (d) routes that refuse several columns, before any budget ------------


def _several_columns_params():
    return pdp.AggregateParams(
        metrics=[M.COUNT], max_partitions_contributed=2,
        max_contributions_per_partition=2,
        value_columns=[pdp.ValueColumn("a", 0, 1, [M.SUM]),
                       pdp.ValueColumn("b", 0, 1, [M.SUM])])


def test_utility_analysis_refuses_several_columns():
    from pipelinedp_tpu import analysis
    from pipelinedp_tpu.analysis import utility_analysis_engine

    accountant = pdp.NaiveBudgetAccountant(total_epsilon=1, total_delta=1e-6)
    engine = utility_analysis_engine.UtilityAnalysisEngine(
        accountant, pdp.LocalBackend())
    options = analysis.UtilityAnalysisOptions(
        epsilon=1, delta=1e-6, aggregate_params=_several_columns_params())
    with pytest.raises(NotImplementedError, match="analysis/"):
        engine.analyze([(0, 0, (0.5, 0.5))], options, EXTRACTORS)
    assert accountant.mechanism_count == 0


def test_parameter_tuning_refuses_several_columns():
    from pipelinedp_tpu.analysis import parameter_tuning

    options = parameter_tuning.TuneOptions(
        epsilon=1, delta=1e-6, aggregate_params=_several_columns_params(),
        function_to_minimize=parameter_tuning.MinimizingFunction.
        ABSOLUTE_ERROR,
        parameters_to_tune=parameter_tuning.ParametersToTune(
            max_partitions_contributed=True))
    with pytest.raises(NotImplementedError, match="analysis/"):
        parameter_tuning._check_tune_args(options, False)


def test_sketch_route_refuses_several_columns():
    from pipelinedp_tpu.utility_analysis import peeker_engine

    accountant = pdp.NaiveBudgetAccountant(total_epsilon=1, total_delta=1e-6)
    engine = peeker_engine.PeekerEngine(accountant, pdp.LocalBackend())
    with pytest.raises(NotImplementedError, match="utility_analysis/"):
        engine.aggregate_sketches([(0, 1.0, 1)], _several_columns_params())
    assert accountant.mechanism_count == 0


def test_wrong_width_is_refused_before_any_launch():
    params = _several_columns_params()
    rows = [(0, 0, (0.5, 0.5, 0.5))]  # three values for two columns
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=1, total_delta=0)
    result = pdp.DPEngine(accountant, pdp.TPUBackend()).aggregate(
        rows, params, EXTRACTORS, public_partitions=[0])
    accountant.compute_budgets()
    with pytest.raises(TypeError, match="value_columns names 2 columns"):
        list(result)


# ---- the parameters, the combiners, the plan ------------------------------


@pytest.mark.parametrize("bad, message", [
    (dict(value_columns=[]), "non-empty sequence"),
    (dict(value_columns=[pdp.ValueColumn("a", 0, 1, [M.SUM])] * 2),
     "names must differ"),
    (dict(value_columns=[pdp.ValueColumn("a", 0, 1, [M.SUM])], min_value=0,
          max_value=1), "own min_value/max_value"),
    (dict(value_columns=[pdp.ValueColumn("a", 0, 1, [M.SUM])],
          metrics=[M.SUM], min_value=None), "COUNT and PRIVACY_ID_COUNT only"),
])
def test_aggregate_params_refuse(bad, message):
    kwargs = dict(metrics=[M.COUNT], max_partitions_contributed=1,
                  max_contributions_per_partition=1)
    kwargs.update(bad)
    with pytest.raises(ValueError, match=message):
        pdp.AggregateParams(**kwargs)


@pytest.mark.parametrize("kwargs, message", [
    (dict(name="not a name", min_value=0, max_value=1, metrics=[M.SUM]),
     "identifier"),
    (dict(name="a", min_value=2, max_value=1, metrics=[M.SUM]),
     "equal to or greater"),
    (dict(name="a", min_value=0, max_value=float("inf"), metrics=[M.SUM]),
     "finite"),
    (dict(name="a", min_value=0, max_value=1, metrics=[M.VARIANCE]),
     "subset of SUM, MEAN"),
    (dict(name="a", min_value=0, max_value=1, metrics=[]),
     "subset of SUM, MEAN"),
])
def test_value_column_refuses(kwargs, message):
    with pytest.raises(ValueError, match=message):
        pdp.ValueColumn(**kwargs)


def test_plan_and_budget_of_q1():
    """Five columns: one compound of five column combiners, eight
    mechanisms in equal shares, COUNT from the first mean column, five
    carried reduce columns beside the pair flag."""
    g = _load("perfbench", "configs", "q1-fewgroups.json")["guarantees"]
    params = _params_of(g)
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=1, total_delta=0)
    with accountant.scope(weight=1):
        compound = combiners.create_compound_combiner(params, accountant)
    assert accountant.mechanism_count == 8
    accountant.compute_budgets()
    assert tuple(compound.metrics_names()) == tuple(g["released"])
    cfg = executor.make_kernel_config(params, compound, 6, False, None)
    assert cfg.value_columns == 5
    assert executor.reduce_column_names(cfg) == [
        "nsum0", "nsum1", "sum2", "sum3", "nsum4"]
    stds = executor.compute_noise_stds(compound, params)
    scales = law.budgets(g)["scales"]
    assert stds[0] == pytest.approx(np.sqrt(2) * scales["count"])
    assert stds[4] == pytest.approx(np.sqrt(2) * scales["sum"][2])
    assert stds[7] == pytest.approx(np.sqrt(2) * scales["nsum"][4])
    min_v, max_v, _, _, mid = executor.kernel_scalars(params)
    assert list(mid) == [25.5, 35000.0, 35000.0, 35000.0, 0.05]
    assert list(min_v) == [1, 0, 0, 0, 0] and max_v[0] == 50


def test_count_alone_beside_sum_columns_has_its_own_mechanism():
    params = _several_columns_params()
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=1, total_delta=0)
    with accountant.scope(weight=1):
        compound = combiners.create_compound_combiner(params, accountant)
    assert accountant.mechanism_count == 3
    assert compound.metrics_names() == ["count", "a_sum", "b_sum"]


# ---- streamed input carries the columns too --------------------------------


def _chunked(pid, pk, values, rows=1000):
    return [(pid[i:i + rows], pk[i:i + rows], values[i:i + rows])
            for i in range(0, len(pid), rows)]


@pytest.mark.parametrize("encode_mode", ["host", "hash_device"])
def test_chunk_source_carries_the_columns(encode_mode):
    """A ChunkSource whose chunks hold values [n, d] releases what the same
    rows pre-encoded release, under one noise seed."""
    pid, pk, values = _loose_rows(seed=5)
    params = pdp.AggregateParams(metrics=[M.COUNT],
                                 max_partitions_contributed=2,
                                 max_contributions_per_partition=2,
                                 value_columns=COLUMNS)
    publics = [0, 1, 2]
    want = _release(pdp.TPUBackend(noise_seed=8),
                    columnar.encode_columns(pid, pk, values,
                                            public_partitions=publics),
                    params, publics, 3.0, pdp.DataExtractors())
    got = _release(pdp.TPUBackend(noise_seed=8),
                   pdp.ChunkSource(_chunked(pid, pk, values),
                                   encode_mode=encode_mode),
                   params, publics, 3.0, pdp.DataExtractors())
    assert got == want


def test_chunk_source_of_one_column_is_the_old_job():
    pid, pk, value = _one_column_rows(seed=6, n=4000)
    bounds = dict(max_partitions_contributed=2,
                  max_contributions_per_partition=3)
    old = pdp.AggregateParams(metrics=[M.COUNT, M.SUM], min_value=0,
                              max_value=5, **bounds)
    new = pdp.AggregateParams(
        metrics=[M.COUNT],
        value_columns=[pdp.ValueColumn("v", 0, 5, [M.SUM])], **bounds)
    want = _release(pdp.TPUBackend(noise_seed=2),
                    pdp.ChunkSource(_chunked(pid, pk, value)), old, None,
                    3.0, pdp.DataExtractors())
    got = _release(pdp.TPUBackend(noise_seed=2),
                   pdp.ChunkSource(_chunked(pid, pk, value[:, None])), new,
                   None, 3.0, pdp.DataExtractors())
    assert want and sorted(got) == sorted(want)
    for key, metrics in want.items():
        assert got[key]._fields == ("count", "v_sum")
        assert tuple(got[key]) == tuple(metrics)
