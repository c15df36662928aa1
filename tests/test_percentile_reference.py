"""The program's percentiles against the benchmark's plain reference.

perfbench/laws/bounded_laplace_geometric_quantiles.py is a numpy quantile
tree written from the published description, importing nothing of the
program; it is what decides `correct` in the cell netflix-pctl-encoded.
Here, at sizes a CPU holds:

  * noise-free (epsilon = 1e6), the released PERCENTILE(50) / (90) of
    every partition equal the reference's noise-free descent over the
    partition's EXPECTED bounded counts within one leaf width — on the
    one-chunk dense path (P = 300) and on the lazy descent (P = 2,000),
    under private selection, with l0 and linf that bind (the interpolation
    inside the leaf is all that the bounding's sample can move: every
    partition's shares leave the ranks wide margins);
  * at the configuration's own epsilon and rehearsal size the law's
    `compare` passes `reference.decide` at the cell's limits on a window
    of the program's jobs, and the reference put in the program's place
    fails it under every break the cell lists.
"""

import json
import os

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import executor
from pipelinedp_tpu.runtime import telemetry
from perfbench import data, reference, traffic
from perfbench import run as perfbench_run
from perfbench.laws import bounded_laplace_geometric_quantiles as law

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "netflix-pctl-encoded"


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _guarantees(**changed):
    return dict(_load("perfbench", "configs",
                      "netflix-percentiles.json")["guarantees"], **changed)


# Values a float32 holds exactly, and (low, high) pairs of them: seven in
# ten of a partition's rows rate `low`, the others `high`, so the median
# is `low` and the 90th percentile `high` with a fifth of the rows to
# spare on either side of both ranks.
PATTERNS = [(1.0, 3.0), (2.0, 5.0), (4.0, 5.0), (2.5, 3.25), (1.0, 4.5)]


def _rows(n_partitions, seed):
    """Rows in which both bounds bind: every id rates four partitions (l0
    = 2 keeps two) and a quarter of its pairs twice (linf = 1 keeps one),
    a repeated pair's two rows alike."""
    rng = np.random.default_rng(seed)
    ids = n_partitions * 100  # 200 bounded rows a partition
    pid = np.repeat(np.arange(ids), 4)
    pk = rng.integers(0, n_partitions, len(pid))
    low, high = np.asarray(PATTERNS)[pk % len(PATTERNS)].T
    values = np.where(rng.random(len(pid)) < 0.7, low, high)
    again = rng.random(len(pid)) < 0.25
    return (np.concatenate([pid, pid[again]]),
            np.concatenate([pk, pk[again]]),
            np.concatenate([values, values[again]]))


@pytest.mark.parametrize("n_partitions, lazy", [(300, False), (2000, True)])
def test_noise_free_percentiles_equal_the_reference(n_partitions, lazy):
    g = _guarantees(epsilon=1e6)
    pid, pk, values = _rows(n_partitions, seed=n_partitions)
    job = perfbench_run.load_cell(CELL)[0]
    form = traffic.build_job(job, {"guarantees": g}, (pid, pk, values))
    before = telemetry.snapshot()
    release = form(12345)
    counted = telemetry.delta(before)
    keys, got = perfbench_run.release_arrays(release)
    assert len(keys) == n_partitions  # selection at this epsilon keeps all
    # One pass over the rows on either tree path (the lazy descent's one
    # sort, the dense histogram's one scatter-add); which path that was
    # shows in the node searches, 2 quantiles x 4 levels x P x 15 on the
    # lazy descent and not recorded on the histogram.
    assert counted["quantile_row_passes"] == 1
    assert counted.get("quantile_node_searches", 0) == (
        2 * 4 * n_partitions * 15 if lazy else 0)
    assert counted["quantile_trees"] == n_partitions

    pairs = law.Pairs(pid, pk, values, g)
    leaves, mean, _ = law.expected_tree_counts(pairs, g)
    want = law.tree_quantiles(mean, leaves, [0.5, 0.9], g)
    want = want[np.searchsorted(pairs.keys, keys)]
    width = (g["max_value"] - g["min_value"]) / 16**4
    np.testing.assert_allclose(got[:, 3:], want, rtol=0, atol=width)
    low, high = np.asarray(PATTERNS)[keys % len(PATTERNS)].T
    # The reference itself answers the pattern's ratings (5.0 lies at the
    # top of the last leaf).
    assert np.all(np.abs(want[:, 0] - low) <= width)
    assert np.all(np.abs(want[:, 1] - high) <= width)
    # Both bounds bound: the released counts are well under the rows'.
    assert got[:, 0].sum() < 0.45 * len(pid)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_laws_tree_walks_as_the_host_tree(seed):
    """The law's numpy tree against ops/quantile_tree.DenseQuantileTree
    (what LocalBackend releases from), noise-free, on values spread over
    twenty arbitrary leaves: two implementations of one descent."""
    from pipelinedp_tpu.aggregate_params import NoiseKind
    from pipelinedp_tpu.ops import quantile_tree

    g = _guarantees()
    rng = np.random.default_rng(seed)
    points = np.sort(rng.uniform(1.0, 5.0, 20))
    values = np.repeat(points, rng.integers(1, 400, 20))
    quantiles = [0.1, 0.5, 0.9, 0.99]
    host = quantile_tree.DenseQuantileTree(1.0, 5.0)
    host.add_entries(values)
    want = host.compute_quantiles(1e12, 0.0, 1, 1, quantiles,
                                  NoiseKind.LAPLACE,
                                  rng=np.random.default_rng(0))
    leaves, counts = np.unique(law.leaf_of(values, g), return_counts=True)
    got = law.tree_quantiles(counts[None, :], leaves, quantiles, g)[0]
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-9)


def test_row_passes_and_node_searches_follow_the_tree_path():
    """One row pass on either path; executor.quantile_node_searches is
    the dispatch's own predicate."""
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.PERCENTILE(50),
                 pdp.Metrics.PERCENTILE(90)],
        max_partitions_contributed=2, max_contributions_per_partition=1,
        min_value=1.0, max_value=5.0)
    from pipelinedp_tpu import combiners
    compound = combiners.create_compound_combiner(
        params, pdp.NaiveBudgetAccountant(1.0, 1e-6))
    cfgs = {p: executor.make_kernel_config(
        params, compound, p, private_selection=False, selection_params=None)
        for p in (300, 512, 513, 17770)}
    assert {p: executor.quantile_row_passes(c) for p, c in cfgs.items()
            } == {300: 1, 512: 1, 513: 1, 17770: 1}
    assert {p: executor.quantile_node_searches(c) for p, c in cfgs.items()
            } == {300: 0, 512: 0, 513: 2 * 4 * 513 * 15,
                  17770: 2 * 4 * 17770 * 15}


# ---------------------------------------------------------------------------
# At the configuration's epsilon, rehearsal size: the comparison itself
# ---------------------------------------------------------------------------

JOBS = 8


@pytest.fixture(scope="module")
def rehearsal():
    """(cell, configuration, expectations, pairs, raw columns) of the new
    cell at HALF its rehearsal size (rows, users and movies alike, so the
    rows per movie are the rehearsal's): tier-1 shares its cores."""
    cell, config, _, _ = perfbench_run.load_cell(CELL)
    config, rows_per_job = perfbench_run.sized(cell, config, rehearse=True)
    rows_per_job //= 2
    config = dict(config, generator=dict(config["generator"], args={
        name: size // 2 for name, size in config["generator"]["args"].items()}))
    columns = data.generate(config["generator"], rows_per_job, 2147483777)
    g = config["guarantees"]
    assert reference.law_of(config) is law
    return (cell, config, law.expectations(*columns, g),
            law.Pairs(*columns, g), columns)


def test_the_program_passes_the_law(rehearsal):
    cell, config, expect, _, columns = rehearsal
    job = traffic.build_job(cell, config, columns)
    releases = [perfbench_run.release_arrays(job(traffic.noise_seed(7, i)))
                for i in range(JOBS)]
    correct, table = reference.decide(law.compare(expect, releases),
                                      cell["limits"])
    assert correct, {k: v for k, v in table.items() if not v["ok"]}
    assert set(table) == set(cell["limits"])


def _control(rehearsal, broken, seed=3):
    cell, config, expect, pairs, _ = rehearsal
    rng = np.random.default_rng(seed)
    releases = [law.simulate_release(pairs, config["guarantees"], rng, broken)
                for _ in range(JOBS)]
    return reference.decide(law.compare(expect, releases), cell["limits"])


def test_the_reference_in_the_programs_place_passes(rehearsal):
    correct, table = _control(rehearsal, None)
    assert correct, {k: v for k, v in table.items() if not v["ok"]}


@pytest.mark.parametrize("broken", _load("perfbench", "workloads",
                                         CELL + ".json")["controls"])
def test_every_listed_break_fails(rehearsal, broken):
    correct, table = _control(rehearsal, broken)
    assert not correct, f"{broken} passed: {table}"


def test_the_cell_lists_every_break_of_the_tree():
    listed = _load("perfbench", "workloads", CELL + ".json")["controls"]
    assert set(law.TREE_BREAKS) <= set(listed)
