"""The configuration `netflix-percentiles` arrived as files (a law, a form,
a configuration, a cell, two per-layer metrics and their `BENCHMARK.json`
entries; the generator was there): its cell rehearses through the whole
harness to a `correct` result line that holds every number of its law and
reads both new metrics, and the breaks of the quantile tree — which
`test_control.py`'s fixed list does not name — fail the cell's comparison
at the rehearsal's size, as the sound reference does not."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import data, reference
from perfbench import run as perfbench_run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "netflix-pctl-encoded"
JOBS = 12


def test_the_cell_rehearses_to_a_correct_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    run = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL,
         "--seed", "2147483659", "--seconds", "4", "--trace", "1",
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert run.returncode == 0, run.stderr[-2000:]
    result = json.loads(run.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["device"]["rehearsal"] is True
    cell, config, layers, _ = perfbench_run.load_cell(CELL)
    assert set(result["compared"]) == set(cell["limits"])
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # 2 quantiles x 4 levels on the lazy descent; a tree a rehearsal movie.
    assert metrics["quantile_row_passes_per_job"] == 8.0
    assert metrics["quantile_trees_per_job"] == float(
        config["rehearsal"]["generator_args"]["movies"])
    # A rehearsal's trace has no device plane: no device metric is reported.
    host_side = {m["name"] for m in layers} - {
        "device_busy_ms_per_job", "device_idle_pct", "release_roofline"}
    assert host_side <= set(metrics), host_side - set(metrics)


@pytest.fixture(scope="module")
def rehearsal():
    cell, config, _, _ = perfbench_run.load_cell(CELL)
    config, rows_per_job = perfbench_run.sized(cell, config, rehearse=True)
    rows = data.generate(config["generator"], rows_per_job, 4242)
    g, law = config["guarantees"], reference.law_of(config)
    return cell, g, law, law.expectations(*rows, g), law.Pairs(*rows, g)


def decide(rehearsal, broken, seed):
    cell, g, law, expect, pairs = rehearsal
    rng = np.random.default_rng(seed)
    releases = [law.simulate_release(pairs, g, rng, broken)
                for _ in range(JOBS)]
    return reference.decide(law.compare(expect, releases), cell["limits"])


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("broken", [
    "tree_noise_off", "tree_noise_half", "tree_noise_one_level",
    "bounding_off_in_tree", "rank_swapped"])
def test_a_broken_tree_is_not_correct(rehearsal, broken, seed):
    assert broken in rehearsal[0]["controls"]
    correct, table = decide(rehearsal, broken, seed)
    assert not correct, f"{broken} passed: {table}"
