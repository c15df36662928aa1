"""A deployment arrives as files: copy `perfbench/` and `BENCHMARK.json` to
a temporary tree, drop in a generator, a job form (another entry point:
`DPEngine.select_partitions`), a law (a key-only comparison), a
configuration, a cell, a per-layer metric and their `BENCHMARK.json`
entries WITHOUT touching any file that was there, and `--rehearse` that
cell to a result line. A name that is not there is an error that lists
what exists. The toy's sizes are a test's, never a cell's.

`check()` is the whole check, so a thin tier-1 test can call it too.
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)

GENERATOR = '''"""Toy rows: (user, key) pairs, every key held by many users."""
import numpy as np


def generate(rows, seed, users, keys):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, users, rows), rng.integers(0, keys, rows) + 100)
'''

FORM = '''"""Toy form: the keys that `DPEngine.select_partitions` releases."""


def build_job(cell, config, columns):
    import pipelinedp_tpu as pdp

    g = config["guarantees"]
    user, key = columns
    rows = list(zip(user.tolist(), key.tolist()))
    params = pdp.SelectPartitionsParams(max_partitions_contributed=g["l0"])
    extractors = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                    partition_extractor=lambda r: r[1])

    def job(seed):
        accountant = pdp.NaiveBudgetAccountant(total_epsilon=g["epsilon"],
                                               total_delta=g["delta"])
        engine = pdp.DPEngine(accountant, pdp.TPUBackend(noise_seed=seed))
        kept = engine.select_partitions(rows, params, extractors)
        accountant.compute_budgets()
        return {int(k): (1.0,) for k in kept}

    return job
'''

LAW = '''"""Toy law: a release may only hold keys that some row bears, and has
to hold most of them (every key here has hundreds of users)."""
import numpy as np


def expectations(user, key, g):
    return {"keys": np.unique(key)}


def compare(expect, releases):
    unknown = sum(int((~np.isin(k, expect["keys"])).sum())
                  for k, _ in releases)
    kept = np.mean([len(k) for k, _ in releases]) / len(expect["keys"])
    return {"unknown_keys": float(unknown), "missing_share": 1.0 - kept}


def min_bytes(rows, kept_partitions, g):
    return rows * 8 + kept_partitions * 4
'''

CONFIG = {
    "name": "toy-keys", "source": "perfbench/tests/test_by_file.py",
    "scale": {"rows_per_job": 40000}, "reduced": [],
    "guarantees": {"law": "toy_keys", "epsilon": 1.0, "delta": 1e-6,
                   "l0": 2},
    "generator": {"name": "toy_rows", "args": {"users": 5000, "keys": 20}},
    "rehearsal": {"rows_per_job": 40000,
                  "generator_args": {"users": 5000, "keys": 20}},
}

CELL = {
    "name": "toy-select", "config": "toy-keys", "chips": 1,
    "traffic": {"name": "toy-select-keys", "driver": "closed_loop",
                "jobs_in_flight": 1, "input_form": "toy_select",
                "drains_per_job": 1},
    "traced_seconds": 1, "controls": [],
    "limits": {"unknown_keys": 0, "missing_share": 0.5},
    "why": "test_by_file's toy",
}

METRIC = {
    "name": "toy_builds_per_job", "layer": "drain and decode",
    "unit": "count/job", "moves": "rows_per_s",
    "reader": "window_builds_per_job", "reads": "a toy",
}


def digest(tree):
    """sha256 of every file under perfbench/ and of BENCHMARK.json."""
    out = {}
    for base, dirs, files in os.walk(os.path.join(tree, "perfbench")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, tree)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def plant(tree, generator="toy_rows", form="toy_select", law="toy_keys"):
    """The toy deployment, as new files plus BENCHMARK.json entries; the
    three names let a test state one that has no file."""
    shutil.copytree(HERE, os.path.join(tree, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tree)
    before = digest(tree)
    bench_dir = os.path.join(tree, "perfbench")

    def write(rel, text):
        path = os.path.join(bench_dir, rel)
        assert not os.path.exists(path), f"{rel} was there"
        with open(path, "w") as f:
            f.write(text if isinstance(text, str) else json.dumps(text))

    write("generators/toy_rows.py", GENERATOR)
    write("forms/toy_select.py", FORM)
    write("laws/toy_keys.py", LAW)
    config = json.loads(json.dumps(CONFIG))
    config["generator"]["name"] = generator
    config["guarantees"]["law"] = law
    cell = json.loads(json.dumps(CELL))
    cell["traffic"]["input_form"] = form
    write("configs/toy-keys.json", config)
    write("workloads/toy-select.json", cell)
    write("layer_metrics/toy_builds_per_job.json", METRIC)
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-keys", "source": CONFIG["source"],
        "file": "perfbench/configs/toy-keys.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": "toy-select", "config": "toy-keys",
        "traffic": "toy-select-keys", "chips": 1, "why": CELL["why"]})
    bench["per_layer"].append({
        "name": METRIC["name"], "unit": METRIC["unit"], "better": "lower",
        "source": "program_counter", "layer": METRIC["layer"],
        "moves": "rows_per_s", "workloads": ["toy-select"]})
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = digest(tree)
    changed = [p for p, h in before.items() if after.get(p) != h]
    assert not changed, f"files that were there were edited: {changed}"


def rehearse(tree, trace):
    """`--rehearse` of the toy cell from the root of `tree`; the program
    itself comes from this repo."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "toy-select",
         "--seed", "2147483659", "--seconds", "0.5", "--trace", str(trace),
         "--rehearse"], cwd=tree, env=env, capture_output=True, text=True,
        timeout=600)


def check(tree):
    """The whole check, on an empty directory `tree`."""
    plant(tree)
    run = rehearse(tree, trace=1)
    assert run.returncode == 0, run.stderr[-2000:]
    result = json.loads(run.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["device"]["rehearsal"] is True
    assert set(result["compared"]) == {"unknown_keys", "missing_share"}
    assert result["metrics"]["toy_builds_per_job"]["value"] >= 0
    assert "pb:job" not in run.stdout  # the result is the last line alone
    assert "compared missing_share" in run.stderr


def test_a_deployment_arrives_as_files(tmp_path):
    check(str(tmp_path))


@pytest.mark.parametrize("kind", ["generators", "forms", "laws"])
def test_a_missing_name_lists_what_exists(tmp_path, kind):
    names = {"generators": {"generator": "no_such_rows"},
             "forms": {"form": "no_such_form"},
             "laws": {"law": "no_such_law"}}[kind]
    plant(str(tmp_path), **names)
    run = rehearse(str(tmp_path), trace=0)
    assert run.returncode != 0
    assert not run.stdout.strip(), run.stdout[-500:]  # no result line
    missing = list(names.values())[0]
    there = {"generators": "netflix_columns", "forms": "chunks_host",
             "laws": "bounded_laplace_geometric"}[kind]
    assert f"perfbench/{kind}/ has no '{missing}'" in run.stderr
    assert there in run.stderr and "toy_" in run.stderr
