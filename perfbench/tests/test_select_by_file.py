"""The configuration `keys-1e7-select` arrived as files (a law, a form, a
configuration, a cell, two per-layer metrics and their `BENCHMARK.json`
entries; the generator was there): its cell rehearses through the whole
harness to a `correct` result line that names every limit of its law and
reads both new counters; the breaks of the key-only law that
`test_control.py`'s fixed list does not name fail the cell's comparison at
the rehearsal's size; and the two faults this cell can have, driven
through the timed path, come out `correct: false` — half the rows left
out, and one block's released ids shifted by a block (every id below P is
borne by a row in a log's own key space, so no released id is unknown:
the tail band and the sure set see it). `test_faults.py`'s two name
numbers and a decode this law does not have (perfbench/conftest.py)."""

import argparse
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
CELL = "keys1e7-select-blocked"
JOBS = 12


def test_the_cell_rehearses_to_a_correct_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    run = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL,
         "--seed", "2147483659", "--seconds", "9", "--trace", "1",
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
    # At most l0 pairs a user survive; three blocks of the rehearsal's
    # 2,200,000 keys gather at one shared capacity, at least the pairs.
    toy = config["rehearsal"]["encoded"]
    assert 0 < metrics["selection_pairs_per_job"] <= (
        config["guarantees"]["l0"] * toy["privacy_ids"])
    assert (metrics["selection_block_rows_per_job"]
            >= metrics["selection_pairs_per_job"])
    assert metrics["release_dispatches_per_job"] == 3 + 1
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


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("broken", [
    "dedupe_off", "eps_double", "delta_x100", "l0_budget_off",
    "laplace_threshold", "shared_draw"])
def test_a_broken_selection_is_not_correct(rehearsal, broken, seed):
    cell, g, law, expect, pairs = rehearsal
    assert broken in cell["controls"]
    rng = np.random.default_rng(seed)
    releases = [law.simulate_release(pairs, g, rng, broken)
                for _ in range(JOBS)]
    correct, table = reference.decide(law.compare(expect, releases),
                                      cell["limits"])
    assert not correct, f"{broken} passed: {table}"


def test_the_cell_lists_every_break_of_the_law(rehearsal):
    cell, _, law, _, _ = rehearsal
    assert set(cell["controls"]) == set(law.BREAKS) - {"half_rows"}


def drive(seed=99):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=12.0,
                              trace=0, rehearse=True, debug_dir=None)
    return perfbench_run.execute(args)


def over(result):
    return [k for k, (value, limit) in result["compared"].items()
            if limit is not None and not value <= limit]


def test_half_the_rows_is_not_correct(monkeypatch):
    """Every second row of the encoded columns never reaches the engine:
    keys with half their users fall down the keep curve."""
    from pipelinedp_tpu import columnar

    encoded_init = columnar.EncodedData.__init__

    def half_rows(self, pid, pk, values, *a, **kw):
        encoded_init(self, pid[::2], pk[::2], values[::2], *a, **kw)

    monkeypatch.setattr(columnar.EncodedData, "__init__", half_rows)
    result = drive()
    assert result["failed"] == 0 and not result["correct"]
    assert {"kept_z", "kept_high_z"} <= set(over(result))


def test_a_block_of_shifted_ids_is_not_correct(monkeypatch):
    """Block 0's kept ids are released one block up (its base taken as
    the block's size, not 0): every one of them is a key some row bears,
    so no key is unknown; the surely-kept keys are missing and the keys
    released in their place are near-singletons of the tail band."""
    from pipelinedp_tpu.parallel import large_p

    stage = large_p._StagedDrain.stage

    def shifted(self, targets, arrays, k, id_base):
        stage(self, targets, arrays, k, id_base or 1 << 20)

    monkeypatch.setattr(large_p._StagedDrain, "stage", shifted)
    result = drive()
    assert result["failed"] == 0 and not result["correct"]
    assert result["compared"]["unknown_keys"][0] == 0
    assert {"sure_missing", "kept_tail_z"} <= set(over(result))
