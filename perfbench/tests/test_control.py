"""The control, at a size a test run can hold: the plain reference (the law
the cell's configuration names) put in the program's place passes every
cell's comparison at the cell's own limits, and with ONE stated guarantee
broken it fails — for each break the cell's file lists under `controls`
(every guarantee its traffic binds on; PERF.md §2 says which one a
configuration's data cannot show)."""

import json
import os

import numpy as np
import pytest

from perfbench import data, reference
from perfbench import run as perfbench_run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "workloads")))
CONTROLS = ("l0_off", "linf_off", "clamp_off", "noise_half", "select_off")
JOBS = 12


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=CELLS)
def cell(request):
    """The cell at its rehearsal size, with the law its configuration
    names: (cell, guarantees, law, expectations, pairs)."""
    cell = load("workloads", request.param)
    config, rows_per_job = perfbench_run.sized(
        cell, load("configs", cell["config"]), rehearse=True)
    rows = data.generate(config["generator"], rows_per_job, 4242)
    g, law = config["guarantees"], reference.law_of(config)
    return cell, g, law, law.expectations(*rows, g), law.Pairs(*rows, g)


def decide(cell, broken, seed):
    cell, g, law, expect, pairs = cell
    rng = np.random.default_rng(seed)
    releases = [law.simulate_release(pairs, g, rng, broken)
                for _ in range(JOBS)]
    return reference.decide(law.compare(expect, releases), cell["limits"])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_in_the_programs_place_is_correct(cell, seed):
    correct, table = decide(cell, None, seed)
    assert correct, {k: v for k, v in table.items() if not v["ok"]}


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("broken", CONTROLS)
def test_control_is_not_correct(cell, broken, seed):
    if broken not in cell[0]["controls"]:
        pytest.skip(f"{cell[0]['name']}: the configuration's data never "
                    f"binds on what {broken} breaks")
    correct, table = decide(cell, broken, seed)
    assert not correct, f"{broken} passed: {table}"
