"""The control, at a size a test run can hold: the plain reference put in
the program's place passes every cell's comparison at the cell's own
limits, and with ONE stated guarantee broken it fails — for each break
the cell's file lists under `controls` (every guarantee its traffic binds
on; PERF.md §2 says which one a configuration's data cannot show)."""

import json
import os

import numpy as np
import pytest

from perfbench import data, reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "workloads")))
CONTROLS = ("l0_off", "linf_off", "clamp_off", "noise_half", "select_off")
JOBS = 12


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=CELLS)
def cell(request):
    cell = load("workloads", request.param)
    config = load("configs", cell["config"])
    generator = dict(config["generator"],
                     args=config["rehearsal"]["generator_args"])
    rows = data.generate(generator, config["rehearsal"]["rows_per_job"], 4242)
    g = config["guarantees"]
    return (cell, g, reference.expectations(*rows, g),
            reference.Pairs(*rows, g))


def decide(cell, broken, seed):
    cell, g, expect, pairs = cell
    rng = np.random.default_rng(seed)
    releases = [reference.simulate_release(pairs, g, rng, broken)
                for _ in range(JOBS)]
    return reference.decide(reference.compare(expect, releases),
                            cell["limits"])


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
