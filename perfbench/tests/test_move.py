"""The move of PR 29 kept every byte: both generators draw the rows the
parent's `data.py` drew, and a one-job rehearsal of each cell the parent
had compares the same numbers to the last digit (fixtures/parent_pr28.json,
recorded on the parent commit in the sandbox). A CPU rehearsal under one
seed and `--seconds 0` is one job, and repeats exactly."""

import argparse
import hashlib
import json
import os

import pytest

from perfbench import data
from perfbench import run as perfbench_run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "fixtures", "parent_pr28.json")) as f:
    PARENT = json.load(f)


@pytest.mark.parametrize("config", ["dense-netflix", "keys-1e7"])
def test_generator_draws_the_parents_rows(config):
    with open(os.path.join(HERE, "configs", config + ".json")) as f:
        config = json.load(f)
    generator = dict(config["generator"],
                     args=config["rehearsal"]["generator_args"])
    columns = data.generate(generator, config["rehearsal"]["rows_per_job"],
                            PARENT["seed"])
    h = hashlib.sha256()
    for column in columns:
        h.update(str(column.dtype).encode())
        h.update(column.tobytes())
    assert h.hexdigest() == PARENT["generators"][generator["name"]]


@pytest.mark.parametrize("workload", sorted(PARENT["compared"]))
def test_cell_compares_the_parents_numbers(workload):
    args = argparse.Namespace(workload=workload, seed=PARENT["seed"],
                              seconds=0.0, trace=0, rehearse=True,
                              debug_dir=None)
    result = perfbench_run.execute(args)
    want = PARENT["compared"][workload]
    assert result["compared"] == want["compared"]
    assert result["run"]["kept"] == want["kept"]
    assert (result["correct"], result["attempted"], result["failed"]) == (
        want["correct"], want["attempted"], want["failed"])
