"""The text law of `keys-1e7-strings` (perfbench/query_text.py).

At the rehearsal's 2,200,000 ranks: every rank's string parses back to
the rank, no two ranks share a string, strings no rank gives parse to -1,
and the length moments are the ones the configuration states — there and
at the configuration's own 10,154,742 ranks. The two hash-route cells'
faults are tests/test_faults.py's, which takes every cell under
workloads/: half the rows left out and two answers exchanged where they
are decoded must each come out `correct: false`, and the sound program
`correct: true`, in the CPU rehearsal.
"""

import json
import os

import numpy as np
import pytest

from perfbench import query_text
from perfbench.tests import test_faults

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW_CELLS = ("netflix-sum-hashdevice", "keys1e7-strings-hashdevice")


def _config():
    with open(os.path.join(HERE, "configs", "keys-1e7-strings.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rehearsal():
    config = _config()
    keys = config["rehearsal"]["generator_args"]["keys"]
    text = query_text.QueryText(config["text"], keys)
    return config, text, text.strings()


def test_every_rank_parses_back(rehearsal):
    _, text, strings = rehearsal
    assert (text.ranks(strings) == np.arange(text.keys)).all()


def test_distinct_ranks_give_distinct_strings(rehearsal):
    _, text, strings = rehearsal
    assert len(set(strings.tolist())) == text.keys


def test_strings_no_rank_gives_parse_outside_the_ranks(rehearsal):
    _, text, strings = rehearsal
    padded = next(s for s in strings if len(s) > 40)
    # Past the last rank the tiers go on: the string of rank `keys` is a
    # query of the law's form that this configuration does not hold.
    beyond = query_text.QueryText(_config()["text"], text.keys + 1)
    foreign = [" ".join(strings[3:7]),  # four query words: no tier has 4
               padded[:-1],  # a pad one letter short
               strings[7] + " ",  # a trailing space
               strings[7].upper(), "", "zzzzzzzzzzzzzzzz",
               beyond.strings()[-1]]
    assert (text.ranks(foreign) == -1).all()


def test_the_stated_moments(rehearsal):
    config, _, strings = rehearsal
    assert query_text.moments(strings) == config["rehearsal"]["text_moments"]
    keys = config["generator"]["args"]["keys"]
    full = query_text.QueryText(config["text"], keys).strings()
    assert query_text.moments(full) == config["text"]["moments"]


def test_the_hash_route_cells_meet_the_faults():
    assert set(NEW_CELLS) <= set(test_faults.CELLS)
