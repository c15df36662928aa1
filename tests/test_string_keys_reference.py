"""String partition keys through the hash-device ingest, against the
benchmark's plain reference.

The cell keys1e7-strings-hashdevice hands `DPEngine.aggregate` the AOL
log's query TEXT through `ChunkSource(encode_mode="hash_device")`: keys
hashed on the host, coded on the device, decoded only at the kept indices
(`device_encode.HashVocab`). Its law, perfbench/laws/
bounded_laplace_geometric.py, sees integer key ranks; perfbench/
query_text.py is the bijection between the two. Here, on a toy log of
600 ranks written as text by that bijection (one-, two- and three-word
queries, some padded past 40 characters), on the blocked route (five
blocks) and on the dense route:

  (a) the hash route's release equals `encode_mode="host"`'s on the same
      chunks under the same noise seed, key for key and value for value;
  (b) over 100 seeded jobs the released strings, mapped back to ranks by
      the bijection's inverse, pass the law's comparison;
  (c) a kept index decoded into another key's string fails (b).
"""

import json
import os

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import device_encode
from pipelinedp_tpu.runtime import telemetry
from perfbench import query_text, reference, traffic
from perfbench.generators import querylog_columns
from perfbench.laws import bounded_laplace_geometric as law

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEYS, USERS, ROWS = 600, 20_000, 60_000
JOBS = 100
ROUTES = {"blocked": dict(large_partition_threshold=100,
                          block_partitions=128),
          "dense": {}}

# The law's window numbers are standardised counts and sums; the runs are
# seeded and repeat. 6 is the limit test_selection_reference.py holds its
# z numbers to: six standard deviations of a sound window (both routes
# read 2.4 and under), where a decode into another key reads in the
# hundreds (test (c)). `max_abs_z`, the largest of some 7,800 values'
# |z| (Laplace tails: 6.5 here), the spreads and the noise numbers keep
# the cells' own limits (PERF.md section 2); the two exact numbers are
# exact.
LIMITS = {"unknown_keys": 0, "sure_missing": 0, "kept_z": 6.0,
          "count_bias_z": 6.0, "sum_bias_z": 6.0, "count_spread": 0.2,
          "sum_spread": 0.2, "count_noise": 0.45, "sum_noise": 0.45,
          "max_abs_z": 18.0}


def _config():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "keys-1e7-strings.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy():
    """The configuration's guarantees and text law over a toy log: its
    lexicon and word lengths, tiers cut to 600 ranks (64 one-word, 256
    two-word, 280 three-word queries), a tenth of them padded; the
    log's generator at 60,000 rows of 20,000 users, so that 18
    partitions are surely kept; clicks as the log draws them."""
    config = _config()
    spec = dict(config["text"], tiers=[64, 16, 8], long_share=0.1)
    text = query_text.QueryText(spec, KEYS)
    args = dict(config["generator"]["args"], keys=KEYS, users=USERS)
    pid, pk, clicks = querylog_columns.generate(ROWS, 20261015, **args)
    g = config["guarantees"]
    return dict(g=g, text=text, pid=pid, pk=pk, clicks=clicks,
                strings=text.strings(),
                expect=law.expectations(pid, pk, clicks, g))


def _job(toy, encode_mode, route):
    """traffic.engine_job on the toy's text chunks: a job(noise seed) ->
    {query string: (count, sum)}."""
    g = toy["g"]
    rows = (toy["pid"], toy["strings"][toy["pk"]], toy["clicks"])
    chunks = traffic.chunked(rows, 8192)

    def backend(seed):
        return pdp.TPUBackend(noise_seed=seed, numeric_mode=g["numeric_mode"],
                              **ROUTES[route])

    return traffic.engine_job(
        g, source=lambda: pdp.ChunkSource(chunks, encode_mode=encode_mode),
        backend=backend)


def _window(toy, route, jobs=JOBS):
    """The law's numbers over `jobs` seeded hash-route jobs, each release
    mapped back to ranks."""
    job = _job(toy, "hash_device", route)
    releases = []
    for i in range(jobs):
        release = job(91_000 + i)
        ranks = toy["text"].ranks(release)
        releases.append((ranks, np.array(list(release.values()))))
    return law.compare(toy["expect"], releases)


def test_the_toy_log_is_text_of_every_shape(toy):
    strings = toy["strings"]
    words = {s.count(" ") + 1 for s in strings}
    assert words >= {1, 2, 3} and max(words) > 3  # the padded ones
    assert max(len(s) for s in strings) > 40
    assert len(set(strings.tolist())) == KEYS
    assert (toy["text"].ranks(strings) == np.arange(KEYS)).all()
    # Rank 0 is the log's most asked query and its shortest.
    assert len(strings[0]) == min(len(s) for s in strings)


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_hash_route_releases_what_host_route_releases(toy, route):
    hashed, host = _job(toy, "hash_device", route), _job(toy, "host", route)
    before = telemetry.snapshot()
    for seed in (5, 6):
        got, want = hashed(seed), host(seed)
        assert got and list(got) == list(want)  # key for key, in order
        assert got == want  # value for value
    counted = telemetry.delta(before)
    # What the hash route's counters say of two jobs: every key's
    # characters walked once, 4 B each, and the merge's entries.
    assert counted["hash_key_bytes"] == 2 * 4 * sum(
        map(len, toy["strings"][toy["pk"]]))
    assert counted["hash_unique_entries"] >= 2 * (KEYS + USERS)
    assert (counted.get("pass2_rows", 0) > 0) == (route == "blocked")


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_released_strings_pass_the_law(toy, route):
    correct, table = reference.decide(_window(toy, route), LIMITS)
    assert correct, table


def test_a_key_decoded_into_another_fails_the_law(toy, monkeypatch):
    """Every kept index decodes into the string of the next rank."""
    text = toy["text"]
    lookup = device_encode.HashVocab._keys_for_hashes

    def next_rank(self, hashes):
        keys = lookup(self, hashes)
        ranks = text.ranks(keys)
        return toy["strings"][(ranks + 1) % KEYS]

    monkeypatch.setattr(device_encode.HashVocab, "_keys_for_hashes",
                        next_rank)
    correct, table = reference.decide(_window(toy, "blocked", jobs=20),
                                      LIMITS)
    assert not correct
    assert table["max_abs_z"]["value"] > LIMITS["max_abs_z"]
