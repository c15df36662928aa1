"""The program's `select_partitions` against the benchmark's plain
reference of a key-only release.

perfbench/laws/selection_geometric.py is numpy alone and imports nothing
of the program; it is what decides `correct` in the cell
keys1e7-select-blocked. Here, at a toy size a CPU holds, every backend's
`DPEngine.select_partitions` is held by that law's `compare` over a window
of seeded jobs — `TPUBackend` on the blocked route (a small
`large_partition_threshold`, six blocks) AND on the dense route, and
`LocalBackend` — and the law's own simulator passes the same limits sound
and fails them under every break the law names. That the blocked route's
released keys equal the dense keep mask's under one key stays where it
was, tests/test_large_p.py.
"""

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import columnar, partition_selection, sampling_utils
from pipelinedp_tpu.runtime import telemetry
from perfbench import reference
from perfbench.laws import selection_geometric as law

G = {"epsilon": 1.0, "delta": 1e-5, "l0": 3,
     "selection": "truncated_geometric"}
USERS, KEYS, VOCAB = 6000, 600, 640  # the last 40 keys bear no row
JOBS = {"tpu_blocked": 200, "tpu_dense": 200, "local": 200}

# Every z is a count of released keys standardised by the law (binomial in
# the jobs); the runs are seeded and repeat. The three backends' 18 z
# readings are all under 1.2 and the law's own simulator reads at most 2.2
# on three seeds; every break reads 20 or more on at least one number
# (`laplace_threshold` 25 on kept_high_z, the weakest). 6 lies between
# with room on both sides, and is 6 standard deviations of a sound count.
# The two exact numbers are exact.
LIMITS = {"unknown_keys": 0, "sure_missing": 0, "kept_z": 6.0,
          "kept_tail_z": 6.0, "kept_low_z": 6.0, "kept_mid_z": 6.0,
          "kept_high_z": 6.0, "mid_dispersion_z": 6.0}


def _rows(seed=20261004):
    """A toy log: every user asks a geometric number of queries (mean 2.9:
    l0 = 3 binds on a quarter of the users), Zipf(1) over 600 keys, and
    three rows in ten ask a (user, query) of the log again."""
    rng = np.random.default_rng(seed)
    asked = rng.geometric(0.35, USERS)
    pid = np.repeat(np.arange(USERS), asked)
    pk = np.minimum(np.exp(rng.random(len(pid)) * np.log(KEYS)).astype(
        np.int64) - 1, KEYS - 1)
    again = rng.integers(0, len(pid), int(0.3 * len(pid)))
    pid, pk = np.concatenate([pid, pid[again]]), np.concatenate([pk, pk[again]])
    order = rng.permutation(len(pid))
    return pid[order].astype(np.int32), pk[order].astype(np.int32)


@pytest.fixture(scope="module")
def toy():
    pid, pk = _rows()
    values = np.zeros(len(pid), np.float32)
    expect = law.expectations(pid, pk, values, G)
    return pid, pk, values, expect, law.Pairs(pid, pk, values, G)


def _release(backend_name, toy, index):
    pid, pk, values, _, _ = toy
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=G["epsilon"],
                                           total_delta=G["delta"])
    params = pdp.SelectPartitionsParams(max_partitions_contributed=G["l0"])
    if backend_name == "local":
        sampling_utils.seed_sampling_rng(1000 + index)
        partition_selection.seed_selection_rng(5000 + index)
        engine = pdp.DPEngine(accountant, pdp.LocalBackend())
        rows = list(zip(pid.tolist(), pk.tolist()))
        extractors = pdp.DataExtractors(
            privacy_id_extractor=lambda r: r[0],
            partition_extractor=lambda r: r[1])
    else:
        blocked = backend_name == "tpu_blocked"
        engine = pdp.DPEngine(accountant, pdp.TPUBackend(
            noise_seed=77_000 + index,
            large_partition_threshold=100 if blocked else 1 << 21,
            block_partitions=128 if blocked else None))
        rows = columnar.EncodedData(pid=pid, pk=pk, values=values,
                                    partition_vocab=range(VOCAB),
                                    n_privacy_ids=USERS)
        extractors = pdp.DataExtractors()
    kept = engine.select_partitions(rows, params, extractors)
    accountant.compute_budgets()
    keys = np.fromiter(kept, dtype=np.int64)
    return keys, np.zeros((len(keys), 0))


@pytest.mark.parametrize("backend_name", sorted(JOBS))
def test_the_backend_passes_the_law(toy, backend_name):
    before = telemetry.snapshot()
    releases = [_release(backend_name, toy, i)
                for i in range(JOBS[backend_name])]
    counted = telemetry.delta(before)
    numbers = law.compare(toy[3], releases)
    correct, table = reference.decide(numbers, LIMITS)
    assert correct, table
    assert set(table) == set(LIMITS)
    # Which route that was: only the blocked one counts its pairs, five
    # blocks of 128 partitions (the last 88 wide) over 640.
    if backend_name == "tpu_blocked":
        jobs = JOBS[backend_name]
        assert counted["selection_pairs"] <= jobs * G["l0"] * USERS
        # Every surviving pair is gathered by its block, at the shared
        # capacity of the largest.
        assert counted["selection_block_rows"] >= counted["selection_pairs"]
        assert counted["release_dispatches"] == jobs * (5 + 1)
    else:
        assert "selection_pairs" not in counted


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_reference_in_the_programs_place_passes(toy, seed):
    rng = np.random.default_rng(seed)
    releases = [law.simulate_release(toy[4], G, rng) for _ in range(200)]
    correct, table = reference.decide(law.compare(toy[3], releases), LIMITS)
    assert correct, table


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("broken", law.BREAKS)
def test_every_break_of_the_law_fails(toy, broken, seed):
    rng = np.random.default_rng(seed)
    releases = [law.simulate_release(toy[4], G, rng, broken)
                for _ in range(200)]
    correct, table = reference.decide(law.compare(toy[3], releases), LIMITS)
    assert not correct, f"{broken} passed: {table}"


def test_the_toy_has_every_band(toy):
    """The limits above hold something only if each band of the keep
    curve is populated, and the sure set is neither empty nor all."""
    keep, sure = toy[3]["keep"], toy[3]["sure"]
    low = 0.0
    for name, high in law.BANDS:
        assert (~sure & (keep > low) & (keep <= high)).sum() >= 10, name
        low = high
    assert 10 <= sure.sum() <= KEYS // 4
    assert len(toy[3]["keys"]) <= KEYS  # the vocabulary's last 40 bear no row


def test_exact_keep_is_the_convolution(toy):
    """`_exact_keep` against a direct convolution of one mid-band
    partition's indicators, and against the closed form where N cannot
    reach the crossover."""
    _, _, _, expect, pairs = toy
    selector = law._selector(G)
    q = law._pair_survives(pairs, G)
    part = int(np.argmin(np.abs(expect["keep"] - 0.5)))
    pmf = np.ones(1)
    for qi in q[pairs.part == part]:
        pmf = np.convolve(pmf, [1.0 - qi, qi])
    want = float(pmf @ selector.keep_probability(np.arange(len(pmf))))
    assert abs(expect["keep"][part] - want) < 1e-12
    assert 0.1 < want < 0.9


def test_a_float32_draw_does_not_round_the_tail_up():
    """The chip draws in float32, whose uniform takes the 2^23 values
    k * 2^-23: `u < p` kept a one-user partition of the cell's job with
    probability 3 * 2^-23 = 3.58e-7 where the budget gives 2.5e-7. The
    cell that holds p is split by a second draw (selection_ops.
    keep_from_uniforms): k = 0, 1 keep, k = 2 keeps with probability
    frac(p * 2^23) = 0.097, k = 3 and up drop."""
    import jax.numpy as jnp
    from pipelinedp_tpu.ops import selection_ops

    p = np.float32(2.5e-7)
    assert 2.0 < p * 2**23 < 3.0
    k = jnp.arange(5, dtype=jnp.float32) / 2**23
    probs = jnp.full(5, p, jnp.float32)

    def decide(tie):
        return np.asarray(selection_ops.keep_from_uniforms(
            k, jnp.full(5, tie, jnp.float32), probs)).tolist()

    assert decide(0.05) == [True, True, True, False, False]
    assert decide(0.5) == [True, True, False, False, False]
    # `u < p` kept k = 2 whatever else was drawn.
    assert np.asarray(k < probs).tolist() == [True, True, True, False, False]
