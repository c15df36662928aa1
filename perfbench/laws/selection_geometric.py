"""The law `selection_geometric`: the plain reference of a KEY-ONLY release
— which partition keys of a log may be published at all — under l0
contribution bounding and truncated-geometric private selection, over rows
of (privacy id, partition key, value). The value column is never read: a
job of this law releases no value, only keys.

Numpy only: nothing here imports the program or takes anything it made.
It imports the law `bounded_laplace_geometric` (numpy alone too) for the
rows' pairing (`Pairs`) and the selection's closed form
(`TruncatedGeometric`), which are the same objects in both laws.

The guarantees (`g`, the configuration's `guarantees`): `epsilon`, `delta`,
`l0`, `selection` (truncated_geometric), `budget_split` and `statement`.

BUDGET (what `DPEngine.select_partitions` does on either backend: it asks
the accountant for ONE budget, the selection's — executor.
lazy_select_partitions and dp_engine._select_private_partitions_internal
each make one `request_budget(GENERIC)` — and `NaiveBudgetAccountant`
gives a job's only mechanism the whole of it): all of epsilon and all of
delta to the one selection mechanism. A user's l0 queries each see
epsilon / l0 and delta / l0.

THE MECHANISM, from the published description (Desfontaines, Voss, Gipson,
Mandayam, "Differentially Private Partition Selection", PoPETs 2022): a
user is counted once per partition however many rows it has there
(distinct (user, partition) pairs); a partition with n users is released
with the probability pi(n) that is largest under (eps', delta')-DP for
every n: pi(n) = delta' (e^{n eps'} - 1) / (e^{eps'} - 1) up to the
crossover, its mirror image 1 - pi decaying geometrically after it, 1
from there on. Departures from that description, each the program's (and
PipelineDP's, and PyDP's) own and so part of the release to hold:
  * the paper's mechanism is for users who each contribute to ONE
    partition; a user in several is bounded to a uniform l0 of its
    partitions, sampled without replacement, and the mechanism runs at
    eps' = eps / l0, delta' = delta / l0 (basic composition over the l0
    partitions a user can move), not at the tighter delta the paper's
    section on multiple contributions derives;
  * pi is taken in the closed form of `TruncatedGeometric` (the paper
    gives the recurrence it solves);
  * no pre-threshold (PyDP's `pre_threshold`) is set;
  * Korolova, Kenthapadi, Mishra, Ntoulas ("Releasing Search Queries and
    Clicks Privately", WWW 2009), whose task this is, threshold a
    Laplace-noised count; the break `laplace_threshold` is that
    mechanism at the same (eps, delta, l0), which keeps fewer keys.

WHAT IS HELD. A release is a random set of keys, so the comparison is with
the keep probability of every partition: E[pi(N)] over the distribution of
N, the partition's number of users after l0 bounding — a sum of
independent indicators, one per user of the partition, each 1 with
probability min(1, l0 / the user's number of partitions). Below the
crossover the mean of pi(N) is exact through N's generating function;
where N can cross it, N's distribution is computed EXACTLY (the
convolution of its indicators, `_exact_keep`) — a normal for N, which the
law `bounded_laplace_geometric` takes at a third of this epsilon and a
fifth of the jobs, is off by more than a window of 30 jobs can overlook —
and partitions far above it are kept surely.

Numbers of `compare` (J jobs of a window over the same rows):
  unknown_keys      released keys that no row bears (with every key of
                    [0, P) borne by a row, as in a log's own key space:
                    keys outside [0, P)); exact, 0
  sure_missing      partitions kept with probability above 1 - 1e-9 that
                    a job did not release; exact, 0
  kept_z            |kept - expected kept| over the partitions that are
                    not surely kept, in standard deviations
  kept_tail_z, kept_low_z, kept_mid_z, kept_high_z
                    the same over the bands of keep probability
                    (0, 1e-4], (1e-4, 0.1], (0.1, 0.9], (0.9, sure): a
                    keep curve of the wrong SHAPE fails where its total
                    balances, and released ids shifted by an offset land
                    in the tail band (near-singletons, kept with
                    probability 1e-7: the delta tail)
  mid_dispersion_z  over the mid band, the sum over partitions of (times
                    released - J pi)^2 against what J independent jobs
                    give (binomial moments), in its standard errors:
                    every job draws its own l0 sample and its own
                    selection; a decision reused from job to job leaves
                    every count above in place and fails here
"""

import math

import numpy as np

from perfbench.laws import bounded_laplace_geometric as base

SURE_KEEP = 1.0 - 1e-9
# Bands of keep probability, upper edges; the last runs up to `sure`.
BANDS = (("tail", 1e-4), ("low", 0.1), ("mid", 0.9), ("high", 1.0))
# `_exact_keep` follows N up to this many standard deviations around its
# mean; beyond them a partition is below the crossover (the generating
# function is exact there) or above every n at which pi(n) < 1 - 1e-12.
SIGMAS = 8.0


def budgets(g):
    """The whole (epsilon, delta) to the one selection mechanism."""
    if g["selection"] != "truncated_geometric":
        raise ValueError("the reference knows truncated-geometric selection")
    return {"select_eps": g["epsilon"], "select_delta": g["delta"]}


def _selector(g, eps_factor=1.0, delta_factor=1.0, l0=None):
    b = budgets(g)
    return base.TruncatedGeometric(b["select_eps"] * eps_factor,
                                   b["select_delta"] * delta_factor,
                                   g["l0"] if l0 is None else l0)


class Pairs(base.Pairs):
    """The distinct (privacy id, partition) pairs of the rows
    (`bounded_laplace_geometric.Pairs`); the value column is not read."""

    def __init__(self, pid, pk, values, g):
        super().__init__(pid, pk, np.zeros(len(pid), dtype=np.int8),
                         dict(g, min_value=0.0, max_value=0.0))


def _pair_survives(pairs, g):
    """P(a pair survives l0 bounding): a uniform l0 of its id's pairs."""
    return np.minimum(1.0, g["l0"] / pairs.partitions_of_id)


def expectations(pid, pk, values, g):
    """Per partition (in the order of `keys`, the sorted distinct partition
    keys): `keep`, the probability that a job releases it, and `sure`."""
    pairs = Pairs(pid, pk, values, g)
    selector = _selector(g)
    n_parts = len(pairs.keys)
    q = _pair_survives(pairs, g)

    def per_partition(w):
        return np.bincount(pairs.part, weights=w, minlength=n_parts)

    mean = per_partition(q)
    sd = np.sqrt(per_partition(q * (1.0 - q)))
    # Below the crossover pi is delta' (e^{N eps'} - 1) / (e^{eps'} - 1),
    # whose mean is exact through the product of (1 - q + q e^{eps'}).
    log_mgf = per_partition(np.log1p(q * math.expm1(selector.eps1)))
    keep = np.clip(selector.delta1 * np.expm1(np.minimum(log_mgf, 700.0)) /
                   math.expm1(selector.eps1), 0.0, 1.0)
    n_one = selector.n_cross + math.log(1e12) / selector.eps1  # pi = 1 above
    above = mean - SIGMAS * sd > n_one
    keep[above] = 1.0
    between = np.flatnonzero((mean + SIGMAS * sd >= selector.n_cross)
                             & ~above)
    keep[between] = _exact_keep(pairs, q, between, selector,
                                int(math.ceil(n_one)) + 1)
    return {"keys": pairs.keys, "keep": keep, "sure": keep > SURE_KEEP}


def _exact_keep(pairs, q, partitions, selector, states):
    """E[pi(N)] for the listed partitions, N's distribution computed
    exactly: the convolution of its independent indicators, one per pair
    of the partition (its users are distinct), followed over the counts
    0 .. states - 1 with everything above lumped into the last, where
    pi = 1. One step per pair, all partitions at once, the partitions
    ordered by their number of pairs so that a step touches only those
    that still have one."""
    if not len(partitions):
        return np.zeros(0)
    index = np.full(len(pairs.keys), -1, dtype=np.int64)
    index[partitions] = np.arange(len(partitions))
    mine = np.flatnonzero(index[pairs.part] >= 0)
    owner = index[pairs.part[mine]]
    counts = np.bincount(owner, minlength=len(partitions))
    by_size = np.argsort(-counts, kind="stable")  # most pairs first
    place = np.empty(len(partitions), dtype=np.int64)
    place[by_size] = np.arange(len(partitions))
    owner = place[owner]
    order = np.argsort(owner, kind="stable")
    owner, q_mine = owner[order], q[mine][order]
    counts = counts[by_size]
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    within = np.arange(len(owner)) - starts[owner]
    table = np.zeros((len(partitions), int(counts.max())))
    table[owner, within] = q_mine
    pmf = np.zeros((len(partitions), states))
    pmf[:, 0] = 1.0
    active = np.searchsorted(-counts, -np.arange(table.shape[1]),
                             side="left")  # partitions with > j pairs
    for j in range(table.shape[1]):
        a = int(active[j])
        qj = table[:a, j, None]
        moved = pmf[:a] * qj
        pmf[:a] -= moved
        pmf[:a, 1:] += moved[:, :-1]
        pmf[:a, -1] += moved[:, -1]
    pi = selector.keep_probability(np.arange(states))
    pi[-1] = 1.0
    return (pmf @ pi)[place]


# ---------------------------------------------------------------------------
# The reference in the program's place (and, broken, the control)
# ---------------------------------------------------------------------------

BREAKS = ("l0_off", "select_off", "dedupe_off", "eps_double", "delta_x100",
          "l0_budget_off", "laplace_threshold", "shared_draw", "half_rows")


def _sample_l0(pairs, l0, rng):
    """The mask of pairs that survive l0 bounding: every id keeps a
    uniform l0-subset of its pairs (Floyd's sampling, l0 steps over all
    ids at once; ids with at most l0 pairs keep all)."""
    n_pairs = len(pairs.pid)
    starts = pairs.id_starts
    sizes = np.diff(starts, append=n_pairs)
    kept = np.zeros(n_pairs, dtype=bool)
    small = sizes <= l0
    kept[np.repeat(small, sizes)] = True
    big_starts, big_sizes = starts[~small], sizes[~small]
    chosen = np.empty((l0, len(big_sizes)), dtype=np.int64)
    for step in range(l0):
        top = big_sizes - l0 + step  # Floyd: j = n - l0 + 1 .. n, 0-based
        pick = (rng.random(len(big_sizes)) * (top + 1)).astype(np.int64)
        taken = (chosen[:step] == pick).any(axis=0)
        chosen[step] = np.where(taken, top, pick)
    kept[(big_starts + chosen).ravel()] = True
    return kept


def laplace_threshold_keep(n, eps, delta, l0):
    """Keep probability of Laplace thresholding (Korolova et al.; Google's
    differential-privacy library's form): the count plus Laplace noise of
    scale l0 / eps is released when above 1 + (l0 / eps) ln(1 / (2 d)),
    d = 1 - (1 - delta)^(1 / l0)."""
    scale = l0 / eps
    d = -math.expm1(math.log1p(-delta) / l0)
    x = (np.asarray(n, dtype=np.float64) - (1.0 + scale * math.log(0.5 / d))
         ) / scale
    p = np.where(x < 0, 0.5 * np.exp(np.minimum(x, 0.0)),
                 1.0 - 0.5 * np.exp(-np.maximum(x, 0.0)))
    return np.where(np.asarray(n) <= 0, 0.0, p)


def simulate_release(pairs, g, rng, broken=None):
    """One release of the stated semantics: (keys, values[n, 0]) of the
    kept partitions. `pairs` is Pairs(...) of the job's rows. `broken`
    names the one guarantee the control breaks:
      l0_off            — a privacy id's partitions are not bounded to l0;
      select_off        — every partition that has a row is released;
      dedupe_off        — a pair counts once per ROW, not once;
      eps_double        — the selection drawn at twice the epsilon (this
                          law's `noise_half`);
      delta_x100        — the selection drawn at a hundred times the delta;
      l0_budget_off     — epsilon and delta not divided by l0;
      laplace_threshold — Laplace thresholding at the same (eps, delta,
                          l0): "a cheaper selection";
      shared_draw       — every job of a window makes the same draw;
      half_rows         — every second row is left out (not a guarantee:
                          the "half of the batch" fault, for the tests)."""
    if broken is not None and broken not in BREAKS:
        raise ValueError(f"unknown break {broken!r}")
    if broken == "shared_draw":
        rng = np.random.default_rng(0)
    l0 = g["l0"]
    n_parts = len(pairs.keys)
    if broken == "l0_off":
        pair_kept = np.ones(len(pairs.pid), dtype=bool)
    else:
        pair_kept = _sample_l0(pairs, l0, rng)
    if broken == "half_rows":
        odd = np.arange(int(pairs.rows.sum())) % 2  # rows in pair order
        pair_kept &= np.add.reduceat(odd, pairs.starts) > 0
    weights = pairs.rows[pair_kept] if broken == "dedupe_off" else None
    ids = np.bincount(pairs.part[pair_kept], weights=weights,
                      minlength=n_parts)
    some = np.flatnonzero(ids > 0)
    if broken == "select_off":
        kept = some
    else:
        if broken == "laplace_threshold":
            b = budgets(g)
            p = laplace_threshold_keep(ids[some], b["select_eps"],
                                       b["select_delta"], l0)
        else:
            selector = _selector(
                g, eps_factor=2.0 if broken == "eps_double" else 1.0,
                delta_factor=100.0 if broken == "delta_x100" else 1.0,
                l0=1 if broken == "l0_budget_off" else None)
            p = selector.keep_probability(ids[some])
        kept = some[rng.random(len(some)) < p]
    return pairs.keys[kept], np.zeros((len(kept), 0))


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------

def compare(expect, releases):
    """The numbers of one window (module docstring). `releases` is a list
    of (keys, values[n, 0]), one per job, every job over the rows `expect`
    was made from."""
    keys, keep, sure = expect["keys"], expect["keep"], expect["sure"]
    jobs = len(releases)
    times = np.zeros(len(keys), dtype=np.int64)  # times released
    unknown = 0
    for got_keys, _ in releases:
        got_keys = np.asarray(got_keys, dtype=np.int64)
        at = np.searchsorted(keys, got_keys)
        known = (at < len(keys)) & (keys[np.minimum(at, len(keys) - 1)]
                                    == got_keys)
        unknown += int((~known).sum())
        times += np.bincount(at[known], minlength=len(keys))
    numbers = {
        "unknown_keys": float(unknown),
        "sure_missing": float((jobs - times[sure]).sum()),
    }

    def kept_z(mask):
        want = jobs * float(keep[mask].sum())
        var = jobs * float((keep[mask] * (1.0 - keep[mask])).sum())
        return abs(float(times[mask].sum()) - want) / math.sqrt(max(var, 1.0))

    unsure = ~sure
    numbers["kept_z"] = kept_z(unsure)
    low = 0.0
    for name, high in BANDS:
        band = unsure & (keep > low) & (keep <= high)
        numbers[f"kept_{name}_z"] = kept_z(band)
        if name == "mid":
            mid = band
        low = high
    # Sum of (X - J pi)^2 over the mid band, X ~ Binomial(J, pi) when the
    # jobs are independent: mean J pq each, variance J pq (1 + (2J - 6) pq).
    pq = keep[mid] * (1.0 - keep[mid])
    got = float(((times[mid] - jobs * keep[mid]) ** 2).sum())
    var = float((jobs * pq * (1.0 + (2.0 * jobs - 6.0) * pq)).sum())
    numbers["mid_dispersion_z"] = abs(got - jobs * float(pq.sum())) / \
        math.sqrt(max(var, 1.0))
    return numbers


# ---------------------------------------------------------------------------
# The roofline's bytes
# ---------------------------------------------------------------------------

ROW_BYTES = 9  # privacy id 4 B, partition id 4 B, valid flag 1 B


def min_bytes(rows, kept_partitions, g):
    """The fewest bytes a release of this job has to move through HBM:
    every row read once (ROW_BYTES: a key-only job reads no value) and
    every kept partition's id (4 B) written once. From shapes alone."""
    return rows * ROW_BYTES + kept_partitions * 4
