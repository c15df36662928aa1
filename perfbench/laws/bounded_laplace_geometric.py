"""The law `bounded_laplace_geometric`: the plain reference of one family
of guarantees — COUNT / SUM / PRIVACY_ID_COUNT per partition under l0 / l∞
contribution bounding and a value clamp, Laplace noise under the naive
accountant's split, truncated-geometric private selection — over rows of
(privacy id, partition key, value).

Numpy only: nothing here imports the program or takes anything it made.
The reference gets the raw rows the generator drew and the guarantees the
configuration's file states (its `guarantees`, which name this law), and
works out from them alone

  * `expectations`: for every partition, the mean and variance of what a
    correct release may say — bounded COUNT, SUM and PRIVACY_ID_COUNT,
    whichever the configuration asks for (a privacy id keeps a uniform
    `l0` of its partitions and a uniform `linf` of its rows in each, values
    clamped to [min_value, max_value]), the number of privacy ids left
    after bounding, and the probability that private selection keeps the
    partition;
  * `simulate_release`: one release of those semantics, drawn with numpy's
    generator — the reference "put in the program's place". With `broken`
    set it breaks ONE stated guarantee: that is the control;
  * `compare`: the numbers, each held to a limit of its own
    (reference.decide), that say whether a set of releases (the jobs of
    one window) is what the guarantees allow;
  * `min_bytes`: the fewest bytes a job of this law has to move through
    the device's memory, for the roofline.

A DP release is random, so the comparison is statistical: every released
value is standardised against the reference's mean and variance for its
partition (sampling variance of the bounding plus the Laplace noise the
budget implies), and the standardised residuals are held to what a sound
release gives — no bias, unit spread, no outlier, the right number of
partitions kept, no key that no row bears.
"""

import math

import numpy as np

# A partition whose keep probability is above this over the whole plausible
# range of its privacy-id count is "surely kept": only there are released
# values free of the selection's conditioning, so only those enter the bias
# and spread numbers (every released value enters `max_abs_z`).
SURE_KEEP = 1.0 - 1e-6
SIGMAS = 6.0


# ---------------------------------------------------------------------------
# Budget and selection closed forms (written from the definitions)
# ---------------------------------------------------------------------------


# The metrics the reference knows, with the short names its numbers carry.
METRICS = {"count": "count", "sum": "sum", "privacy_id_count": "ids"}


def budgets(g):
    """The naive accountant's split: ε in equal shares over the mechanisms
    of the job (one per metric, and the private selection); δ only to the
    selection, Laplace mechanisms take none. Returns the Laplace scale of
    each metric (L1 sensitivity ÷ its ε) and the selection's (ε, δ)."""
    if g["noise"] != "laplace" or g["selection"] != "truncated_geometric":
        raise ValueError("the reference knows Laplace noise and "
                         "truncated-geometric selection")
    metrics = list(g["metrics"])
    if not metrics or any(m not in METRICS for m in metrics):
        raise ValueError(f"the reference knows {sorted(METRICS)}")
    share = g["epsilon"] / (len(metrics) + 1)
    l0, linf = g["l0"], g["linf"]
    magnitude = max(abs(g["min_value"]), abs(g["max_value"]))
    sensitivity = {"count": l0 * linf, "sum": l0 * linf * magnitude,
                   "privacy_id_count": l0}
    return {"scales": {m: sensitivity[m] / share for m in metrics},
            "select_eps": share, "select_delta": g["delta"]}


class TruncatedGeometric:
    """Optimal partition selection (Desfontaines, Voss, Gipson: "Differentially
    private partition selection"), closed form in the number n of privacy ids:
    π(n) grows as δ'(e^{nε'}−1)/(e^{ε'}−1) up to the crossover, then 1−π(n)
    decays geometrically; ε' = ε/l0, δ' = δ/l0."""

    def __init__(self, eps, delta, l0):
        self.eps1, self.delta1 = eps / l0, delta / l0
        t = math.tanh(self.eps1 / 2.0)
        self.n_cross = 1 + int(math.floor(
            math.log1p(t * (1.0 - self.delta1) / self.delta1) / self.eps1))
        self.pi_cross = float(self._rise(np.float64(self.n_cross)))

    def _rise(self, n):
        return self.delta1 * np.expm1(n * self.eps1) / math.expm1(self.eps1)

    def keep_probability(self, n):
        n = np.asarray(n, dtype=np.float64)
        rise = np.minimum(self._rise(np.minimum(n, self.n_cross)), 1.0)
        decay = np.exp(-np.maximum(n - self.n_cross, 0.0) * self.eps1)
        geo = math.exp(-self.eps1) * (1.0 - decay) / -math.expm1(-self.eps1)
        fall = 1.0 - np.maximum(
            decay * (1.0 - self.pi_cross) - self.delta1 * geo, 0.0)
        p = np.where(n <= self.n_cross, rise, fall)
        return np.where(n <= 0, 0.0, np.clip(p, 0.0, 1.0))


# ---------------------------------------------------------------------------
# Rows -> (privacy id, partition) pairs
# ---------------------------------------------------------------------------


class Pairs:
    """The distinct (privacy id, partition) pairs of the rows, ordered by
    privacy id, with each pair's row count, clamped value sum and sum of
    squares, and how many partitions its privacy id touches. `raw` and
    `clamped` are the rows' values, as given and clamped, in pair order."""

    def __init__(self, pid, pk, values, g):
        pid = np.asarray(pid).astype(np.int64)
        pk = np.asarray(pk).astype(np.int64)
        if pid.min() < 0 or pk.min() < 0 or pk.max() >= 1 << 32:
            raise ValueError("ids must be non-negative and below 2^32")
        n = len(pid)
        packed = (pid << 32) | pk
        order = np.argsort(packed)  # rows of one pair are alike: any order
        packed = packed[order]
        self.raw = np.asarray(values)[order].astype(np.float64)
        self.clamped = np.clip(self.raw, g["min_value"], g["max_value"])
        self.starts = _starts(packed)
        self.rows = np.diff(self.starts, append=n)
        self.pid = packed[self.starts] >> 32
        self.pk = packed[self.starts] & 0xFFFFFFFF
        self.sum = np.add.reduceat(self.clamped, self.starts)
        self.sumsq = np.add.reduceat(self.clamped * self.clamped, self.starts)
        self.id_starts = _starts(self.pid)
        per_id = np.diff(self.id_starts, append=len(self.pid))
        self.partitions_of_id = np.repeat(per_id, per_id)
        if int(self.pk.max()) < 1 << 27:  # a table beats a sort
            present = np.zeros(int(self.pk.max()) + 1, dtype=bool)
            present[self.pk] = True
            self.keys = np.flatnonzero(present)
            self.part = (np.cumsum(present) - 1)[self.pk]
        else:
            self.keys, self.part = np.unique(self.pk, return_inverse=True)


def _starts(sorted_ids):
    """Positions at which a sorted array takes a new value."""
    first = np.empty(len(sorted_ids), dtype=bool)
    first[0] = True
    np.not_equal(sorted_ids[1:], sorted_ids[:-1], out=first[1:])
    return np.flatnonzero(first)


def expectations(pid, pk, values, g):
    """Per partition (in the order of `keys`, the sorted distinct partition
    keys): `mean[m]` and `var[m]` of each released metric m of
    g["metrics"], and the keep probability. See the module docstring."""
    pairs = Pairs(pid, pk, values, g)
    b = budgets(g)
    l0, linf = g["l0"], g["linf"]
    n_parts = len(pairs.keys)

    def per_partition(w):
        return np.bincount(pairs.part, weights=w, minlength=n_parts)

    q = np.minimum(1.0, l0 / pairs.partitions_of_id)  # pair survives l0
    c = pairs.rows.astype(np.float64)
    kept_rows = np.minimum(c, linf)
    mean_v = pairs.sum / c
    pop_var = np.maximum(pairs.sumsq / c - mean_v * mean_v, 0.0)
    # A uniform linf-subset of the pair's c rows, without replacement.
    pair_sum = kept_rows * mean_v
    pair_var = np.where(c > linf,
                        kept_rows * pop_var * (c - kept_rows) /
                        np.maximum(c - 1.0, 1.0), 0.0)
    qq = q * (1.0 - q)
    sampled = {  # mean and sampling variance of the bounded statistic
        "count": (per_partition(q * kept_rows),
                  per_partition(qq * kept_rows * kept_rows)),
        "sum": (per_partition(q * pair_sum),
                per_partition(q * pair_var + qq * pair_sum * pair_sum)),
        "privacy_id_count": (per_partition(q), per_partition(qq)),
    }
    out = {
        "keys": pairs.keys,
        "mean": {m: sampled[m][0] for m in b["scales"]},
        "var": {m: sampled[m][1] + 2.0 * scale * scale
                for m, scale in b["scales"].items()},
        "noise_var": {m: 2.0 * scale * scale
                      for m, scale in b["scales"].items()},
        "ids_mean": sampled["privacy_id_count"][0],
        "ids_var": sampled["privacy_id_count"][1],
        "ids_max": per_partition(np.ones_like(q)),
    }
    selector = TruncatedGeometric(b["select_eps"], b["select_delta"], l0)
    out["keep"], out["sure"] = _keep_probability(out, q, per_partition,
                                                 selector)
    return out


def _keep_probability(e, q, per_partition, selector):
    """E[π(N)] for N the partition's number of privacy ids after bounding,
    a sum of independent Bernoulli(q). Below the crossover π is
    δ'(e^{Nε'}−1)/(e^{ε'}−1), whose mean is exact through the product of
    the factors (1−q+q·e^{ε'}); a partition that can reach the crossover is
    summed over a normal for N (it then holds hundreds of ids)."""
    eps1 = selector.eps1
    log_mgf = per_partition(np.log1p(q * math.expm1(eps1)))
    rise = selector.delta1 * np.expm1(np.minimum(log_mgf, 700.0)) / \
        math.expm1(eps1)
    keep = np.clip(rise, 0.0, 1.0)
    sd = np.sqrt(e["ids_var"])
    high = np.flatnonzero(e["ids_max"] > selector.n_cross)
    sure = np.zeros(len(keep), dtype=bool)
    if len(high):
        mu, s = e["ids_mean"][high], sd[high]
        steps = np.linspace(-SIGMAS, SIGMAS, 49)
        n = np.clip(mu[:, None] + steps[None, :] * s[:, None], 0.0,
                    e["ids_max"][high][:, None])
        w = np.exp(-0.5 * steps * steps)
        w /= w.sum()
        keep[high] = selector.keep_probability(np.rint(n)) @ w
        low_end = np.maximum(mu - SIGMAS * s - 1.0, 0.0)
        sure[high] = selector.keep_probability(np.floor(low_end)) > SURE_KEEP
    return keep, sure


# ---------------------------------------------------------------------------
# The reference in the program's place (and, broken, the control)
# ---------------------------------------------------------------------------

BREAKS = ("l0_off", "linf_off", "clamp_off", "noise_half", "select_off",
          "half_rows")


def simulate_release(pairs, g, rng, broken=None):
    """One release of the stated semantics: (keys, values) of the kept
    partitions, `values` one column per metric of g["metrics"], in that
    order. `pairs` is Pairs(...) of the job's rows. `broken` names the one
    guarantee the control breaks:
      l0_off     — a privacy id's partitions are not bounded to l0;
      linf_off   — a pair's rows are not bounded to linf;
      clamp_off  — values are summed as given, not clamped;
      noise_half — noise calibrated to twice the ε the budget gives;
      select_off — every partition that has a row is released;
      half_rows  — every second row is left out (not a guarantee: the
                   "half of the batch" fault, for the tests)."""
    if broken is not None and broken not in BREAKS:
        raise ValueError(f"unknown break {broken!r}")
    b = budgets(g)
    l0, linf = g["l0"], g["linf"]
    n_pairs, n_parts = len(pairs.pid), len(pairs.keys)
    # l0: a uniform l0 of each privacy id's pairs.
    by_id = np.lexsort((rng.random(n_pairs), pairs.pid))
    rank = np.empty(n_pairs, dtype=np.int64)
    rank[by_id] = np.arange(n_pairs) - np.repeat(
        pairs.id_starts, np.diff(pairs.id_starts, append=n_pairs))
    pair_kept = np.ones(n_pairs, bool) if broken == "l0_off" else rank < l0
    # linf: a uniform linf of each pair's rows.
    n_rows = len(pairs.clamped)
    pair_of_row = np.repeat(np.arange(n_pairs), pairs.rows)
    row_kept = pair_kept[pair_of_row]
    if broken == "half_rows":
        row_kept &= (np.arange(n_rows) % 2).astype(bool)
    if broken != "linf_off":
        long_rows = np.flatnonzero(pairs.rows[pair_of_row] > linf)
        if len(long_rows):
            by_pair = long_rows[np.lexsort((rng.random(len(long_rows)),
                                            pair_of_row[long_rows]))]
            within = np.arange(len(by_pair)) - np.searchsorted(
                pair_of_row[by_pair], pair_of_row[by_pair], side="left")
            row_kept[by_pair[within >= linf]] = False
    part_of_row = pairs.part[pair_of_row]
    values = pairs.raw if broken == "clamp_off" else pairs.clamped
    has_row = np.zeros(n_pairs, bool)
    has_row[pair_of_row[row_kept]] = True
    ids = np.bincount(pairs.part[has_row], minlength=n_parts)
    exact = {
        "count": np.bincount(part_of_row[row_kept], minlength=n_parts),
        "sum": np.bincount(part_of_row[row_kept], weights=values[row_kept],
                           minlength=n_parts),
        "privacy_id_count": ids,
    }
    if broken == "select_off":
        keep = ids > 0
    else:
        selector = TruncatedGeometric(b["select_eps"], b["select_delta"], l0)
        keep = rng.random(n_parts) < selector.keep_probability(ids)
    shrink = 0.5 if broken == "noise_half" else 1.0
    columns = [exact[m] + rng.laplace(0.0, scale * shrink, n_parts)
               for m, scale in b["scales"].items()]
    return pairs.keys[keep], np.stack(columns, axis=1)[keep]


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------

def compare(expect, releases):
    """The numbers of one window. `releases` is a list of (keys, values),
    one per job, every job over the rows `expect` was made from; `values`
    has one column per released metric, in the order of `expect["mean"]`.

      unknown_keys   released keys that no row bears (exact: 0)
      sure_missing   surely-kept partitions a job did not release
      kept_z         |kept − expected kept| over the partitions that are
                     not surely kept, in standard deviations of that number
      <m>_bias_z     |mean standardised residual| of the surely-kept
                     partitions' values of metric m (count, sum, ids), in
                     standard errors of that mean
      <m>_spread     |rms standardised residual − 1| over the same values
      <m>_noise      |v̂ ÷ v − 1|, v the variance of the noise the budget
                     gives metric m and v̂ its estimate from the same
                     values: the mean of (residual² − the partition's
                     sampling variance), weighted by 1 ÷ total variance².
                     Where the bounding's sampling variance dwarfs the
                     noise, the spread cannot see the noise; this can
      max_abs_z      the largest |standardised residual| of any released
                     value of any metric
    """
    keys = expect["keys"]
    sure, keep = expect["sure"], expect["keep"]
    unsure = ~sure
    metrics = list(expect["mean"])
    unknown = missing = kept_unsure = 0
    z_sure = {m: [] for m in metrics}
    var_sure = {m: [] for m in metrics}
    worst = 0.0
    for got_keys, got_values in releases:
        got_keys = np.asarray(got_keys, dtype=np.int64)
        got_values = np.asarray(got_values, dtype=np.float64).reshape(
            len(got_keys), len(metrics))
        at = np.searchsorted(keys, got_keys)
        known = (at < len(keys)) & (keys[np.minimum(at, len(keys) - 1)]
                                    == got_keys)
        unknown += int((~known).sum())
        at = at[known]
        on_sure = sure[at]
        missing += int(sure.sum() - on_sure.sum())
        kept_unsure += int((~on_sure).sum())
        for column, m in enumerate(metrics):
            z = (got_values[known, column] - expect["mean"][m][at]) / \
                np.sqrt(expect["var"][m][at])
            if len(z):
                worst = max(worst, float(np.abs(z).max()))
            z_sure[m].append(z[on_sure])
            var_sure[m].append(expect["var"][m][at][on_sure])
    jobs = len(releases)
    want = jobs * float(keep[unsure].sum())
    want_var = jobs * float((keep[unsure] * (1.0 - keep[unsure])).sum())
    numbers = {
        "unknown_keys": float(unknown),
        "sure_missing": float(missing),
        "kept_z": abs(kept_unsure - want) / math.sqrt(max(want_var, 1.0)),
    }
    for m in metrics:
        z = np.concatenate(z_sure[m]) if z_sure[m] else np.zeros(0)
        short = METRICS[m]
        numbers[short + "_bias_z"] = (
            abs(float(z.mean())) * math.sqrt(len(z)) if len(z) else math.inf)
        numbers[short + "_spread"] = (
            abs(math.sqrt(float((z * z).mean())) - 1.0) if len(z)
            else math.inf)
        numbers[short + "_noise"] = math.inf
        if len(z):
            total = np.concatenate(var_sure[m])
            v = expect["noise_var"][m]
            # residual² − sampling variance = (z² − 1)·total + v
            v_hat = float((((z * z - 1.0) * total + v) / total**2).sum() /
                          (1.0 / total**2).sum())
            numbers[short + "_noise"] = abs(v_hat / v - 1.0)
    numbers["max_abs_z"] = worst
    return numbers


# ---------------------------------------------------------------------------
# The roofline's bytes
# ---------------------------------------------------------------------------

ROW_BYTES = 13  # privacy id 4 B, partition id 4 B, value 4 B, valid flag 1 B


def min_bytes(rows, kept_partitions, g):
    """The fewest bytes a release of this job has to move through HBM:
    every row read once (ROW_BYTES) and every kept partition's released
    columns (4 B each, one per metric of g["metrics"]) written once. From
    shapes alone; the operations are negligible beside it (a handful per
    row), so the roofline is the memory one."""
    return rows * ROW_BYTES + kept_partitions * len(g["metrics"]) * 4
