"""The law `columns_laplace_public`: the plain reference of one family of
guarantees — COUNT and, per value column, SUM and/or MEAN over rows of
(privacy id, partition id, d values), under l0 / l∞ contribution bounding
with ONE sample shared by all columns, a clamp per column, Laplace noise
under the naive accountant's split, and PUBLIC partitions: every partition
of the list is released, the empty ones as noise.

Numpy only: nothing here imports the program or takes anything it made.
The reference gets the raw rows the generator drew and the guarantees the
configuration's file states (its `guarantees`, which name this law):

    epsilon, l0, linf, public_partitions (P: the ids 0..P-1),
    columns   [{name, min_value, max_value, metrics ⊆ [sum, mean]}, ...]
    released  the released fields of a partition, in the order of a
              release's tuple: `count`, `<name>_sum`, `<name>_mean`

Semantics (what the program's docstring states, AggregateParams): a
privacy id keeps a uniform l0 of its partitions and, in each, a uniform
linf of its rows WITHOUT replacement — one sample, every column reads the
same rows; a row outside every partition (partition id < 0) is dropped;
each sampled value is clamped to its column's [min, max]. Mechanisms, each
an equal share of ε: a column with `mean` holds two — a Laplace count
Ĉ = C + Lap(l0·linf ÷ share) and a Laplace normalised sum
N̂ = Σ(x − mid) + Lap(l0·linf·(max − min)/2 ÷ share), mid = (min + max)/2 —
and releases mean = mid + N̂ ÷ max(1, Ĉ) and, where asked, sum = mean × Ĉ;
a column with `sum` alone holds one, Σx + Lap(l0·linf·max(|min|, |max|) ÷
share). `count` is the Ĉ of the first column with `mean` (no mechanism of
its own), or a count mechanism of its own where no column has one.

What a sound release may show, per partition and released field:

  * count: mean Σ q·k over the partition's (id, partition) pairs — q the
    chance the pair survives l0, k = min(rows, linf) — and the sampling
    variance Σ q(1−q)k² plus the Laplace variance. EXACTLY zero sampling
    variance where l0 does not bind: every pair then keeps k rows whatever
    the draw;
  * a sum: Σ q·k·(pair's mean value), sampling variance of a uniform
    k-subset without replacement, k·s²·(c − k)/(c − 1) per pair of c rows,
    plus the Laplace variance (for a mean column's sum, mid·Ĉ + N̂ — linear
    where Ĉ ≥ 1 — mid²·Var Lap_count + Var Lap_nsum);
  * a mean: the delta method on N̂ ÷ Ĉ about r = E N ÷ E C: variance
    Var(N̂ − r·Ĉ) ÷ (E C)², the sampling part from the values shifted by
    mid + r. It is tight because the count is large: the law holds a
    mean column's mean and sum only on partitions whose expected count is
    at least THIN (100) standard deviations of the count's noise (at SF10
    the thinnest populated group holds 388,000 rows against a noise sd of
    1,086: the second-order term is under 1e-5 of the variance). On
    thinner partitions — the empty ones — max(1, Ĉ) and the product
    mean × Ĉ are not linear in the noise and those fields are NOT held;
    `count` and the sum-only columns are linear everywhere, so an empty
    public partition is held to noise about 0 through them.

`simulate_release` is the reference put in the program's place; with
`broken` it breaks ONE stated guarantee (the control). `compare` gives the
numbers (reference.decide holds each to the cell's limit); `min_bytes` the
roofline's bytes.
"""

import math

import numpy as np

THIN = 100.0
TABLE_MAX = 1 << 28  # (ids × partitions) up to here: a table beats a sort


# ---------------------------------------------------------------------------
# The stated guarantees -> released fields, mechanisms, Laplace scales
# ---------------------------------------------------------------------------


def released_fields(g):
    """[(name, kind, column)] in the order of a release's tuple; kind is
    count | sum | mean, column the index into g["columns"] (−1: count)."""
    by_name = {"count": ("count", -1)}
    for j, column in enumerate(g["columns"]):
        for metric in column["metrics"]:
            if metric not in ("sum", "mean"):
                raise ValueError("a column's metrics are sum and mean")
            by_name[f"{column['name']}_{metric}"] = (metric, j)
    return [(name,) + by_name[name] for name in g["released"]]


def budgets(g):
    """The naive accountant's split — ε in equal shares over the
    mechanisms — and each mechanism's Laplace scale b (L1 sensitivity ÷
    its ε): `count`, and per column `nsum` (a mean column) or `sum`."""
    if g["noise"] != "laplace":
        raise ValueError("the reference knows Laplace noise")
    has_mean = ["mean" in c["metrics"] for c in g["columns"]]
    counted = any(kind == "count" for _, kind, _ in released_fields(g))
    mechanisms = sum(2 if m else 1 for m in has_mean) + (
        1 if counted and not any(has_mean) else 0)
    share = g["epsilon"] / mechanisms
    rows = g["l0"] * g["linf"]
    scales = {"count": rows / share, "nsum": [], "sum": []}
    for column in g["columns"]:
        lo, hi = column["min_value"], column["max_value"]
        scales["nsum"].append(rows * (hi - lo) / 2.0 / share)
        scales["sum"].append(rows * max(abs(lo), abs(hi)) / share)
    return {"mechanisms": mechanisms, "share": share, "scales": scales,
            "first_mean": has_mean.index(True) if any(has_mean) else None}


def middles(g):
    return np.array([c["min_value"] + (c["max_value"] - c["min_value"]) / 2.0
                     for c in g["columns"]])


# ---------------------------------------------------------------------------
# Rows -> (privacy id, partition) pairs
# ---------------------------------------------------------------------------


class Pairs:
    """The distinct (privacy id, partition) pairs of the rows that lie in
    a public partition: per pair its id, partition, row count `rows`, how
    many partitions its id touches, and per column the sum `s1[j]` and sum
    of squares `s2[j]` of its clamped values less the column's middle.
    `sorted_rows()` (the simulator's) are the rows in pair order."""

    def __init__(self, pid, pk, values, g):
        self.P = int(g["public_partitions"])
        self.mid = middles(g)
        pk = np.asarray(pk).astype(np.int64)
        inside = (pk >= 0) & (pk < self.P)
        pid = np.asarray(pid).astype(np.int64)[inside]
        if len(pid) and pid.min() < 0:
            raise ValueError("privacy ids must be non-negative")
        self._values = np.asarray(values).reshape(len(pk), -1)
        self._inside = inside
        self.d = len(g["columns"])
        if self._values.shape[1] != self.d:
            raise ValueError("one value per column and row")
        code = pid * self.P + pk[inside]
        top = (int(pid.max()) + 1) * self.P if len(pid) else 1
        if top <= TABLE_MAX:
            present = np.zeros(top, dtype=bool)
            present[code] = True
            codes = np.flatnonzero(present)
            self._pair_of_row = (np.cumsum(present) - 1)[code]
        else:
            codes, self._pair_of_row = np.unique(code, return_inverse=True)
        n_pairs = len(codes)
        self.pid, self.part = codes // self.P, codes % self.P
        self.rows = np.bincount(self._pair_of_row, minlength=n_pairs)
        self.id_starts = _starts(self.pid)
        per_id = np.diff(self.id_starts, append=n_pairs)
        self.partitions_of_id = np.repeat(per_id, per_id)
        self.bounds = [(c["min_value"], c["max_value"])
                       for c in g["columns"]]
        self.s1, self.s2 = [], []
        for j in range(self.d):
            x = self.column(j, clamped=True)
            self.s1.append(np.bincount(self._pair_of_row, weights=x,
                                       minlength=n_pairs))
            x *= x
            self.s2.append(np.bincount(self._pair_of_row, weights=x,
                                       minlength=n_pairs))
        self._sorted = None

    def column(self, j, clamped):
        """Column j of the rows inside a partition, less its middle, as
        given or clamped (float64, in row order)."""
        x = self._values[:, j][self._inside].astype(np.float64)
        if clamped:
            np.clip(x, *self.bounds[j], out=x)
        x -= self.mid[j]
        return x

    def sorted_rows(self):
        """(order, pair of each row) with the rows in pair order."""
        if self._sorted is None:
            order = np.argsort(self._pair_of_row, kind="stable")
            self._sorted = (order, self._pair_of_row[order])
        return self._sorted


def _starts(sorted_ids):
    """Positions at which a sorted array takes a new value."""
    if not len(sorted_ids):
        return np.zeros(0, dtype=np.int64)
    first = np.empty(len(sorted_ids), dtype=bool)
    first[0] = True
    np.not_equal(sorted_ids[1:], sorted_ids[:-1], out=first[1:])
    return np.flatnonzero(first)


# ---------------------------------------------------------------------------
# What a sound release may show
# ---------------------------------------------------------------------------


def expectations(pid, pk, values, g):
    """Per public partition 0..P−1 and released field (g["released"]):
    `mean`, `var`, the share `noise_share` of the variance that is Laplace
    noise, and `held` — False where the law cannot standardise the field
    (a mean column's mean and sum on a thin partition). See the module
    docstring."""
    pairs = Pairs(pid, pk, values, g)
    b = budgets(g)
    scales, mid, P = b["scales"], pairs.mid, pairs.P
    l0, linf = g["l0"], g["linf"]

    def per_partition(w):
        return np.bincount(pairs.part, weights=w, minlength=P)

    q = np.minimum(1.0, l0 / pairs.partitions_of_id)  # pair survives l0
    qq = q * (1.0 - q)
    c = pairs.rows.astype(np.float64)
    k = np.minimum(c, linf)
    shrink = np.where(c > linf, k * (c - k) / np.maximum(c - 1.0, 1.0), 0.0)

    def bounded_sum(t1, t2):
        """Mean and sampling variance, per partition, of the bounded sum
        of a row value whose pair sums are t1 and sums of squares t2."""
        mean_v = t1 / c
        pop_var = np.maximum(t2 / c - mean_v * mean_v, 0.0)
        pair_sum = k * mean_v
        return (per_partition(q * pair_sum),
                per_partition(q * shrink * pop_var + qq * pair_sum * pair_sum))

    count_mean = per_partition(q * k)
    count_var = per_partition(qq * k * k)
    count_noise = 2.0 * scales["count"] ** 2
    thick = count_mean >= THIN * math.sqrt(count_noise)
    out = {"keys": np.arange(P), "names": list(g["released"]),
           "pooled": pooled_fields(g), "mean": {}, "var": {},
           "noise_share": {}, "held": {}, "count_mean": count_mean}
    everywhere = np.ones(P, dtype=bool)
    for name, kind, j in released_fields(g):
        if kind == "count":
            mean, sampling, noise, held = (count_mean, count_var,
                                           count_noise, everywhere)
        else:
            s1, s2 = pairs.s1[j], pairs.s2[j]
            by_mean = "mean" in g["columns"][j]["metrics"]
            noise_n = 2.0 * scales["nsum"][j] ** 2
            if kind == "sum":  # of the clamped values: shift back by mid
                mean, sampling = bounded_sum(
                    s1 + c * mid[j], s2 + 2.0 * mid[j] * s1 + c * mid[j] ** 2)
                noise = (mid[j] ** 2 * count_noise + noise_n if by_mean
                         else 2.0 * scales["sum"][j] ** 2)
                held = thick if by_mean else everywhere
            else:  # the delta method about r = E N / E C
                n_mean, _ = bounded_sum(s1, s2)
                r = n_mean / np.maximum(count_mean, 1.0)
                rp = r[pairs.part]
                _, sampling = bounded_sum(
                    s1 - c * rp, s2 - 2.0 * rp * s1 + c * rp * rp)
                size = np.maximum(count_mean, 1.0) ** 2
                mean = mid[j] + r
                sampling = sampling / size
                noise = (noise_n + r * r * count_noise) / size
                held = thick
        out["mean"][name] = mean
        out["var"][name] = sampling + noise
        out["noise_share"][name] = noise / (sampling + noise)
        out["held"][name] = held
    return out


# ---------------------------------------------------------------------------
# The reference in the program's place (and, broken, the control)
# ---------------------------------------------------------------------------

BREAKS = ("linf_off", "clamp_off", "noise_half", "swap_columns", "half_rows")


def simulate_release(pairs, g, rng, broken=None):
    """One release of the stated semantics: (keys, values) of the P public
    partitions, `values` one column per field of g["released"], in that
    order. `pairs` is Pairs(...) of the job's rows. `broken` names the one
    guarantee the control breaks:
      linf_off     — a pair's rows are not bounded to linf;
      clamp_off    — values are summed as given, not clamped;
      noise_half   — noise calibrated to twice the ε the budget gives;
      swap_columns — the first two columns' answers exchanged (what only a
                     job of several columns can get wrong);
      half_rows    — every second row is left out (not a guarantee: the
                     "half of the batch" fault, for the tests)."""
    if broken is not None and broken not in BREAKS:
        raise ValueError(f"unknown break {broken!r}")
    b = budgets(g)
    scales, mid, P = b["scales"], pairs.mid, pairs.P
    l0, linf = g["l0"], g["linf"]
    n_pairs = len(pairs.pid)
    order, pair_of_row = pairs.sorted_rows()
    n_rows = len(order)
    # l0: a uniform l0 of each privacy id's pairs.
    pair_kept = np.ones(n_pairs, dtype=bool)
    if n_pairs and pairs.partitions_of_id.max() > l0:
        by_id = np.lexsort((rng.random(n_pairs), pairs.pid))
        rank = np.empty(n_pairs, dtype=np.int64)
        rank[by_id] = np.arange(n_pairs) - np.repeat(
            pairs.id_starts, np.diff(pairs.id_starts, append=n_pairs))
        pair_kept = rank < l0
    row_kept = pair_kept[pair_of_row]
    if broken == "half_rows":
        row_kept &= (np.arange(n_rows) % 2).astype(bool)
    # linf: a uniform linf of each pair's rows, without replacement.
    if broken != "linf_off":
        long_rows = np.flatnonzero(pairs.rows[pair_of_row] > linf)
        if len(long_rows):
            shuffled = long_rows[np.argsort(
                pair_of_row[long_rows] + rng.random(len(long_rows)))]
            within = np.arange(len(shuffled)) - np.searchsorted(
                pair_of_row[shuffled], pair_of_row[shuffled], side="left")
            row_kept[shuffled[within >= linf]] = False
    part_of_row = pairs.part[pair_of_row][row_kept]
    kept_rows = order[row_kept]
    count = np.bincount(part_of_row, minlength=P).astype(np.float64)
    shrink = 0.5 if broken == "noise_half" else 1.0

    def laplace(scale):
        return rng.laplace(0.0, scale * shrink, P)

    fields = {}
    for j, column in enumerate(g["columns"]):
        x = pairs.column(j, clamped=broken != "clamp_off")[kept_rows]
        nsum = np.bincount(part_of_row, weights=x, minlength=P)
        if "mean" in column["metrics"]:
            noisy_count = count + laplace(scales["count"])
            mean = mid[j] + (nsum + laplace(scales["nsum"][j])) / np.maximum(
                1.0, noisy_count)
            fields[f"{column['name']}_mean"] = mean
            fields[f"{column['name']}_sum"] = mean * noisy_count
            if j == b["first_mean"]:
                fields["count"] = noisy_count
        else:
            fields[f"{column['name']}_sum"] = (
                nsum + mid[j] * count + laplace(scales["sum"][j]))
    if "count" not in fields:
        fields["count"] = count + laplace(scales["count"])
    if broken == "swap_columns":
        first, second = (c["name"] for c in g["columns"][:2])
        for metric in ("sum", "mean"):
            a, z = f"{first}_{metric}", f"{second}_{metric}"
            if a in g["released"] and z in g["released"]:
                fields[a], fields[z] = fields[z], fields[a]
    return np.arange(P), np.stack([fields[name] for name in g["released"]],
                                  axis=1)


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------

def pooled_fields(g):
    """The released fields whose noise draws are independent of one
    another's, one per mechanism the release shows: `count`, a mean
    column's mean, a sum-only column's sum. (A mean column's sum is its
    mean × its count: the same draws again.)"""
    by_mean = {c["name"] for c in g["columns"] if "mean" in c["metrics"]}
    return [name for name, kind, j in released_fields(g)
            if kind != "sum" or g["columns"][j]["name"] not in by_mean]


def factor_z(factor, variance):
    """|ln factor| in standard errors of `factor` at 1: a factor on a
    variance is a scale, so its logarithm is what is near normal and what
    a half and a double move equally. A factor of 0 or less (no spread at
    all where the law says there is some) reads infinite."""
    if not factor > 0.0 or not variance > 0.0:
        return math.inf
    return abs(math.log(factor)) / math.sqrt(variance)


def compare(expect, releases):
    """The numbers of one window. `releases` is a list of (keys, values),
    one per job, every job over the rows `expect` was made from; `values`
    has one column per released field, in the order of expect["names"].

      unknown_keys    released keys that are no public partition (exact: 0)
      missing_keys    public partitions a job did not release (exact: 0)
      <field>_bias_z  |mean standardised residual| of the field's HELD
                      values, in standard errors of that mean
      spread_z        |ln mean z²| in its standard errors, z the
                      standardised residuals POOLED over the held values of
                      the fields of expect["pooled"] (pooled_fields: the
                      ones whose noise is independent): a window holds some
                      ten jobs of P partitions, too few values a field to
                      hold a spread of its own
      noise_z         |ln f̂| in its standard errors, f the factor on the
                      variance of the noise the budget gives, estimated
                      from the same pooled values: f̂ = 1 + Σ w(z² − 1) ÷
                      Σ w², w the share of the value's variance that is
                      noise. Noise for twice the ε reads f = 1/4
      max_abs_z       the largest |standardised residual| of any held value

    Both pooled numbers are in standard errors, like the biases, so one
    limit holds them whatever the window's job count: a z² has variance
    2 + 3w² (a Laplace draw's excess kurtosis is 3, the sampling part is
    a sum over many pairs and near normal), hence Var mean z² =
    Σ(2 + 3w²) ÷ n² and Var f̂ = Σ w²(2 + 3w²) ÷ (Σ w²)²."""
    keys, names = expect["keys"], expect["names"]
    unknown = missing = 0
    z_of = {name: [] for name in names}
    w_of = {name: [] for name in names}
    for got_keys, got_values in releases:
        got_keys = np.asarray(got_keys, dtype=np.int64)
        got_values = np.asarray(got_values, dtype=np.float64).reshape(
            len(got_keys), len(names))
        known = (got_keys >= 0) & (got_keys < len(keys))
        unknown += int((~known).sum())
        at = got_keys[known]
        missing += len(keys) - len(np.unique(at))
        for column, name in enumerate(names):
            held = expect["held"][name][at]
            here = at[held]
            z = (got_values[known, column][held] - expect["mean"][name][here]
                 ) / np.sqrt(expect["var"][name][here])
            z_of[name].append(z)
            w_of[name].append(expect["noise_share"][name][here])
    numbers = {"unknown_keys": float(unknown), "missing_keys": float(missing)}
    for name in names:
        z = np.concatenate(z_of[name]) if z_of[name] else np.zeros(0)
        numbers[name + "_bias_z"] = (
            abs(float(z.mean())) * math.sqrt(len(z)) if len(z) else math.inf)
    every = np.concatenate([v for name in names for v in z_of[name]] or
                           [np.zeros(0)])
    z = np.concatenate([v for name in expect["pooled"] for v in z_of[name]]
                       or [np.zeros(0)])
    w = np.concatenate([v for name in expect["pooled"] for v in w_of[name]]
                       or [np.zeros(0)])
    if len(z):
        tail = 2.0 + 3.0 * w * w  # Var z² of each value
        ww = float((w * w).sum())
        numbers["spread_z"] = factor_z(float((z * z).mean()),
                                       float(tail.sum()) / len(z) ** 2)
        numbers["noise_z"] = (
            factor_z(1.0 + float((w * (z * z - 1.0)).sum()) / ww,
                     float((w * w * tail).sum()) / ww ** 2)
            if ww > 0.0 else math.inf)
        numbers["max_abs_z"] = float(np.abs(every).max())
    else:
        numbers.update(spread_z=math.inf, noise_z=math.inf,
                       max_abs_z=math.inf)
    return numbers


# ---------------------------------------------------------------------------
# The roofline's bytes
# ---------------------------------------------------------------------------


def min_bytes(rows, kept_partitions, g):
    """The fewest bytes a release of this job has to move through HBM:
    every row read once — privacy id 4 B, partition id 4 B, valid flag 1 B
    and 4 B a value column — and every released partition's fields (4 B
    each) written once. From shapes alone; the operations are negligible
    beside it, so the roofline is the memory one."""
    return (rows * (9 + 4 * len(g["columns"])) +
            kept_partitions * len(g["released"]) * 4)
