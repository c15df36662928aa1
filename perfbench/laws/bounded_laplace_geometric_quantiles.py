"""The law `bounded_laplace_geometric_quantiles`: the plain reference of
the upstream movie-ratings job WITH its percentiles — COUNT / SUM /
PRIVACY_ID_COUNT as the law `bounded_laplace_geometric` holds them, and
PERCENTILE(p) per partition from ONE differentially private quantile tree
— over rows of (privacy id, partition key, value).

Numpy only: nothing here imports the program or takes anything it made.
It imports the law `bounded_laplace_geometric` (numpy alone too) for the
three sums, which are held exactly as that law holds them, at the budget
split this job has (below); what is new here is the tree.

The guarantees (`g`, the configuration's `guarantees`): that law's keys,
with `metrics` naming the sums and `percentile_<p>` columns in released
order, and `tree` = {height, branching}.

BUDGET (what LocalBackend does: combiners.create_compound_combiner asks
the naive accountant for one budget per sum metric and ONE for the
QuantileCombiner whatever the number of percentiles; the engine adds the
selection's): epsilon in equal shares over len(sums) + 1 (the tree) + 1
(the selection) mechanisms; all of delta to the selection. The tree
splits ITS share in equal parts over its `height` levels
(ops/quantile_tree.per_level_noise_std): a privacy id adds at most
l0 * linf to the counts of one level, so every node count gets Laplace
noise of scale l0 * linf * height / share.

THE TREE, from the published description (Google's differential-privacy
library, `QuantileTree`, which PyDP wraps and upstream PipelineDP's
QuantileCombiner calls; written from memory, no network here): the range
[min_value, max_value] is cut into branching^height equal leaves; a value
adds one to every node on its root-to-leaf path (the root holds no
count); a query noises each node it reads ONCE (a second quantile that
reads the same node sees the same noisy count), clips noisy counts at 0,
and walks down: at each level the target rank q * (sum of the children)
picks the first child whose cumulative count reaches it, the rank is
carried into that child as a share of its mass, and the answer is
interpolated linearly inside the leaf; answers are made monotone in q.
Departures from that description, each as upstream PipelineDP-TPU's host
tree (ops/quantile_tree.DenseQuantileTree) states them, because the
release to hold is LocalBackend's:
  * a root whose children are all clipped to 0 answers the middle of the
    range; lower down, a node whose children are all 0 is walked into its
    first child and the walk ends at that subtree's lowest leaf (the
    library stops and interpolates inside the node it stands in);
  * no cut-off for children holding a tiny share of their parent (the
    library treats such subtrees as noise);
  * monotone by a running maximum over the answers in order of q.

WHAT IS HELD. A percentile is not a sum: its noise is not additive, and
under this budget (node scale 40 against a few hundred bounded ratings in
most released movies) a sound answer is often a neighbouring rating or a
point in an empty stretch of the range. So the comparison is with the
DISTRIBUTION the stated tree gives, which `expectations` draws by running
this module's own tree on the partition's expected bounded counts (the
sampling law of the sums: a uniform l0 of an id's partitions, a uniform
linf of its rows in each; a rating's count a sum of independent
indicators, drawn as a normal), `DRAWS` times per partition, only for the
partitions private selection surely keeps (the others' counts are
conditioned by having been kept). From those draws, per partition: the
probability that the answer lands on the leaf of the expected rating (the
q-quantile of the expected counts: `hit`), the probability that it lands
in that leaf's node at level `NEAR_LEVEL` (`near`: under this budget the
upper levels, whose nodes hold 1/16 and 1/256 of the range, are found by
a few hundred ratings, the leaf only by thousands), and whether the
answer is SURE: every draw hit, and the bounded count is at least
`SURE_NODES` node scales. The floor comes from the node scale: a rating's
node has 15 empty siblings at every level, whose clipped noise adds 7.5
scales in the mean (sd 3.4) to the mass the rank is taken in, and the
tree's own draws on the ratings' shares put the answer on the leaf in 98 %
of trees at 100 scales and in every one of 4,000 at 150 (sandbox, PR 35).

Numbers of `compare` beside the sums' (names for PERCENTILE(50), (90)):
  pctl_outside   released percentiles outside [min_value, max_value]
                 (exact: 0)
  pctl_disorder  releases whose higher percentile is below the lower
                 (exact: 0)
  p50_miss, p90_miss   share of the released answers of SURE partitions
                 that are not on the expected rating's leaf, within
                 `LEAF_SLACK` leaf widths (the interpolation moves inside
                 the leaf; float32 may put a value in the next leaf)
  p50_hit_z, p90_hit_z  over the surely-kept partitions: |hits − expected
                 hits| in standard deviations of that count (binomial in
                 the jobs, widened by the draws' own error). No noise,
                 half the noise, noise on one level only or a tree fed the
                 unbounded rows all hit too often; noise too wide, too
                 seldom.
  p50_near_z, p90_near_z  the same for landing in the expected rating's
                 node at level `NEAR_LEVEL`: it is where the partitions of
                 a few hundred bounded ratings, most of those released,
                 carry their information.
"""

import math

import numpy as np

from perfbench.laws import bounded_laplace_geometric as base

Pairs = base.Pairs

# The tree's own draws per surely-kept partition: a probability read from
# them is off by at most sqrt(0.25 / 512) = 0.022, an error every job of a
# window shares and the z numbers carry as the factor (1 + jobs / DRAWS).
DRAWS = 512
# An answer is sure only above this many node scales of bounded ratings
# (module docstring: 98 % of trees on the leaf at 100, every one at 150).
SURE_NODES = 100.0
# `near`: within the rating's node at this level (1/256 of the range, 1/64
# of a rating step): the level a few hundred ratings still find.
NEAR_LEVEL = 2
# Leaf widths an answer may lie off its rating's leaf: the value -> leaf
# product is taken in float32 on the chip and can round into the next leaf.
LEAF_SLACK = 1.0
# Distinct leaves the rows may fall on (ratings: 5). The law keeps a count
# per partition and leaf; continuous values would need another law.
MAX_LEAVES = 64
DRAW_SEED = 20260435  # the draws are a fixed function of the rows


# ---------------------------------------------------------------------------
# The guarantees' split
# ---------------------------------------------------------------------------


def _split(g):
    """(names of the sums, [(column name, q)] of the percentiles, the
    guarantees the law of the sums reads at this job's split)."""
    sums = [m for m in g["metrics"] if m in base.METRICS]
    pctls = []
    for m in g["metrics"]:
        if m in base.METRICS:
            continue
        if not m.startswith("percentile_"):
            raise ValueError(f"the reference knows {sorted(base.METRICS)} "
                             f"and percentile_<p>, not {m!r}")
        pctls.append((m, float(m[len("percentile_"):].replace("_", "."))
                      / 100.0))
    if not sums or not pctls:
        raise ValueError("this law holds sums AND percentiles")
    if list(g["metrics"]) != sums + [m for m, _ in pctls]:
        raise ValueError("released order: the sums, then the percentiles")
    mechanisms = len(sums) + 2  # the sums, ONE tree, the selection
    # The law of the sums splits epsilon over len(metrics) + 1: hand it the
    # epsilon that gives each of them this job's share.
    sums_g = dict(g, metrics=sums,
                  epsilon=g["epsilon"] * (len(sums) + 1) / mechanisms)
    return sums, pctls, sums_g


def budgets(g):
    """The sums' scales and the selection's (eps, delta) as the law of the
    sums gives them at this split, and `node_scale`: the Laplace scale of
    one tree node."""
    sums, _, sums_g = _split(g)
    b = base.budgets(sums_g)
    share = g["epsilon"] / (len(sums) + 2)
    height = int(g["tree"]["height"])
    b["node_scale"] = g["l0"] * g["linf"] * height / share
    return b


def _shape(g):
    height, branching = int(g["tree"]["height"]), int(g["tree"]["branching"])
    n_leaves = branching**height
    width = (g["max_value"] - g["min_value"]) / n_leaves
    return height, branching, n_leaves, width


def leaf_of(values, g):
    """The leaf a value falls on: equal cuts of [min_value, max_value],
    the top value in the last leaf."""
    _, _, n_leaves, _ = _shape(g)
    frac = (np.asarray(values, dtype=np.float64) - g["min_value"]) / (
        g["max_value"] - g["min_value"])
    return np.clip((frac * n_leaves).astype(np.int64), 0, n_leaves - 1)


# ---------------------------------------------------------------------------
# The tree (numpy, from the published description; module docstring)
# ---------------------------------------------------------------------------


def tree_quantiles(counts, leaves, quantiles, g, scale=0.0, rng=None,
                   levels=None, from_top=False):
    """Answers of T quantile trees: `counts[T, R]` rows on the R distinct
    `leaves`; returns [T, len(quantiles)]. Every node read gets Laplace
    noise of `scale` (none at 0), the same draw for every quantile that
    reads it; `levels` noises only those levels (the control);
    `from_top` takes the rank from the top of the order (the control)."""
    height, B, n_leaves, width = _shape(g)
    counts = np.asarray(counts, dtype=np.float64)
    T = len(counts)
    leaves = np.asarray(leaves, dtype=np.int64)
    order = np.argsort(np.asarray(quantiles), kind="stable")
    answers = np.empty((T, len(quantiles)))
    read = [[] for _ in range(height + 1)]  # per level: (node, noisy)
    rows = np.arange(T)
    middle = g["min_value"] + (g["max_value"] - g["min_value"]) / 2

    def children(level, node):
        """Noisy clipped counts [T, B] of `node`'s children at `level`."""
        at_level = leaves // B**(height - level)
        true = np.zeros((T, B))
        for r, n in enumerate(at_level):
            under = node == n // B
            true[under, n % B] += counts[under, r]
        noisy = true
        if scale > 0 and (levels is None or level in levels):
            noisy = true + rng.laplace(0.0, scale, (T, B))
        for seen_node, seen in read[level]:  # one draw per node
            same = seen_node == node
            noisy = np.where(same[:, None], seen, noisy)
        read[level].append((node, noisy))
        return np.maximum(noisy, 0.0)

    for position in order:
        q = quantiles[position]
        q = 1.0 - q if from_top else q
        node = np.zeros(T, dtype=np.int64)
        kids = children(1, node)
        total = kids.sum(axis=1)
        target = q * total
        for level in range(1, height + 1):
            cum = np.cumsum(kids, axis=1)
            child = np.minimum((cum < target[:, None]).sum(axis=1), B - 1)
            before = np.where(child > 0, cum[rows, np.maximum(child - 1, 0)],
                              0.0)
            mass = kids[rows, child]
            target = target - before
            node = node * B + child
            if level < height:
                kids = children(level + 1, node)
                target = target / np.maximum(mass, 1e-12) * kids.sum(axis=1)
        inside = np.clip(target / np.maximum(mass, 1e-12), 0.0, 1.0)
        value = g["min_value"] + (node + inside) * width
        value = np.clip(value, g["min_value"], g["max_value"])
        answers[:, position] = np.where(total <= 0, middle, value)
    ordered = np.maximum.accumulate(answers[:, order], axis=1)
    answers[:, order] = ordered
    return answers


def on_node(values, leaf, g, level=None):
    """Whether an answer lies in the node that holds `leaf` at `level`
    (the leaf itself by default), within LEAF_SLACK leaf widths."""
    height, B, _, width = _shape(g)
    span = B**(height - (height if level is None else level))  # in leaves
    low = g["min_value"] + (leaf // span) * span * width
    off = np.maximum(low - values, values - (low + span * width))
    return off <= LEAF_SLACK * width


def expected_leaf(mean_counts, leaves, q):
    """Per tree, the leaf (among `leaves`, ascending) holding the
    q-quantile of the expected counts."""
    cum = np.cumsum(mean_counts, axis=1)
    at = (cum < q * cum[:, -1:]).sum(axis=1)
    return np.asarray(leaves)[np.minimum(at, len(leaves) - 1)]


# ---------------------------------------------------------------------------
# Expectations
# ---------------------------------------------------------------------------


def _row_leaves(pairs, g, values=None):
    """(distinct leaves ascending, each row's index among them), rows in
    pair order."""
    leaf = leaf_of(pairs.clamped if values is None else values, g)
    present = np.zeros(_shape(g)[2], dtype=bool)
    present[leaf] = True
    leaves = np.flatnonzero(present)
    if len(leaves) > MAX_LEAVES:
        raise ValueError(f"the rows fall on {len(leaves)} leaves; this law "
                         f"holds a tree of at most {MAX_LEAVES} (ratings)")
    return leaves, (np.cumsum(present) - 1)[leaf]


def expected_tree_counts(pairs, g):
    """(the distinct leaves the rows fall on, and per partition — in the
    order of pairs.keys — the mean [P, R] and the sampling variance [P, R]
    of each leaf's bounded count)."""
    l0, linf = g["l0"], g["linf"]
    n_parts = len(pairs.keys)
    leaves, row_leaf = _row_leaves(pairs, g)
    survive = np.minimum(1.0, l0 / pairs.partitions_of_id)  # the pair, l0
    c = pairs.rows.astype(np.float64)
    kept_rows = np.minimum(c, linf)
    mean = np.empty((n_parts, len(leaves)))
    var = np.empty((n_parts, len(leaves)))
    for r in range(len(leaves)):
        share = np.add.reduceat((row_leaf == r).astype(np.float64),
                                pairs.starts) / c
        # A kept row of the pair is on this leaf with probability `share`;
        # exact for linf = 1 (one indicator a pair), rows of one pair taken
        # as independent above that.
        p = survive * share
        mean[:, r] = np.bincount(pairs.part, weights=kept_rows * p,
                                 minlength=n_parts)
        var[:, r] = np.bincount(pairs.part, weights=kept_rows * p * (1 - p),
                                minlength=n_parts)
    return leaves, mean, var


def expectations(pid, pk, values, g):
    """The sums' expectations (law `bounded_laplace_geometric` at this
    job's split) and, under "tree": the distinct `leaves`, per partition
    the `mean` and `var` of each leaf's bounded count, and for the
    surely-kept partitions (`at`, indices into `keys`) per percentile the
    `leaf` of the expected rating, the `hit` and `near` probabilities and
    whether the answer is `sure` (module docstring)."""
    sums, pctls, sums_g = _split(g)
    e = base.expectations(pid, pk, values, sums_g)
    b = budgets(g)
    leaves, mean, var = expected_tree_counts(Pairs(pid, pk, values, g), g)
    at = np.flatnonzero(e["sure"])
    rng = np.random.default_rng(DRAW_SEED)
    tree = {"leaves": leaves, "mean": mean, "var": var, "at": at,
            "node_scale": b["node_scale"], "g": g, "pctl": {}}
    if len(at):
        drawn = np.repeat(mean[at], DRAWS, axis=0) + rng.normal(
            0.0, 1.0, (len(at) * DRAWS, len(leaves))) * np.sqrt(
                np.repeat(var[at], DRAWS, axis=0))
        answers = tree_quantiles(np.maximum(np.rint(drawn), 0.0), leaves,
                                 [q for _, q in pctls], g,
                                 scale=b["node_scale"], rng=rng)
        enough = mean[at].sum(axis=1) >= SURE_NODES * b["node_scale"]
        for column, (name, q) in enumerate(pctls):
            leaf = expected_leaf(mean[at], leaves, q)
            drawn_leaf = np.repeat(leaf, DRAWS)
            hits = on_node(answers[:, column], drawn_leaf, g).reshape(
                len(at), DRAWS)
            near = on_node(answers[:, column], drawn_leaf, g,
                           NEAR_LEVEL).reshape(len(at), DRAWS)
            tree["pctl"][name] = {"leaf": leaf, "hit": hits.mean(axis=1),
                                  "near": near.mean(axis=1),
                                  "sure": hits.all(axis=1) & enough}
    e["tree"] = tree
    return e


# ---------------------------------------------------------------------------
# The reference in the program's place (and, broken, the control)
# ---------------------------------------------------------------------------

TREE_BREAKS = ("tree_noise_off", "tree_noise_half", "tree_noise_one_level",
               "bounding_off_in_tree", "rank_swapped")
BREAKS = base.BREAKS + TREE_BREAKS


def _bounded_rows(pairs, g, rng, broken):
    """One draw of the contribution bounding: which rows (in pair order)
    a release keeps — a uniform l0 of each id's pairs, a uniform linf of
    each pair's rows — and which pairs keep a row."""
    l0, linf = g["l0"], g["linf"]
    n_pairs, n_rows = len(pairs.pid), len(pairs.clamped)
    by_id = np.lexsort((rng.random(n_pairs), pairs.pid))
    rank = np.empty(n_pairs, dtype=np.int64)
    rank[by_id] = np.arange(n_pairs) - np.repeat(
        pairs.id_starts, np.diff(pairs.id_starts, append=n_pairs))
    pair_kept = np.ones(n_pairs, bool) if broken == "l0_off" else rank < l0
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
    has_row = np.zeros(n_pairs, bool)
    has_row[pair_of_row[row_kept]] = True
    return row_kept, pair_of_row, has_row


def simulate_release(pairs, g, rng, broken=None):
    """One release of the stated semantics: (keys, values) of the kept
    partitions, one column per name of g["metrics"]. The sums, the
    selection and their breaks are the law `bounded_laplace_geometric`'s,
    over ONE bounded sample that the tree is fed too. The tree's breaks:
      tree_noise_off        — the tree's nodes are not noised;
      tree_noise_half       — node noise for twice the tree's epsilon;
      tree_noise_one_level  — only the root's children are noised;
      bounding_off_in_tree  — the tree is fed every row, unbounded;
      rank_swapped          — the rank is taken from the top of the order
                              (PERCENTILE(90) answers the 10th)."""
    if broken is not None and broken not in BREAKS:
        raise ValueError(f"unknown break {broken!r}")
    sums, pctls, _ = _split(g)
    b = budgets(g)
    n_parts = len(pairs.keys)
    row_kept, pair_of_row, has_row = _bounded_rows(pairs, g, rng, broken)
    part_of_row = pairs.part[pair_of_row]
    values = pairs.raw if broken == "clamp_off" else pairs.clamped
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
        selector = base.TruncatedGeometric(b["select_eps"],
                                           b["select_delta"], g["l0"])
        keep = rng.random(n_parts) < selector.keep_probability(ids)
    shrink = 0.5 if broken == "noise_half" else 1.0
    columns = [exact[m] + rng.laplace(0.0, b["scales"][m] * shrink, n_parts)
               for m in sums]
    # The tree, over the same bounded rows.
    leaves, row_leaf = _row_leaves(pairs, g, values)
    fed = (np.ones(len(row_kept), bool) if broken == "bounding_off_in_tree"
           else row_kept)
    counts = np.bincount(part_of_row[fed] * len(leaves) + row_leaf[fed],
                         minlength=n_parts * len(leaves)).reshape(
                             n_parts, len(leaves))
    scale = b["node_scale"] * shrink * {
        "tree_noise_off": 0.0, "tree_noise_half": 0.5}.get(broken, 1.0)
    answers = tree_quantiles(
        counts[keep], leaves, [q for _, q in pctls], g, scale=scale, rng=rng,
        levels=(1,) if broken == "tree_noise_one_level" else None,
        from_top=broken == "rank_swapped")
    released = np.concatenate([np.stack(columns, axis=1)[keep], answers],
                              axis=1)
    return pairs.keys[keep], released


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------


def short(name):
    """percentile_50 -> p50, the prefix of its numbers."""
    return "p" + name[len("percentile_"):]


def compare(expect, releases):
    """The numbers of one window: the sums' (law
    `bounded_laplace_geometric`'s `compare`, on the sums' columns) and the
    percentiles' (module docstring). `releases`: (keys, values) per job,
    `values` one column per name of the guarantees' `metrics`."""
    tree = expect["tree"]
    g = tree["g"]
    sums, pctls, _ = _split(g)
    n_sums = len(sums)
    releases = [(np.asarray(k, dtype=np.int64),
                 np.asarray(v, dtype=np.float64).reshape(
                     len(k), n_sums + len(pctls))) for k, v in releases]
    numbers = base.compare(expect, [(k, v[:, :n_sums]) for k, v in releases])
    keys = expect["keys"]
    sure_keys = keys[tree["at"]]
    outside = disorder = 0
    tally = {(name, kind): np.zeros(3)  # got, expected, its variance
             for name, _ in pctls for kind in ("hit", "near")}
    sure_n = {name: 0 for name, _ in pctls}
    sure_missed = {name: 0 for name, _ in pctls}
    jobs = len(releases)
    by_q = np.argsort([q for _, q in pctls], kind="stable")
    for got_keys, got in releases:
        answers = got[:, n_sums:]
        outside += int(((answers < g["min_value"]) |
                        (answers > g["max_value"]) |
                        ~np.isfinite(answers)).sum())
        disorder += int((np.diff(answers[:, by_q], axis=1) < 0).any(
            axis=1).sum())
        # The surely-kept partitions this job released, as rows of `at`.
        where = np.searchsorted(sure_keys, got_keys)
        found = (where < len(sure_keys)) & (
            sure_keys[np.minimum(where, len(sure_keys) - 1)] == got_keys)
        where = where[found]
        for column, (name, _) in enumerate(pctls):
            spec = tree["pctl"].get(name)
            if spec is None or not len(where):
                continue
            answer = answers[found, column]
            hit = on_node(answer, spec["leaf"][where], g)
            near = on_node(answer, spec["leaf"][where], g, NEAR_LEVEL)
            for kind, landed in (("hit", hit), ("near", near)):
                p = spec[kind][where]
                # Binomial in the job, and the draws' own error in `p`,
                # which every job of the window shares.
                tally[name, kind] += (landed.sum(), p.sum(), (
                    p * (1 - p)).sum() * (1.0 + jobs / DRAWS))
            answer_sure = spec["sure"][where]
            sure_n[name] += int(answer_sure.sum())
            sure_missed[name] += int((answer_sure & ~hit).sum())
    numbers["pctl_outside"] = float(outside)
    numbers["pctl_disorder"] = float(disorder)
    for name, _ in pctls:
        numbers[short(name) + "_miss"] = (
            sure_missed[name] / sure_n[name] if sure_n[name] else math.inf)
        for kind in ("hit", "near"):
            landed, want, want_var = tally[name, kind]
            numbers[f"{short(name)}_{kind}_z"] = (
                float(abs(landed - want)) / math.sqrt(max(want_var, 1.0))
                if want else math.inf)
    return numbers


# ---------------------------------------------------------------------------
# The roofline's bytes
# ---------------------------------------------------------------------------


def min_bytes(rows, kept_partitions, g):
    """The fewest bytes a release of this job has to move through HBM:
    every row read once (13 B: ids, value, valid flag) — whatever builds
    the tree, a row need be read no more than once — and every kept
    partition's released columns (4 B each, one per name of
    g["metrics"]) written once."""
    return rows * base.ROW_BYTES + kept_partitions * len(g["metrics"]) * 4
