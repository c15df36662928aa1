"""Unit tests for the device quantile kernel (executor.quantile_outputs)
against the host DenseQuantileTree on identical data, and of the served
lazy descent (rows sorted once by (partition, leaf), node boundaries by
search) against the scatter form it replaced, kept here as the reference.

Uses a small tree (branching 4, height 2 -> 16 leaves) so the lazy descent
is exercised with a handful of partitions.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import jax.random
import numpy as np
import pytest

from pipelinedp_tpu import executor
from pipelinedp_tpu.aggregate_params import NoiseKind
from pipelinedp_tpu.ops import quantile_tree


def _make_cfg(n_partitions, quantiles, chunk, branching=4, height=2,
              noise_kind=NoiseKind.LAPLACE, secure=False):
    plan = (executor.MetricPlanEntry('quantiles',
                                     tuple(f"q{i}"
                                           for i in range(len(quantiles))),
                                     1),)
    return executor.KernelConfig(n_partitions=n_partitions,
                                 linf=0,
                                 l0=0,
                                 total_bound=0,
                                 sample_per_partition=False,
                                 clip_per_value=False,
                                 clip_pair_sum=False,
                                 bounds_enforced=True,
                                 noise_kind=noise_kind,
                                 private_selection=False,
                                 selection=None,
                                 max_rows_per_privacy_id=1,
                                 plan=plan,
                                 degenerate_range=False,
                                 quantiles=tuple(quantiles),
                                 tree_height=height,
                                 branching=branching,
                                 quantile_chunk=chunk,
                                 secure=secure)


MIN_V, MAX_V = 0.0, 16.0


def _device_quantiles(values_per_partition, quantiles, chunk):
    P = len(values_per_partition)
    pks, leaves = [], []
    for p, vals in enumerate(values_per_partition):
        for v in vals:
            pks.append(p)
            leaves.append(v)
    cfg = _make_cfg(P, quantiles, chunk)
    n_leaves = cfg.branching**cfg.tree_height
    leaf_idx = np.clip(
        ((np.asarray(leaves, dtype=np.float64) - MIN_V) / (MAX_V - MIN_V) *
         n_leaves).astype(np.int32), 0, n_leaves - 1)
    qrows = (jnp.asarray(pks, dtype=jnp.int32), jnp.asarray(leaf_idx),
             jnp.ones(len(pks), dtype=bool))
    stds = jnp.asarray([1e-9])
    out = executor.quantile_outputs(qrows, MIN_V, MAX_V, stds,
                                    jax.random.PRNGKey(0), cfg)
    return np.stack(
        [np.asarray(out[f"q{i}"]) for i in range(len(quantiles))], axis=1)


def _host_quantiles(values, quantiles):
    tree = quantile_tree.DenseQuantileTree(MIN_V, MAX_V, height=2,
                                           branching_factor=4)
    tree.add_entries(values)
    return tree.compute_quantiles(1e9, 1e-5, 1, 1, list(quantiles),
                                  NoiseKind.LAPLACE,
                                  rng=np.random.default_rng(0))


# Note: bimodal counts are deliberately unbalanced (9 vs 11) — an exact tie
# at a subtree boundary makes the descent direction noise-driven on both the
# host and the device, which is correct DP behavior but untestable.
PARTITIONS = [
    [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0],
    [0.5] * 9 + [15.5] * 11,
    [10.0],
    list(np.linspace(0.1, 15.9, 100)),
    [3.3] * 7,
]


@pytest.mark.parametrize("chunk", [1, 2, 5])
def test_matches_host_tree(chunk):
    qs = [0.1, 0.5, 0.9]
    device = _device_quantiles(PARTITIONS, qs, chunk)
    for p, vals in enumerate(PARTITIONS):
        host = _host_quantiles(vals, qs)
        np.testing.assert_allclose(device[p], host, atol=1e-3,
                                   err_msg=f"partition {p}")


def test_chunked_equals_unchunked():
    qs = [0.25, 0.75]
    np.testing.assert_allclose(_device_quantiles(PARTITIONS, qs, 2),
                               _device_quantiles(PARTITIONS, qs, 5),
                               atol=1e-3)


def test_empty_partition_stays_in_range():
    # An empty tree's quantile is noise-driven (like the host path); it must
    # still be a finite value inside [min, max] and not disturb neighbors.
    device = _device_quantiles([[], [5.0] * 20], [0.5], 2)
    assert MIN_V <= device[0][0] <= MAX_V
    assert np.isfinite(device[0][0])
    assert device[1][0] == pytest.approx(5.5, abs=0.2)


def test_monotone_across_unsorted_quantiles():
    device = _device_quantiles(PARTITIONS, [0.9, 0.1, 0.5], 5)
    for p in range(len(PARTITIONS)):
        assert device[p][1] <= device[p][2] <= device[p][0]


def test_lazy_descent_many_partitions():
    # P >> quantile_chunk routes to the lazy path: per-level [P, B] counts
    # instead of chunked dense histograms. Parity with the host tree must
    # hold across a few hundred random partitions — except where a target
    # lands exactly on a subtree boundary, where the descent direction is
    # legitimately noise-driven (same caveat as the curated PARTITIONS); so
    # we require exact host agreement on >=90% of partitions and
    # leaf-resolution agreement with the true quantile everywhere.
    rng = np.random.default_rng(0)
    partitions = [
        list(rng.uniform(0.5, 15.5, size=rng.integers(5, 40)))
        for _ in range(300)
    ]
    qs = [0.25, 0.5, 0.9]
    device = _device_quantiles(partitions, qs, chunk=8)
    leaf_width = (MAX_V - MIN_V) / 16
    exact = 0
    for p, vals in enumerate(partitions):
        host = _host_quantiles(vals, qs)
        if np.allclose(device[p], host, atol=1e-3):
            exact += 1
        # The tree's value must land (to leaf resolution) between the
        # order statistic at q and the next one — exact boundary ties can
        # legitimately resolve to either side.
        svals = np.sort(vals)
        for qi, q in enumerate(qs):
            k = min(int(np.ceil(q * len(svals))) - 1, len(svals) - 1)
            lo = svals[max(k, 0)] - 2.5 * leaf_width
            hi = svals[min(k + 1, len(svals) - 1)] + 2.5 * leaf_width
            assert lo <= device[p][qi] <= hi, (p, q, device[p][qi], lo, hi)
    assert exact >= 270, f"only {exact}/300 partitions matched host exactly"


def test_lazy_descent_secure_noise():
    # The lazy path's per-node noise goes through the snapped table sampler
    # in secure mode; at tiny std the released quantiles still match.
    import dataclasses
    import jax
    from pipelinedp_tpu.ops import secure_noise

    cfg = _make_cfg(len(PARTITIONS), (0.5,), chunk=2)
    cfg = dataclasses.replace(cfg, secure=True)
    n_leaves = cfg.branching**cfg.tree_height
    pks, leaves = [], []
    for p, vals in enumerate(PARTITIONS):
        for v in vals:
            pks.append(p)
            leaves.append(
                min(int((v - MIN_V) / (MAX_V - MIN_V) * n_leaves),
                    n_leaves - 1))
    qrows = (jnp.asarray(pks, dtype=jnp.int32),
             jnp.asarray(leaves, dtype=jnp.int32),
             jnp.ones(len(pks), dtype=bool))
    stds = np.asarray([1e-6])
    thr_hi, thr_lo, gran = secure_noise.build_tables(stds, NoiseKind.LAPLACE)
    out = executor.quantile_outputs(
        qrows, MIN_V, MAX_V, jnp.asarray(stds), jax.random.PRNGKey(0), cfg,
        secure_tables=(jnp.asarray(thr_hi), jnp.asarray(thr_lo),
                       jnp.asarray(gran)))
    for p, vals in enumerate(PARTITIONS):
        host = _host_quantiles(vals, [0.5])
        assert np.asarray(out["q0"])[p] == pytest.approx(host[0], abs=0.05)


def test_noise_std_shared_with_host():
    # The kernel's std comes from the same helper the host tree uses.
    std = quantile_tree.per_level_noise_std(2.0, 1e-6, 3, 4, 4,
                                            NoiseKind.LAPLACE)
    assert std == pytest.approx(np.sqrt(2.0) * (3 * 4) / (2.0 / 4))


# --- The served lazy descent against the scatter form it replaced. ---


def scatter_form_lazy_quantile_outputs(qrows, min_v, max_v, stds, key, cfg,
                                       psum_axis=None, secure_tables=None):
    """The lazy descent as it was served until PR 38, the REFERENCE: every
    level of every quantile gathers parent[row_pk] for every row and
    scatter-adds the rows under it into the [P, B] children. Same keys,
    same noise, same _descend_trees; only where the counts come from
    differs. tests/test_sharded.py puts it in executor's place."""
    row_pk, row_leaf, row_keep = qrows
    B, h = cfg.branching, cfg.tree_height
    P = cfg.n_partitions
    f = executor._ftype()
    i32 = jnp.int32
    qidx = executor.quantile_std_index(cfg.plan)
    std = stds[qidx].astype(f)
    plan_names = next(e.outputs for e in cfg.plan if e.kind == 'quantiles')
    arange_b = jnp.arange(B, dtype=i32)
    partition_ids = jnp.arange(P, dtype=i32)

    def noisy_children(level, parent, within):
        del within  # every level passes over all the rows again
        shift = B**(h - level)
        row_node = (row_leaf // shift).astype(i32)
        par = parent[jnp.minimum(row_pk, P - 1)]
        in_path = row_keep & (row_node // B == par) & (row_pk < P)
        seg = jnp.where(in_path, row_pk * B + (row_node % B), P * B)
        counts = jax.ops.segment_sum(in_path.astype(i32), seg,
                                     num_segments=P * B + 1)[:P * B].reshape(
                                         P, B)
        if psum_axis is not None:
            counts = jax.lax.psum(counts, psum_axis)
        node_ids = (parent * B)[:, None] + arange_b
        keys = executor._node_noise_keys(jax.random.fold_in(key, level),
                                         node_ids, partition_ids)
        noisy = executor._noisy_node_counts(counts, keys, std, cfg,
                                            secure_tables, qidx)
        return jnp.maximum(noisy, 0.0), None

    per_partition = executor._descend_trees(noisy_children, P, min_v, max_v,
                                            cfg)
    return {
        name: per_partition[:, j].astype(f)
        for j, name in enumerate(plan_names)
    }


def _layout(name, P, n_leaves, n=4000, seed=0):
    """(row_pk, row_leaf, row_keep) of one row layout."""
    rng = np.random.default_rng(seed)
    pk = rng.integers(0, P, n)
    # Few distinct values a partition, as ratings have, plus a spread.
    leaf = np.where(rng.random(n) < 0.5,
                    rng.integers(0, 5, n) * (n_leaves // 5),
                    rng.integers(0, n_leaves, n))
    keep = rng.random(n) < 0.7
    if name == "all_kept":
        keep[:] = True
    elif name == "none_kept":
        keep[:] = False
    elif name == "one_partition":
        pk[:] = P // 3
    elif name == "empty_partitions":
        pk = (pk // 4) * 4  # three partitions in four hold no row
    elif name == "pad_rows":
        # Sentinel and beyond, kept or not: never counted.
        pk[::3] = P
        pk[1::7] = P + 5
    elif name == "edge_leaves":
        leaf = np.where(rng.random(n) < 0.5, 0, n_leaves - 1)
    else:
        assert name == "mixed", name
    return (jnp.asarray(pk, dtype=jnp.int32),
            jnp.asarray(leaf, dtype=jnp.int32), jnp.asarray(keep))


def _both_forms(cfg, qrows, std=2.0):
    """Served and reference percentiles of one launch, noise ON, one key."""
    stds = jnp.asarray([std])
    secure_tables = None
    if cfg.secure:
        from pipelinedp_tpu.ops import secure_noise
        secure_tables = tuple(
            jnp.asarray(t)
            for t in secure_noise.build_tables(np.asarray([std]),
                                               cfg.noise_kind))
    assert executor._lazy_quantiles(cfg)
    out = []
    for form in (executor.quantile_outputs,
                 scatter_form_lazy_quantile_outputs):
        run = jax.jit(
            functools.partial(form, min_v=MIN_V, max_v=MAX_V, cfg=cfg))
        out.append(
            run(qrows, stds=stds, key=jax.random.PRNGKey(11),
                secure_tables=secure_tables))
    return out


def _assert_same_release(served, reference, cfg):
    assert sorted(served) == sorted(reference)
    for name in served:
        got, want = np.asarray(served[name]), np.asarray(reference[name])
        assert got.shape == (cfg.n_partitions,)
        np.testing.assert_array_equal(got, want, err_msg=name)


@pytest.mark.parametrize("noise", ["laplace", "gaussian", "secure"])
@pytest.mark.parametrize("layout", [
    "all_kept", "none_kept", "one_partition", "empty_partitions", "pad_rows",
    "edge_leaves"
])
def test_sorted_descent_releases_what_the_scatter_form_did(layout, noise):
    """Noise on, a fixed key: the served descent's percentiles are the
    scatter form's bit for bit, because its counts are the same integers
    and every node keeps its key."""
    P = 40  # five chunks of 8
    cfg = _make_cfg(P, (0.9, 0.1, 0.5), chunk=8,
                    noise_kind=(NoiseKind.GAUSSIAN if noise == "gaussian"
                                else NoiseKind.LAPLACE),
                    secure=noise == "secure")
    qrows = _layout(layout, P, cfg.branching**cfg.tree_height)
    served, reference = _both_forms(cfg, qrows)
    _assert_same_release(served, reference, cfg)
    if layout != "none_kept":
        # The noise is on: two partitions' answers differ.
        assert len(np.unique(np.asarray(served["q0"]))) > 1


@pytest.mark.parametrize("quantiles", [(0.5,), (0.9, 0.1, 0.5)],
                         ids=["one_quantile", "three_quantiles"])
@pytest.mark.parametrize("shape", [
    dict(P=40, chunk=8, branching=4, height=2),
    dict(P=3000, chunk=8, branching=4, height=2),
    dict(P=600, chunk=512, branching=16, height=4),
    dict(P=2100, chunk=1, branching=16, height=5),
], ids=["above_one_chunk", "far_above_one_chunk", "default_tree",
        "key_does_not_fit_int32"])
def test_sorted_descent_equals_scatter_form_across_shapes(shape, quantiles):
    """P just above and far above one chunk, the default tree, and a tree
    whose (partition, leaf) pairs do not fit one int32 key (the two-key
    sort): the same release as the scatter form in each."""
    P = shape["P"]
    cfg = _make_cfg(P, quantiles, shape["chunk"], shape["branching"],
                    shape["height"])
    n_leaves = cfg.branching**cfg.tree_height
    fits = (P + 1) * n_leaves <= 2**31
    assert fits == (shape["height"] < 5)
    qrows = _layout("mixed", P, n_leaves, n=6000, seed=P)
    served, reference = _both_forms(cfg, qrows)
    _assert_same_release(served, reference, cfg)


def test_first_at_least_is_searchsorted_inside_each_range():
    """The descent's one search primitive against numpy: per range, the
    first position whose element reaches the bound; empty ranges, ranges
    at the column's end and bounds beyond every element included."""
    rng = np.random.default_rng(3)
    col = np.sort(rng.integers(0, 50, 500)).astype(np.int32)
    lo = rng.integers(0, 501, 64)
    hi = np.minimum(lo + rng.integers(0, 200, 64), 500)
    hi[:4], lo[:4] = 500, 500  # closed at the very end
    bound = rng.integers(-2, 60, (64, 7)).astype(np.int32)
    got = executor._first_at_least(jnp.asarray(col),
                                   jnp.asarray(lo, jnp.int32)[:, None],
                                   jnp.asarray(hi, jnp.int32)[:, None],
                                   jnp.asarray(bound))
    want = np.stack([
        lo[i] + np.searchsorted(col[lo[i]:hi[i]], bound[i], side="left")
        for i in range(64)
    ])
    np.testing.assert_array_equal(np.asarray(got), want)


def test_counters_of_the_two_quantile_paths():
    """One pass over the rows on either path; node searches only where
    the descent is lazy: quantiles x height x P x (B - 1)."""
    lazy = _make_cfg(600, (0.5, 0.9), chunk=512, branching=16, height=4)
    dense = _make_cfg(512, (0.5, 0.9), chunk=512, branching=16, height=4)
    none = dataclasses.replace(lazy, quantiles=())
    assert [executor.quantile_row_passes(c) for c in (lazy, dense, none)
           ] == [1, 1, 0]
    assert [executor.quantile_node_searches(c) for c in (lazy, dense, none)
           ] == [2 * 4 * 600 * 15, 0, 0]
