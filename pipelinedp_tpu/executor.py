"""Fused columnar DP aggregation executor.

This is the TPU replacement for the reference's interpreted op graph
(dp_engine.py:101-176): contribution bounding, per-partition combining,
private partition selection and noise run as ONE jit-compiled XLA program
over columnar arrays:

    rows (pid, pk, value)
      -> sort by (pid, pair_hash, pk, u)  # ONE payload-carrying sort
      -> Linf bounding: row rank < max_contributions_per_partition
      -> L0 bounding: pair rank < l0      # scans over hash-ordered pairs
      -> sort by kept-pk                  # partition grouping
      -> per-partition dense columns      # cumsum-diff at boundaries
      -> DP partition selection           # closed-form keep probs + Bernoulli
      -> noise, metric formulas           # vectorized, stds are traced inputs

The three shuffles of the reference (SURVEY.md §3.1) become two
payload-carrying sorts with scan-based ranking in between — no gathers, no
scatters, no host round-trips, no per-partition C++ calls (TPU scatters and
gathers at 33M-row scale cost ~0.3-0.5s each; sorts with payloads ~0.3s
total, scans ~ms).

The program is split in two phases so the multi-chip path
(parallel/sharded.py) can insert a psum between them:

    partial_columns(rows_shard)  -> dense per-partition partial columns
    [lax.psum over the mesh]
    finalize(columns)            -> selection + noise + metric formulas

Budget laziness: noise stddevs and selection (eps, delta) enter as *traced*
scalars, so BudgetAccountant.compute_budgets() may run after compilation;
the engine wraps execution in a lazy generator that runs on first iteration.
"""

import contextlib
import dataclasses
import functools
import hashlib
import logging
import math
import threading
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pipelinedp_tpu import columnar
from pipelinedp_tpu import combiners as dp_combiners
from pipelinedp_tpu import dp_computations
from pipelinedp_tpu import numeric as rt_numeric
from pipelinedp_tpu.aggregate_params import (AggregateParams, MechanismType,
                                             Metrics, NoiseKind, NormKind)
from pipelinedp_tpu.ops import noise as noise_ops
from pipelinedp_tpu.ops import secure_noise
from pipelinedp_tpu.ops import segment_ops
from pipelinedp_tpu.ops import selection_ops
from pipelinedp_tpu.runtime import aot as rt_aot
from pipelinedp_tpu.runtime import faults as rt_faults
from pipelinedp_tpu.runtime import observability as rt_observability
from pipelinedp_tpu.runtime import pipeline as rt_pipeline
from pipelinedp_tpu.runtime import telemetry as rt_telemetry
from pipelinedp_tpu.runtime import trace as rt_trace
from pipelinedp_tpu.runtime import watchdog as rt_watchdog


def _ftype():
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


@dataclass(frozen=True)
class MetricPlanEntry:
    """Static description of one child combiner's device computation."""
    kind: str  # count | privacy_id_count | sum | mean | variance
    outputs: Tuple[str, ...]  # metric names in the child's output order
    n_stds: int  # number of noise stddevs the entry consumes
    # Several value columns (AggregateParams.value_columns): the column of
    # values[n, d] a sum / mean entry reads and the label its released
    # fields carry; -1 = the one scalar column.
    column: int = -1
    label: str = ''

    @property
    def released(self) -> Tuple[str, ...]:
        """The released field names, in output order."""
        if self.column < 0:
            return self.outputs
        return tuple(dp_combiners.column_field_name(self.label, o)
                     for o in self.outputs)

    def col(self, name: str) -> str:
        """The reduce column `name` ('sum' / 'nsum') of this entry's
        value column."""
        return name if self.column < 0 else f'{name}{self.column}'


@dataclass(frozen=True)
class KernelConfig:
    """Hashable static configuration of the fused kernel."""
    n_partitions: int
    linf: int  # 0 = no per-partition row sampling
    l0: int  # 0 = no cross-partition pair sampling
    total_bound: int  # max_contributions (0 = unset)
    sample_per_partition: bool
    clip_per_value: bool
    clip_pair_sum: bool
    bounds_enforced: bool
    noise_kind: NoiseKind
    private_selection: bool
    selection: Optional[selection_ops.SelectionParams]
    max_rows_per_privacy_id: int
    plan: Tuple[MetricPlanEntry, ...]
    degenerate_range: bool  # min_value == max_value
    # Vector-sum mode: values are (n, vector_size) rows; the final
    # per-partition vector is clipped to the norm ball and noised
    # per-coordinate (reference combiners.py:742-788 semantics).
    vector_size: int = 0  # 0 = scalar values
    vector_max_norm: float = 0.0
    vector_norm_kind: Optional[NormKind] = None
    # Percentile mode: DP quantiles from a per-partition dense hierarchical
    # histogram — the device form of ops/quantile_tree.DenseQuantileTree
    # (leaf scatter-add = add_entries, psum = merge, per-level noise +
    # vectorized descent = compute_quantiles).
    quantiles: Tuple[float, ...] = ()
    tree_height: int = 0
    branching: int = 0
    quantile_chunk: int = 0  # partitions per histogram chunk (memory bound)
    # Secure release mode: snapped grid + discrete table-sampled noise
    # (ops/secure_noise.py) instead of continuous f32 draws — the device
    # counterpart of the reference's PyDP snapped mechanisms
    # (dp_computations.py:131-152).
    secure: bool = False
    # Accumulation discipline: "fast" is the historical f32
    # chunked-cumsum path (bit-identical to every pre-existing release);
    # "safe" accumulates segment sums through a compensated double-word
    # scan (ops/segment_ops.compensated_cumsum) — exact for
    # integer-valued contributions up to ~2^48 per partition — and arms
    # the release sentinel's overflow classification
    # (pipelinedp_tpu/numeric.py).
    numeric_mode: str = "fast"

    @property
    def value_columns(self) -> int:
        """Several scalar value columns bounded in one pass
        (AggregateParams.value_columns): values are (n, value_columns)
        rows, the traced min_v / max_v / mid are [value_columns] arrays and
        each column has the one sum / mean plan entry that names it.
        0 = the one scalar column (or vector mode)."""
        return sum(entry.column >= 0 for entry in self.plan)


SUPPORTED_COLUMNAR_METRICS = (Metrics.COUNT, Metrics.PRIVACY_ID_COUNT,
                              Metrics.SUM, Metrics.MEAN, Metrics.VARIANCE,
                              Metrics.VECTOR_SUM)


def supports(params: AggregateParams) -> bool:
    """Whether the fused columnar path can run this aggregation."""
    if params.custom_combiners:
        return False
    if (Metrics.VECTOR_SUM in params.metrics and
            any(m.is_percentile for m in params.metrics)):
        return False  # degenerate combination; generic path decides
    return True


def build_plan(
        compound: dp_combiners.CompoundCombiner
) -> Tuple[MetricPlanEntry, ...]:
    """Builds the static metric plan from a CompoundCombiner's children."""
    plan = []
    for child in compound.combiners:
        child, column, label = dp_combiners.unwrap_column(child)
        if isinstance(child, dp_combiners.CountCombiner):
            plan.append(MetricPlanEntry('count', ('count',), 1))
        elif isinstance(child, dp_combiners.PrivacyIdCountCombiner):
            plan.append(
                MetricPlanEntry('privacy_id_count', ('privacy_id_count',), 1))
        elif isinstance(child, dp_combiners.SumCombiner):
            plan.append(MetricPlanEntry('sum', ('sum',), 1, column, label))
        elif isinstance(child, dp_combiners.MeanCombiner):
            names = child.metrics_names()
            outputs = ['mean'] + [m for m in ('count', 'sum') if m in names]
            plan.append(
                MetricPlanEntry('mean', tuple(outputs), 2, column, label))
        elif isinstance(child, dp_combiners.VarianceCombiner):
            # True output order = VarianceCombiner.compute_metrics insertion
            # order (variance, then count/sum/mean as requested).
            names = child.metrics_names()
            outputs = ['variance'] + [
                m for m in ('count', 'sum', 'mean') if m in names
            ]
            plan.append(MetricPlanEntry('variance', tuple(outputs), 3))
        elif isinstance(child, dp_combiners.VectorSumCombiner):
            plan.append(MetricPlanEntry('vector_sum', ('vector_sum',), 1))
        elif isinstance(child, dp_combiners.QuantileCombiner):
            plan.append(
                MetricPlanEntry('quantiles', tuple(child.metrics_names()), 1))
        else:
            raise NotImplementedError(
                f"Combiner {type(child).__name__} has no columnar lowering")
    return tuple(plan)


def compute_noise_stds(compound: dp_combiners.CompoundCombiner,
                       params: AggregateParams) -> np.ndarray:
    """Noise stddevs for every plan entry, in plan order.

    Must be called after BudgetAccountant.compute_budgets(): mechanisms are
    materialized from the (now filled) specs. The result feeds the kernel as
    a traced array — the budget two-phase protocol on device.
    """
    stds: List[float] = []
    for child in compound.combiners:
        child, _, _ = dp_combiners.unwrap_column(child)
        if isinstance(
                child,
            (dp_combiners.CountCombiner, dp_combiners.PrivacyIdCountCombiner,
             dp_combiners.SumCombiner)):
            stds.append(child.get_mechanism().std)
        elif isinstance(child, dp_combiners.MeanCombiner):
            mech = child.get_mechanism()
            stds.append(mech.count_mechanism.std)
            stds.append(mech.sum_mechanism.std)
        elif isinstance(child, dp_combiners.VarianceCombiner):
            stds.extend(_variance_stds(child, params))
        elif isinstance(child, dp_combiners.VectorSumCombiner):
            stds.append(
                dp_computations.vector_noise_std(
                    child._params.additive_vector_noise_params))
        elif isinstance(child, dp_combiners.QuantileCombiner):
            from pipelinedp_tpu.ops import quantile_tree as qt_ops
            stds.append(
                qt_ops.per_level_noise_std(
                    child._params.eps, child._params.delta,
                    params.max_partitions_contributed,
                    params.max_contributions_per_partition,
                    child._tree_height, params.noise_kind))
        else:
            raise NotImplementedError(type(child))
    return np.asarray(stds, dtype=np.float64)


def compute_noise_sensitivities(compound: dp_combiners.CompoundCombiner,
                                params: AggregateParams) -> np.ndarray:
    """Per-slot norm sensitivities, in the same order as compute_noise_stds
    (l1 for Laplace slots, l2 for Gaussian) — consumed by the secure-noise
    grid calibration, which must compensate the +1 grid-unit sensitivity
    snapping introduces."""
    sens: List[float] = []
    for child in compound.combiners:
        child, _, _ = dp_combiners.unwrap_column(child)
        if isinstance(
                child,
            (dp_combiners.CountCombiner, dp_combiners.PrivacyIdCountCombiner,
             dp_combiners.SumCombiner)):
            sens.append(child.get_mechanism().sensitivity)
        elif isinstance(child, dp_combiners.MeanCombiner):
            mech = child.get_mechanism()
            sens.append(mech.count_mechanism.sensitivity)
            sens.append(mech.sum_mechanism.sensitivity)
        elif isinstance(child, dp_combiners.VarianceCombiner):
            sens.extend(
                dp_computations.compute_dp_var_noise_sensitivities(
                    params.max_partitions_contributed,
                    params.max_contributions_per_partition, params.min_value,
                    params.max_value, params.noise_kind))
        elif isinstance(child, dp_combiners.VectorSumCombiner):
            sens.append(
                dp_computations.vector_noise_sensitivity(
                    child._params.additive_vector_noise_params))
        elif isinstance(child, dp_combiners.QuantileCombiner):
            # Per tree level each privacy id touches <= l0 partitions x linf
            # rows, one node per row: l1 = l0*linf (Laplace), l2 =
            # sqrt(l0)*linf (Gaussian) — matching per_level_noise_std's
            # calibration.
            l0 = params.max_partitions_contributed
            linf = params.max_contributions_per_partition
            if params.noise_kind == NoiseKind.LAPLACE:
                sens.append(float(l0 * linf))
            else:
                sens.append(math.sqrt(l0) * linf)
        else:
            raise NotImplementedError(type(child))
    return np.asarray(sens, dtype=np.float64)


def _variance_stds(child: dp_combiners.VarianceCombiner,
                   params: AggregateParams) -> List[float]:
    """The three noise stds of compute_dp_var (shared helper, so the TPU
    path can never diverge from the host calibration)."""
    return list(
        dp_computations.compute_dp_var_noise_stds(
            child._params.eps, child._params.delta,
            params.max_partitions_contributed,
            params.max_contributions_per_partition, params.min_value,
            params.max_value, params.noise_kind))


def _leaf_indices(values, min_v, max_v, n_leaves: int):
    """Quantile-tree leaf index per value (DenseQuantileTree._leaf_index)."""
    span = max_v - min_v
    frac = (values - min_v) / jnp.where(span > 0, span, 1.0)
    return jnp.clip((frac * n_leaves).astype(jnp.int32), 0, n_leaves - 1)


def _hash_mix(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 finalizer: uint32 -> well-mixed uint32."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _pair_hash(pid, pk, key: jax.Array):
    """Salted uniform hash of (pid, pk) — the per-pair sampling rank.

    Ranking a privacy unit's pairs by this hash is a uniform permutation of
    its partitions (counter-based analogue of the reference's RNG sampling,
    contribution_bounders.py:87-92), with no second sort and no scatter.

    Returns two independent u32 lanes (64 bits total). A single 32-bit lane
    collides at the birthday bound (~2^16 pairs per privacy unit), and the
    deterministic pk tie-break would then systematically favor low partition
    ids; the second lane makes collided pairs order uniformly.
    """
    salts = jax.random.bits(key, (4,), jnp.uint32)
    h = _hash_mix(pid.astype(jnp.uint32) * jnp.uint32(0x9E3779B9) + salts[0])
    lane0 = _hash_mix(h ^ _hash_mix(pk.astype(jnp.uint32) + salts[1]))
    h2 = _hash_mix(pid.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B) + salts[2])
    lane1 = _hash_mix(h2 ^ _hash_mix(pk.astype(jnp.uint32) + salts[3]))
    return lane0, lane1


def _sort_rows(keys, payloads):
    """One lax.sort carrying payload columns (no post-sort gathers)."""
    out = jax.lax.sort(tuple(keys) + tuple(payloads), num_keys=len(keys))
    return out[:len(keys)], out[len(keys):]


def bounded_row_columns(pid: jnp.ndarray, pk: jnp.ndarray,
                        values: jnp.ndarray, valid: jnp.ndarray, min_v, max_v,
                        min_s, max_s, mid, rows_key: jax.Array,
                        cfg: KernelConfig):
    """Phase 1a: contribution bounding -> per-row reduction columns.

    Returns (spk, keep_row, pair_start, reduce_cols, qrows): the bounded row
    stream in (pid, pair-hash) sort order. Independent of the partition-axis
    size except as an invalid-row sentinel — this is the seam the blocked
    large-partition-space path (parallel/large_p.py) splits at, resuming the
    reduction per partition block.

    TPU-shaped plan (scatter/gather-free hot path): ONE payload-carrying
    sort by (pid, pair_hash, pk, row_rand). Pairs are then contiguous,
    ordered within each pid by a salted uniform hash — so cross-partition
    (L0) bounding is just "pair rank < l0", computed with scans; Linf
    bounding is "row rank < linf" within the pair. No pair slots are
    materialized, no scatter-back.
    """
    f = _ftype()
    n = pid.shape[0]
    P = cfg.n_partitions
    i32 = jnp.int32
    values = values.astype(f)
    key_total, key_linf, key_l0 = jax.random.split(rows_key, 3)

    vector = bool(cfg.vector_size)
    width = cfg.vector_size or cfg.value_columns  # 0 = one scalar column
    # Single source of truth for which reduce columns exist; out-of-band
    # assemblers (parallel/large_p.py) read the same list.
    col_names = reduce_column_names(cfg)
    need_sum = 'sum' in col_names
    need_nsum = 'nsum' in col_names
    need_nsum2 = 'nsum2' in col_names

    pk_sent = jnp.where(valid, pk, P).astype(i32)
    pid_sent = jnp.where(valid, pid, jnp.iinfo(i32).max).astype(i32)

    def value_cols(vals):
        return [vals[:, d] for d in range(width)] if width else [vals]

    def from_cols(cols_):
        return jnp.stack(cols_, axis=1) if width else cols_[0]

    if cfg.bounds_enforced:
        # No privacy ids: every row is its own contribution group; no
        # bounding sorts — straight to the partition reduction.
        spk, sval, new_pair = pk_sent, values, valid
        keep_row = valid
        pair_start = keep_row
    else:
        pid_in, pk_in, vcols_in, valid_in = (pid_sent, pk_sent,
                                             value_cols(values), valid)
        if cfg.total_bound:
            # Total-contribution bounding: uniform <=K subset of each pid's
            # rows, ranked by one sort over (pid, rand).
            rand0 = jax.random.uniform(key_total, (n,))
            (spid0, _), pay0 = _sort_rows([pid_in, rand0],
                                          [pk_in] + vcols_in + [valid_in])
            new_pid0 = segment_ops.boundary_mask(spid0)
            _, rank0 = segment_ops.segment_starts_and_ids(new_pid0)
            valid0 = pay0[-1] & (rank0 < cfg.total_bound)
            pid_in = jnp.where(valid0, spid0, jnp.iinfo(i32).max)
            pk_in = jnp.where(valid0, pay0[0], P)
            vcols_in = list(pay0[1:-1])
            valid_in = valid0

        # The one bounding sort: (pid, pair_hash64, pk, row_rand) + payloads.
        hpair0, hpair1 = _pair_hash(pid_in, pk_in, key_l0)
        rand = jax.random.uniform(key_linf, (n,))
        (spid, _, _, spk, _), pay = _sort_rows(
            [pid_in, hpair0, hpair1, pk_in, rand], vcols_in + [valid_in])
        sval = from_cols(pay[:-1])
        svalid = pay[-1]
        new_pair = segment_ops.boundary_mask(spid, spk)
        _, rank = segment_ops.segment_starts_and_ids(new_pair)
        if cfg.sample_per_partition and cfg.linf:
            row_mask = svalid & (rank < cfg.linf)
        else:
            row_mask = svalid
        if cfg.l0:
            new_pid = segment_ops.boundary_mask(spid)
            pair_rank = segment_ops.segment_rank_of_segments(new_pair, new_pid)
            keep_row = row_mask & (pair_rank < cfg.l0)  # pair_rank is 0-based
        else:
            keep_row = row_mask
        pair_start = new_pair & keep_row

    qrows = None
    if cfg.quantiles:
        leaf = _leaf_indices(sval, min_v, max_v,
                             cfg.branching**cfg.tree_height)
        qrows = (spk, leaf, keep_row)

    # --- Contribution columns (Linf value/pair-sum clipping regimes). ---
    if cfg.value_columns:
        # Several scalar columns, ONE sample: each clamped to its own range
        # (min_v / max_v / mid are [value_columns] arrays).
        reduce_cols = {}
        for entry in cfg.plan:
            if entry.column < 0:
                continue
            j = entry.column
            clipped = jnp.clip(sval[:, j], min_v[j], max_v[j])
            if entry.kind == 'sum':
                reduce_cols[entry.col('sum')] = jnp.where(
                    keep_row, clipped, 0.0)
            else:
                reduce_cols[entry.col('nsum')] = jnp.where(
                    keep_row, clipped - mid[j], 0.0)
    elif vector:
        vcontrib = jnp.where(keep_row[:, None], sval, 0.0)
        reduce_cols = {'v%d' % d: vcontrib[:, d]
                       for d in range(cfg.vector_size)}
    else:
        clipped = jnp.clip(sval, min_v, max_v) if cfg.clip_per_value else sval
        contrib = jnp.where(keep_row, clipped, 0.0)
        if cfg.clip_pair_sum:
            if cfg.bounds_enforced:
                contrib = jnp.clip(contrib, min_s, max_s)
            else:
                # Per-(pid, pk) sum clipping: pair totals via cumsum
                # differences at pair boundaries, re-emitted once per pair.
                c = segment_ops.chunked_cumsum(contrib)
                cpad = jnp.concatenate([jnp.zeros(1, c.dtype), c])
                starts_row = segment_ops.segment_start_positions(new_pair)
                ends_row = segment_ops.next_segment_start(new_pair)
                pair_total = cpad[ends_row] - cpad[starts_row]
                contrib = jnp.where(pair_start,
                                    jnp.clip(pair_total, min_s, max_s), 0.0)
        reduce_cols = {}
        if need_sum:
            reduce_cols['sum'] = contrib
        if need_nsum:
            ncontrib = jnp.where(keep_row, clipped - mid, 0.0)
            reduce_cols['nsum'] = ncontrib
            if need_nsum2:
                reduce_cols['nsum2'] = ncontrib * ncontrib
    return spk, keep_row, pair_start, reduce_cols, qrows


def reduce_column_names(cfg: KernelConfig) -> List[str]:
    """The reduce_cols keys bounded_row_columns emits for this config —
    callers that assemble row columns out-of-band (the blocked large-P path
    on empty inputs) build them from here, not from observed outputs."""
    if cfg.vector_size:
        return ['v%d' % d for d in range(cfg.vector_size)]
    if cfg.value_columns:
        return [e.col('sum' if e.kind == 'sum' else 'nsum')
                for e in cfg.plan if e.column >= 0]
    names = []
    if any(e.kind == 'sum' for e in cfg.plan):
        names.append('sum')
    if any(e.kind in ('mean', 'variance') for e in cfg.plan):
        names.append('nsum')
    if any(e.kind == 'variance' for e in cfg.plan):
        names.append('nsum2')
    return names


def offsets_by_histogram(n_rows: int, n_partitions: int) -> bool:
    """Whether a segment reduce of `n_rows` rows onto `n_partitions`
    partitions finds its partition offsets by one histogram rather than
    by binary search: true when the search's gathered elements,
    (P + 1) x ceil(log2(n_rows + 1)), outnumber the rows the histogram's
    scatter-add touches (on the chip the two cost 6.7 and 7.0 ns an
    element: PERF.md §6, PRs 38 and 40). Both shapes are static at trace time. A
    blocked block program (1,179,648 rows onto 2^20) lies on the
    histogram's side; every dense release of the cells (2^24 rows onto
    17,770) on the search's."""
    return (n_partitions + 1) * int(n_rows).bit_length() > n_rows


def record_offset_histograms(rows, n_partitions: int,
                             n_shards: int = 1) -> None:
    """Counts one launch whose segment reduce takes the histogram form
    (offsets_by_histogram); a launch on the search side counts nothing.

    `rows`: the launch's row column as the caller holds it — its last axis
    the rows (a leading axis, the lanes of a batched launch), split evenly
    over `n_shards` on a mesh — or, for a blocked block program, its static
    row capacity."""
    n_rows = np.shape(rows)[-1] if np.ndim(rows) else int(rows)
    if offsets_by_histogram(n_rows // n_shards, n_partitions):
        rt_telemetry.record("segment_offset_histograms")


def _segment_offsets(spk2, n_partitions: int, by_histogram: bool):
    """int32[P + 1] start of each partition's run in the sorted key
    column `spk2` (= searchsorted(spk2, arange(P + 1), side='left')).

    The histogram form counts the rows of each key and takes the prefix
    sums: starts[k] = #{rows with key < k}, bit for bit the search's. A
    key outside [0, P) is clamped into the bin before 0 or the sentinel
    bin P, where the search puts it too; the clamp keeps the keys
    sorted."""
    i32 = jnp.int32
    P = n_partitions
    if not by_histogram:
        return jnp.searchsorted(spk2, jnp.arange(P + 1, dtype=i32),
                                side='left').astype(i32)
    bins = jnp.clip(spk2, -1, P) + 1
    counts = jnp.zeros(P + 2, i32).at[bins].add(1, indices_are_sorted=True)
    return jnp.cumsum(counts[:P + 1], dtype=i32)


def reduce_rows_to_partitions(spk, keep_row, pair_start, reduce_cols,
                              n_partitions: int, vector_size: int,
                              presorted: bool = False,
                              numeric_mode: str = "fast"):
    """Phase 1b: dense [0, n_partitions) partition columns from the bounded
    row stream.

    ONE payload-carrying sort by kept-partition id, then per-partition
    reductions as cumsum differences at each partition's row offset —
    counts are exact integers, float sums use a chunked cumsum to bound
    f32 rounding bias. Together with the bounding sort, the reference's
    three shuffles (SURVEY.md §3.1) cost two sorts total. The offsets come
    from a binary search or from one histogram, whichever the static
    shapes make cheaper (offsets_by_histogram); both give the same bits.

    `presorted`: the caller guarantees rows already arrive ordered by
    (keep_row desc, spk asc) — i.e. kept rows first, ascending partition —
    so the sort is skipped (the blocked large-P path compacts rows into
    exactly this order once and reuses it for every block).
    """
    f = _ftype()
    i32 = jnp.int32
    P = n_partitions
    key2 = jnp.where(keep_row, spk, P).astype(i32)
    names = list(reduce_cols)
    if presorted:
        spk2 = key2
        pay2 = [pair_start.astype(i32)] + [reduce_cols[m] for m in names]
    else:
        (spk2,), pay2 = _sort_rows([key2],
                                   [pair_start.astype(i32)] +
                                   [reduce_cols[m] for m in names])
    starts = _segment_offsets(spk2, P,
                              offsets_by_histogram(spk.shape[0], P))

    if numeric_mode == "safe":
        # Compensated double-word prefixes: segment sums exact for
        # integer-valued contributions to ~2^48 (vs 2^24 for plain f32),
        # ~1-2 ulp of a double accumulation for float contributions.
        def seg_reduce(col):
            hi, lo = segment_ops.compensated_cumsum(col)
            return segment_ops.compensated_segment_diff(
                hi, lo, starts).astype(f)
    else:
        def seg_reduce(col):
            cpad = jnp.concatenate(
                [jnp.zeros(1, col.dtype),
                 segment_ops.chunked_cumsum(col)])
            return (cpad[starts[1:]] - cpad[starts[:-1]]).astype(f)

    part_count = (starts[1:] - starts[:-1]).astype(f)
    part_pid_count = seg_reduce(pay2[0])
    cols = dict(count=part_count,
                pid_count=part_pid_count,
                row_count=part_pid_count)
    reduced = {m: seg_reduce(pay2[1 + j]) for j, m in enumerate(names)}
    if vector_size:
        cols['vsum'] = jnp.stack(
            [reduced['v%d' % d] for d in range(vector_size)], axis=1)
    else:
        cols.update(reduced)
    return cols


def partial_columns(pid: jnp.ndarray, pk: jnp.ndarray, values: jnp.ndarray,
                    valid: jnp.ndarray, min_v, max_v, min_s, max_s, mid,
                    rows_key: jax.Array, cfg: KernelConfig):
    """Phase 1: contribution bounding + per-partition partial columns.

    Runs per shard on the multi-chip path (each privacy unit's rows must be
    co-located on one shard). Returns (cols, qrows): a dict of f[P] dense
    columns (count / sum / nsum / nsum2 / pid_count / row_count) plus, in
    percentile mode, the bounded row stream (pk, tree_leaf, keep) feeding
    the per-partition quantile histograms (None otherwise).
    """
    # Named scopes are op metadata only (the north star's phase names):
    # a device trace then says which phase an HLO op belongs to.
    with jax.named_scope("bound_sort"):
        spk, keep_row, pair_start, reduce_cols, qrows = bounded_row_columns(
            pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
            rows_key, cfg)
    with jax.named_scope("segment_reduce"):
        cols = reduce_rows_to_partitions(spk, keep_row, pair_start,
                                         reduce_cols, cfg.n_partitions,
                                         cfg.vector_size,
                                         numeric_mode=cfg.numeric_mode)
    return cols, qrows


def _clip_rows_to_norm_ball(vecs, max_norm: float, norm_kind: NormKind):
    """Row-wise vector clipping, matching dp_computations._clip_vector."""
    kind = norm_kind.value
    if kind == "linf":
        return jnp.clip(vecs, -max_norm, max_norm)
    if kind in ("l1", "l2"):
        order = int(kind[-1])
        norms = jnp.linalg.norm(vecs, ord=order, axis=-1, keepdims=True)
        # norm == 0 -> vector is all-zero; scale value is then irrelevant.
        scale = jnp.minimum(1.0, max_norm / jnp.where(norms > 0, norms, 1.0))
        return vecs * scale
    raise NotImplementedError(f"Vector Norm of kind '{kind}' is not supported")


def finalize(cols, min_v, mid, stds: jnp.ndarray, final_key: jax.Array,
             cfg: KernelConfig, secure_tables=None):
    """Phase 2: DP partition selection + noise + metric formulas.

    On the multi-chip path `cols` are globally psum'd columns; this phase is
    computed identically on every shard (same key -> same results).

    secure_tables: (thr_hi (S, L) u32, thr_lo (S, L) u32, gran (S,)) built
    by secure_noise.build_tables — required when cfg.secure.
    """
    f = _ftype()
    key_sel, key_noise = jax.random.split(final_key, 2)
    part_row_count = cols['row_count']
    P = cfg.n_partitions

    if cfg.private_selection:
        est = jnp.ceil(part_row_count / cfg.max_rows_per_privacy_id).astype(
            jnp.int64 if jax.config.jax_enable_x64 else jnp.int32)
        keep = selection_ops.sample_keep_decisions(key_sel, est, cfg.selection)
    else:
        keep = jnp.ones(P, dtype=bool)

    if cfg.secure and secure_tables is None:
        raise ValueError("cfg.secure requires secure_tables "
                         "(secure_noise.build_tables)")

    outputs = {}
    std_offset = 0
    for i, entry in enumerate(cfg.plan):
        ekey = jax.random.fold_in(key_noise, i)
        kind = cfg.noise_kind

        def noised(col, std_idx, subkey_idx):
            subkey = jax.random.fold_in(ekey, subkey_idx)
            if cfg.secure:
                thr_hi, thr_lo, gran = secure_tables
                return secure_noise.snapped_noisy(col.astype(f), subkey,
                                                  thr_hi[std_idx],
                                                  thr_lo[std_idx],
                                                  gran[std_idx])
            return col + noise_ops.additive_noise(subkey, col.shape,
                                                  stds[std_idx].astype(f),
                                                  kind)

        if entry.kind == 'count':
            outputs['count'] = noised(cols['count'], std_offset, 0)
        elif entry.kind == 'privacy_id_count':
            outputs['privacy_id_count'] = noised(cols['pid_count'],
                                                 std_offset, 0)
        elif entry.kind == 'sum':
            outputs[entry.released[0]] = noised(cols[entry.col('sum')],
                                                std_offset, 0)
        elif entry.kind == 'mean':
            dp_count = noised(cols['count'], std_offset, 0)
            dp_nsum = noised(cols[entry.col('nsum')], std_offset + 1, 1)
            denom = jnp.maximum(1.0, dp_count)
            dp_mean = (mid if entry.column < 0 else
                       mid[entry.column]) + dp_nsum / denom
            named = dict(zip(entry.outputs, entry.released))
            outputs[named['mean']] = dp_mean
            if 'count' in entry.outputs:
                outputs['count'] = dp_count
            if 'sum' in entry.outputs:
                outputs[named['sum']] = dp_mean * dp_count
        elif entry.kind == 'vector_sum':
            clipped_vsum = _clip_rows_to_norm_ball(cols['vsum'],
                                                   cfg.vector_max_norm,
                                                   cfg.vector_norm_kind)
            outputs['vector_sum'] = noised(clipped_vsum, std_offset, 0)
        elif entry.kind == 'quantiles':
            pass  # computed from the row stream by quantile_outputs()
        elif entry.kind == 'variance':
            dp_count = noised(cols['count'], std_offset, 0)
            denom = jnp.maximum(1.0, dp_count)
            if cfg.degenerate_range:
                dp_nmean = jnp.full_like(cols['count'], min_v)
                dp_nsqmean = dp_nmean * dp_nmean
            else:
                dp_nmean = noised(cols['nsum'], std_offset + 1, 1) / denom
                dp_nsqmean = noised(cols['nsum2'], std_offset + 2, 2) / denom
            variance = dp_nsqmean - dp_nmean * dp_nmean
            dp_mean = dp_nmean + (0.0 if cfg.degenerate_range else mid)
            outputs['variance'] = variance
            if 'mean' in entry.outputs:
                outputs['mean'] = dp_mean
            if 'count' in entry.outputs:
                outputs['count'] = dp_count
            if 'sum' in entry.outputs:
                outputs['sum'] = dp_mean * dp_count
        std_offset += entry.n_stds

    return outputs, keep, part_row_count


def quantile_std_index(plan: Tuple[MetricPlanEntry, ...]) -> int:
    """Index of the quantile entry's noise std within the stds array."""
    offset = 0
    for entry in plan:
        if entry.kind == 'quantiles':
            return offset
        offset += entry.n_stds
    raise ValueError("plan has no quantiles entry")


def _descend_trees(children_of, n_trees: int, min_v, max_v,
                   cfg: KernelConfig):
    """Vectorized root-to-leaf descent over n_trees noisy quantile trees.

    Device mirror of DenseQuantileTree._single_quantile + the monotonicity
    enforcement of compute_quantiles, unrolled over the static tree height.
    THE single copy of the descent arithmetic: the dense path supplies
    ``children_of`` as precomputed-histogram gathers, the lazy path as
    on-demand searches of the sorted rows — so the two executions cannot
    drift.

    children_of(level, parent, within) -> (counts, edges): the non-negative
    noisy counts [n_trees, B] of each tree's ``parent`` node's children at
    ``level`` (parents live at level-1; the root is node 0 at level 0),
    and whatever the supplier wants back at the next level. ``within`` is
    None at the root and (the last call's edges, the child chosen there)
    below it: the lazy path carries each child's range of rows in it, the
    dense path nothing.
    """
    B, h = cfg.branching, cfg.tree_height
    L = B**h
    f = _ftype()
    mid_value = min_v + (max_v - min_v) / 2

    results = []
    for q in cfg.quantiles:
        node = jnp.zeros(n_trees, dtype=jnp.int32)
        children, edges = children_of(1, node, None)
        total = children.sum(axis=-1)
        target = q * total
        for level in range(1, h + 1):
            cum = jnp.cumsum(children, axis=-1)
            # searchsorted(cum, target, side='left'), clamped to B-1.
            child = jnp.minimum(
                jnp.sum(cum < target[:, None], axis=-1).astype(jnp.int32),
                B - 1)
            before = jnp.where(
                child > 0,
                jnp.take_along_axis(cum,
                                    jnp.maximum(child - 1, 0)[:, None],
                                    axis=1)[:, 0], 0.0)
            target = target - before
            node = node * B + child  # node == 0 at level 1
            if level < h:
                nxt, edges = children_of(level + 1, node, (edges, child))
                child_mass = jnp.take_along_axis(children, child[:, None],
                                                 axis=1)[:, 0]
                target = target / jnp.maximum(child_mass,
                                              1e-12) * nxt.sum(axis=-1)
                children = nxt
            else:
                leaf_count = jnp.maximum(
                    jnp.take_along_axis(children, child[:, None],
                                        axis=1)[:, 0], 1e-12)
        leaf_width = (max_v - min_v) / L
        leaf_lo = min_v + node.astype(f) * leaf_width
        frac = jnp.clip(target / leaf_count, 0.0, 1.0)
        value = jnp.clip(leaf_lo + frac * leaf_width, min_v, max_v)
        results.append(jnp.where(total <= 0, mid_value, value))
    stacked = jnp.stack(results, axis=-1)  # (n_trees, n_q)

    # Monotonicity in quantile order (compute_quantiles' cummax).
    order = np.argsort(np.asarray(cfg.quantiles), kind="stable")
    inverse = np.argsort(order, kind="stable")
    mono = jax.lax.cummax(stacked[:, order], axis=1)
    return mono[:, inverse]


def _descend_quantiles(noisy_levels, min_v, max_v, cfg: KernelConfig):
    """Descent over precomputed noisy level histograms (the dense path)."""
    B = cfg.branching
    C = noisy_levels[0].shape[0]
    arange_b = jnp.arange(B, dtype=jnp.int32)

    def children_of(level, parent, within):
        del within  # the histogram holds every node: nothing to carry
        idxs = parent[:, None] * B + arange_b
        return jnp.maximum(
            jnp.take_along_axis(noisy_levels[level - 1], idxs, axis=1),
            0.0), None

    return _descend_trees(children_of, C, min_v, max_v, cfg)


def _node_noise_keys(level_key: jax.Array, node_ids: jnp.ndarray,
                     partition_ids: jnp.ndarray) -> jax.Array:
    """Deterministic PRNG key per (level, partition, node).

    Lazy tree noising must give a node the SAME noise on every visit (two
    noisy copies of one count would double-spend budget), so keys derive
    from the node's identity, not the visit order.
    """
    pkeys = jax.vmap(jax.random.fold_in,
                     in_axes=(None, 0))(level_key, partition_ids)  # [P]
    return jax.vmap(
        lambda kp, row: jax.vmap(lambda nid: jax.random.fold_in(kp, nid))
        (row))(pkeys, node_ids)  # [P, B]


def _noisy_node_counts(counts: jnp.ndarray, keys: jax.Array, std,
                       cfg: KernelConfig, secure_tables, qidx: int):
    """Adds per-node-keyed noise to lazily-computed tree node counts."""
    f = _ftype()
    if cfg.secure:
        thr_hi, thr_lo, gran = secure_tables
        uhi = jax.vmap(
            jax.vmap(lambda k: jax.random.bits(k, (), jnp.uint32)))(
                jax.vmap(jax.vmap(lambda k: jax.random.fold_in(k, 0)))(keys))
        ulo = jax.vmap(
            jax.vmap(lambda k: jax.random.bits(k, (), jnp.uint32)))(
                jax.vmap(jax.vmap(lambda k: jax.random.fold_in(k, 1)))(keys))
        return secure_noise.snapped_release(counts.astype(f), uhi, ulo,
                                            thr_hi[qidx], thr_lo[qidx],
                                            gran[qidx])
    draws = jax.vmap(jax.vmap(lambda k: jax.random.normal(k, ())))(keys) \
        if cfg.noise_kind == NoiseKind.GAUSSIAN else \
        jax.vmap(jax.vmap(lambda k: jax.random.laplace(k, ())))(keys)
    scale = std if cfg.noise_kind == NoiseKind.GAUSSIAN else std / jnp.sqrt(
        2.0)
    return counts.astype(f) + draws.astype(f) * scale


def _first_at_least(col: jnp.ndarray, lo, hi, bound):
    """First position in [lo, hi) of the ascending int32 `col` whose element
    is at least `bound`, hi where none is: one binary search per element of
    `bound` (lo, hi broadcast against it), all in step, one gathered element
    a search and step. The loop runs while any range is open, so its length
    follows the longest range handed in, not len(col)."""
    lo = jnp.broadcast_to(lo, bound.shape)
    hi = jnp.broadcast_to(hi, bound.shape)

    def halve(ranges):
        lo, hi = ranges
        mid = lo + (hi - lo) // 2
        # A closed range may sit at len(col): clipped, and not moved.
        below = jnp.take(col, mid, mode="clip") < bound
        is_open = lo < hi
        return (jnp.where(is_open & below, mid + 1, lo),
                jnp.where(is_open & ~below, mid, hi))

    lo, _ = jax.lax.while_loop(lambda ranges: jnp.any(ranges[0] < ranges[1]),
                               halve, (lo, hi))
    return lo


def _lazy_quantile_outputs(qrows, min_v, max_v, stds, key: jax.Array,
                           cfg: KernelConfig,
                           psum_axis: Optional[str] = None,
                           secure_tables=None):
    """Per-partition DP quantiles by lazy root-to-leaf descent over rows
    sorted ONCE by (partition, leaf).

    Instead of materializing every chunk of the dense [P, leaves]
    histogram, each descent level counts only the B children of every
    partition's CURRENT node ([P, B] memory) and noises them with per-node
    deterministic noise (_node_noise_keys) — the released values are
    identical in distribution to noising the whole tree and reading the
    descent path.

    The sort is the one pass over the rows (rows that are not kept go to the
    sentinel partition P, behind every real run). Floor division is
    monotone, so that order is also sorted by (partition, node at level l)
    for every l: the rows under a partition's current node are a contiguous
    range, the whole run at the root, and its B children's counts are the
    differences of B + 1 positions in it — the range's two ends and B - 1
    searches between them (_first_at_least). The chosen child's range is
    carried to the next level beside the node (_descend_trees' `within`).
    Work is one sort of the rows plus O(n_quantiles * height * P * B)
    searches, each as long as the log of the longest range.

    Under psum_axis every shard sorts and searches its own rows; the [P, B]
    counts are psum'd before the noise, whose key is replicated, so every
    shard chooses the same child and narrows its own range to it.
    """
    row_pk, row_leaf, row_keep = qrows
    B, h = cfg.branching, cfg.tree_height
    L = B**h
    P = cfg.n_partitions
    f = _ftype()
    i32 = jnp.int32
    qidx = quantile_std_index(cfg.plan)
    std = stds[qidx].astype(f)
    plan_names = next(e.outputs for e in cfg.plan if e.kind == 'quantiles')
    if cfg.secure and secure_tables is None:
        raise ValueError("cfg.secure requires secure_tables "
                         "(secure_noise.build_tables)")
    arange_b = jnp.arange(B, dtype=i32)
    partition_ids = jnp.arange(P, dtype=i32)

    part = jnp.where(row_keep & (row_pk < P), row_pk, P).astype(i32)
    leaf = row_leaf.astype(i32)
    run_ends = jnp.arange(P + 1, dtype=i32)
    if (P + 1) * L <= 2**31:
        # Every (partition, leaf), the sentinel's too, fits ONE int32 key
        # (a shape, never an option): one sort operand, and a search
        # compares the same column against the partition's offset + leaf.
        col = jax.lax.sort(part * L + leaf)
        offset = partition_ids * L
        starts = jnp.searchsorted(col, run_ends * L, side="left")
    else:
        part_sorted, col = jax.lax.sort((part, leaf), num_keys=2)
        offset = jnp.zeros(P, dtype=i32)
        starts = jnp.searchsorted(part_sorted, run_ends, side="left")
    starts = starts.astype(i32)

    def noisy_children(level, parent, within):
        """Noisy counts of each partition's `parent` node's B children at
        `level` (levels 1..h; parent ids live at level-1), and the B + 1
        row positions that bound them."""
        if within is None:
            lo, hi = starts[:-1], starts[1:]
        else:
            edges, child = within
            lo = jnp.take_along_axis(edges, child[:, None], axis=1)[:, 0]
            hi = jnp.take_along_axis(edges, child[:, None] + 1, axis=1)[:, 0]
        node_ids = parent[:, None] * B + arange_b  # the children, level l
        first_leaf = offset[:, None] + node_ids[:, 1:] * B**(h - level)
        inner = _first_at_least(col, lo[:, None], hi[:, None], first_leaf)
        edges = jnp.concatenate([lo[:, None], inner, hi[:, None]], axis=1)
        counts = edges[:, 1:] - edges[:, :-1]
        if psum_axis is not None:
            counts = jax.lax.psum(counts, psum_axis)
        keys = _node_noise_keys(jax.random.fold_in(key, level), node_ids,
                                partition_ids)
        noisy = _noisy_node_counts(counts, keys, std, cfg, secure_tables,
                                   qidx)
        return jnp.maximum(noisy, 0.0), edges

    per_partition = _descend_trees(noisy_children, P, min_v, max_v, cfg)
    return {
        name: per_partition[:, j].astype(f)
        for j, name in enumerate(plan_names)
    }


def _lazy_quantiles(cfg: KernelConfig) -> bool:
    """Whether the trees of cfg.n_partitions partitions exceed one dense
    histogram chunk, so quantile_outputs descends lazily."""
    return -(-cfg.n_partitions // max(cfg.quantile_chunk, 1)) > 1


def quantile_row_passes(cfg: KernelConfig) -> int:
    """Passes over the bounded row stream that quantile_outputs takes in
    one launch, from the static config alone: the one sort by (partition,
    leaf) on the lazy path (the descent then searches the sorted rows and
    passes over them no more), the one scatter-add of the whole histogram
    on the one-chunk dense path, none without percentiles. The telemetry
    counter `quantile_row_passes` has no other source."""
    return 1 if cfg.quantiles else 0


def quantile_node_searches(cfg: KernelConfig) -> int:
    """Boundary positions the lazy descent looks up in the sorted rows in
    one launch, from the static config alone: B - 1 a partition, level and
    quantile (what is left of the tree's random-access work scales with
    it); none where one dense histogram chunk holds every partition's
    leaves, none without percentiles. The telemetry counter
    `quantile_node_searches` has no other source, and shares
    _lazy_quantiles with the dispatch below."""
    if not _lazy_quantiles(cfg):
        return 0
    return (len(cfg.quantiles) * cfg.tree_height * cfg.n_partitions *
            (cfg.branching - 1))


def quantile_outputs(qrows, min_v, max_v, stds, key: jax.Array,
                     cfg: KernelConfig, psum_axis: Optional[str] = None,
                     secure_tables=None):
    """Per-partition DP quantiles from the bounded row stream.

    Builds the dense per-partition tree histograms chunk-by-chunk over the
    partition axis (bounding peak memory at quantile_chunk * n_leaves),
    noises every tree node with the per-level-calibrated std, and descends.
    On the multi-chip path the chunk histograms are psum'd over the mesh —
    the device form of quantile-tree merge — and noise/descent run
    replicated (same key on every shard).

    Two regimes: when one chunk covers every partition (the default 65536-
    leaf tree covers 512 partitions per chunk) the dense histogram is built
    in a single pass. Larger partition spaces switch to the lazy descent
    (_lazy_quantile_outputs): one sort of the rows by (partition, leaf),
    then searches of the sorted rows for the children of each partition's
    current node, with [P, branching] peak memory.
    """
    if _lazy_quantiles(cfg):
        return _lazy_quantile_outputs(qrows, min_v, max_v, stds, key, cfg,
                                      psum_axis, secure_tables)
    row_pk, row_leaf, row_keep = qrows
    B, h = cfg.branching, cfg.tree_height
    L = B**h
    P = cfg.n_partitions
    C = cfg.quantile_chunk
    f = _ftype()
    qidx = quantile_std_index(cfg.plan)
    std = stds[qidx].astype(f)
    plan_names = next(e.outputs for e in cfg.plan if e.kind == 'quantiles')
    if cfg.secure and secure_tables is None:
        raise ValueError("cfg.secure requires secure_tables "
                         "(secure_noise.build_tables)")

    def chunk_fn(c):
        base = c * C
        rel = row_pk - base
        in_chunk = row_keep & (rel >= 0) & (rel < C)
        idx = jnp.where(in_chunk, rel * L + row_leaf, C * L)
        # i32 accumulation: on the f32 TPU path a float scatter-add would
        # silently saturate at 2^24 rows per (partition, leaf) cell.
        hist = jax.ops.segment_sum(in_chunk.astype(jnp.int32), idx,
                                   num_segments=C * L + 1)[:C * L]
        hist = hist.reshape(C, L)
        # Stay in i32 through the cross-shard psum and level roll-ups:
        # casting to f32 first loses exactness above 2^24 per cell. Bound:
        # i32 wraps above 2^31 rows per tree node per invocation; callers
        # streaming more rows than that must split into multiple kernel
        # invocations (the chunked ingest path already does).
        if psum_axis is not None:
            hist = jax.lax.psum(hist, psum_axis)
        # Clean per-level counts (level l has B^l nodes), then noise.
        counts = [hist]
        for level in range(h - 1, 0, -1):
            counts.append(counts[-1].reshape(C, B**level, B).sum(axis=-1))
        counts.reverse()  # counts[l-1] : (C, B^l)
        ckey = jax.random.fold_in(key, c)
        noisy = []
        for l in range(h):
            nkey = jax.random.fold_in(ckey, l)
            if cfg.secure:
                # Node counts are integers: snapping to the secure grid +
                # table-sampled discrete noise, same release discipline as
                # the scalar metric slots (ops/secure_noise.py).
                thr_hi, thr_lo, gran = secure_tables
                noisy.append(
                    secure_noise.snapped_noisy(counts[l].astype(f), nkey,
                                               thr_hi[qidx], thr_lo[qidx],
                                               gran[qidx]))
            else:
                noisy.append(counts[l].astype(f) + noise_ops.additive_noise(
                    nkey, counts[l].shape, std, cfg.noise_kind))
        return _descend_quantiles(noisy, min_v, max_v, cfg)

    # Multi-chunk configurations were dispatched to the lazy descent above,
    # so exactly one dense pass remains.
    per_partition = chunk_fn(jnp.int32(0))[:P]
    return {
        name: per_partition[:, j].astype(f)
        for j, name in enumerate(plan_names)
    }


def _aggregate_trace(pid, pk, values, valid, min_v, max_v, min_s, max_s,
                     mid, stds, rng_key, cfg: KernelConfig,
                     secure_tables=None, psum_axis: Optional[str] = None,
                     combine=None):
    """THE dense aggregation body: bound and reduce the rows to
    per-partition partial columns, select, noise. Every dense entry
    point — one chip or a shard of a mesh, solo or a lane of a batch,
    compacted or the reference form — wraps it, so no two of them can
    release different noise.

    Under shard_map psum_axis names the mesh axis (None on one chip),
    and a shard differs in three places: the sampling key is folded
    with the shard's index (one privacy id's rows all live on one
    shard; the finalize key is NOT folded, so selection and noise run
    replicated), the partial columns go through `combine(cols, cfg)` —
    handed in by the mesh wrapper, not imported here — and
    quantile_outputs psums its histograms over the axis.
    large_p._block_trace(psum_axis=) is the same pattern.

    Returns (outputs, keep bool[P], row_count)."""
    meshed = psum_axis is not None
    if meshed:
        # Read before the split: the op order the mesh's programs have
        # always had (their compile-cache key is the program text).
        shard_idx = jax.lax.axis_index(psum_axis)
    rows_key, final_key = jax.random.split(rng_key, 2)
    if meshed:
        rows_key = jax.random.fold_in(rows_key, shard_idx)
    cols, qrows = partial_columns(pid, pk, values, valid, min_v, max_v, min_s,
                                  max_s, mid, rows_key, cfg)
    if meshed:
        cols = combine(cols, cfg)
    with jax.named_scope("select_noise"):
        outputs, keep, row_count = finalize(cols, min_v, mid, stds,
                                            final_key, cfg, secure_tables)
    if cfg.quantiles:
        with jax.named_scope("quantile_tree"):
            qkey = jax.random.fold_in(rng_key, 7919)
            outputs.update(
                quantile_outputs(qrows, min_v, max_v, stds, qkey, cfg,
                                 psum_axis=psum_axis,
                                 secure_tables=secure_tables))
    return outputs, keep, row_count


def compact_release(outputs, keep):
    """Kept-first compaction of the finalize outputs INSIDE the program:
    stable argsort of ~keep puts kept partitions at the front in
    ascending id order — exactly np.nonzero(keep) — so the host fetches
    one scalar gate plus O(kept) values instead of the dense bool[P] +
    [P] columns. The blocked block body (parallel/large_p._block_trace)
    compacts the same way.

    Returns (n_kept, ids_sorted int32[P], outputs_sorted)."""
    with jax.named_scope("compact"):
        order = jnp.argsort(~keep, stable=True).astype(jnp.int32)
        outputs_sorted = {name: col[order] for name, col in outputs.items()}
        return keep.sum(), order, outputs_sorted


def aggregate_release_trace(pid, pk, values, valid, min_v, max_v, min_s,
                            max_s, mid, stds, rng_key, cfg: KernelConfig,
                            secure_tables=None,
                            psum_axis: Optional[str] = None, combine=None):
    """The traced dense RELEASE: _aggregate_trace, then the kept-first
    compaction. The served entry points here and in parallel/sharded.py
    are jit / vmap / shard_map around this one call: a change to the
    release is made here, once.

    Returns (n_kept, ids_sorted int32[P], outputs_sorted, row_count)."""
    outputs, keep, row_count = _aggregate_trace(
        pid, pk, values, valid, min_v, max_v, min_s, max_s, mid, stds,
        rng_key, cfg, secure_tables, psum_axis, combine)
    n_kept, order, outputs_sorted = compact_release(outputs, keep)
    return n_kept, order, outputs_sorted, row_count


@functools.partial(jax.jit, static_argnames=("cfg",))
def aggregate_kernel(pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
                     stds, rng_key, cfg: KernelConfig, secure_tables=None):
    """Reference form, not served: the body without the compaction —
    dense [P] columns plus the keep mask, which the tests compare the
    blocked and meshed routes against. Nothing under pipelinedp_tpu/
    calls it.

    Returns (outputs, keep bool[P], row_count)."""
    return _aggregate_trace(pid, pk, values, valid, min_v, max_v, min_s,
                            max_s, mid, stds, rng_key, cfg, secure_tables)


@functools.partial(jax.jit, static_argnames=("cfg",))
def aggregate_release_kernel(pid, pk, values, valid, min_v, max_v, min_s,
                             max_s, mid, stds, rng_key, cfg: KernelConfig,
                             secure_tables=None):
    """The release program of the dense route on one chip: the whole
    post-encode chain — contribution bounding, per-partition stats, DP
    selection, noise, kept-first compaction — as ONE device program
    (one launch, no intermediate host syncs). Equal to aggregate_kernel
    + np.nonzero(keep) bit for bit: the same body, and compact_release
    orders kept partitions exactly as nonzero would."""
    return aggregate_release_trace(pid, pk, values, valid, min_v, max_v,
                                   min_s, max_s, mid, stds, rng_key, cfg,
                                   secure_tables)


# Compile/dispatch attribution + AOT executable routing (runtime/aot.py
# wraps runtime/trace.probe_jit): traced calls that grow the jit cache
# are counted as compiles with their wall seconds per entry point, and
# with the backend's aot knob on, warm calls execute the cached
# .lower().compile() executable instead of re-entering jit's Python
# dispatch.
aggregate_kernel = rt_aot.aot_probe("aggregate_kernel", aggregate_kernel,
                                    static_argnames=("cfg",))
aggregate_release_kernel = rt_aot.aot_probe("aggregate_release_kernel",
                                            aggregate_release_kernel,
                                            static_argnames=("cfg",))


@functools.partial(jax.jit, static_argnames=("cfg",))
def batched_aggregate_release_kernel(pid, pk, values, valid, min_v, max_v,
                                     min_s, max_s, mid, stds, rng_keys,
                                     cfg: KernelConfig, secure_tables=None):
    """Lane-stacked aggregate_release_kernel: ONE launch releases L jobs.

    Row arrays carry a leading job-lane axis ([L, n] / [L, n, V]) and
    rng_keys is the [L, 2] stack of each job's own base key; scalars,
    stds and cfg are shared (lanes coalesce only on an identical launch
    fingerprint — see service/batching.py). The body is
    aggregate_release_trace vmapped over the lane axis, and threefry
    keys are counter-based and elementwise, so lane l's outputs are
    bit-identical to aggregate_release_kernel on that lane's arrays and
    key alone — the megabatching guarantee the batching tier asserts
    per lane."""

    def lane(pid_l, pk_l, values_l, valid_l, key_l):
        return aggregate_release_trace(pid_l, pk_l, values_l, valid_l,
                                       min_v, max_v, min_s, max_s, mid,
                                       stds, key_l, cfg, secure_tables)

    return jax.vmap(lane)(pid, pk, values, valid, rng_keys)


batched_aggregate_release_kernel = rt_aot.aot_probe(
    "batched_aggregate_release_kernel", batched_aggregate_release_kernel,
    static_argnames=("cfg",))


def select_partition_counts(pid, pk, valid, key: jax.Array, l0: int,
                            n_partitions: int) -> jnp.ndarray:
    """Per-partition privacy-id counts after pair dedupe + L0 sampling.

    The counting stage of standalone partition selection (the reference's
    group-by-pid / dedupe / sample / count shuffle chain,
    dp_engine.py:224-278): ONE payload-carrying sort by
    (pid, pair_hash64, pk) lands duplicates of a (pid, pk) pair adjacent
    and orders each pid's distinct pairs by a salted uniform hash — so
    "sample l0 partitions without replacement" is just "pair rank < l0",
    exactly the aggregation kernel's L0 machinery (bounded_row_columns —
    same sentinel convention and _pair_hash ranking; the sorts stay
    separate because that path must also carry value payloads and a
    per-row Linf rand key) — then one scatter-add of the surviving
    pair-start rows builds the dense count vector.

    Memory is O(rows) + the int32[P] counts, and P (the partition
    vocabulary size) never exceeds the row count.

    Returns counts: int32[n_partitions].
    """
    spk, kept_pair = _select_kept_pairs(pid, pk, valid, key, l0,
                                        n_partitions)
    P = n_partitions
    idx = jnp.where(kept_pair, spk, P)
    counts = jnp.zeros((P + 1,), jnp.int32).at[idx].add(
        kept_pair.astype(jnp.int32))
    return counts[:P]


def _select_kept_pairs(pid, pk, valid, key: jax.Array, l0: int,
                       n_partitions: int):
    """Dedupe (pid, pk) pairs and L0-sample each id's partitions.

    The shared counting core of standalone selection: returns
    (spk int32[n], kept_pair bool[n]) — the pid-sorted stream's partition
    ids and the mask of pair-start rows that survive sampling; each kept
    row contributes exactly one privacy id to its partition's count.
    """
    i32 = jnp.int32
    P = n_partitions
    with jax.named_scope("select_pairs"):
        pid_sent = jnp.where(valid, pid, jnp.iinfo(i32).max).astype(i32)
        pk_sent = jnp.where(valid, pk, P).astype(i32)
        hp0, hp1 = _pair_hash(pid_sent, pk_sent, key)
        (spid, _, _, spk), pay = _sort_rows([pid_sent, hp0, hp1, pk_sent],
                                            [valid])
        svalid = pay[0]
        new_pair = segment_ops.boundary_mask(spid, spk)
        new_pid = segment_ops.boundary_mask(spid)
        pair_rank = segment_ops.segment_rank_of_segments(new_pair, new_pid)
        kept_pair = new_pair & svalid & (pair_rank < l0)
    return spk, kept_pair


@functools.partial(jax.jit, static_argnames=("l0", "n_partitions"))
def select_kept_pair_stream(pid, pk, valid, rng_key, l0: int,
                            n_partitions: int):
    """Compacting counterpart of select_partition_counts for huge P.

    Instead of scatter-adding into a dense int32[P] vector, sorts the
    surviving pairs' partition ids to the front (dropped rows carry an
    int32-max sentinel and sink to the tail). The resulting
    partition-ascending stream is what the blocked selection path
    (parallel/large_p.select_partitions_blocked) bins into partition
    blocks — dense [P] state never exists on any device. How many pairs
    survived is not an output: the blocked drivers read it from the block
    offsets they fetch anyway (the sentinel sorts above every partition
    id), and a count left on the device would be a second source of it.

    Returns spk_sorted int32[n].
    """
    spk, kept_pair = _select_kept_pairs(pid, pk, valid, rng_key, l0,
                                        n_partitions)
    with jax.named_scope("select_compact"):
        sort_key = jnp.where(kept_pair, spk, jnp.iinfo(jnp.int32).max)
        (spk_sorted,), _ = _sort_rows([sort_key], [])
    return spk_sorted


select_kept_pair_stream = rt_aot.aot_probe(
    "select_kept_pair_stream", select_kept_pair_stream,
    static_argnames=("l0", "n_partitions"))


def _select_partitions_trace(pid, pk, valid, rng_key, l0: int,
                             n_partitions: int,
                             selection: selection_ops.SelectionParams,
                             psum_axis: Optional[str] = None):
    """THE standalone-selection body: count each partition's privacy
    ids after pair dedupe + L0 sampling, draw the keep decisions. Under
    shard_map psum_axis names the mesh axis (as in _aggregate_trace):
    the sampling key is folded with the shard's index, the int32[P]
    counts are psum'd, and the selection key is NOT folded, so every
    shard holds the same keep mask.

    Returns keep: bool[n_partitions]."""
    meshed = psum_axis is not None
    if meshed:
        shard_idx = jax.lax.axis_index(psum_axis)  # before the split
    key_l0, key_sel = jax.random.split(rng_key)
    if meshed:
        key_l0 = jax.random.fold_in(key_l0, shard_idx)
    counts = select_partition_counts(pid, pk, valid, key_l0, l0,
                                     n_partitions)
    if meshed:
        counts = jax.lax.psum(counts, psum_axis)
    return selection_ops.sample_keep_decisions(key_sel, counts, selection)


def select_release_trace(pid, pk, valid, rng_key, l0: int,
                         n_partitions: int,
                         selection: selection_ops.SelectionParams,
                         psum_axis: Optional[str] = None):
    """The traced selection RELEASE: _select_partitions_trace, then the
    kept-first compaction in compact_release's order (np.nonzero's), so
    the host fetches one scalar and O(kept) ids. Every served selection
    entry point, here and in parallel/sharded.py, is jit / vmap /
    shard_map around this one call.

    Returns (n_kept, ids_sorted int32[n_partitions])."""
    keep = _select_partitions_trace(pid, pk, valid, rng_key, l0,
                                    n_partitions, selection, psum_axis)
    order = jnp.argsort(~keep, stable=True).astype(jnp.int32)
    return keep.sum(), order


@functools.partial(jax.jit,
                   static_argnames=("l0", "n_partitions", "selection"))
def select_partitions_kernel(pid, pk, valid, rng_key, l0: int,
                             n_partitions: int,
                             selection: selection_ops.SelectionParams):
    """Reference form, not served: the selection body without the
    compaction — the dense keep mask the tests compare the blocked and
    meshed routes against. Nothing under pipelinedp_tpu/ calls it.

    Returns keep: bool[n_partitions]."""
    return _select_partitions_trace(pid, pk, valid, rng_key, l0,
                                    n_partitions, selection)


@functools.partial(jax.jit,
                   static_argnames=("l0", "n_partitions", "selection"))
def select_partitions_release_kernel(pid, pk, valid, rng_key, l0: int,
                                     n_partitions: int,
                                     selection:
                                     selection_ops.SelectionParams):
    """Standalone DP partition selection on one chip as ONE device
    program: select_partition_counts + the vectorized selection closed
    forms (ops/selection_ops.py) + the kept-first compaction.
    Returns (n_kept, ids_sorted int32[n_partitions])."""
    return select_release_trace(pid, pk, valid, rng_key, l0, n_partitions,
                                selection)


select_partitions_kernel = rt_aot.aot_probe(
    "select_partitions_kernel", select_partitions_kernel,
    static_argnames=("l0", "n_partitions", "selection"))
select_partitions_release_kernel = rt_aot.aot_probe(
    "select_partitions_release_kernel", select_partitions_release_kernel,
    static_argnames=("l0", "n_partitions", "selection"))


@functools.partial(jax.jit,
                   static_argnames=("l0", "n_partitions", "selection"))
def batched_select_partitions_release_kernel(
        pid, pk, valid, rng_keys, l0: int, n_partitions: int,
        selection: selection_ops.SelectionParams):
    """Lane-stacked select_partitions_release_kernel: row arrays carry a
    leading job-lane axis and rng_keys is [L, 2]; lane l's (n_kept,
    ids_sorted) is bit-identical to the solo kernel on that lane alone
    (same vmap/threefry argument as batched_aggregate_release_kernel)."""

    def lane(pid_l, pk_l, valid_l, key_l):
        return select_release_trace(pid_l, pk_l, valid_l, key_l, l0,
                                    n_partitions, selection)

    return jax.vmap(lane)(pid, pk, valid, rng_keys)


batched_select_partitions_release_kernel = rt_aot.aot_probe(
    "batched_select_partitions_release_kernel",
    batched_select_partitions_release_kernel,
    static_argnames=("l0", "n_partitions", "selection"))


def blocked_job_id(kind: str, static_config, noise_seed) -> str:
    """Default journal job id: a digest of the static kernel configuration
    and the noise seed, stable across processes (sha1 of reprs, not
    Python's salted hash) so a crashed run and its resume agree on the
    key space. Callers with several identical aggregations per pipeline
    must pass distinct TPUBackend(job_id=...) values instead."""
    digest = hashlib.sha1(
        repr((static_config, noise_seed)).encode()).hexdigest()[:12]
    return f"{kind}-{digest}"


def _shared_runtime_kwargs(backend) -> dict:
    """The entries every meshed or blocked driver takes alike from
    TPUBackend: retry, the watchdog deadline knobs and, on a mesh, the
    elastic device-loss tolerance (the unsharded drivers already run at
    the one-device floor)."""
    kwargs = dict(retry=backend.retry)
    if backend.timeout_s is not None:
        kwargs["timeout_s"] = backend.timeout_s
    if backend.watchdog is not None:
        kwargs["watchdog"] = backend.watchdog
    if backend.mesh is not None:
        if backend.elastic:
            kwargs["elastic"] = True
        if backend.elastic_grow:
            kwargs["elastic_grow"] = True
        if backend.min_devices != 1:
            kwargs["min_devices"] = backend.min_devices
    return kwargs


def _blocked_runtime_kwargs(backend, kind: str, static_config) -> dict:
    """The failure-semantics kwargs (retry/journal/job_id, the watchdog
    deadline knobs, plus the block_partitions failure-domain size when
    set) threaded from TPUBackend into the blocked drivers."""
    journal = backend.journal
    job_id = backend.job_id
    if journal is not None and backend.noise_seed is None:
        logging.warning(
            "journaled blocked execution without a fixed noise_seed: a "
            "resumed run derives a fresh base key, so only journaled "
            "blocks keep their original results — set "
            "TPUBackend(noise_seed=...) for a deterministic resume.")
    if journal is not None and job_id is None:
        job_id = blocked_job_id(kind, static_config, backend.noise_seed)
    kwargs = _shared_runtime_kwargs(backend)
    kwargs.update(journal=journal, job_id=job_id)
    # Compute/drain overlap (the drainer-thread mode of
    # _dispatch_blocks): opt-in via TPUBackend(overlap_drain=True) —
    # drain deadlines then include dispatch-side compile contention,
    # so the default stays the serial consume loop.
    if backend.overlap_drain:
        kwargs["overlap"] = True
    if backend.block_partitions is not None:
        kwargs["block_partitions"] = backend.block_partitions
    # Attribute the job's health record to this backend so
    # TPUBackend.health() can answer for the aggregations it actually
    # ran. Without an explicit/derived job_id the drivers fall back to
    # their own function name as the job key.
    if job_id is not None:
        backend._health_jobs.add(job_id)
    else:
        meshed = backend.mesh is not None
        backend._health_jobs.add({
            "aggregate": "aggregate_blocked_sharded"
                         if meshed else "aggregate_blocked",
            "select": "select_partitions_blocked_sharded"
                      if meshed else "select_partitions_blocked",
        }.get(kind, kind))
    return kwargs


def _dense_runtime_kwargs(backend, kind: str) -> dict:
    """The runtime kwargs (retry, watchdog deadlines, job attribution,
    elastic device-loss tolerance) threaded from TPUBackend into the
    DENSE meshed drivers (sharded_aggregate_arrays /
    sharded_select_partitions), which share the blocked drivers' runtime
    entry but have no journal — the whole run is one program, so a
    resume IS a re-run under the same key."""
    kwargs = _shared_runtime_kwargs(backend)
    if backend.job_id is not None:
        kwargs["job_id"] = backend.job_id
    backend._health_jobs.add(backend.job_id or kind)
    return kwargs


def resolve_n_partitions(backend, n_partitions: int) -> int:
    """Honors TPUBackend(max_partitions=...): a fixed static result width
    lets one compiled program be reused across datasets."""
    if backend.max_partitions is not None:
        if backend.max_partitions < n_partitions:
            raise ValueError(
                f"TPUBackend(max_partitions={backend.max_partitions}) is "
                f"smaller than the {n_partitions} partitions in the data.")
        return backend.max_partitions
    return n_partitions


def stream_chunk_source(backend, source, public_list=None):
    """Chunked entry of the lazy drivers: encodes a runtime.pipeline
    ChunkSource through the streaming executor (thread-pool encode +
    bounded staging queue + device-resident bucket accumulation) under
    the backend's encode_threads / pipeline_depth knobs and watchdog.

    Returns a device-resident EncodedData pre-padded to the pad_rows
    bucket — bit-identical kernel inputs to the serial encode of the
    same chunks, so pipelined and serial runs release the same noise.
    """
    wd = backend.watchdog
    if wd is None and backend.timeout_s is not None:
        wd = rt_watchdog.Watchdog(timeout_s=backend.timeout_s)
    threads = backend.encode_threads
    if threads is None:
        threads = rt_pipeline.default_encode_threads()
    encode_mode = source.encode_mode
    if encode_mode is None:
        encode_mode = backend.encode_mode
    from pipelinedp_tpu import ingest
    with rt_watchdog.activate(wd):
        return ingest.stream_encode_columns(
            source.chunks,
            public_partitions=public_list,
            nonfinite=source.nonfinite,
            encode_threads=threads,
            pipeline_depth=backend.pipeline_depth,
            encode_mode=encode_mode,
            load=source._load)


def _encode_input(backend, rows, data_extractors, public_list=None):
    """Shared encode stage of the lazy drivers: ChunkSource streams
    through the pipeline, everything else takes columnar.encode."""
    if isinstance(rows, rt_pipeline.ChunkSource):
        return stream_chunk_source(backend, rows, public_list)
    with rt_trace.span("encode"):
        return columnar.encode(rows, data_extractors, public_list)


@dataclass
class ReleaseLaunch:
    """One job's dense fused release launch, offered to the active
    launch interceptor (the service's megabatching tier) instead of
    dispatching solo.

    Carries exactly the arrays/statics the solo kernel call would get:
    for kind="aggregate" the pad_rows-padded row arrays plus the traced
    scalars/stds and the static cfg; for kind="select" the selection
    arrays (padded for a single-device launch, unpadded for a meshed
    one — the meshed dispatcher stages lanes itself, exactly like
    stage_rows_to_mesh's host path) plus the static (l0, n_partitions,
    selection) triple. `key` is the job's own base noise key — lanes
    keep their solo keys, which is what makes a batched lane's release
    bit-identical to its solo run."""
    kind: str  # "aggregate" | "select"
    mesh: Any
    reshard: str
    pid: Any
    pk: Any
    valid: Any
    key: Any
    values: Any = None
    scalars: Optional[Tuple[float, ...]] = None
    stds: Any = None
    cfg: Optional[KernelConfig] = None
    secure_tables: Any = None
    l0: int = 0
    n_partitions: int = 0
    selection: Any = None


# Per-thread launch interceptor: the service's batching tier installs a
# callable here around a job's execution; the dense fused launch sites
# below offer their ReleaseLaunch to it before dispatching solo. The
# interceptor returns the lane's kernel-shaped result (the job ran as
# one lane of a megabatched launch) or None (run solo — lone lane at
# window expiry, mixed specs, or a batched dispatch falling back).
_LAUNCH_INTERCEPTOR = threading.local()


def _active_launch_interceptor():
    return getattr(_LAUNCH_INTERCEPTOR, "fn", None)


@contextlib.contextmanager
def launch_interceptor(fn):
    """Installs `fn` as this thread's release-launch interceptor (None
    reinstalls nothing). Scoped: the previous interceptor is restored
    on exit, so nested jobs cannot leak a coalescer across threads."""
    prev = getattr(_LAUNCH_INTERCEPTOR, "fn", None)
    _LAUNCH_INTERCEPTOR.fn = fn
    try:
        yield
    finally:
        _LAUNCH_INTERCEPTOR.fn = prev


def _offerable(interceptor, arr, backend) -> bool:
    """A launch can join a batch only when an interceptor is active,
    rows are host numpy (streamed/device-resident encodings keep their
    solo device path), and a meshed backend is not forced onto the
    collective reshard (the batched meshed dispatcher stages lanes
    through the host LPT permutation — the same path solo host-numpy
    staging takes)."""
    return (interceptor is not None and isinstance(arr, np.ndarray)
            and (backend.mesh is None or backend.reshard != "device"))


def lazy_select_partitions(backend, col, params, data_extractors,
                           budget_accountant, report_generator):
    """Graph-time setup + lazily executed device partition selection.

    Budget is requested NOW (graph time); the device program runs when the
    returned generator is first iterated — after compute_budgets(). Mirrors
    lazy_aggregate's laziness contract. With a meshed backend the counting
    stage runs shard-local (rows sharded by privacy id) and the counts are
    psum'd over the mesh (parallel/sharded.sharded_select_partitions).
    """
    with rt_observability.mechanism_label("partition_selection"):
        budget = budget_accountant.request_budget(
            mechanism_type=MechanismType.GENERIC)
    strategy = params.partition_selection_strategy
    pre_threshold_str = (f", pre_threshold={params.pre_threshold}"
                         if params.pre_threshold else "")
    report_generator.add_stage(
        lambda: f"Private Partition selection: using {strategy.value} "
        f"method with (eps={budget.eps}, delta={budget.delta}"
        f"{pre_threshold_str})")
    rows = col

    def materialise():
        encoded = _encode_input(backend, rows, data_extractors)
        selection = selection_ops.selection_params_from_host(
            strategy, budget.eps, budget.delta,
            params.max_partitions_contributed, params.pre_threshold)
        n_partitions = resolve_n_partitions(backend, encoded.n_partitions)
        key = noise_ops.make_noise_key(backend.noise_seed)
        threshold = backend.large_partition_threshold
        if threshold is not None and n_partitions > threshold:
            # Huge partition spaces: neither the dense count vector nor
            # the bool[P] keep vector is ever materialized — the
            # blocked path transfers O(kept) ids only. With a mesh
            # the blocked path itself runs sharded (pid-sharded pass 1,
            # one int32[C] psum per block).
            from pipelinedp_tpu.parallel import large_p
            runtime_kwargs = _blocked_runtime_kwargs(
                backend, "select",
                (n_partitions, params.max_partitions_contributed, selection))
            with budget_accountant.no_new_mechanisms(
                    "blocked partition selection execution"), \
                    rt_aot.activate(backend.aot):
                if backend.mesh is not None:
                    kept_ids = large_p.select_partitions_blocked_sharded(
                        backend.mesh, encoded.pid, encoded.pk, encoded.valid,
                        key, params.max_partitions_contributed, n_partitions,
                        selection, reshard=backend.reshard,
                        **runtime_kwargs)
                else:
                    kept_ids = large_p.select_partitions_blocked(
                        encoded.pid, encoded.pk, encoded.valid, key,
                        params.max_partitions_contributed, n_partitions,
                        selection, **runtime_kwargs)
            vocab = encoded.partition_vocab
            n_real = len(vocab)
            with rt_trace.span("post_process"):
                if hasattr(vocab, "prefetch"):
                    vocab.prefetch(idx for idx in kept_ids if idx < n_real)
                for idx in kept_ids:
                    if idx < n_real:
                        # staticcheck: disable=release-taint — sanctioned release: partition keys are decoded ONLY at indices the DP selection kernel kept (noise + threshold); the selection mechanism registered with the ledger is the sanitizer
                        yield vocab[idx]
            return
        interceptor = _active_launch_interceptor()
        if backend.mesh is not None:
            from pipelinedp_tpu.parallel import sharded
            with budget_accountant.no_new_mechanisms(
                    "sharded partition selection execution"), \
                    rt_aot.activate(backend.aot):
                result = None
                if _offerable(interceptor, encoded.pid, backend):
                    result = interceptor(ReleaseLaunch(
                        kind="select", mesh=backend.mesh,
                        reshard=backend.reshard,
                        pid=encoded.pid, pk=encoded.pk,
                        valid=encoded.valid, key=key,
                        l0=params.max_partitions_contributed,
                        n_partitions=n_partitions, selection=selection))
                if result is None:
                    result = sharded.sharded_select_partitions(
                        backend.mesh, encoded.pid, encoded.pk,
                        encoded.valid, key,
                        params.max_partitions_contributed, n_partitions,
                        selection, reshard=backend.reshard,
                        **_dense_runtime_kwargs(
                            backend, "sharded_select_partitions"))
                rt_telemetry.record("release_dispatches")
        else:
            # Selection never reads values; a zero-width column keeps
            # pad_rows from copying the real one. A COPY of the container —
            # pre-encoded callers may reuse their EncodedData afterwards.
            slim = dataclasses.replace(
                encoded, values=np.zeros((encoded.n_rows, 0), np.float64))
            pid, pk, _, valid = pad_rows(slim)
            with rt_trace.span("dispatch"), rt_aot.activate(backend.aot):
                result = None
                if _offerable(interceptor, pid, backend):
                    result = interceptor(ReleaseLaunch(
                        kind="select", mesh=None, reshard="auto",
                        pid=pid, pk=pk, valid=valid, key=key,
                        l0=params.max_partitions_contributed,
                        n_partitions=n_partitions, selection=selection))
                if result is None:
                    result = select_partitions_release_kernel(
                        jnp.asarray(pid), jnp.asarray(pk),
                        jnp.asarray(valid), key,
                        params.max_partitions_contributed, n_partitions,
                        selection)
                rt_telemetry.record("release_dispatches")
        vocab = encoded.partition_vocab
        n_real = len(vocab)
        with rt_trace.span("drain"):
            # One scalar gate, then O(kept) ids cross the link: a
            # bucket-length prefix, cut to k on the host
            # (rt_pipeline.KeptPrefix; same ascending order as np.nonzero
            # over the dense keep vector).
            n_kept, order = result
            (kept_idx,) = rt_pipeline.fetch_kept((order,), int(n_kept))
            rt_telemetry.record("release_dispatches", 2)
        with rt_trace.span("post_process"):
            if hasattr(vocab, "prefetch"):
                vocab.prefetch(idx for idx in kept_idx if idx < n_real)
            for idx in kept_idx:
                if idx < n_real:
                    # staticcheck: disable=release-taint — sanctioned release: partition keys are decoded ONLY at indices the DP selection kernel kept (noise + threshold); the selection mechanism registered with the ledger is the sanitizer
                    yield vocab[idx]

    def generator():
        # The root span of one materialised selection, as lazy_aggregate's
        # `aggregate` is of an aggregation: its `agg` sequence number is
        # the request identifier every span of the job inherits, and it
        # is open across yields, from the first pull to exhaustion.
        with rt_trace.span("select_partitions", agg=rt_trace.next_agg()):
            yield from materialise()

    return generator()


def make_kernel_config(
        params: AggregateParams,
        compound: dp_combiners.CompoundCombiner,
        n_partitions: int,
        private_selection: bool,
        selection_params: Optional[selection_ops.SelectionParams],
        secure: bool = False,
        numeric_mode: str = "fast") -> KernelConfig:
    """Builds the static kernel config from aggregation parameters."""
    vector = Metrics.VECTOR_SUM in (params.metrics or [])
    clip_per_value = params.bounds_per_contribution_are_set and not vector
    clip_pair_sum = params.bounds_per_partition_are_set and not vector
    max_rows = 1
    if params.contribution_bounds_already_enforced:
        max_rows = (params.max_contributions or
                    params.max_contributions_per_partition or 1)
    degenerate = (params.min_value is not None and
                  params.min_value == params.max_value)
    quantiles: Tuple[float, ...] = ()
    tree_height = branching = quantile_chunk = 0
    quantile_combiners = [
        c for c in compound.combiners
        if isinstance(c, dp_combiners.QuantileCombiner)
    ]
    if quantile_combiners:
        qc = quantile_combiners[0]
        if degenerate:
            raise ValueError("max_value must be > min_value")
        quantiles = tuple(qc._quantiles_to_compute)
        tree_height = qc._tree_height
        branching = qc._branching_factor
        # Chunk the partition axis so one chunk's leaf histogram stays under
        # ~2^25 elements (128 MiB in f32) regardless of n_partitions; each
        # extra chunk costs another pass over the row stream.
        n_leaves = branching**tree_height
        quantile_chunk = max(1, min(n_partitions, (1 << 25) // n_leaves))
    return KernelConfig(
        n_partitions=n_partitions,
        linf=params.max_contributions_per_partition or 0,
        l0=(0 if params.max_contributions else
            (params.max_partitions_contributed or 0)),
        total_bound=params.max_contributions or 0,
        sample_per_partition=compound.expects_per_partition_sampling(),
        clip_per_value=clip_per_value,
        clip_pair_sum=clip_pair_sum,
        bounds_enforced=params.contribution_bounds_already_enforced,
        noise_kind=params.noise_kind,
        private_selection=private_selection,
        selection=selection_params,
        max_rows_per_privacy_id=max_rows,
        plan=build_plan(compound),
        degenerate_range=degenerate,
        vector_size=(params.vector_size or 0) if vector else 0,
        vector_max_norm=(params.vector_max_norm or 0.0) if vector else 0.0,
        vector_norm_kind=params.vector_norm_kind if vector else None,
        quantiles=quantiles,
        tree_height=tree_height,
        branching=branching,
        quantile_chunk=quantile_chunk,
        secure=secure,
        numeric_mode=numeric_mode)


def kernel_scalars(params: AggregateParams):
    """Traced clipping scalars (0.0 placeholders when unused); with
    several value columns min_v, max_v and mid are [d] arrays."""
    if params.value_columns:
        min_v = np.asarray([c.min_value for c in params.value_columns],
                           dtype=np.float64)
        max_v = np.asarray([c.max_value for c in params.value_columns],
                           dtype=np.float64)
        mid = dp_computations.compute_middle(min_v, max_v)
        return min_v, max_v, 0.0, 0.0, mid
    min_v = params.min_value if params.min_value is not None else 0.0
    max_v = params.max_value if params.max_value is not None else 0.0
    min_s = (params.min_sum_per_partition
             if params.min_sum_per_partition is not None else 0.0)
    max_s = (params.max_sum_per_partition
             if params.max_sum_per_partition is not None else 0.0)
    mid = (dp_computations.compute_middle(min_v, max_v)
           if params.min_value is not None else 0.0)
    return min_v, max_v, min_s, max_s, mid


def _round_up_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def row_bucket(n: int) -> int:
    """Power-of-two row-count bucket (floor 8).

    THE row-shape bucketing of the whole package: pad_rows pads datasets
    to it, and the streaming executor's device accumulator
    (runtime/pipeline.DeviceRowAccumulator) sizes its chunk buffers and
    final columns to it — so every row shape entering the persistent jit
    entry points lands on one of ~log2(n) buckets and repeated calls
    with varying chunk/dataset sizes hit the compile cache instead of
    retracing (the `jit_cache_misses` delta in the bench receipt proves
    it: 0 on the second warm end-to-end call)."""
    return max(8, _round_up_pow2(n))


def pad_rows(encoded: columnar.EncodedData):
    """Pads row arrays to the power-of-two row bucket (invalid-marked),
    so jit compilation is reused across datasets of similar size.

    Device-resident encodings (ingest.stream_encode_columns) pad with jnp
    on device — a host round-trip here would undo the streamed upload.
    Pipelined encodings arrive already padded to exactly this bucket
    (DeviceRowAccumulator.finalize), so this is a no-op for them.

    Host columns are copied to the bucket on the host, for the callers
    that hand padded HOST arrays on: a launch offered to the service's
    interceptor, the meshed dense route and one-chip select_partitions.
    The one-chip dense aggregation does not come here with host columns:
    rt_pipeline.stage_host_rows sends them up as they are and the pad
    exists on the device only — these are the pad values it writes."""
    n = encoded.n_rows
    n_pad = row_bucket(n)
    if n_pad == n:
        return (encoded.pid, encoded.pk, encoded.values,
                encoded.valid)
    pad = n_pad - n
    with rt_trace.span("dense.pad", rows=n, padded=n_pad):
        return _padded_copies(encoded, pad)


def _padded_copies(encoded: columnar.EncodedData, pad: int):
    """pad_rows' copy of every column, `pad` invalid rows longer."""
    if isinstance(encoded.pid, jax.Array):
        pid = jnp.concatenate([encoded.pid, jnp.zeros(pad, jnp.int32)])
        pk = jnp.concatenate([encoded.pk, jnp.full(pad, -1, jnp.int32)])
        values = jnp.concatenate([
            encoded.values,
            jnp.zeros((pad,) + encoded.values.shape[1:],
                      encoded.values.dtype)
        ])
        valid = jnp.concatenate([encoded.valid, jnp.zeros(pad, bool)])
        return pid, pk, values, valid
    pid = np.concatenate([encoded.pid, np.zeros(pad, np.int32)])
    pk = np.concatenate([encoded.pk, np.full(pad, -1, np.int32)])
    values = np.concatenate([
        encoded.values,
        np.zeros((pad,) + encoded.values.shape[1:], np.float64)
    ])
    valid = np.concatenate([encoded.valid, np.zeros(pad, bool)])
    return pid, pk, values, valid


def lazy_aggregate(backend, col, params: AggregateParams, data_extractors,
                   public_partitions, budget_accountant, report_generator):
    """Graph-time setup + lazily executed fused aggregation.

    Budgets are requested NOW (graph time); the device program runs when the
    returned generator is first iterated — after compute_budgets().
    """
    compound = dp_combiners.create_compound_combiner(params,
                                                     budget_accountant)
    private = public_partitions is None
    selection_budget = None
    if private:
        with rt_observability.mechanism_label("partition_selection"):
            selection_budget = budget_accountant.request_budget(
                mechanism_type=MechanismType.GENERIC)

    # Report stages (mirrors the generic path narration).
    if not private:
        report_generator.add_stage(
            "Public partition selection: dropped non public partitions")
    if not params.contribution_bounds_already_enforced:
        if params.max_contributions:
            report_generator.add_stage(
                f"User contribution bounding: randomly selected not "
                f"more than {params.max_contributions} contributions")
        else:
            if compound.expects_per_partition_sampling():
                report_generator.add_stage(
                    f"Per-partition contribution bounding: for each privacy_id "
                    f"and each partition, randomly select "
                    f"max(actual_contributions_per_partition, "
                    f"{params.max_contributions_per_partition}) contributions.")
            report_generator.add_stage(
                f"Cross-partition contribution bounding: for each privacy_id "
                f"randomly select max(actual_partition_contributed, "
                f"{params.max_partitions_contributed}) partitions")
    if private:
        strategy = params.partition_selection_strategy
        pre_threshold_str = (f", pre_threshold={params.pre_threshold}"
                             if params.pre_threshold else "")
        report_generator.add_stage(
            lambda: f"Private Partition selection: using {strategy.value} "
            f"method with (eps={selection_budget.eps}, "
            f"delta={selection_budget.delta}{pre_threshold_str})")
    for stage in compound.explain_computation():
        report_generator.add_stage(stage)

    public_list = (list(public_partitions)
                   if public_partitions is not None else None)
    rows = col  # materialized at execution time

    def materialise(root):
        encoded = _encode_input(backend, rows, data_extractors, public_list)
        # Chaos ingest seam: the extreme_values fault kind poisons the
        # encoded value column here — AFTER encoding (so partition/pid
        # structure is untouched) and BEFORE any driver dispatch (so all
        # four driver routes see the same poisoned rows).
        poisoned = rt_faults.maybe_extreme_rows(encoded.values, encoded.pk)
        if poisoned is not None:
            encoded = dataclasses.replace(encoded, values=poisoned)
        if Metrics.VECTOR_SUM in (params.metrics or []):
            expected = (params.vector_size,)
            got = encoded.values.shape[1:]
            if got != expected:
                raise TypeError(f"Shape mismatch: {got} != {expected}")
        n_columns = len(params.value_columns or ())
        if n_columns and encoded.values.shape[1:] != (n_columns,):
            raise TypeError(
                f"value_columns names {n_columns} columns; a row's values "
                f"have shape {encoded.values.shape[1:]}")
        root.set(value_columns=n_columns or 1)
        rt_telemetry.record("value_columns", n_columns or 1)
        selection_params = None
        if private:
            selection_params = selection_ops.selection_params_from_host(
                params.partition_selection_strategy, selection_budget.eps,
                selection_budget.delta, params.max_partitions_contributed,
                params.pre_threshold)
        n_partitions = resolve_n_partitions(backend, encoded.n_partitions)
        threshold = backend.large_partition_threshold
        blocked = threshold is not None and n_partitions > threshold
        root.set(rows=encoded.n_rows, n_partitions=n_partitions,
                 route=("mesh" if backend.mesh is not None else
                        "blocked" if blocked else "dense"))
        secure = bool(backend.secure_noise)
        numeric_mode = backend.numeric_mode
        cfg = make_kernel_config(params, compound, n_partitions, private,
                                 selection_params, secure=secure,
                                 numeric_mode=numeric_mode)
        if cfg.quantiles:
            # Once per materialised aggregation with percentiles: the row
            # passes and node searches its trees take (on the blocked route
            # each block's program takes them over its own rows) and the
            # trees built.
            rt_telemetry.record("quantile_row_passes",
                                quantile_row_passes(cfg))
            searches = quantile_node_searches(cfg)
            if searches:  # none on the one-chunk histogram: not recorded
                rt_telemetry.record("quantile_node_searches", searches)
            rt_telemetry.record("quantile_trees", n_partitions)
        stds = compute_noise_stds(compound, params)
        secure_tables = None
        if secure:
            snap_bits = backend.snap_grid_bits
            thr_hi, thr_lo, gran = secure_noise.build_tables(
                stds, params.noise_kind,
                sensitivities=compute_noise_sensitivities(compound, params),
                grid_floor=(None if snap_bits is None
                            else 2.0 ** int(snap_bits)))
            secure_tables = (jnp.asarray(thr_hi), jnp.asarray(thr_lo),
                             jnp.asarray(gran, dtype=_ftype()))
        key = noise_ops.make_noise_key(backend.noise_seed)
        min_v, max_v, min_s, max_s, mid = kernel_scalars(params)
        if blocked:
            # Very large partition spaces: never materialize dense [0, P)
            # columns; process the partition axis in blocks
            # (parallel/large_p.py) and emit only kept partitions. Raw
            # encoded columns go in directly — large_p pads to its own
            # capacities, so the dense path's pow2 pad_rows copy would
            # only inflate the row count here. With a meshed backend the
            # blocked path itself runs over the mesh (pid-sharded pass 1,
            # one [C] psum per block).
            from pipelinedp_tpu.parallel import large_p
            runtime_kwargs = _blocked_runtime_kwargs(backend, "aggregate",
                                                     cfg)
            # Execution — retries, journal resume and OOM re-planning
            # included — must never touch the epsilon ledger: mechanisms
            # registered at graph-build time above, and a registration
            # here would double-spend the budget.
            with budget_accountant.no_new_mechanisms(
                    "blocked aggregation execution"), \
                    rt_aot.activate(backend.aot):
                if backend.mesh is not None:
                    kept_ids, blocked_outputs = \
                        large_p.aggregate_blocked_sharded(
                            backend.mesh, encoded.pid, encoded.pk,
                            encoded.values, encoded.valid, min_v, max_v,
                            min_s, max_s, mid, np.asarray(stds), key, cfg,
                            secure_tables=secure_tables,
                            reshard=backend.reshard, **runtime_kwargs)
                else:
                    kept_ids, blocked_outputs = large_p.aggregate_blocked(
                        encoded.pid, encoded.pk, encoded.values,
                        encoded.valid, min_v, max_v, min_s, max_s, mid,
                        np.asarray(stds), key, cfg,
                        secure_tables=secure_tables, **runtime_kwargs)
            with rt_trace.span("post_process"):
                # staticcheck: disable=release-taint — sanctioned release: the vocab is indexed only by kept_ids the blocked DP selection emitted, and every metric column was noised inside the block kernel before draining
                yield from decode_blocked_results(kept_ids, blocked_outputs,
                                                  encoded.partition_vocab,
                                                  compound)
            return
        interceptor = _active_launch_interceptor()
        # Several value columns carry array scalars, which a batch's
        # launch fingerprint cannot hold: such a job runs solo.
        offer = (_offerable(interceptor, encoded.pid, backend)
                 and not cfg.value_columns)
        host = isinstance(encoded.pid, np.ndarray)
        if host and backend.mesh is None and not offer:
            # Host columns bound for one chip go up as they are and are
            # padded there (rt_pipeline.stage_host_rows). An offered
            # launch and the mesh's staging take padded HOST arrays.
            pid, pk, values, valid = (encoded.pid, encoded.pk,
                                      encoded.values, None)
        else:
            pid, pk, values, valid = pad_rows(encoded)
        with budget_accountant.no_new_mechanisms(
                "fused aggregation execution"), rt_aot.activate(backend.aot):
            batched = None
            if offer:
                batched = interceptor(ReleaseLaunch(
                    kind="aggregate", mesh=backend.mesh,
                    reshard=backend.reshard, pid=pid, pk=pk,
                    values=values, valid=valid, key=key,
                    scalars=(min_v, max_v, min_s, max_s, mid),
                    stds=np.asarray(stds), cfg=cfg,
                    secure_tables=secure_tables))
            if batched is not None:
                result = batched
            elif backend.mesh is not None:
                from pipelinedp_tpu.parallel import sharded
                result = sharded.sharded_aggregate_arrays(
                    backend.mesh, pid, pk, values, valid, min_v, max_v,
                    min_s, max_s, mid, stds, key, cfg, secure_tables,
                    reshard=backend.reshard,
                    **_dense_runtime_kwargs(backend,
                                            "sharded_aggregate_arrays"))
            else:
                columns = (pid, pk, values, valid)
                if host:
                    # Host columns (a pre-encoded EncodedData, rows
                    # encoded here, or the padded copies of a launch the
                    # interceptor declined): staged slab by slab; the
                    # call returns when they ARE up (the kernel cannot
                    # start before). Device-resident inputs were counted
                    # where they went up
                    # (DeviceRowAccumulator._append_now).
                    columns = rt_pipeline.stage_host_rows(*columns)
                with rt_trace.span("dispatch"):
                    result = aggregate_release_kernel(
                        *columns, min_v, max_v, min_s, max_s, mid,
                        jnp.asarray(stds), key, cfg, secure_tables)
                record_offset_histograms(columns[0], n_partitions)
            rt_telemetry.record("release_dispatches")
        with rt_trace.span("post_process"):
            n_kept, order, outputs, _ = result
            # Fail-closed numeric sentinel: one scalar reduction over
            # the kept released columns BEFORE any value is decoded.
            # Its scalar fetch is the first barrier after the launch:
            # the host's wait for the release kernel is here.
            with rt_trace.span("release_wait", what="sentinel"):
                rt_numeric.check_release(outputs, n_kept=n_kept,
                                         numeric_mode=numeric_mode,
                                         context="dense release")
            # staticcheck: disable=release-taint — sanctioned release: the compacted ids/columns are the fused kernel's DP-selected partitions and its noised outputs, reordered kept-first inside the program
            yield from decode_release_results(n_kept, order, outputs,
                                              encoded.partition_vocab,
                                              compound)

    def generator():
        # The root span of one materialised aggregation: its `agg`
        # sequence number is the request identifier every span of the job
        # inherits. Like post_process it is open across yields, from
        # the first pull to exhaustion, so its time includes whatever the
        # consumer does between pulls; no other span may be.
        with rt_trace.span("aggregate", agg=rt_trace.next_agg()) as root:
            yield from materialise(root)

    return generator()


def _decode_rows(outputs, row_idx_pairs, partition_vocab: Sequence[Any],
                 compound: dp_combiners.CompoundCombiner):
    """Shared emit loop: (output row, partition id) pairs -> results.

    `outputs` are HOST columns holding the released rows and nothing
    else: the blocked drivers' concatenated drains, or a dense release's
    columns cut to its kept count (decode_release_results).

    Field order = concatenated plan-entry outputs, which build_plan stores
    in each child's true compute_metrics insertion order — identical to
    CompoundCombiner.compute_metrics on the generic path.
    """
    rt_telemetry.record("release_dispatches")  # the drain these rows took
    field_order: List[str] = [
        name for entry in build_plan(compound) for name in entry.released
    ]
    n_real = len(partition_vocab)
    row_idx_pairs = list(row_idx_pairs)
    if hasattr(partition_vocab, "prefetch"):
        # Hash-encoded vocabulary (device_encode.HashVocab): decode
        # EXACTLY the DP-selected indices in one O(kept) batch instead
        # of one lookup round trip per emitted partition.
        partition_vocab.prefetch(
            idx for _, idx in row_idx_pairs if idx < n_real)
    for row, idx in row_idx_pairs:
        if idx >= n_real:
            continue  # padding partitions beyond the vocabulary
        values = tuple(
            # Vector-valued columns (e.g. vector_sum) decode to ndarrays,
            # scalars to floats — matching the generic combiner outputs.
            (np.asarray(outputs[name][row], dtype=np.float64)
             if outputs[name].ndim > 1 else float(outputs[name][row]))
            for name in field_order)
        yield (partition_vocab[idx],
               dp_combiners._create_named_tuple_instance(
                   "MetricsTuple", tuple(field_order), values))


def decode_blocked_results(kept_ids, outputs, partition_vocab: Sequence[Any],
                           compound: dp_combiners.CompoundCombiner):
    """Blocked large-P output (kept ids + compacted columns) -> results."""
    return _decode_rows(outputs, enumerate(np.asarray(kept_ids)),
                        partition_vocab, compound)


def decode_release_results(n_kept, order, outputs,
                           partition_vocab: Sequence[Any],
                           compound: dp_combiners.CompoundCombiner):
    """Compacted dense release (aggregate_release_kernel / the meshed
    route, whose arrays are replicated; a batched lane's host copies) ->
    results. One scalar sync gates the drain: the kept ids and every
    column cross as one bucket-length prefix each, all copies in flight
    before the single barrier, and are cut to the kept count on the host
    (rt_pipeline.KeptPrefix — no device program depends on the count, and
    no row the selection dropped gets past it). Pure indexing: the
    emitted stream is np.asarray(col)[:k]'s."""
    with rt_trace.span("release_wait", what="n_kept"):
        k = int(n_kept)  # the one sync; gates O(kept) transfers
    rt_telemetry.record("release_dispatches")
    with rt_trace.span("drain"):
        ids, *columns = rt_pipeline.fetch_kept((order, *outputs.values()), k)
    return _decode_rows(dict(zip(outputs, columns)), enumerate(ids),
                        partition_vocab, compound)
