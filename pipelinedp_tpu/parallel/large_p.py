"""Very large partition spaces: blocked partition-axis execution.

The dense fused kernel materializes [0, P) columns — ideal up to P ~ 10^6,
but at P = 10^7..10^9 (the reference's unbounded-key shuffle regime,
``pipeline_dp/pipeline_backend.py:339-352``) a replicated dense partition
axis no longer fits. This module shards the PARTITION axis instead:

  1. **Bound once** (device): contribution bounding is a row-space
     computation (executor.bounded_row_columns) independent of P; the same
     kernel then compacts (drops bounded-away rows) and orders the
     survivors by partition id — all on device, one extra payload sort.
  2. **Bin by partition block**: block b owns partitions [b*C, (b+1)*C);
     block row ranges come from one searchsorted over the compacted stream.
  3. **Finalize per block** (device): each block segment-sums its own rows
     into a dense [C] slice and runs DP selection + noise on just that
     slice (selection and noise are pointwise over partitions, so blocks
     are independent — no collective, no rescans: total work is
     O(n log n + P)).
  4. **Compact**: kept partitions are sorted to the front ON DEVICE, so
     only O(kept) values ever cross the device->host boundary instead of
     dense [C] outputs per block.

Two row-staging regimes, switched on whether the rows fit one device chunk.
The chunk is what the device can hold of pass 1: a share of its memory
limit over the pass-1 program's bytes per row (_pass1_row_budget; 8.5e7
rows of COUNT+SUM on a 16 GB chip, 2^24 where the platform reports no
memory stats). An explicit row_chunk= overrides it.

  * **Device-resident** (n <= the budget, the common case): rows never
    return to the host between passes; per-block inputs are device-side
    gathers at host-known offsets. Host traffic = the padded input columns
    up once, block offsets + kept results down.
  * **Host-staged** (n > the budget): the host sorts the rows by privacy
    id, chunks split on privacy-id boundaries are bounded+compacted on
    device, the compacted survivors staged back to host, merged, and
    re-uploaded — preserving the O(budget + C) device-memory bound at
    any n.

The meshed variants (aggregate_blocked_sharded /
select_partitions_blocked_sharded) scale both passes D-way: rows shard by
privacy id — device-resident inputs through the on-device all_to_all
reshard (parallel/reshard.py; rows never touch the host), host inputs
through the exact LPT permutation — and each block costs one [C]-sized
psum over ICI.

Failure semantics (pipelinedp_tpu/runtime, README "Failure semantics"):
every driver takes retry= (transient dispatch/sync failures re-dispatch
under the SAME fold_in(final_key, b) key — bit-identical noise, no second
release), journal=/job_id= (consumed blocks' drained results recorded
with CRC32 integrity checks for resume; replayed blocks never
re-dispatch, corrupt records quarantine and recompute),
timeout_s=/watchdog= (per-operation deadlines: a timed-out dispatch or
drain retries same-key, repeated timeouts degrade like OOM, a timed-out
reshard collective falls back to the host permutation), and degrades on
OOM by halving the partition block capacity and re-planning the
remaining range (run_with_degradation; re-planned blocks draw fresh
keys — nothing was released for them). The meshed drivers additionally
take elastic=/min_devices= (device-loss tolerance: a device-fatal
failure rebuilds a smaller mesh from the surviving devices and
re-enters the driver — block keys are geometry-independent, so the
degraded run replays the same release; the one-device floor falls back
to the unsharded driver, and losses past min_devices raise
MeshDegradationError with a resume pointer). Each run executes inside
its job's health scope (runtime/health.py), so retries, timeouts,
fallbacks, quarantines and mesh degradations surface in
TPUBackend.health().
"""

import dataclasses
import functools
import logging
import threading
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pipelinedp_tpu import executor
from pipelinedp_tpu import numeric as rt_numeric
from pipelinedp_tpu.ops import segment_ops
# Canonical shape arithmetic lives with the mesh helpers; re-exported here
# because the blocked path made the name public first.
from pipelinedp_tpu.parallel.mesh import host_fetch, round_capacity
from pipelinedp_tpu.runtime import aot as rt_aot
from pipelinedp_tpu.runtime import entry as rt_entry
from pipelinedp_tpu.runtime import faults as rt_faults
from pipelinedp_tpu.runtime import journal as rt_journal
from pipelinedp_tpu.runtime import observability as rt_observability
from pipelinedp_tpu.runtime import pipeline as rt_pipeline
from pipelinedp_tpu.runtime import retry as rt_retry
from pipelinedp_tpu.runtime import telemetry as rt_telemetry
from pipelinedp_tpu.runtime import trace as rt_trace
from pipelinedp_tpu.runtime import watchdog as rt_watchdog

# One shared depth for the async block pipeline: _dispatch_blocks keeps at
# most this many block kernels in flight, and _StagedDrain keeps at most
# this many blocks' O(kept) result buffers staged. The residency reasoning
# (in-flight outputs + staged drains both bounded by the same window, so
# HBM holds O(depth * C), never O(P)) only holds while these agree. The
# constant itself moved to runtime/pipeline.py — the streaming ingest
# executor bounds its staging window with the SAME depth — and is
# re-exported here because the blocked path made the name public first.
PIPELINE_DEPTH = rt_pipeline.PIPELINE_DEPTH

# Key lane for OOM-re-planned block generations: block keys must be a pure
# function of (final_key, plan generation, block index) so that a RETRIED
# block redraws bit-identical noise while a RE-PLANNED block (different
# partition geometry after a capacity halving) can never collide with a
# key an earlier-generation block already consumed.
_REPLAN_KEY_LANE = 0x7265706C  # 'repl'


def _block_noise_key(final_key, generation: int, block: int):
    if generation == 0:
        # Generation 0 preserves the historical fold_in(final_key, b)
        # derivation: fault-free runs (and retries within them) are
        # bit-compatible with pre-runtime releases.
        return jax.random.fold_in(final_key, block)
    return jax.random.fold_in(
        jax.random.fold_in(final_key, _REPLAN_KEY_LANE + generation), block)


@jax.named_scope("p1_bound_compact")
def _bound_compact_trace(pid, pk, values, valid, min_v, max_v, min_s, max_s,
                         mid, key, cfg: executor.KernelConfig):
    """Traceable body shared by the single-device kernel and the per-shard
    function of the meshed path: bound contributions, drop bounded-away
    rows, order survivors by partition id (dropped rows carry an int32-max
    sentinel and sort to the tail)."""
    spk, keep_row, pair_start, reduce_cols, qrows = \
        executor.bounded_row_columns(pid, pk, values, valid, min_v, max_v,
                                     min_s, max_s, mid, key, cfg)
    names = list(reduce_cols)
    sort_key = jnp.where(keep_row, spk, jnp.iinfo(jnp.int32).max)
    payloads = ([pair_start.astype(jnp.int32)] +
                [reduce_cols[m] for m in names])
    if cfg.quantiles:
        payloads.append(qrows[1])  # per-row leaf index
    (spk_s,), pay = executor._sort_rows([sort_key], payloads)
    cols_s = {m: pay[1 + j] for j, m in enumerate(names)}
    leaf_s = pay[-1] if cfg.quantiles else None
    return spk_s, pay[0].astype(bool), cols_s, leaf_s, keep_row.sum()


@functools.partial(jax.jit, static_argnames=("cfg",))
def _bounded_compact_kernel(pid, pk, values, valid, min_v, max_v, min_s,
                            max_s, mid, key, cfg: executor.KernelConfig):
    """Single-device bound+compact. Returns (spk, pair_start, reduce_cols,
    leaf, n_kept); with percentiles, `leaf` carries each row's
    quantile-tree leaf index through the same compaction sort."""
    return _bound_compact_trace(pid, pk, values, valid, min_v, max_v, min_s,
                                max_s, mid, key, cfg)


_bounded_compact_kernel = rt_aot.aot_probe("blocked_bound_compact",
                                           _bounded_compact_kernel,
                                           static_argnames=("cfg",))


@jax.named_scope("block_finalize")
def _block_trace(spk_s, pair_s, cols_s, leaf_s, lo, length, base, min_v,
                 max_v, mid, stds, key, cfg: executor.KernelConfig,
                 cap: int, secure_tables=None, psum_axis=None):
    """Traceable body shared by the single-device block kernel and the
    per-shard function of the meshed path: finalize one partition block
    from the (shard-local) compacted row stream.

    Gathers `cap` rows at host-known offset `lo` (rows beyond `length` are
    masked), reduces them onto the block's dense [C] slice — psum'd over
    `psum_axis` when running under shard_map, the meshed path's one
    collective per block — then runs selection + noise (and, with
    percentiles, the block's quantile descent) and sorts kept partitions
    to the front so the host can fetch exactly n_kept results.
    """
    idx = jnp.arange(cap, dtype=jnp.int32)
    valid = idx < length
    take = lambda a: jnp.take(a, lo + idx, mode="clip")
    spk_rel = jnp.where(valid, take(spk_s) - base, cfg.n_partitions)
    spk_rel = spk_rel.astype(jnp.int32)
    pair = take(pair_s) & valid
    cols = {
        name: jnp.where(valid, take(col), jnp.zeros((), col.dtype))
        for name, col in cols_s.items()
    }
    # Rows were compacted into (kept-first, spk-ascending) order by
    # _bound_compact_trace; the block slice preserves it, and masked
    # tail rows carry the cfg.n_partitions sentinel — still sorted.
    dense = executor.reduce_rows_to_partitions(spk_rel, valid, pair, cols,
                                               cfg.n_partitions,
                                               cfg.vector_size,
                                               presorted=True,
                                               numeric_mode=cfg.numeric_mode)
    if psum_axis is not None:
        if cfg.numeric_mode == "safe":
            # Compensated cross-shard combine: a plain psum would re-round
            # away what the compensated segment sums just preserved.
            dense = jax.tree.map(
                lambda x: segment_ops.compensated_psum(x, psum_axis), dense)
        else:
            dense = jax.tree.map(lambda x: jax.lax.psum(x, psum_axis), dense)
    outputs, keep, _ = executor.finalize(dense, min_v, mid, stds, key, cfg,
                                         secure_tables)
    if cfg.quantiles:
        # Per-block quantile trees over just the block's rows: relative
        # partition ids index trees [0, C); quantile_outputs picks the lazy
        # descent whenever the block exceeds one dense histogram chunk, so
        # peak memory stays O(C * branching), never O(C * leaves).
        with jax.named_scope("quantile_tree"):
            qkey = jax.random.fold_in(key, 7919)
            outputs.update(
                executor.quantile_outputs((spk_rel, take(leaf_s), valid),
                                          min_v, max_v, stds, qkey, cfg,
                                          psum_axis=psum_axis,
                                          secure_tables=secure_tables))
    order = jnp.argsort(~keep, stable=True)  # kept partitions first
    ids_sorted = order.astype(jnp.int32)
    outputs_sorted = {name: col[order] for name, col in outputs.items()}
    return keep.sum(), ids_sorted, outputs_sorted


@functools.partial(jax.jit, static_argnames=("cfg", "cap"))
def _block_kernel_dev(spk_s, pair_s, cols_s, leaf_s, lo, length, base, min_v,
                      max_v, mid, stds, key, cfg: executor.KernelConfig,
                      cap: int, secure_tables=None):
    """Single-device finalize of one partition block (see _block_trace)."""
    return _block_trace(spk_s, pair_s, cols_s, leaf_s, lo, length, base,
                        min_v, max_v, mid, stds, key, cfg, cap,
                        secure_tables)


_block_kernel_dev = rt_aot.aot_probe("blocked_block_kernel",
                                     _block_kernel_dev,
                                     static_argnames=("cfg", "cap"))


@jax.jit
def _block_offsets_dev(spk_s, boundaries):
    """Row offset of each block boundary in the compacted, spk-sorted
    stream (a program of its own so that its ops carry the scope)."""
    with jax.named_scope("block_offsets"):
        return jnp.searchsorted(spk_s, boundaries, side="left")


_block_offsets_dev = rt_trace.probe_jit("_block_offsets_dev",
                                        _block_offsets_dev)


def _chunk_ends(pid_sorted: np.ndarray, row_chunk: int) -> np.ndarray:
    """Chunk end offsets, each extended to the next privacy-id boundary.

    A privacy id's rows must stay in one chunk (L0 bounding is global per
    id), so a single id with more rows than row_chunk forces an oversized
    chunk — the one irreducible violation of the O(row_chunk) memory bound;
    it is logged so the operator knows which workload property caused it.
    """
    import logging
    n = len(pid_sorted)
    ends = []
    start = 0
    while start < n:
        end = min(start + row_chunk, n)
        if end < n:
            end = int(
                np.searchsorted(pid_sorted, pid_sorted[end - 1],
                                side="right"))
        if end - start > 2 * row_chunk:
            logging.warning(
                "large_p: a single privacy id spans %d rows (> 2x row_chunk="
                "%d); its chunk cannot be split without breaking per-id "
                "contribution bounding. Device memory for this chunk scales "
                "with that id's row count.", end - start, row_chunk)
        ends.append(end)
        start = end
    return np.asarray(ends)


class _Replay:
    """A block whose results come from the journal instead of a dispatch."""

    __slots__ = ("record",)

    def __init__(self, record: rt_journal.BlockRecord):
        self.record = record


# The shared runtime-entry discipline (knob validation, health scope,
# watchdog activation, elastic mesh degradation) moved to
# runtime/entry.py so the dense sharded drivers share it; the historical
# name stays importable from here.
_runtime_entry = rt_entry.runtime_entry


def _fallback_blocked_aggregate(args, kwargs, job):
    """Elastic floor of aggregate_blocked_sharded: the unsharded blocked
    driver on the surviving device. Bit-compatible by construction — both
    drivers split rng_key the same way and derive the same
    fold_in(final_key, b) block keys, and the D=1 pass-1 sampling key
    (fold_in(rows_key, 0)) matches the single-chunk unsharded one."""
    kw = {k: v for k, v in kwargs.items() if k != "reshard"}
    return aggregate_blocked(*args[1:], job_id=job, **kw)


def _fallback_blocked_select(args, kwargs, job):
    """Elastic floor of select_partitions_blocked_sharded (see
    _fallback_blocked_aggregate)."""
    kw = {k: v for k, v in kwargs.items() if k != "reshard"}
    return select_partitions_blocked(*args[1:], job_id=job, **kw)


def _sync_scalars(result) -> None:
    """Forces the 0-d leaves (the n_kept gates) to host — the sync point
    where asynchronously-dispatched block failures surface."""
    for leaf in jax.tree_util.tree_leaves(result):
        if getattr(leaf, "ndim", None) == 0:
            np.asarray(leaf)


def _dispatch_blocks(block_iter, consume,
                     max_in_flight: int = PIPELINE_DEPTH,
                     retry_policy: Optional[rt_retry.RetryPolicy] = None,
                     overlap: bool = False) -> int:
    """Bounded-window async block dispatch shared by every blocked driver.

    jax execution is async, so the device pipelines upcoming block kernels
    while the host drains earlier results instead of paying one
    latency-bound sync per block. The
    window is bounded: each in-flight block pins O(C) output buffers in
    HBM, and an unbounded pipeline over P/C blocks would hold O(P)
    results — the exact footprint this module exists to avoid.

    `block_iter` yields (block_index, entry) pairs where entry is either a
    _Replay (journaled results, consumed with no device work) or a
    zero-arg dispatch closure. The closure is re-invokable: it derives its
    own fold_in key, so re-dispatching it for a retry redraws bit-identical
    noise. Transient failures — at dispatch or at the consume-side sync —
    are retried with bounded backoff; OOM-classified failures surface as
    BlockOOMError AFTER all earlier in-flight blocks are drained, so the
    caller can re-plan from exactly the failed block.
    `consume(block_index, result)` syncs and drains one block. Returns
    the number of blocks dispatched (replays excluded).

    overlap=True (TPUBackend(overlap_drain=True); off by default) runs
    consume() on a dedicated drainer thread: block b's drain sync,
    journal fsync and staged transfers come OFF the dispatch thread, so
    block b+1's dispatch is issued while b is still draining (true
    compute/drain double-buffering — the serial mode only overlapped up
    to the window boundary, then blocked the dispatch loop on the
    oldest drain). Opt-in because drain deadlines now measure wall time
    that includes dispatch-side compile contention: on a shared-core
    host a watchdog-armed run can spiral (drain starves behind a
    compile -> deadline expiry -> retry/degrade -> more compiles), so
    pair overlap with a generous timeout_s or none. The drainer runs
    under the dispatch thread's watchdog, health scope, fault schedule
    and AOT activation; blocks are consumed strictly FIFO on the one
    thread, so journal records, result order and fold_in keys are
    bit-identical to overlap=False — asserted in tests — and a drain
    failure surfaces on the dispatch thread with the same
    classification (BlockOOMError for degradable faults) after the
    earlier in-flight blocks have drained.
    """
    policy = retry_policy or rt_retry.DEFAULT_POLICY
    pending = []
    n_dispatched = 0

    def start(b, make):
        # The per-block dispatch span gives the trace a block-granular
        # timeline alongside the watchdog's "dispatch" heartbeats/guards.
        with rt_trace.span("dispatch", block=b):
            result = rt_retry.retry_call(make, policy, block=b)
        rt_telemetry.record("release_dispatches", block=b)
        # Start the host copy of each scalar output (the n_kept gates) at
        # dispatch time: by the time consume() syncs on it, the value has
        # already crossed the link — int(n_kept) would otherwise pay one
        # blocking device->host round trip per block.
        for leaf in jax.tree_util.tree_leaves(result):
            if getattr(leaf, "ndim", None) == 0:
                _copy_to_host_async(leaf)
        return result

    def consume_one(b, entry, make):
        if make is None:  # journal replay
            consume(b, entry)
            return
        result = entry
        attempt = 0
        while True:
            try:
                rt_faults.maybe_fail("consume", b)
                # The drain sync runs under its own watchdog deadline
                # (when one is active): an expiry surfaces as a transient
                # BlockTimeoutError and re-dispatches the same key below.
                with rt_watchdog.guard("drain", b), \
                        rt_trace.span("drain", block=b):
                    rt_faults.maybe_hang(b, point="drain")
                    with rt_trace.span("release_wait", block=b):
                        _sync_scalars(result)
                break
            except Exception as e:  # noqa: BLE001 - classified below
                if (not rt_retry.is_transient(e) or
                        attempt >= policy.max_retries):
                    raise
                delay = policy.delay(attempt)
                attempt += 1
                if rt_retry.is_timeout(e):
                    rt_telemetry.record("block_timeouts", block=b)
                rt_telemetry.record("block_retries", block=b)
                logging.warning(
                    "block %d failed at its sync point (%s); re-dispatching "
                    "under the same block key (retry %d/%d in %.2fs) — "
                    "noise is bit-identical, no second release", b,
                    type(e).__name__, attempt, policy.max_retries, delay)
                time.sleep(delay)
                result = start(b, make)
        with rt_trace.span("consume", block=b):
            consume(b, result)

    def _degradable(err):
        # Exhausted timeouts degrade exactly like OOM: halving the block
        # capacity shrinks per-block work, so the smaller block can land
        # inside the deadline — and the timed-out block never produced
        # consumed output, so the re-plan's fresh keys release nothing
        # twice.
        return rt_retry.is_oom(err) or rt_retry.is_timeout(err)

    def consume_or_oom(b, entry, make):
        try:
            consume_one(b, entry, make)
        except Exception as err:  # noqa: BLE001 - classified below: degradable (OOM/timeout) converts to BlockOOMError, the rest re-raise
            if make is not None and _degradable(err):
                raise rt_retry.BlockOOMError(b, err) from err
            raise

    active_wd = rt_watchdog.active()
    if overlap and max_in_flight > 1:
        return _dispatch_blocks_overlapped(block_iter, start,
                                           consume_or_oom, max_in_flight,
                                           active_wd, _degradable)
    for b, entry in block_iter:
        if active_wd is not None:
            active_wd.beat("dispatch")
        if isinstance(entry, _Replay):
            pending.append((b, entry, None))
        else:
            n_dispatched += 1
            try:
                result = start(b, entry)
            except Exception as err:  # noqa: BLE001 - classified below after the in-flight drain: degradable -> BlockOOMError, the rest re-raise
                # Drain the earlier in-flight blocks first: their results
                # (and journal records) must survive the abort so a
                # degradation or resume continues from this block, not
                # from zero. A secondary drain failure must not mask the
                # original error.
                try:
                    while pending:
                        consume_one(*pending.pop(0))
                except Exception:  # noqa: BLE001 - original error wins
                    logging.exception(
                        "draining in-flight blocks after a dispatch "
                        "failure itself failed; earlier results may be "
                        "incomplete")
                if _degradable(err):
                    raise rt_retry.BlockOOMError(b, err) from err
                raise
            pending.append((b, result, entry))
        if len(pending) >= max_in_flight:
            consume_or_oom(*pending.pop(0))
    while pending:
        consume_or_oom(*pending.pop(0))
    return n_dispatched


def _dispatch_blocks_overlapped(block_iter, start, consume_or_oom,
                                max_in_flight: int, active_wd,
                                _degradable) -> int:
    """The drainer-thread mode of _dispatch_blocks (see its docstring).

    The dispatch thread only issues device work and enqueues (b, result,
    make) triples into a bounded FIFO; one drainer thread syncs,
    journals and stages every block in order. The queue bound IS the
    in-flight window (a full queue blocks the enqueue — the same
    backpressure the serial pending list applied), so HBM residency is
    unchanged. Thread-scoped runtime context (watchdog activation,
    health job scope, fault schedule, AOT routing) is captured on the
    dispatch thread and re-activated on the drainer, so drain guards,
    counter attribution and injected consume faults behave exactly as
    in serial mode."""
    import queue as _queue

    from pipelinedp_tpu.runtime import health as rt_health

    job_health = rt_health.current()
    fault_schedule = rt_faults.active()
    aot_on = rt_aot.enabled()
    cause = rt_trace.current()  # the drainer's spans name the driver's
    drain_q: "_queue.Queue" = _queue.Queue(maxsize=max_in_flight)
    drain_err: list = []
    n_dispatched = 0

    def drainer():
        import contextlib as _ctx
        fault_scope = (rt_faults.inject(fault_schedule)
                       if fault_schedule is not None else
                       _ctx.nullcontext())
        with rt_health.track(job_health), rt_watchdog.activate(active_wd), \
                rt_aot.activate(aot_on), fault_scope, \
                rt_trace.span("drainer", parent=cause):
            while True:
                item = drain_q.get()
                if item is None:
                    return
                if drain_err:
                    # A failed block poisons the rest of the window: the
                    # serial mode would never have consumed them either
                    # (their journal records would land AFTER the failed
                    # block's on a resume — out-of-order durability).
                    continue
                try:
                    consume_or_oom(*item)
                except BaseException as e:  # noqa: BLE001 - transported to the dispatch thread verbatim; consume_or_oom already classified it
                    drain_err.append(e)

    thread = threading.Thread(target=drainer, name="pdp-block-drain",
                              daemon=True)
    thread.start()
    dispatch_err = None
    failed_block = None
    try:
        for b, entry in block_iter:
            if active_wd is not None:
                active_wd.beat("dispatch")
            if drain_err:
                break
            if isinstance(entry, _Replay):
                drain_q.put((b, entry, None))
                continue
            n_dispatched += 1
            try:
                result = start(b, entry)
            except Exception as err:  # noqa: BLE001 - classified after the in-flight drain below, exactly like serial mode
                dispatch_err, failed_block = err, b
                break
            drain_q.put((b, result, entry))
    finally:
        # Sentinel AFTER everything queued: the drainer finishes draining
        # the in-flight window (journal durability for earlier blocks)
        # before the dispatch thread surfaces any failure.
        drain_q.put(None)
        thread.join()
    if dispatch_err is not None:
        if drain_err:
            logging.exception(
                "draining in-flight blocks after a dispatch failure "
                "itself failed; earlier results may be incomplete",
                exc_info=drain_err[0])
        if _degradable(dispatch_err):
            raise rt_retry.BlockOOMError(failed_block,
                                         dispatch_err) from dispatch_err
        raise dispatch_err
    if drain_err:
        raise drain_err[0]
    return n_dispatched


# The async-copy helper moved to runtime/pipeline.py (the dense
# executor's drain shares it); the historical name stays importable.
_copy_to_host_async = rt_pipeline.copy_to_host_async


def _materialize_block_record(ids_sorted, outputs_sorted, k: int,
                              b_base: int) -> rt_journal.BlockRecord:
    """Journal-record materialization with overlapped copies: the kept ids
    and every column cross as one bucket-length prefix each
    (rt_pipeline.KeptPrefix), all copies started before the one barrier,
    overlapping each other and the still-running block compute. The
    record holds exactly the k kept rows: the prefix's leftover rows are
    cut on the host before the record exists."""
    ids, *columns = rt_pipeline.fetch_kept(
        (ids_sorted, *outputs_sorted.values()), k)
    rt_telemetry.record("release_dispatches")
    return rt_journal.BlockRecord(
        ids=ids.astype(np.int64) + b_base,
        outputs=dict(zip(outputs_sorted, columns)))


class _StagedDrain:
    """Overlapped O(kept) result drains for the blocked drivers.

    consume() used to np.asarray each kept slice as its block was
    consumed — one blocking device->host round trip per array, so a
    10-block run with 3 output columns paid ~30 serial round trips.
    Staging instead starts the host copies of a block's kept prefix
    (rt_pipeline.KeptPrefix: a bucket-length slice of the ids and of each
    column) and defers the barrier: transfers overlap each other and the
    remaining block compute, and the cut to the kept count happens on the
    host at drain time. Order is preserved per target list (blocks are
    consumed ascending), so the concatenation contracts of the drivers
    are unchanged.

    Residency stays bounded: staged device buffers would otherwise
    accumulate O(total kept) in HBM — the exact footprint the bounded
    dispatch window exists to avoid. end_block() (called once per
    consumed block) materializes and frees blocks older than
    `max_staged_blocks`; those blocks finished computing a full window
    ago, so draining them rarely blocks and still overlaps the
    in-flight compute."""

    def __init__(self, max_staged_blocks: int = PIPELINE_DEPTH):
        self._staged = []
        self._block_sizes = []
        self._open = 0  # entries staged since the last end_block()
        self._max = max_staged_blocks

    def stage(self, targets, arrays, k: int, id_base: int) -> None:
        """At drain time, append the first k rows of arrays[i] to
        targets[i]; arrays[0] are the block's kept ids, relative to its
        first partition `id_base`, and land as global int64 ids. The host
        copies start now."""
        self._staged.append(
            (targets, rt_pipeline.KeptPrefix(arrays, k), id_base))
        self._open += 1

    def end_block(self, block: int) -> None:
        """Mark the end of block `block`'s stage() calls (its consume
        calls this); drains the oldest staged block once more than
        max_staged_blocks are pending, under span p2.wait: the host
        blocked on the copies of a block a window old."""
        self._block_sizes.append(self._open)
        self._open = 0
        while len(self._block_sizes) > self._max:
            n = self._block_sizes.pop(0)
            if n:
                with rt_trace.span("p2.wait", block=block):
                    self._drain_n(n)

    def materialize(self) -> None:
        """Drain everything still staged (call after the dispatch loop)."""
        self._block_sizes.clear()
        self._open = 0
        self._drain_n(len(self._staged))

    def _drain_n(self, n: int) -> None:
        for targets, prefix, id_base in self._staged[:n]:
            ids, *columns = prefix.host()
            for target, host in zip(
                    targets, (ids.astype(np.int64) + id_base, *columns)):
                target.append(host)
        del self._staged[:n]


def _seed_pass1(seconds: float) -> None:
    """Feeds the pass-1 wall time into telemetry and the active
    watchdog's auto-deadline profile: pass 1 touches every row, so any
    single block is strictly cheaper and multiplier * this time is a
    generous per-block deadline (floored by the watchdog's
    min_timeout_s; explicit timeout_s overrides it entirely)."""
    rt_telemetry.record_duration("p1_bound_compact", seconds)
    wd = rt_watchdog.active()
    if wd is not None:
        wd.seed_profile(seconds)


def _pad_to(a, cap: int):
    widths = ((0, cap - len(a)),) + ((0, 0),) * (a.ndim - 1)
    if isinstance(a, jax.Array):
        # Device-resident columns (streamed ingest) pad on device; np.pad
        # would silently download them.
        return jnp.pad(a, widths)
    return np.pad(a, widths)


def _pad_rows(cap: int, *columns):
    """Pass 1's columns padded to `cap` rows with zeros (False in the
    valid mask), under span p1.pad (rows, cap, the bytes written). On
    host columns the span is the host's np.pad; on jax.Array columns
    the pad is a device dispatch and the span times only the dispatch."""
    with rt_trace.span("p1.pad", rows=len(columns[0]), cap=cap) as sp:
        padded = tuple(_pad_to(a, cap) for a in columns)
        sp.set(bytes=_nbytes(*padded))
    return padded


def _nbytes(*arrays) -> int:
    return sum(int(a.nbytes) for a in arrays)


# How many rows pass 1 keeps device-resident: a share of the device's
# memory limit over _bounded_compact_kernel's bytes per padded row.
#
# Bytes per row are memory_analysis() of the program (argument + output +
# temp; f32 values, i32 keys, as the chip runs), compiled on a TPU v5e
# for the keys-1e7 cfg and for the described v5e at the other sizes; the
# two agree to the byte where both were read (PERF.md §6, PR 28):
#
#     rows          cfg              argument  output  temp    total
#     4,194,304     COUNT            13.0      5.0     16.2    34.2
#     4,194,304     COUNT+SUM        13.0      9.0     16.3    38.3
#     23,068,672    COUNT+SUM        13.0      9.0     24.1    46.1
#     23,068,672    +PERCENTILE      13.0      13.0    20.1    46.1
#     100,663,296   COUNT+SUM        13.0      9.0     28.0    50.0
#
# 13 B in (pid, pk, value, valid), 5 B out (spk, pair flag) and 4 B out
# per carried column (a reduce column; the leaf index with percentiles).
# The sort's temp grows in 4 B steps with the row count, and a second
# carried column found room inside it at the sizes read; the model takes
# the largest temp seen and charges every column in full: 46 + 4 each,
# i.e. 50 B for COUNT+SUM. After a window of keys-1e7 jobs (23,068,672
# padded rows) the device's peak_bytes_in_use read 582.8 MB, 25.3 B a
# row: the analysis bounds what the allocator really holds.
#
# A quarter of the limit: what pass 1 leaves must hold the caller's own
# columns where the input was device-resident (13 B a row again), the
# compacted outputs that stay for pass 2, and PIPELINE_DEPTH block
# programs in flight. On a v5e (bytes_limit 16,909,336,064) that is
# 84.5 M rows of COUNT+SUM; the log's job peaks at 3.4 % of the limit,
# 7.3 times under the quarter.
_PASS1_MEMORY_SHARE = 0.25
_PASS1_BASE_ROW_BYTES = 46.0
_PASS1_COLUMN_ROW_BYTES = 4.0
# Where the platform reports no memory stats (the CPU): the fixed chunk
# every job had before the budget followed the device.
_PASS1_ROWS_WITHOUT_MEMORY_STATS = 1 << 24


def _pass1_bytes_per_row(cfg: executor.KernelConfig) -> float:
    """Device bytes _bounded_compact_kernel needs per padded row: cfg
    moves it only through the columns the compaction sort carries — the
    reduce columns and, with percentiles, the leaf index."""
    carried = len(executor.reduce_column_names(cfg)) + bool(cfg.quantiles)
    return _PASS1_BASE_ROW_BYTES + _PASS1_COLUMN_ROW_BYTES * carried


def _pass1_row_budget(cfg: executor.KernelConfig, device) -> int:
    """The rows pass 1 may hold on `device` at once: its share of the
    device's memory limit over the program's bytes per row. Rows within
    it stay device-resident; more are host-staged in chunks of it."""
    limit = rt_observability.device_bytes_limit([device])
    if limit is None:
        return _PASS1_ROWS_WITHOUT_MEMORY_STATS
    return int(_PASS1_MEMORY_SHARE * limit / _pass1_bytes_per_row(cfg))


def _bound_and_compact_host_staged(pid, pk, values, valid, min_v, max_v,
                                   min_s, max_s, mid, rows_key, cfg,
                                   row_chunk):
    """n > row_chunk: bound+compact chunk-by-chunk, stage survivors on host.

    Chunks split on privacy-id boundaries (L0 bounding is global per id);
    each chunk's survivors arrive already spk-sorted, the host merges them
    with one argsort over the concatenation. The p1.* spans split the
    round trip: host sort, per chunk p1.chunk (its p1.pad, then the
    launch) / sync / copy-down, then the host merge (the upload of the
    merged stream is the caller's).
    """
    with rt_trace.span("p1.host_sort", rows=len(pid)):
        order = np.argsort(pid, kind="stable")
        pid_s, pk_s, values_s, valid_s = (pid[order], pk[order],
                                          values[order], valid[order])
    b_pk, b_pair, b_leaf = [], [], []
    b_cols = {name: [] for name in executor.reduce_column_names(cfg)}
    start = 0
    for ci, end in enumerate(_chunk_ends(pid_s, row_chunk)):
        end = int(end)  # a numpy scalar would not export as a span attr
        sl = slice(start, end)
        cap = round_capacity(end - start)
        with rt_trace.span("p1.chunk", chunk=ci, rows=end - start, cap=cap):
            rows_in = _pad_rows(cap, pid_s[sl], pk_s[sl], values_s[sl],
                                valid_s[sl])
            rt_telemetry.record("h2d_bytes", _nbytes(*rows_in))
            spk, pair, cols, leaf, n_kept = _bounded_compact_kernel(
                *rows_in, min_v, max_v, min_s, max_s, mid,
                jax.random.fold_in(rows_key, ci), cfg)
            del rows_in  # the padded host copies die with the launch
        with rt_trace.span("p1.chunk_wait", chunk=ci):
            k = int(n_kept)  # the only per-chunk sync; bounds the d2h volume
        with rt_trace.span("p1.fetch", chunk=ci, rows=k) as sp:
            # The survivors cross as one bucket-length prefix per column
            # (no program per survivor count) and are cut to k here.
            targets = [b_pk, b_pair, *(b_cols[name] for name in cols)]
            arrays = [spk, pair, *cols.values()]
            if cfg.quantiles:
                targets.append(b_leaf)
                arrays.append(leaf)
            fetch = rt_pipeline.KeptPrefix(arrays, k)
            sp.set(bytes=fetch.nbytes)
            for target, host in zip(targets, fetch.host()):
                target.append(host)
        start = end

    with rt_trace.span("p1.merge", chunks=len(b_pk)):
        spk_all = np.concatenate(b_pk) if b_pk else np.zeros(0, np.int32)
        pair_all = np.concatenate(b_pair) if b_pair else np.zeros(0, bool)
        cols_all = {
            name: (np.concatenate(chunks) if chunks else np.zeros(0))
            for name, chunks in b_cols.items()
        }
        order2 = np.argsort(spk_all, kind="stable")
        leaf_all = None
        if cfg.quantiles:
            leaf_all = (np.concatenate(b_leaf)
                        if b_leaf else np.zeros(0, np.int32))[order2]
        return spk_all[order2], pair_all[order2], {
            name: col[order2] for name, col in cols_all.items()
        }, leaf_all


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def _sharded_bound_compact(pid, pk, values, valid, min_v, max_v, min_s,
                           max_s, mid, rows_key, boundaries,
                           cfg: executor.KernelConfig, mesh):
    """Pass 1 over the mesh: per-shard bound + compact + spk-sort.

    Rows are pid-sharded, so contribution bounding (global per privacy id)
    is shard-local and the O(n log n) compaction sort — the dominant
    pass-1 cost — parallelizes D ways with zero collectives. Each shard
    also searchsorts its own stream against the block boundaries, so the
    host downloads one [S, n_blocks+1] offsets table instead of any rows.
    """
    from jax.sharding import PartitionSpec
    from pipelinedp_tpu.parallel.mesh import SHARD_AXIS, shard_map
    SP = PartitionSpec

    def per_shard(pid_s, pk_s, values_s, valid_s, key_r, boundaries_r):
        shard_idx = jax.lax.axis_index(SHARD_AXIS)
        key_s = jax.random.fold_in(key_r, shard_idx)
        spk_sorted, pair_s, cols_s, leaf_s, _ = _bound_compact_trace(
            pid_s, pk_s, values_s, valid_s, min_v, max_v, min_s, max_s, mid,
            key_s, cfg)
        starts = jnp.searchsorted(spk_sorted, boundaries_r,
                                  side="left").astype(jnp.int32)
        # all_gather -> replicated [S, n_blocks+1]: the driver needs every
        # shard's offsets on every host, and on a multi-controller mesh a
        # replicated table is the only layout host_fetch can read (a
        # process cannot address another host's table shard).
        starts = jax.lax.all_gather(starts, SHARD_AXIS, axis=0)
        if leaf_s is None:  # shard_map needs a concrete pytree leaf
            leaf_s = jnp.zeros(0, jnp.int32)
        return spk_sorted, pair_s, cols_s, leaf_s, starts

    fn = shard_map(per_shard,
                   mesh=mesh,
                   in_specs=(SP(SHARD_AXIS), SP(SHARD_AXIS),
                             SP(SHARD_AXIS), SP(SHARD_AXIS), SP(), SP()),
                   out_specs=(SP(SHARD_AXIS), SP(SHARD_AXIS),
                              SP(SHARD_AXIS), SP(SHARD_AXIS), SP()))
    return fn(pid, pk, values, valid, rows_key, boundaries)


_sharded_bound_compact = rt_aot.aot_probe("sharded_bound_compact",
                                          _sharded_bound_compact,
                                          static_argnames=("cfg", "mesh"))


@functools.partial(jax.jit, static_argnames=("cfg", "cap", "mesh"))
def _sharded_block_kernel(spk_all, pair_all, cols_all, leaf_all, lo_r, len_r,
                          base, min_v, max_v, mid, stds, key,
                          cfg: executor.KernelConfig, cap: int, mesh,
                          secure_tables=None):
    """Pass 2 over the mesh: one partition block, shard-local reduce + one
    [C] psum + replicated finalize.

    Each shard gathers its own `cap` stream rows at its own host-known
    offset (lo_r/len_r are per-shard tables indexed by axis_index),
    segment-sums them onto the block's dense [C] slice, and ONE psum over
    ICI combines the partials — the only collective. Selection + noise +
    kept-first compaction then run replicated under the same key, so every
    device holds identical O(kept)-transferable results.
    """
    from jax.sharding import PartitionSpec
    from pipelinedp_tpu.parallel.mesh import SHARD_AXIS, shard_map
    SP = PartitionSpec

    def per_shard(spk_s, pair_s, cols_s, leaf_s, lo_all, len_all, stds_r,
                  key_r, tables_r):
        shard_idx = jax.lax.axis_index(SHARD_AXIS)
        return _block_trace(spk_s, pair_s, cols_s, leaf_s,
                            lo_all[shard_idx], len_all[shard_idx], base,
                            min_v, max_v, mid, stds_r, key_r, cfg, cap,
                            tables_r, psum_axis=SHARD_AXIS)

    fn = shard_map(per_shard,
                   mesh=mesh,
                   in_specs=(SP(SHARD_AXIS), SP(SHARD_AXIS),
                             SP(SHARD_AXIS), SP(SHARD_AXIS), SP(), SP(),
                             SP(), SP(), SP()),
                   out_specs=(SP(), SP(), SP()))
    return fn(spk_all, pair_all, cols_all, leaf_all, lo_r, len_r, stds, key,
              secure_tables)


_sharded_block_kernel = rt_aot.aot_probe(
    "sharded_block_kernel", _sharded_block_kernel,
    static_argnames=("cfg", "cap", "mesh"))


@functools.partial(jax.jit, static_argnames=("mesh",))
def _sharded_block_offsets(spk_all, boundaries, mesh):
    """Per-shard block offsets of the compacted stream against a NEW set
    of boundaries — the re-planning counterpart of the searchsorted fused
    into pass 1, used after an OOM degradation changes the block plan."""
    from jax.sharding import PartitionSpec
    from pipelinedp_tpu.parallel.mesh import SHARD_AXIS, shard_map
    SP = PartitionSpec

    def per_shard(spk_s, boundaries_r):
        starts = jnp.searchsorted(spk_s, boundaries_r,
                                  side="left").astype(jnp.int32)
        # Replicated for the same multi-controller host_fetch reason as
        # the pass-1 offsets table.
        return jax.lax.all_gather(starts, SHARD_AXIS, axis=0)

    fn = shard_map(per_shard, mesh=mesh,
                   in_specs=(SP(SHARD_AXIS), SP()),
                   out_specs=SP())
    return fn(spk_all, boundaries)


_sharded_block_offsets = rt_aot.aot_probe("sharded_block_offsets",
                                          _sharded_block_offsets,
                                          static_argnames=("mesh",))


def _range_row_cap(block_starts: np.ndarray) -> int:
    """ONE static row-gather capacity for every block of a planned
    range: the largest block's row count ([n_blocks + 1] offsets, or
    [D, n_blocks + 1] per-shard offsets), capacity-rounded.

    `cap` is a static argument of the block kernels, and each block
    kernel carries a stable argsort over its [C] partition slice whose
    TPU compile costs minutes (measured for the chip, PR 22: ~128 s per
    distinct block program at C = 2^20). A per-block capacity compiled
    one such program per distinct block row count — ~n_blocks of them
    on skewed keys — before the first block could run; a per-range
    capacity compiles one (plus one for a narrower final block).
    Smaller blocks gather masked tail rows up to the shared capacity;
    their results are unchanged (masked rows carry the sentinel
    partition and zero contributions)."""
    lens = np.diff(block_starts, axis=-1)
    return round_capacity(int(lens.max()) if lens.size else 0)


def _block_boundaries(base: int, capacity: int, n_blocks: int) -> np.ndarray:
    """int64 block boundaries over [base, base + n_blocks * capacity],
    clamped into int32 range: partition ids are < P <= int32 max and
    dropped rows carry the int32-max sentinel, so a clamped boundary still
    lands left of every sentinel (same overflow guard everywhere)."""
    return np.minimum(
        base + np.arange(n_blocks + 1, dtype=np.int64) * capacity,
        np.iinfo(np.int32).max).astype(np.int32)


@_runtime_entry("aggregate_blocked_sharded",
                fallback=_fallback_blocked_aggregate)
def aggregate_blocked_sharded(mesh,
                              pid,
                              pk,
                              values,
                              valid,
                              min_v,
                              max_v,
                              min_s,
                              max_s,
                              mid,
                              stds,
                              rng_key,
                              cfg: executor.KernelConfig,
                              *,
                              block_partitions: int = 1 << 20,
                              secure_tables=None,
                              reshard: str = "auto",
                              overlap: bool = False,
                              retry: Optional[rt_retry.RetryPolicy] = None,
                              journal: Optional[rt_journal.BlockJournal] = None,
                              job_id: Optional[str] = None
                              ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    """aggregate_blocked over a device mesh: the huge-P counterpart of
    sharded.sharded_aggregate_arrays.

    The reference's unbounded-key regime scales across workers by handing
    the shuffle to Beam/Spark (pipeline_dp/pipeline_backend.py:339-352);
    here the same scaling is mesh-native: rows shard by privacy id (pass 1
    — bounding + the dominant compaction sort — runs D-way parallel with
    no collectives), and each partition block costs exactly one [C]-sized
    psum over ICI before replicated selection/noise. Dense [P] state never
    exists on any device, host traffic stays O(kept), and per-device HBM
    holds O(rows/D + C) — the mesh extends the single-device row capacity
    D-fold with no host staging anywhere on the device-resident path.

    Device-resident (streamed-ingest) columns reshard entirely on device:
    pid-hash bucketize -> one padded jax.lax.all_to_all over the mesh axis
    -> shard-local compaction (reshard.device_reshard_rows_by_pid); only a
    [D, D] count table and the [D, n_blocks+1] block-offset table ever
    cross to the host. Host-numpy inputs — which pay one upload regardless
    — take the exact load-balanced host permutation
    (sharded.shard_rows_by_pid), also reachable as the reshard="host"
    escape hatch. See stage_rows_to_mesh for the padding model.

    Failure semantics (shared with every blocked driver): transient block
    failures retry under the same fold_in key (bit-identical noise), OOM
    halves the partition block capacity and re-plans the remaining range,
    and a journal records each consumed block's drained results for
    resume — see README "Failure semantics". With elastic=True a
    device-fatal failure additionally rebuilds a smaller mesh from the
    surviving devices and re-enters here (block keys are independent of
    mesh geometry, so the degraded run replays the same release) — see
    README "Degraded-mesh semantics".

    Returns (kept_partition_ids int64[M], {metric: f[M]}) — identical
    contract to aggregate_blocked.
    """
    from pipelinedp_tpu.parallel.reshard import stage_rows_to_mesh

    # Chaos ingest seam (no-op without an active extreme_values fault).
    _poisoned = rt_faults.maybe_extreme_rows(values, pk)
    if _poisoned is not None:
        values = _poisoned

    P = cfg.n_partitions
    n_shards = mesh.devices.size
    pid, pk, values, valid = stage_rows_to_mesh(
        mesh, pid, pk, values, valid, reshard,
        values_dtype=np.dtype(executor._ftype()))

    rows_key, final_key = jax.random.split(rng_key, 2)
    stds = jnp.asarray(stds)

    C0 = min(block_partitions, P)
    n_blocks0 = -(-P // C0)
    boundaries0 = _block_boundaries(0, C0, n_blocks0)

    t_p1 = time.perf_counter()
    with rt_trace.span("contribution_bounding"):
        spk_all, pair_all, cols_all, leaf_all, starts = \
            _sharded_bound_compact(
                pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
                rows_key, jnp.asarray(boundaries0), cfg, mesh)
        # The one per-aggregation host download that scales with n_blocks,
        # not rows: each shard's block offsets (host_fetch = sanctioned
        # under the transfer guard).
        starts0 = host_fetch(starts).reshape(n_shards, n_blocks0 + 1)
    _seed_pass1(time.perf_counter() - t_p1)

    output_names = [name for e in cfg.plan for name in e.outputs]
    kept_ids = []
    kept_outputs = {name: [] for name in output_names}
    job = job_id or "aggregate_blocked_sharded"

    drain = _StagedDrain()

    def append_record(record: rt_journal.BlockRecord):
        if record.n_kept:
            kept_ids.append(record.ids)
            for name, col in record.outputs.items():
                kept_outputs.setdefault(name, []).append(col)

    def run_range(base, C, gen, end):
        n_blocks = -(-(end - base) // C)
        if gen == 0 and C == C0:
            # Generation 0 starts at base 0 with capacity C0, so the
            # offsets fused into pass 1 are a prefix of the plan. (A
            # resumed plan journaled under a different capacity — the
            # _load_plan override warning — recomputes instead.)
            starts_r = starts0[:, :n_blocks + 1]
        else:
            starts_r = host_fetch(
                _sharded_block_offsets(
                    spk_all, jnp.asarray(_block_boundaries(base, C,
                                                           n_blocks)),
                    mesh)).reshape(n_shards, n_blocks + 1)
        if end == P:  # the plan's last range: its last boundary is >= P
            rt_telemetry.record("pass2_rows", int(starts_r[:, -1].sum()))
        row_cap = _range_row_cap(starts_r)

        def consume(j, result):
            b_base = base + j * C
            if isinstance(result, _Replay):
                append_record(result.record)
                drain.end_block(j)
                return
            n_kept, ids_sorted, outputs_sorted = result
            # Fail-closed sentinel BEFORE the journal persist: a
            # numerically poisoned block must never become a durable
            # record a later replay would release.
            with rt_trace.span("p2.wait", block=j):
                rt_numeric.check_release(
                    outputs_sorted, n_kept=n_kept,
                    numeric_mode=cfg.numeric_mode,
                    context=f"blocked meshed release (base {b_base})")
                k = int(n_kept)  # sync; gates O(kept) transfers
            if journal is not None:
                record = _materialize_block_record(ids_sorted,
                                                   outputs_sorted, k,
                                                   b_base)
                journal.put(job, rt_journal.block_key(b_base, C), record)
                append_record(record)
            elif k:
                drain.stage(
                    [kept_ids, *(kept_outputs.setdefault(name, [])
                                 for name in outputs_sorted)],
                    [ids_sorted, *outputs_sorted.values()], k, b_base)
            drain.end_block(j)

        def block_iter():
            for j in range(n_blocks):
                b_base = base + j * C
                if journal is not None:
                    record = journal.get(job,
                                         rt_journal.block_key(b_base, C))
                    if record is not None:
                        rt_telemetry.record("journal_replays", block=j)
                        yield (j, _Replay(record))
                        continue
                lo = starts_r[:, j].astype(np.int32)
                lens = (starts_r[:, j + 1] - starts_r[:, j]).astype(np.int32)
                if int(lens.sum()) == 0 and cfg.private_selection:
                    # Row-less on every shard: selection provably emits
                    # nothing.
                    continue
                c_actual = min(C, end - b_base)
                cfg_block = dataclasses.replace(cfg, n_partitions=c_actual)
                yield (j, functools.partial(
                    _sharded_block_kernel, spk_all, pair_all, cols_all,
                    leaf_all, jnp.asarray(lo), jnp.asarray(lens), b_base,
                    min_v, max_v, mid, stds,
                    _block_noise_key(final_key, gen, j), cfg_block,
                    row_cap, mesh, secure_tables))

        dispatched = _dispatch_blocks(block_iter(), consume,
                                      retry_policy=retry, overlap=overlap)
        if dispatched:
            rt_telemetry.record("pass2_block_rows",
                                dispatched * row_cap * n_shards)

    rt_retry.run_with_degradation(run_range, P, C0, journal=journal,
                                  job_id=job)
    drain.materialize()

    kept = (np.concatenate(kept_ids) if kept_ids else np.zeros(0, np.int64))
    return kept, {
        name: (np.concatenate(chunks) if chunks else np.zeros(0))
        for name, chunks in kept_outputs.items()
    }


def _selection_block_trace(spk_kept, lo, length, base, c_actual, key,
                           selection, cap: int, psum_axis=None):
    """Traceable body shared by the single-device and meshed selection
    block kernels: selection decisions for one partition block of the
    kept-pair stream.

    Gathers `cap` stream rows at host-known offset `lo`, scatter-adds the
    block's per-partition privacy-id counts into a dense [C] slice —
    psum'd over `psum_axis` under shard_map — draws the keep decisions,
    and sorts kept relative ids to the front so the host fetches exactly
    n_kept ids — the aggregate path's O(kept) compaction (_block_trace)
    applied to standalone selection.
    """
    from pipelinedp_tpu.ops import selection_ops
    with jax.named_scope("selection_block"):
        idx = jnp.arange(cap, dtype=jnp.int32)
        valid = idx < length
        rel = jnp.where(valid,
                        jnp.take(spk_kept, lo + idx, mode="clip") - base,
                        c_actual).astype(jnp.int32)
        counts = jnp.zeros((c_actual + 1,), jnp.int32).at[rel].add(
            valid.astype(jnp.int32))[:c_actual]
        if psum_axis is not None:
            counts = jax.lax.psum(counts, psum_axis)
        keep = selection_ops.sample_keep_decisions(key, counts, selection)
        order = jnp.argsort(~keep, stable=True).astype(jnp.int32)
        return keep.sum(), order


@functools.partial(jax.jit,
                   static_argnames=("c_actual", "selection", "cap"))
def _selection_block_kernel(spk_kept, lo, length, base, c_actual, key,
                            selection, cap: int):
    """Single-device selection block kernel (see _selection_block_trace)."""
    return _selection_block_trace(spk_kept, lo, length, base, c_actual, key,
                                  selection, cap)


_selection_block_kernel = rt_aot.aot_probe(
    "selection_block_kernel", _selection_block_kernel,
    static_argnames=("c_actual", "selection", "cap"))


@functools.partial(jax.jit,
                   static_argnames=("l0", "n_partitions", "mesh"))
def _sharded_select_compact(pid, pk, valid, rows_key, boundaries, l0: int,
                            n_partitions: int, mesh):
    """Selection pass 1 over the mesh: per-shard kept-pair compaction.

    Rows are pid-sharded, so pair dedupe + L0 sampling
    (executor.select_kept_pair_stream) are shard-local; each shard also
    searchsorts its own stream against the block boundaries.
    """
    from jax.sharding import PartitionSpec
    from pipelinedp_tpu.parallel.mesh import SHARD_AXIS, shard_map
    SP = PartitionSpec

    def per_shard(pid_s, pk_s, valid_s, key_r, boundaries_r):
        shard_idx = jax.lax.axis_index(SHARD_AXIS)
        key_s = jax.random.fold_in(key_r, shard_idx)
        spk_sorted = executor.select_kept_pair_stream(
            pid_s, pk_s, valid_s, key_s, l0, n_partitions)
        starts = jnp.searchsorted(spk_sorted, boundaries_r,
                                  side="left").astype(jnp.int32)
        # Replicated offsets (all_gather): see _sharded_bound_compact.
        return spk_sorted, jax.lax.all_gather(starts, SHARD_AXIS, axis=0)

    fn = shard_map(per_shard,
                   mesh=mesh,
                   in_specs=(SP(SHARD_AXIS), SP(SHARD_AXIS),
                             SP(SHARD_AXIS), SP(), SP()),
                   out_specs=(SP(SHARD_AXIS), SP()))
    return fn(pid, pk, valid, rows_key, boundaries)


_sharded_select_compact = rt_aot.aot_probe(
    "sharded_select_compact", _sharded_select_compact,
    static_argnames=("l0", "n_partitions", "mesh"))


@functools.partial(jax.jit,
                   static_argnames=("c_actual", "selection", "cap", "mesh"))
def _sharded_selection_block(spk_all, lo_r, len_r, base, c_actual, key,
                             selection, cap: int, mesh):
    """Selection pass 2 over the mesh: shard-local block counts + one [C]
    psum + replicated decisions/compaction (see _selection_block_trace)."""
    from jax.sharding import PartitionSpec
    from pipelinedp_tpu.parallel.mesh import SHARD_AXIS, shard_map
    SP = PartitionSpec

    def per_shard(spk_s, lo_all, len_all, key_r):
        shard_idx = jax.lax.axis_index(SHARD_AXIS)
        return _selection_block_trace(spk_s, lo_all[shard_idx],
                                      len_all[shard_idx], base, c_actual,
                                      key_r, selection, cap,
                                      psum_axis=SHARD_AXIS)

    fn = shard_map(per_shard,
                   mesh=mesh,
                   in_specs=(SP(SHARD_AXIS), SP(), SP(), SP()),
                   out_specs=(SP(), SP()))
    return fn(spk_all, lo_r, len_r, key)


_sharded_selection_block = rt_aot.aot_probe(
    "sharded_selection_block", _sharded_selection_block,
    static_argnames=("c_actual", "selection", "cap", "mesh"))


@_runtime_entry("select_partitions_blocked_sharded",
                fallback=_fallback_blocked_select)
def select_partitions_blocked_sharded(mesh,
                                      pid,
                                      pk,
                                      valid,
                                      rng_key,
                                      l0: int,
                                      n_partitions: int,
                                      selection,
                                      *,
                                      block_partitions: int = 1 << 20,
                                      reshard: str = "auto",
                                      overlap: bool = False,
                                      retry: Optional[
                                          rt_retry.RetryPolicy] = None,
                                      journal: Optional[
                                          rt_journal.BlockJournal] = None,
                                      job_id: Optional[str] = None
                                      ) -> np.ndarray:
    """select_partitions_blocked over a device mesh.

    Rows shard by privacy id (device-resident inputs via the on-device
    all_to_all reshard, host inputs via the exact LPT permutation — see
    stage_rows_to_mesh); pass 1 — pair dedupe, L0 sampling and the
    compaction sort — runs D-way parallel with no further collectives;
    each partition block costs one int32[C] psum over ICI before
    replicated decisions. Neither dense [P] counts nor a bool[P] keep
    vector ever exists on any device, and host traffic stays
    O(rows/D + kept) for host inputs, O(D^2 + n_blocks + kept) for
    device-resident ones.

    Returns kept_partition_ids int64[M], ascending — identical contract
    to select_partitions_blocked.
    """
    from pipelinedp_tpu.parallel.reshard import stage_rows_to_mesh

    P = n_partitions
    n_shards = mesh.devices.size
    key_l0, key_sel = jax.random.split(rng_key)
    # Zero-width values column: selection never reads values, and a real
    # one would cost an O(rows) gather (or exchange) in the reshard.
    if isinstance(pid, jax.Array):
        dummy_values = jnp.zeros((pid.shape[0], 0), jnp.float32)
    else:
        dummy_values = np.zeros((len(pid), 0), np.float32)
    pid, pk, _, valid = stage_rows_to_mesh(mesh, pid, pk, dummy_values,
                                           valid, reshard)

    C0 = min(block_partitions, P)
    n_blocks0 = -(-P // C0)
    t_p1 = time.perf_counter()
    with rt_trace.span("contribution_bounding"):
        spk_all, starts = _sharded_select_compact(
            pid, pk, valid, key_l0,
            jnp.asarray(_block_boundaries(0, C0, n_blocks0)), l0, P, mesh)
        starts0 = host_fetch(starts).reshape(n_shards, n_blocks0 + 1)
    _seed_pass1(time.perf_counter() - t_p1)

    kept_ids = []
    job = job_id or "select_partitions_blocked_sharded"

    drain = _StagedDrain()

    def run_range(base, C, gen, end):
        n_blocks = -(-(end - base) // C)
        if gen == 0 and C == C0:
            # Generation 0 starts at base 0 with capacity C0, so the
            # offsets fused into pass 1 are a prefix of the plan. (A
            # resumed plan journaled under a different capacity — the
            # _load_plan override warning — recomputes instead.)
            starts_r = starts0[:, :n_blocks + 1]
        else:
            starts_r = host_fetch(
                _sharded_block_offsets(
                    spk_all, jnp.asarray(_block_boundaries(base, C,
                                                           n_blocks)),
                    mesh)).reshape(n_shards, n_blocks + 1)
        row_cap = _range_row_cap(starts_r)

        def consume(j, result):
            b_base = base + j * C
            if isinstance(result, _Replay):
                if result.record.n_kept:
                    kept_ids.append(result.record.ids)
                drain.end_block(j)
                return
            n_kept, order = result
            with rt_trace.span("p2.wait", block=j):
                k = int(n_kept)  # sync; gates the O(kept) transfer
            if journal is not None:
                # Journaled runs materialize per block, as the aggregate
                # routes' _materialize_block_record does: the record holds
                # exactly the k kept ids.
                (ids,) = rt_pipeline.fetch_kept((order,), k)
                ids = ids.astype(np.int64) + b_base
                journal.put(job, rt_journal.block_key(b_base, C),
                            rt_journal.BlockRecord(ids=ids, outputs={}))
                if k:
                    kept_ids.append(ids)
            elif k:
                drain.stage([kept_ids], [order], k, b_base)
            drain.end_block(j)

        def block_iter():
            for j in range(n_blocks):
                b_base = base + j * C
                if journal is not None:
                    record = journal.get(job,
                                         rt_journal.block_key(b_base, C))
                    if record is not None:
                        rt_telemetry.record("journal_replays", block=j)
                        yield (j, _Replay(record))
                        continue
                lo = starts_r[:, j].astype(np.int32)
                lens = (starts_r[:, j + 1] - starts_r[:, j]).astype(np.int32)
                if int(lens.sum()) == 0:
                    # Row-less on every shard: keep probability is 0.
                    continue
                c_actual = min(C, end - b_base)
                yield (j, functools.partial(
                    _sharded_selection_block, spk_all, jnp.asarray(lo),
                    jnp.asarray(lens), b_base, c_actual,
                    _block_noise_key(key_sel, gen, j), selection,
                    row_cap, mesh))

        _dispatch_blocks(block_iter(), consume, retry_policy=retry,
                         overlap=overlap)

    rt_retry.run_with_degradation(run_range, P, C0, journal=journal,
                                  job_id=job)
    drain.materialize()

    if not kept_ids:
        return np.zeros(0, np.int64)
    return np.concatenate(kept_ids)


@_runtime_entry("select_partitions_blocked")
def select_partitions_blocked(pid,
                              pk,
                              valid,
                              rng_key,
                              l0: int,
                              n_partitions: int,
                              selection,
                              *,
                              block_partitions: int = 1 << 20,
                              overlap: bool = False,
                              retry: Optional[rt_retry.RetryPolicy] = None,
                              journal: Optional[
                                  rt_journal.BlockJournal] = None,
                              job_id: Optional[str] = None
                              ) -> np.ndarray:
    """Standalone DP partition selection over a huge partition space.

    Same semantics as executor.select_partitions_kernel (the reference's
    select_partitions at unbounded key cardinality,
    pipeline_dp/dp_engine.py:224-278), but neither the dense int32[P]
    count vector nor the bool[P] keep vector ever exists: pass 1 compacts
    the L0-sampled pair stream on device (executor.select_kept_pair_stream),
    pass 2 bins it into partition blocks and transfers only each block's
    kept ids — O(rows + kept) host traffic at any P.

    Where the wall time goes is read from rt_trace: contribution_bounding
    (p1.pad, the host's pad to the row capacity, then p1.upload: host
    columns going up, blocking until they are there, counted in
    h2d_bytes, then pass 1's launch), block_offsets (the host's first
    wait for the device: pass 1's two sorts and the search), and per
    block dispatch / drain (release_wait inside) / consume (p2.wait
    inside: int(n_kept), already on the host, and the staged copies of
    a block a window old); the staged ids come down in the last drain.
    Two counters say what pass 2 worked on:
    selection_pairs, the pairs that survived dedupe and l0 — its ONE
    source is the last block offset (every surviving pair sorts below
    it, every dropped row's sentinel above), which the host holds
    anyway — and selection_block_rows, the row_cap rows each dispatched
    block program gathers and scatters, however few of them are pairs.

    Returns kept_partition_ids int64[M], ascending.
    """
    P = n_partitions
    key_l0, key_sel = jax.random.split(rng_key)
    on_host = not isinstance(pid, jax.Array)
    if on_host:
        pid, pk, valid = np.asarray(pid), np.asarray(pk), np.asarray(valid)
    cap = round_capacity(len(pid))
    t_p1 = time.perf_counter()
    with rt_trace.span("contribution_bounding", rows=len(pid)):
        rows_in = _pad_rows(cap, pid, pk, valid)
        if on_host:
            nbytes = _nbytes(*rows_in)
            rt_telemetry.record("h2d_bytes", nbytes)
            with rt_trace.span("p1.upload", bytes=nbytes):
                rows_in = jax.block_until_ready(
                    tuple(jnp.asarray(a) for a in rows_in))
        spk_sorted = executor.select_kept_pair_stream(*rows_in, key_l0, l0,
                                                      P)
        del rows_in  # pass 2 reads the compacted stream only
    _seed_pass1(time.perf_counter() - t_p1)

    C0 = min(block_partitions, P)
    kept_ids = []
    job = job_id or "select_partitions_blocked"

    drain = _StagedDrain()

    def run_range(base, C, gen, end):
        n_blocks = -(-(end - base) // C)
        # The fetch waits for pass 1 (dispatched async) and the search.
        with rt_trace.span("block_offsets", blocks=n_blocks):
            block_starts = host_fetch(
                jnp.searchsorted(spk_sorted,
                                 jnp.asarray(_block_boundaries(base, C,
                                                               n_blocks)),
                                 side="left"))
        if end == P:  # the plan's last range: its last boundary is >= P
            rt_telemetry.record("selection_pairs", int(block_starts[-1]))
        row_cap = _range_row_cap(block_starts)

        def consume(j, result):
            b_base = base + j * C
            if isinstance(result, _Replay):
                if result.record.n_kept:
                    kept_ids.append(result.record.ids)
                drain.end_block(j)
                return
            n_kept, order = result
            with rt_trace.span("p2.wait", block=j):
                k = int(n_kept)  # sync; gates the O(kept) transfer
            if journal is not None:
                # Journaled runs materialize per block, as the aggregate
                # routes' _materialize_block_record does: the record holds
                # exactly the k kept ids.
                (ids,) = rt_pipeline.fetch_kept((order,), k)
                ids = ids.astype(np.int64) + b_base
                journal.put(job, rt_journal.block_key(b_base, C),
                            rt_journal.BlockRecord(ids=ids, outputs={}))
                if k:
                    kept_ids.append(ids)
            elif k:
                drain.stage([kept_ids], [order], k, b_base)
            drain.end_block(j)

        def block_iter():
            for j in range(n_blocks):
                b_base = base + j * C
                if journal is not None:
                    record = journal.get(job,
                                         rt_journal.block_key(b_base, C))
                    if record is not None:
                        rt_telemetry.record("journal_replays", block=j)
                        yield (j, _Replay(record))
                        continue
                lo, hi = int(block_starts[j]), int(block_starts[j + 1])
                if lo == hi:
                    # Selection keeps empty partitions with probability 0
                    # (selection_ops.keep_probabilities: n <= 0 -> 0):
                    # row-less blocks provably emit nothing.
                    continue
                c_actual = min(C, end - b_base)
                yield (j, functools.partial(
                    _selection_block_kernel, spk_sorted, lo, hi - lo,
                    b_base, c_actual, _block_noise_key(key_sel, gen, j),
                    selection, row_cap))

        dispatched = _dispatch_blocks(block_iter(), consume,
                                      retry_policy=retry, overlap=overlap)
        if dispatched:
            rt_telemetry.record("selection_block_rows",
                                dispatched * row_cap)

    rt_retry.run_with_degradation(run_range, P, C0, journal=journal,
                                  job_id=job)
    with rt_trace.span("drain"):
        drain.materialize()
    rt_telemetry.record("release_dispatches")  # the drain the ids took

    if not kept_ids:
        return np.zeros(0, np.int64)
    out = np.concatenate(kept_ids)
    # Blocks are consumed in order but each block's kept ids arrive in
    # keep-first argsort order (ascending within the kept prefix because
    # the argsort is stable) — already globally ascending.
    return out


@_runtime_entry("aggregate_blocked")
def aggregate_blocked(pid,
                      pk,
                      values,
                      valid,
                      min_v,
                      max_v,
                      min_s,
                      max_s,
                      mid,
                      stds,
                      rng_key,
                      cfg: executor.KernelConfig,
                      *,
                      block_partitions: int = 1 << 20,
                      row_chunk: Optional[int] = None,
                      secure_tables=None,
                      overlap: bool = False,
                      retry: Optional[rt_retry.RetryPolicy] = None,
                      journal: Optional[rt_journal.BlockJournal] = None,
                      job_id: Optional[str] = None
                      ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    """DP aggregation over an arbitrarily large partition space.

    Same semantics as executor.aggregate_kernel — including percentiles,
    whose per-block quantile trees descend lazily (O(C * branching) peak
    memory) over the block's own rows — but the partition axis is processed
    in blocks of `block_partitions` and only kept partitions are returned.

    row_chunk: pass 1 stays device-resident while the rows fit it and is
    host-staged in chunks of it above. None (what every served call
    passes) takes _pass1_row_budget: what the device's memory holds of
    this cfg's pass-1 program. An integer is that many rows, whatever
    the device (the tests' seam).

    Where the wall time goes is read from rt_trace: contribution_bounding
    (staged=host|device) with its p1.* children (p1.pad, the host's pad
    to the row capacity, inside each p1.chunk), block_offsets (the
    host's first wait: pass 1), per block dispatch / drain (release_wait
    inside: block b's scalars) / consume (p2.wait inside: the sentinel's
    flags program, which queues behind every block in flight, int(n_kept)
    and the staged copies of a block a window old), and the last drain.
    On a journal-less run the host's whole wait for the device is
    block_offsets + release_wait + p2.wait. Two counters say what pass 2
    worked on, as select_partitions_blocked's two do: pass2_rows, the
    bounded survivors (the last block offset), and pass2_block_rows,
    row_cap times the block programs dispatched.

    retry/journal/job_id: failure-semantics knobs (module docstring).
    Journaled runs materialize each block's results at consume time (one
    sync per block) so the record is durable immediately — the staged
    drain's transfer overlap is traded for crash-resumability.

    Returns (kept_partition_ids int64[M], {metric: f[M]}).
    """
    t0 = time.perf_counter()
    # Chaos ingest seam (no-op without an active extreme_values fault).
    _poisoned = rt_faults.maybe_extreme_rows(values, pk)
    if _poisoned is not None:
        values = _poisoned
    P = cfg.n_partitions
    device_resident = isinstance(pid, jax.Array)
    if device_resident:
        # Streamed-ingest columns stay on device (no download/re-upload);
        # only the chunked host-staging regime below needs host copies.
        values = values.astype(executor._ftype())
    else:
        pid = np.asarray(pid)
        pk = np.asarray(pk)
        # Pre-cast to the kernel float dtype: the kernel casts on device
        # anyway, and float64 host arrays would double the upload volume.
        values = np.asarray(values, dtype=np.dtype(executor._ftype()))
        valid = np.asarray(valid)
    n = len(pid)

    rows_key, final_key = jax.random.split(rng_key, 2)
    stds = jnp.asarray(stds)

    # --- Pass 1: bound rows, compact + spk-sort the survivors. ------------
    if row_chunk is None:
        # One host's devices are of one kind: the first one's limit is
        # the limit of whichever holds the rows.
        row_chunk = _pass1_row_budget(cfg, jax.local_devices()[0])
    host_staged = n > row_chunk
    with rt_trace.span("contribution_bounding", rows=n,
                       staged="host" if host_staged else "device"):
        if not host_staged:
            # Device-resident: one kernel call, rows stay in HBM for
            # pass 2.
            rt_telemetry.record("pass1_device_resident")
            cap = round_capacity(n)
            with rt_trace.span("p1.chunk", chunk=0, rows=n, cap=cap):
                rows_in = _pad_rows(cap, pid, pk, values, valid)
                if not device_resident:
                    nbytes = _nbytes(*rows_in)
                    rt_telemetry.record("h2d_bytes", nbytes)
                    with rt_trace.span("p1.upload", bytes=nbytes):
                        rows_in = tuple(jnp.asarray(a) for a in rows_in)
                spk_all, pair_all, cols_all, leaf_all, _ = \
                    _bounded_compact_kernel(
                        *rows_in, min_v, max_v, min_s, max_s, mid,
                        jax.random.fold_in(rows_key, 0), cfg)
                del rows_in  # pass 2 reads the compacted outputs only
        else:
            if device_resident:
                # Host staging re-chunks on privacy-id boundaries with
                # host argsorts; one download is unavoidable here.
                pid, pk, values, valid = (np.asarray(pid), np.asarray(pk),
                                          np.asarray(values),
                                          np.asarray(valid))
                rt_telemetry.record("d2h_bytes",
                                    _nbytes(pid, pk, values, valid))
            spk_all, pair_all, cols_all, leaf_all = \
                _bound_and_compact_host_staged(
                    pid, pk, values, valid, min_v, max_v, min_s, max_s,
                    mid, rows_key, cfg, row_chunk)
            # Blocks gather from device-resident arrays either way;
            # per-block inputs are O(block rows), so upload the merged
            # stream once.
            with rt_trace.span("p1.upload") as sp:
                nbytes = _nbytes(spk_all, pair_all, *cols_all.values(),
                                 *([] if leaf_all is None else [leaf_all]))
                sp.set(bytes=nbytes)
                rt_telemetry.record("h2d_bytes", nbytes)
                spk_all = jnp.asarray(spk_all)
                pair_all = jnp.asarray(pair_all)
                cols_all = {
                    name: jnp.asarray(col)
                    for name, col in cols_all.items()
                }
                if leaf_all is not None:
                    leaf_all = jnp.asarray(leaf_all)
    # Pass 1 was dispatched async — the wall time here under-measures on
    # the device-resident branch, but the watchdog floors the auto
    # deadline and takes the max over later completed-guard observations,
    # so the seed only has to be the right order of magnitude.
    _seed_pass1(time.perf_counter() - t0)

    # --- Pass 2: bin by partition block, finalize each block. -------------
    # Dropped rows carry an int32-max sentinel > P, so searchsorted over
    # the compacted stream yields both block offsets AND the survivor
    # count (boundary overflow guard: _block_boundaries).
    C0 = min(block_partitions, P)
    output_names = [name for e in cfg.plan for name in e.outputs]
    kept_ids = []
    kept_outputs = {name: [] for name in output_names}
    job = job_id or "aggregate_blocked"

    drain = _StagedDrain()

    def append_record(record: rt_journal.BlockRecord):
        if record.n_kept:
            kept_ids.append(record.ids)
            for name, col in record.outputs.items():
                kept_outputs.setdefault(name, []).append(col)

    def run_range(base, C, gen, end):
        n_blocks = -(-(end - base) // C)
        # The fetch waits for pass 1 (dispatched async) and the search.
        with rt_trace.span("block_offsets", blocks=n_blocks):
            block_starts = host_fetch(
                _block_offsets_dev(
                    spk_all,
                    jnp.asarray(_block_boundaries(base, C, n_blocks))))
        if end == P:  # the plan's last range: its last boundary is >= P
            rt_telemetry.record("pass2_rows", int(block_starts[-1]))
        row_cap = _range_row_cap(block_starts)

        def consume(j, result):
            b_base = base + j * C
            if isinstance(result, _Replay):
                append_record(result.record)
                drain.end_block(j)
                return
            n_kept, ids_sorted, outputs_sorted = result
            # Fail-closed sentinel BEFORE the journal persist: a
            # numerically poisoned block must never become a durable
            # record a later replay would release.
            with rt_trace.span("p2.wait", block=j):
                rt_numeric.check_release(
                    outputs_sorted, n_kept=n_kept,
                    numeric_mode=cfg.numeric_mode,
                    context=f"blocked release (base {b_base})")
                k = int(n_kept)  # sync; gates O(kept) transfers
            if journal is not None:
                # Journaled runs materialize per block (one sync each) so
                # the record is durable the moment the block is consumed —
                # the overlap the staged drain buys is traded for
                # crash-resumability (the overlapped drainer thread takes
                # that sync off the dispatch path; the copies themselves
                # still batch through copy_to_host_async).
                record = _materialize_block_record(ids_sorted,
                                                   outputs_sorted, k,
                                                   b_base)
                journal.put(job, rt_journal.block_key(b_base, C), record)
                append_record(record)
            elif k:
                drain.stage(
                    [kept_ids, *(kept_outputs.setdefault(name, [])
                                 for name in outputs_sorted)],
                    [ids_sorted, *outputs_sorted.values()], k, b_base)
            drain.end_block(j)

        def block_iter():
            for j in range(n_blocks):
                b_base = base + j * C
                if journal is not None:
                    record = journal.get(job,
                                         rt_journal.block_key(b_base, C))
                    if record is not None:
                        rt_telemetry.record("journal_replays", block=j)
                        yield (j, _Replay(record))
                        continue
                lo, hi = int(block_starts[j]), int(block_starts[j + 1])
                if lo == hi and cfg.private_selection:
                    # Private selection keeps empty partitions with
                    # probability 0 (selection_ops.keep_probabilities:
                    # n <= 0 -> 0), so row-less blocks provably emit
                    # nothing — skip their device work. In the sparse
                    # 10^9-partition regime this skips nearly every block.
                    continue
                c_actual = min(C, end - b_base)
                cfg_block = dataclasses.replace(cfg, n_partitions=c_actual)
                yield (j, functools.partial(
                    _block_kernel_dev, spk_all, pair_all, cols_all,
                    leaf_all, lo, hi - lo, b_base, min_v, max_v, mid, stds,
                    _block_noise_key(final_key, gen, j), cfg_block,
                    row_cap, secure_tables))

        dispatched = _dispatch_blocks(block_iter(), consume,
                                      retry_policy=retry, overlap=overlap)
        if dispatched:
            rt_telemetry.record("pass2_block_rows", dispatched * row_cap)

    rt_retry.run_with_degradation(run_range, P, C0, journal=journal,
                                  job_id=job)
    with rt_trace.span("drain"):
        drain.materialize()
    # Each block emits kept partitions in ascending relative id (the compact
    # sort is stable) and blocks are consumed in ascending order, so the
    # concatenation is already globally ascending.
    kept = (np.concatenate(kept_ids) if kept_ids else np.zeros(0, np.int64))
    return kept, {
        name: (np.concatenate(chunks) if chunks else np.zeros(0))
        for name, chunks in kept_outputs.items()
    }
