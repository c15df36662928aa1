"""Multi-chip sharded DP aggregation (shard_map + psum over ICI).

Strategy (SURVEY.md §2.5 "TPU-native equivalent"): the reference's three
keyed shuffles become one on-device exchange —

  1. Rows are sharded by privacy-unit id, so all of a privacy unit's rows
     live on one shard and contribution bounding (the by-pid "shuffle") is
     shard-local. Device-resident inputs (streamed ingest) are resharded
     entirely on device: pid-hash bucketize -> padded jax.lax.all_to_all
     over the mesh axis -> shard-local compaction
     (parallel/reshard.device_reshard_rows_by_pid); only a [D, D] count
     table ever crosses to the host. Host-numpy inputs — which pay one
     upload regardless — take the exact load-balanced host permutation
     (heavy ids greedy-LPT, tail serpentine: shard_rows_by_pid), also
     reachable as the reshard="host" escape hatch.
  2. Each shard computes dense per-partition partial columns
     (executor.partial_columns) — the by-partition "shuffle" is a local
     segment-sum into the dense [0, P) layout.
  3. One lax.psum over the mesh combines the partials; partition selection
     and noise then run replicated (same PRNG key on every shard, so every
     shard holds identical results with no broadcast step).

The collective cost is one all_to_all of the row payload (device-resident
inputs only) plus one psum of (~6 x P) floats per aggregation, riding ICI —
compared to the reference's full data shuffle over the network.
"""

import threading
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from pipelinedp_tpu import executor
from pipelinedp_tpu.ops import segment_ops
from pipelinedp_tpu.ops import selection_ops
from pipelinedp_tpu.parallel.mesh import SHARD_AXIS, round_capacity, shard_map
from pipelinedp_tpu.parallel.reshard import stage_rows_to_mesh
from pipelinedp_tpu.runtime import aot as rt_aot
from pipelinedp_tpu.runtime import entry as rt_entry
from pipelinedp_tpu.runtime import faults as rt_faults
from pipelinedp_tpu.runtime import retry as rt_retry
from pipelinedp_tpu.runtime import trace as rt_trace

# Concurrent multi-device program launches are NOT safe on every
# platform: XLA's CPU collectives rendezvous by arrival order, so two
# shard_map programs dispatched from different host threads can
# interleave their per-device AllReduce participants — each program
# captures some of the device threads and both wait forever for the
# rest (observed as `collective_ops_utils.h ... may be stuck`). Real
# TPU runtimes serialize program launches on the device stream, so the
# hazard is exclusively multi-THREADED hosts: the service worker pool
# (and its megabatch coalescer) is the only place this tree launches
# collectives from more than one thread, so the service brackets its
# lifetime with enable/disable below and every meshed release dispatch
# — solo or megabatched — then runs under the lock and BLOCKS on its
# outputs before releasing it, so one program's collectives fully
# drain before the next program's begin. Outside a service the guard
# stands down entirely: single-threaded callers keep XLA's async
# dispatch pipelining (forcing a drain per launch costs ~20% on
# dispatch-heavy meshed paths like percentile descent). An RLock, so
# an elastic re-entry (device-loss fallback re-dispatching inside the
# guarded region) cannot self-deadlock.
_COLLECTIVE_LAUNCH_LOCK = threading.RLock()
_COLLECTIVE_SERIALIZE_LOCK = threading.Lock()
_collective_serialize_depth = 0  # guarded by _COLLECTIVE_SERIALIZE_LOCK


def enable_collective_serialization() -> None:
    """Turns on collective-launch serialization (refcounted). Called by
    every component that launches meshed programs from worker threads
    — the service worker pool — BEFORE its first worker starts."""
    global _collective_serialize_depth
    with _COLLECTIVE_SERIALIZE_LOCK:
        _collective_serialize_depth += 1


def disable_collective_serialization() -> None:
    """Drops one serialization hold, after the holder's workers have
    all joined."""
    global _collective_serialize_depth
    with _COLLECTIVE_SERIALIZE_LOCK:
        _collective_serialize_depth = max(0, _collective_serialize_depth - 1)


def _collective_launch(dispatch):
    """Runs `dispatch` (a thunk returning jax outputs); while any
    multi-threaded launcher holds a serialization enable, the dispatch
    runs under the collective-launch lock and blocks until the program
    has drained."""
    with _COLLECTIVE_SERIALIZE_LOCK:
        serialize = _collective_serialize_depth > 0
    if not serialize:
        return dispatch()
    with _COLLECTIVE_LAUNCH_LOCK:
        return jax.block_until_ready(dispatch())


def shard_rows_by_pid(pid: np.ndarray, pk: np.ndarray, values: np.ndarray,
                      valid: np.ndarray, n_shards: int):
    """Reorders + pads rows so each privacy id's rows land on exactly one
    shard, with shards load-balanced by ROW COUNT, all shards equal-sized.

    Assignment is two-phase load balancing: the heaviest few thousand ids go
    greedy-LPT (each to the least-loaded shard, catching hot-id skew), and
    the long tail — whose counts are near-uniform — is laid out serpentine
    over the shards in one vectorized pass, so the host cost stays O(U)
    numpy, not O(U) Python, at hundreds of millions of unique ids. Per-shard
    capacity is rounded up keeping 4 significant bits (<= 12.5% slack —
    bounded jit-cache shapes without power-of-two's up-to-2x waste).

    Returns arrays of length n_shards * rows_per_shard whose s-th block is
    shard s's rows (invalid-padded) — the layout shard_map expects for a
    leading-axis split.
    """
    import heapq
    _, inverse, ucounts = np.unique(pid, return_inverse=True,
                                    return_counts=True)
    heavy_first = np.argsort(-ucounts, kind="stable")
    shard_of_uid = np.empty(len(ucounts), dtype=np.int64)
    n_greedy = min(len(ucounts), max(n_shards * 64, 4096))
    heap = [(0, s) for s in range(n_shards)]
    for uid in heavy_first[:n_greedy]:
        load, s = heapq.heappop(heap)
        shard_of_uid[uid] = s
        heapq.heappush(heap, (load + int(ucounts[uid]), s))
    tail = heavy_first[n_greedy:]
    if len(tail):
        # Serpentine over shards ordered lightest-first after phase 1.
        shard_order = np.array([s for _, s in sorted(heap)], dtype=np.int64)
        rank = np.arange(len(tail))
        block, offset = divmod(rank, n_shards)
        pos = np.where(block % 2 == 0, offset, n_shards - 1 - offset)
        shard_of_uid[tail] = shard_order[pos]
    shard = shard_of_uid[inverse]
    order = np.argsort(shard, kind="stable")
    counts = np.bincount(shard, minlength=n_shards)
    per_shard = round_capacity(int(counts.max()))
    n_out = n_shards * per_shard

    out_pid = np.zeros(n_out, dtype=pid.dtype)
    out_pk = np.full(n_out, -1, dtype=pk.dtype)
    out_values = np.zeros((n_out,) + values.shape[1:], dtype=values.dtype)
    out_valid = np.zeros(n_out, dtype=bool)

    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    # Position of each (sorted) row inside its shard block.
    positions = np.arange(len(pid)) - offsets[shard[order]]
    dest = shard[order] * per_shard + positions
    out_pid[dest] = pid[order]
    out_pk[dest] = pk[order]
    out_values[dest] = values[order]
    out_valid[dest] = valid[order]
    return out_pid, out_pk, out_values, out_valid


def _combine_partials(cols, cfg):
    """One psum combines the shards' partial columns; numeric_mode="safe"
    routes float partials through the compensated cross-shard sum so the
    combine cannot re-introduce the rounding the compensated segment
    sums removed. cfg.numeric_mode is static, so the default mode
    compiles the identical psum program it always has."""
    if cfg.numeric_mode == "safe":
        return jax.tree.map(
            lambda x: segment_ops.compensated_psum(x, SHARD_AXIS), cols)
    return jax.tree.map(lambda x: jax.lax.psum(x, SHARD_AXIS), cols)


@partial(jax.jit, static_argnames=("cfg", "mesh"))
def _sharded_release_kernel(pid, pk, values, valid, min_v, max_v, min_s,
                            max_s, mid, stds, rng_key,
                            cfg: executor.KernelConfig, mesh: Mesh,
                            secure_tables=None):
    """The dense release over the mesh: executor.aggregate_release_trace
    as each shard's body. Rows arrive sharded by privacy id; the body
    folds the shard index into the sampling key, combines the shards'
    partial columns through _combine_partials (looked up here at trace
    time and handed to the body), and runs selection, noise and the
    kept-first compaction replicated over the combined columns — every
    device holds the identical O(kept)-transferable release and the
    driver fetches one scalar gate."""

    def per_shard(pid_s, pk_s, values_s, valid_s, stds_r, key_r, tables_r):
        return executor.aggregate_release_trace(
            pid_s, pk_s, values_s, valid_s, min_v, max_v, min_s, max_s, mid,
            stds_r, key_r, cfg, tables_r, psum_axis=SHARD_AXIS,
            combine=_combine_partials)

    fn = shard_map(per_shard,
                   mesh=mesh,
                   in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                             P(SHARD_AXIS), P(), P(), P()),
                   out_specs=P())
    return fn(pid, pk, values, valid, stds, rng_key, secure_tables)


@partial(jax.jit,
         static_argnames=("l0", "n_partitions", "selection", "mesh"))
def _sharded_select_release_kernel(pid, pk, valid, rng_key, l0: int,
                                   n_partitions: int,
                                   selection: selection_ops.SelectionParams,
                                   mesh: Mesh):
    """Standalone selection over the mesh: executor.select_release_trace
    as each shard's body (shard-local counts, one psum of int32[P],
    decisions and compaction replicated)."""

    def per_shard(pid_s, pk_s, valid_s, key_r):
        return executor.select_release_trace(pid_s, pk_s, valid_s, key_r,
                                             l0, n_partitions, selection,
                                             psum_axis=SHARD_AXIS)

    fn = shard_map(per_shard,
                   mesh=mesh,
                   in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                             P()),
                   out_specs=(P(), P()))
    return fn(pid, pk, valid, rng_key)


@partial(jax.jit, static_argnames=("cfg", "mesh"))
def _sharded_batched_release_kernel(pid, pk, values, valid, min_v, max_v,
                                    min_s, max_s, mid, stds, rng_keys,
                                    cfg: executor.KernelConfig, mesh: Mesh,
                                    secure_tables=None):
    """Lane-stacked _sharded_release_kernel: ONE launch releases L jobs
    over the mesh. Row arrays carry a leading job-lane axis over the
    per-shard blocked layout ([L, D*cap] / [L, D*cap, V], every lane
    staged by the SAME host LPT permutation its solo run would take) and
    rng_keys is the [L, 2] stack of the jobs' own base keys. The same
    body, vmapped over the lane axis inside each shard — fold_in of the
    shard index, the combine of partial columns and the replicated
    finalize/compaction all batch elementwise, so lane l's release is
    bit-identical to its solo meshed run."""

    def per_shard(pid_s, pk_s, values_s, valid_s, stds_r, keys_r,
                  tables_r):

        def lane(pid_l, pk_l, values_l, valid_l, key_l):
            return executor.aggregate_release_trace(
                pid_l, pk_l, values_l, valid_l, min_v, max_v, min_s,
                max_s, mid, stds_r, key_l, cfg, tables_r,
                psum_axis=SHARD_AXIS, combine=_combine_partials)

        return jax.vmap(lane)(pid_s, pk_s, values_s, valid_s, keys_r)

    fn = shard_map(per_shard,
                   mesh=mesh,
                   in_specs=(P(None, SHARD_AXIS), P(None, SHARD_AXIS),
                             P(None, SHARD_AXIS), P(None, SHARD_AXIS),
                             P(), P(), P()),
                   out_specs=P())
    return fn(pid, pk, values, valid, stds, rng_keys, secure_tables)


@partial(jax.jit,
         static_argnames=("l0", "n_partitions", "selection", "mesh"))
def _sharded_batched_select_release_kernel(
        pid, pk, valid, rng_keys, l0: int, n_partitions: int,
        selection: selection_ops.SelectionParams, mesh: Mesh):
    """Lane-stacked _sharded_select_release_kernel (same lane-axis and
    bit-identity contract as _sharded_batched_release_kernel)."""

    def per_shard(pid_s, pk_s, valid_s, keys_r):

        def lane(pid_l, pk_l, valid_l, key_l):
            return executor.select_release_trace(pid_l, pk_l, valid_l,
                                                 key_l, l0, n_partitions,
                                                 selection,
                                                 psum_axis=SHARD_AXIS)

        return jax.vmap(lane)(pid_s, pk_s, valid_s, keys_r)

    fn = shard_map(per_shard,
                   mesh=mesh,
                   in_specs=(P(None, SHARD_AXIS), P(None, SHARD_AXIS),
                             P(None, SHARD_AXIS), P()),
                   out_specs=(P(), P()))
    return fn(pid, pk, valid, rng_keys)


# Compile/dispatch attribution + AOT executable routing for the dense
# meshed entry points (runtime/aot.py wraps runtime/trace.probe_jit).
_sharded_release_kernel = rt_aot.aot_probe(
    "sharded_release_kernel", _sharded_release_kernel,
    static_argnames=("cfg", "mesh"))
_sharded_batched_release_kernel = rt_aot.aot_probe(
    "sharded_batched_release_kernel", _sharded_batched_release_kernel,
    static_argnames=("cfg", "mesh"))
_sharded_batched_select_release_kernel = rt_aot.aot_probe(
    "sharded_batched_select_release_kernel",
    _sharded_batched_select_release_kernel,
    static_argnames=("l0", "n_partitions", "selection", "mesh"))
_sharded_select_release_kernel = rt_aot.aot_probe(
    "sharded_select_release_kernel", _sharded_select_release_kernel,
    static_argnames=("l0", "n_partitions", "selection", "mesh"))


def _fallback_select_partitions(args, kwargs, job):
    """Elastic floor of sharded_select_partitions: the single-device
    selection release kernel on the surviving device. The selection key
    (key_sel half of the split) is replicated on the mesh, so the
    single-device decisions are the same release."""

    def go(mesh, pid, pk, valid, rng_key, l0, n_partitions, selection,
           reshard="auto", retry=None, job_id=None):
        del mesh, reshard, job_id
        from pipelinedp_tpu.parallel.large_p import _pad_rows
        rows_in = _pad_rows(round_capacity(len(pid)), pid, pk, valid)
        return rt_retry.retry_call(
            lambda: executor.select_partitions_release_kernel(
                *(jnp.asarray(a) for a in rows_in), rng_key, l0,
                n_partitions, selection),
            retry, what="single-device select_partitions dispatch")

    return go(*args, **kwargs)


def _fallback_aggregate_arrays(args, kwargs, job):
    """Elastic floor of sharded_aggregate_arrays: the single-device
    release kernel (identical output contract; the finalize/noise key is
    the replicated half of the same split, so released noise is the
    same release)."""

    def go(mesh, pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
           stds, rng_key, cfg, secure_tables=None, reshard="auto",
           retry=None, job_id=None):
        del mesh, reshard, job_id
        from pipelinedp_tpu.parallel.large_p import _pad_rows
        if isinstance(values, jax.Array):
            values = values.astype(executor._ftype())
        else:
            values = np.asarray(values, dtype=np.dtype(executor._ftype()))
        rows_in = _pad_rows(round_capacity(len(pid)), pid, pk, values,
                            valid)
        return rt_retry.retry_call(
            lambda: executor.aggregate_release_kernel(
                *(jnp.asarray(a) for a in rows_in), min_v, max_v,
                min_s, max_s, mid, jnp.asarray(stds), rng_key, cfg,
                secure_tables),
            retry, what="single-device aggregation dispatch")

    return go(*args, **kwargs)


@rt_entry.runtime_entry("sharded_select_partitions",
                        fallback=_fallback_select_partitions)
def sharded_select_partitions(mesh: Mesh, pid, pk, valid, rng_key, l0: int,
                              n_partitions: int,
                              selection: selection_ops.SelectionParams,
                              reshard: str = "auto",
                              retry: rt_retry.RetryPolicy = None,
                              job_id: Optional[str] = None):
    """Standalone partition selection over the mesh: shard rows by privacy
    id (on-device all_to_all for device-resident inputs, host LPT
    permutation otherwise — see stage_rows_to_mesh), count shard-locally
    (executor.select_partition_counts), psum the int32[P] count vector
    over ICI, select replicated.

    Runtime knobs (shared entry, runtime/entry.py): timeout_s=/watchdog=
    deadlines, job_id= health attribution, elastic=/min_devices=
    device-loss tolerance (the one-device floor runs the single-device
    selection release kernel — the selection key is replicated, so
    decisions are the same release).

    Returns (n_kept, ids_sorted int32[n_partitions]), replicated across
    the mesh: kept ids compacted to the front inside the same program,
    in np.nonzero's order (executor.select_release_trace).
    """
    # Zero-width values column: selection never reads values, and a real
    # column would cost an O(rows) gather/scatter (or exchange) in the
    # reshard.
    if isinstance(pid, jax.Array):
        dummy_values = jnp.zeros((pid.shape[0], 0), jnp.float32)
    else:
        dummy_values = np.zeros((len(pid), 0), np.float32)
    pid, pk, _, valid = stage_rows_to_mesh(mesh, pid, pk, dummy_values,
                                           valid, reshard)
    # Retried dispatches reuse the identical rng_key: a retry is a replay
    # of the same selection decisions, never a second draw.
    with rt_trace.span("dispatch"):
        return _collective_launch(lambda: rt_retry.retry_call(
            lambda: _sharded_select_release_kernel(
                pid, pk, valid, rng_key, l0, n_partitions, selection, mesh),
            retry, what="sharded select_partitions dispatch"))


@rt_entry.runtime_entry("sharded_aggregate_arrays",
                        fallback=_fallback_aggregate_arrays)
def sharded_aggregate_arrays(mesh: Mesh, pid, pk, values, valid, min_v, max_v,
                             min_s, max_s, mid, stds, rng_key,
                             cfg: executor.KernelConfig, secure_tables=None,
                             reshard: str = "auto",
                             retry: rt_retry.RetryPolicy = None,
                             job_id: Optional[str] = None):
    """Shards rows by pid over `mesh` and runs the two-phase release program.

    Accepts host numpy arrays or device-resident jax arrays (any length);
    device-resident columns reshard over ICI without touching the host
    (stage_rows_to_mesh). Returns the compacted
    (n_kept, ids_sorted, outputs_sorted, row_count) release of
    executor.aggregate_release_kernel, replicated across the mesh
    (kept-first ordering inside the one program, so the caller fetches a
    scalar gate + O(kept) columns).

    Runtime knobs (shared entry, runtime/entry.py): timeout_s=/watchdog=
    deadlines, job_id= health attribution, and elastic=/min_devices=
    device-loss tolerance — a device-fatal failure rebuilds a smaller
    mesh from the survivors and re-enters; the one-device floor runs the
    single-device release kernel (the finalize/noise key is replicated, so
    every geometry releases the same noise).
    """
    # Chaos ingest seam (no-op without an active extreme_values fault).
    _poisoned = rt_faults.maybe_extreme_rows(values, pk)
    if _poisoned is not None:
        values = _poisoned
    pid, pk, values, valid = stage_rows_to_mesh(
        mesh, pid, pk, values, valid, reshard,
        values_dtype=np.dtype(executor._ftype()))
    # Retried dispatches reuse the identical rng_key, so the redrawn noise
    # is bit-identical — a retry replays the same release.
    with rt_trace.span("dispatch"):
        return _collective_launch(lambda: rt_retry.retry_call(
            lambda: _sharded_release_kernel(
                pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
                jnp.asarray(stds), rng_key, cfg, mesh, secure_tables),
            retry, what="sharded aggregation dispatch"))
