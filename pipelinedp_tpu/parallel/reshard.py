"""Device-native pid reshard: all_to_all over ICI instead of host staging.

Every meshed aggregation needs each privacy unit's rows co-located on one
shard (contribution bounding is global per id). The original implementation
(sharded.shard_rows_by_pid) permutes all rows ON THE HOST and re-uploads —
an O(rows) host round trip that forfeits the mesh's D-fold row-capacity
claim the moment the inputs are already device-resident (streamed ingest).
This module keeps the rows in HBM end to end:

  1. **Bucketize** (per shard, on device): dest(row) = mix(pid) mod D — a
     salted murmur-style hash, identical on every shard, so all rows of a
     privacy id map to one destination no matter where they start.
  2. **Count exchange** (the one host fetch): the [D, D] send-count table
     is REDUCED ON DEVICE (one psum for the receive loads, one pmax for
     the largest send bucket) to a replicated int32[3] stats vector —
     [max send bucket, max receive load, total valid rows] — and only
     that crosses to the host (mesh.host_fetch). This is what makes the
     exchange safe on a multi-controller mesh: a process can never
     address another host's shard of the table, but every process can
     read its own replica of the reduced stats, and because the stats
     are bit-identical everywhere, every controller derives the SAME
     static capacities and compiles the SAME exchange program (divergent
     capacities would deadlock the collective).
  3. **Pack and padded all_to_all**: each shard sorts its rows ONCE by
     destination with every column riding the key as a sort payload
     (jax.lax.sort, stable, invalid rows last), so bucket d is the
     contiguous run of the sorted columns that holds destination d: a
     dynamic_slice of cap_send rows, masked past the bucket's count
     with the invalid fills. ONE jax.lax.all_to_all per column moves
     the [D, cap_send] buckets over the SHARD_AXIS mesh axis (ICI
     within a host, DCN across hosts on a pod), and one more moves the
     [D] send counts — the received counts stand in for a plane of
     valid flags.
  4. **Compaction**: received bucket d is valid in its first
     r_counts[d] rows, so each shard copies the buckets in order
     d = 0..D-1 into one buffer at offs[d] = the rows received before
     bucket d (dynamic_update_slice: each bucket's invalid tail is
     overwritten by the next bucket's head) and slices to the host-known
     output capacity — the order a stable valid-first sort of the
     received slots gives, and the dense leading-axis layout every
     meshed kernel consumes; valid = arange < rows received.

No row is addressed one at a time in either program: a per-row gather or
scatter runs at 9-26 ns a row on a TPU v5e where a sort payload or a
contiguous copy streams (PERF.md, PR 36: nine such gathers were 3.0 s of
a 3.4 s device job at 2^24 rows a shard), so the send counts are D
masked sums and rows move only as sort payloads and slices.

Capacity caching: the rounded (cap_send, out_cap) pair is cached per
exchange geometry (mesh devices, padded per-shard capacity, salt, value
column shape/dtype). A repeated exchange at a cached geometry dispatches
the exchange kernel OPTIMISTICALLY at the cached capacities — overlapping
the stats fetch instead of blocking on it — and only falls back to a
re-dispatch when the fetched stats show the cached capacity no longer
fits (counted in the ``reshard_capacity_reuse`` telemetry counter when it
does fit). The cache is per-process and keyed purely by call geometry, so
every controller of a multi-process mesh makes the same hit/miss decision
and stays on the same compiled program.

Load balance, re-derived for the hash-bucketed layout: shard_rows_by_pid
balanced ROW counts exactly (greedy-LPT heavy ids + serpentine tail), so
its per-shard capacity was max-load-optimal up to round_capacity slack.
Hash bucketing balances UNIQUE IDS in expectation instead: with U ids of
weights w_1..w_U (sum n), a shard's expected load is n/D and the deviation
is driven by the heaviest ids (Var = sum w_i^2 * (D-1)/D^2) — near-uniform
workloads land within a few percent of n/D, while a single id holding a
large fraction of all rows makes its shard irreducibly hot (the same
irreducible case greedy-LPT had). Padding waste is bounded and asserted:
the output capacity is round_capacity(max shard load) (<= 12.5% slack over
the measured max), and a >2x max/mean skew logs a warning naming the
hash-balance assumption that broke.

The host path (sharded.shard_rows_by_pid) remains for host-numpy inputs —
where one upload is unavoidable and the exact LPT balance is free — and as
the reshard="host" escape hatch on every meshed entry point.
"""

import collections
import contextlib
import functools
import logging
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from pipelinedp_tpu.runtime.concurrency import guarded_by

from pipelinedp_tpu.parallel import mesh as mesh_lib
from pipelinedp_tpu.parallel.mesh import (SHARD_AXIS, host_fetch,
                                          round_capacity, row_sharding,
                                          rows_per_shard, shard_map)
from pipelinedp_tpu.runtime import faults as rt_faults
from pipelinedp_tpu.runtime import retry as rt_retry
from pipelinedp_tpu.runtime import telemetry as rt_telemetry
from pipelinedp_tpu.runtime import trace as rt_trace
from pipelinedp_tpu.runtime import watchdog as rt_watchdog

# Fetches at or below this many elements are control-plane sized; the
# transfer-guard treats anything larger as row data.
_CONTROL_TABLE_ELEMENTS = 1 << 12


def _dest_shard(pid, n_shards: int, salt: int):
    """Destination shard of each row: murmur-mixed pid hash mod D.

    A pure function of pid (identical on every shard), so co-location
    needs no coordination. int64 pids fold to uint32 first — collisions
    only merge ids onto one shard, never split one id across shards.
    """
    from pipelinedp_tpu.executor import _hash_mix
    h = _hash_mix(pid.astype(jnp.uint32) * jnp.uint32(0x9E3779B9) ^
                  jnp.uint32(salt))
    return (h % jnp.uint32(n_shards)).astype(jnp.int32)


def _send_dest(pid, valid, n_shards: int, salt: int):
    """Destination shard of each row, n_shards for an invalid one (so a
    sort by it puts the invalid rows last)."""
    return jnp.where(valid, _dest_shard(pid, n_shards, salt), n_shards)


def _send_counts(dest, n_shards: int):
    """int32[D]: this shard's rows per destination, as D masked sums —
    streaming passes, where a scatter-add into D bins visits the rows one
    at a time."""
    return jnp.stack([jnp.sum(dest == d, dtype=jnp.int32)
                      for d in range(n_shards)])


@functools.partial(jax.jit,
                   static_argnames=("n_shards", "salt", "mesh"))
def _count_stats_kernel(pid, valid, n_shards: int, salt: int, mesh: Mesh):
    """Replicated int32[3] = [max send bucket, max receive load, total
    valid rows]: the [D, D] send-count table reduced on device (psum for
    the per-destination receive loads, pmax for the largest send bucket).
    The only data the host sees before the exchange — and, being fully
    replicated, the only form a multi-controller process could fetch at
    all (each reads its local replica; no host ever addresses another
    host's table shard)."""

    def per_shard(pid_s, valid_s):
        counts = _send_counts(_send_dest(pid_s, valid_s, n_shards, salt),
                              n_shards)
        recv = jax.lax.psum(counts, SHARD_AXIS)
        max_send = jax.lax.pmax(counts.max(), SHARD_AXIS)
        return jnp.stack([max_send, recv.max(), recv.sum()])

    fn = shard_map(per_shard, mesh=mesh,
                   in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                   out_specs=P())
    return fn(pid, valid)


@functools.partial(jax.jit,
                   static_argnames=("cap_send", "out_cap", "n_shards",
                                    "salt", "mesh"))
def _exchange_kernel(pid, pk, values, valid, cap_send: int, out_cap: int,
                     n_shards: int, salt: int, mesh: Mesh):
    """Pack -> all_to_all -> compact, one jit program, zero host traffic
    and no per-row random access: the rows travel as the payloads of one
    sort and as contiguous slices.

    Each shard sorts its rows by destination with every column riding the
    key, cuts bucket d as the contiguous run of the sorted columns that
    holds destination d (invalid-padded to cap_send), exchanges bucket d
    with shard d over the mesh axis together with the [D] send counts,
    then copies the received buckets' valid heads back to back and slices
    to the host-known out_cap — the dense leading-axis layout the meshed
    kernels consume. A bucket longer than cap_send, or more received rows
    than out_cap, is truncated: the caller's stats fetch sees that the
    capacities did not fit and dispatches again.
    """
    # Rows out a shard: out_cap of the D * cap_send received slots.
    length = min(out_cap, n_shards * cap_send)

    def per_shard(pid_s, pk_s, values_s, valid_s):
        if values_s.ndim == 1:
            value_cols = [values_s]
        else:
            value_cols = [values_s[:, c] for c in range(values_s.shape[1])]
        fills = [0, -1] + [0] * len(value_cols)

        with jax.named_scope("exchange_pack"):
            dest = _send_dest(pid_s, valid_s, n_shards, salt)
            _, *sorted_cols = jax.lax.sort(
                (dest, pid_s, pk_s, *value_cols), num_keys=1,
                is_stable=True)
            held = _send_counts(dest, n_shards)
            starts = jnp.cumsum(held, dtype=jnp.int32) - held
            counts = jnp.minimum(held, cap_send)
            slot_valid = (jnp.arange(cap_send, dtype=jnp.int32)[None, :] <
                          counts[:, None])

            def buckets(col, fill):
                padded = jnp.concatenate(
                    [col, jnp.full((cap_send,), fill, col.dtype)])
                runs = jnp.stack([
                    jax.lax.dynamic_slice_in_dim(padded, starts[d],
                                                 cap_send)
                    for d in range(n_shards)
                ])
                return jnp.where(slot_valid, runs,
                                 jnp.asarray(fill, col.dtype))

            packed = [buckets(c, f) for c, f in zip(sorted_cols, fills)]

        def exchange(x):
            return jax.lax.all_to_all(x, SHARD_AXIS, 0, 0, tiled=True)

        r_counts = exchange(counts)
        received = [exchange(b) for b in packed]

        with jax.named_scope("exchange_compact"):
            offs = jnp.cumsum(r_counts, dtype=jnp.int32) - r_counts

            def compact(r_col, fill):
                # Bucket d's invalid tail is overwritten by bucket d+1's
                # head; the last tail is fill like the buffer's own rows.
                out = jnp.full((length + cap_send,), fill, r_col.dtype)
                for d in range(n_shards):
                    out = jax.lax.dynamic_update_slice_in_dim(
                        out, r_col[d], offs[d], axis=0)
                return out[:length]

            o_pid, o_pk, *o_cols = [
                compact(c, f) for c, f in zip(received, fills)
            ]
            if values_s.ndim == 1:
                o_val = o_cols[0]
            elif o_cols:
                o_val = jnp.stack(o_cols, axis=1)
            else:
                o_val = jnp.zeros((length, 0), values_s.dtype)
            o_valid = (jnp.arange(length, dtype=jnp.int32) <
                       r_counts.sum())
            return o_pid, o_pk, o_val, o_valid

    fn = shard_map(per_shard, mesh=mesh, in_specs=(P(SHARD_AXIS),) * 4,
                   out_specs=(P(SHARD_AXIS),) * 4)
    return fn(pid, pk, values, valid)


# Compile/dispatch attribution for the reshard entry points (trace
# summaries separate all_to_all compiles from steady-state exchanges).
_count_stats_kernel = rt_trace.probe_jit("reshard_count_stats",
                                         _count_stats_kernel)
_exchange_kernel = rt_trace.probe_jit("reshard_exchange", _exchange_kernel)


def _row_payload_bytes(*cols) -> int:
    """Total byte size of the row columns a staging path moves."""
    return int(sum(getattr(c, "nbytes", 0) for c in cols))


def _pad_and_shard(mesh: Mesh, per_shard_cap: int, pid, pk, values, valid):
    """Pads device columns to n_shards * per_shard_cap (invalid-marked) and
    lays them out as an even leading-axis split over the mesh — all on
    device (device_put between device layouts is a device-to-device copy,
    ICI on a pod). Columns already at the target length and layout (the
    multi-host ingest uploads per-process shards pre-padded to exactly
    this split) pass through untouched — no eager cross-process copy."""
    n_shards = mesh.devices.size
    pad = n_shards * per_shard_cap - pid.shape[0]
    sharding = row_sharding(mesh)

    def padded(col, fill):
        if pad:
            widths = ((0, pad),) + ((0, 0),) * (col.ndim - 1)
            col = jnp.pad(col, widths, constant_values=fill)
        if getattr(col, "sharding", None) == sharding:
            return col
        return jax.device_put(col, sharding)

    return (padded(pid, 0), padded(pk, -1), padded(values, 0),
            padded(valid, False))


# Rounded (cap_send, out_cap) pairs per exchange geometry, insertion-
# ordered for deterministic FIFO eviction. Per-process and keyed purely
# by call geometry, so every controller of a multi-process mesh makes
# the same hit/miss decision (a divergent static capacity would compile
# divergent collectives and deadlock the exchange).
_capacity_lock = threading.Lock()
_capacity_cache: "collections.OrderedDict[tuple, Tuple[int, int]]" = \
    collections.OrderedDict()
_CAPACITY_CACHE_MAX = 64
_GUARDED_BY = guarded_by("_capacity_lock", "_capacity_cache")


def reset_capacity_cache() -> None:
    """Drops the cached exchange capacities (test isolation)."""
    with _capacity_lock:
        _capacity_cache.clear()


def _capacity_key(mesh: Mesh, per_in: int, salt: int, values) -> tuple:
    return (tuple(getattr(d, "id", d) for d in mesh.devices.flat),
            int(per_in), int(salt), tuple(values.shape[1:]),
            str(values.dtype))


def _warn_skew(max_recv: int, total: int, n_shards: int) -> None:
    if total and max_recv * n_shards > 2 * total:
        logging.warning(
            "device reshard: hash-bucketed max shard load %d > 2x mean "
            "(%.0f) — a few privacy ids dominate the row mass, so the "
            "hash balance assumption (load ~ n/D) does not hold for this "
            "input; the hot shard bounds the padded capacity.", max_recv,
            total / n_shards)


def device_reshard_rows_by_pid(mesh: Mesh, pid, pk, values, valid,
                               salt: int = 0):
    """Device-native counterpart of sharded.shard_rows_by_pid.

    Takes device-resident row columns (any one-device or mesh layout),
    returns (pid, pk, values, valid) of length n_shards * out_cap laid out
    as an even leading-axis split over `mesh`, every privacy id's rows on
    exactly one shard, invalid-padded. Rows never visit the host; the only
    device->host traffic is the replicated int32[3] count-stats vector
    (mesh.host_fetch) — multi-controller safe, since each process reads
    its own replica of the on-device-reduced table.

    Repeated exchanges at a cached geometry dispatch optimistically at
    the cached capacities, overlapping the stats fetch with the exchange
    instead of serializing capacity-sync -> dispatch; the fetched stats
    then either confirm the fit (reshard_capacity_reuse) or trigger one
    corrective re-dispatch at the exact capacities (rare: the row
    distribution grew past the cached bucket).
    """
    n_shards = mesh.devices.size
    n = pid.shape[0]
    if n_shards == 1:
        cap = round_capacity(n)
        return _pad_and_shard(mesh, cap, pid, pk, values, valid)
    per_in = rows_per_shard(n, n_shards)
    pid, pk, values, valid = _pad_and_shard(mesh, per_in, pid, pk, values,
                                            valid)
    stats_dev = _count_stats_kernel(pid, valid, n_shards, salt, mesh)
    key = _capacity_key(mesh, per_in, salt, values)
    with _capacity_lock:
        cached = _capacity_cache.get(key)
    out = None
    if cached is not None:
        # Optimistic dispatch at the cached capacities: the exchange
        # compiles/runs while the stats land, so the steady-state path
        # never blocks on the capacity sync before dispatching.
        out = _exchange_kernel(pid, pk, values, valid, cached[0],
                               cached[1], n_shards, salt, mesh)
    max_send, max_recv, total = (
        int(x) for x in host_fetch(stats_dev))
    if cached is not None and max_send <= cached[0] and \
            max_recv <= cached[1]:
        rt_telemetry.record("reshard_capacity_reuse")
        _warn_skew(max_recv, total, n_shards)
        return out
    cap_send = round_capacity(max_send)
    out_cap = round_capacity(max_recv)
    # Padding-waste bound: round_capacity guarantees <= 12.5% slack over
    # the measured max shard load (+ the 8-row floor). Asserted so a
    # future capacity-rounding change cannot silently break the memory
    # story this reshard is sold on.
    assert out_cap <= max(-(-9 * max_recv) // 8, 8), (out_cap, max_recv)
    with _capacity_lock:
        _capacity_cache[key] = (cap_send, out_cap)
        while len(_capacity_cache) > _CAPACITY_CACHE_MAX:
            _capacity_cache.popitem(last=False)
    _warn_skew(max_recv, total, n_shards)
    return _exchange_kernel(pid, pk, values, valid, cap_send, out_cap,
                            n_shards, salt, mesh)


def stage_rows_to_mesh(mesh: Mesh, pid, pk, values, valid,
                       reshard: str = "auto",
                       values_dtype: Optional[np.dtype] = None):
    """Shared input staging of every meshed entry point: rows in (host or
    device), pid-co-located mesh-sharded rows out.

    reshard:
      * "auto" (default) — device-resident inputs take the collective
        reshard (rows never touch the host); host inputs take the exact
        LPT host permutation (they pay one upload either way).
      * "host" — force the host permutation (escape hatch: exact row
        balance, or a platform without all_to_all).
      * "device" — force the collective (host inputs are uploaded once,
        unbalanced, then exchanged on device).

    Both permutations are pure functions of the TARGET mesh geometry:
    the collective destination is hash(pid) mod D and the host path is
    an LPT layout over D shards, with nothing cached against the mesh
    the rows were previously staged for. That is what makes elastic
    mesh degradation (runtime/retry.run_with_mesh_degradation) a plain
    re-entry: after a device loss the driver calls this again with the
    shrunken mesh and the permutation rebuilds for the new D — already
    invalid-padded inputs restage correctly because every kernel masks
    by `valid`.

    Multi-controller meshes (is_fully_addressable False): device-resident
    inputs must be GLOBAL arrays over the mesh (the multi-host ingest,
    ingest.encode_local_shard_to_mesh, builds them from per-process
    shards), and the collective exchange is the only reshard —
    reshard='host' is rejected and a failed collective propagates
    instead of degrading, since no process can materialize the other
    hosts' rows. Host-numpy inputs are accepted under the standard
    multi-controller contract that every process passes the identical
    array (each computes the same permutation and uploads it replicated).
    """
    if reshard not in ("auto", "host", "device"):
        raise ValueError(f"reshard must be auto|host|device, got {reshard}")
    if reshard == "host" and not mesh_lib.is_fully_addressable(mesh):
        raise ValueError(
            "reshard='host' is unavailable on a multi-controller mesh: "
            "the LPT permutation needs every row materialized on one "
            "host, and no process can address the other hosts' shards. "
            "Use reshard='auto' (the collective exchange) instead.")
    device_resident = isinstance(pid, jax.Array)
    use_device = (reshard == "device" or
                  (reshard == "auto" and device_resident))
    if use_device:
        if values_dtype is not None:
            values = values.astype(values_dtype)
        if not device_resident:
            pid, pk, values, valid = (jnp.asarray(pid), jnp.asarray(pk),
                                      jnp.asarray(values),
                                      jnp.asarray(valid))
        try:
            # The collective exchange runs under its own watchdog deadline
            # (when one is active on this thread): a hang on the
            # all_to_all fabric surfaces as BlockTimeoutError and degrades
            # to the host permutation exactly like a failed collective.
            # The span carries the exchanged row-payload byte count so
            # trace summaries attribute collective volume.
            with rt_watchdog.guard("collective"), \
                    rt_trace.span(
                        "reshard.collective",
                        bytes=_row_payload_bytes(pid, pk, values, valid)):
                # A device LOST during the exchange is not a collective
                # failure the host permutation can route around — the
                # mesh itself contains a dead chip — so device-fatal
                # errors propagate to the elastic degradation loop
                # (classified below), which rebuilds a smaller mesh and
                # re-derives this permutation for the new geometry.
                rt_faults.maybe_fail("device_loss", point="collective")
                rt_faults.maybe_fail("collective")
                rt_faults.maybe_hang(point="collective")
                return device_reshard_rows_by_pid(mesh, pid, pk, values,
                                                  valid)
        except Exception as e:  # noqa: BLE001 - classified below
            if not _is_collective_failure(e):
                raise
            if not mesh_lib.is_fully_addressable(mesh):
                # A multi-controller mesh has no host permutation to
                # degrade to: no process can materialize the other
                # hosts' rows, so the failure propagates (the elastic
                # loop may still rebuild a smaller mesh if the cause is
                # device-fatal; a plain collective fault is terminal
                # here, exactly like a failed psum would be).
                logging.warning(
                    "device collective reshard failed on a "
                    "multi-controller mesh (%s) — the host LPT fallback "
                    "needs every row addressable on one host, so the "
                    "failure propagates.", type(e).__name__)
                raise
            # The fallback is a transient-style recovery attempt and
            # spends the job-wide retry budget (exhaustion raises typed
            # instead of grinding through composed chaos faults).
            rt_retry.consume_retry_budget("reshard host fallback")
            rt_telemetry.record("reshard_host_fallbacks")
            logging.warning(
                "device collective reshard failed (%s: %s); gracefully "
                "degrading to the host LPT permutation — rows stage "
                "through the host for this aggregation (one O(rows) "
                "round trip), results are unchanged.", type(e).__name__,
                str(e).splitlines()[0][:200])
            # host_fetch = the sanctioned materialization channel; the
            # fallback legitimately moves rows through the host.
            pid, pk, values, valid = (host_fetch(pid), host_fetch(pk),
                                      host_fetch(values), host_fetch(valid))
    from pipelinedp_tpu.parallel import sharded
    with rt_trace.span("reshard.host") as sp:
        values = np.asarray(values)
        if values_dtype is not None:
            values = values.astype(values_dtype, copy=False)
        pid, pk, values, valid = sharded.shard_rows_by_pid(
            np.asarray(pid), np.asarray(pk), values, np.asarray(valid),
            mesh.devices.size)
        sp.set(bytes=_row_payload_bytes(pid, pk, values, valid))
        sharding = row_sharding(mesh)
        return (jax.device_put(jnp.asarray(pid), sharding),
                jax.device_put(jnp.asarray(pk), sharding),
                jax.device_put(jnp.asarray(values), sharding),
                jax.device_put(jnp.asarray(valid), sharding))


def _is_collective_failure(exc: BaseException) -> bool:
    """Failures worth degrading to the host reshard for: the injected
    collective fault, a deadline expiry on the exchange, transient
    runtime failures, or an error naming the exchange itself.
    Programming errors (shape/type) must propagate — and so must
    device-fatal failures: a host permutation cannot route around a
    dead chip that is still part of the mesh, so those go to the
    elastic degradation loop instead, which rebuilds the permutation
    for the shrunken geometry."""
    if isinstance(exc, rt_faults.InjectedCollectiveError):
        return True
    if isinstance(exc, rt_watchdog.BlockTimeoutError):
        return True
    if rt_retry.is_device_fatal(exc):
        return False
    if isinstance(exc, rt_faults.InjectedFault):
        return False
    if rt_retry.is_transient(exc):
        return True
    msg = str(exc)
    return any(marker in msg for marker in ("all_to_all", "all-to-all",
                                            "collective", "AllToAll"))


@contextlib.contextmanager
def forbid_row_fetches(max_elements: int = _CONTROL_TABLE_ELEMENTS):
    """Transfer guard proving rows never leave the device in its scope.

    jax.transfer_guard cannot catch device->host reads on the CPU backend
    (arrays are host-backed, the "transfer" is zero-copy), so the guard
    instruments the actual host-materialization entry point instead:
    np.asarray of a jax.Array larger than a control table raises unless
    it runs inside mesh.host_fetch. Used by the transfer-guard tests and
    the multi-chip dryrun to prove the device-resident path performs zero
    O(rows) host transfers before dispatch.
    """
    real_asarray = np.asarray

    def guarded(a, *args, **kwargs):
        if (isinstance(a, jax.Array) and a.size > max_elements and
                not getattr(mesh_lib._sanctioned_fetch, "active", False)):
            raise AssertionError(
                f"O(rows) device->host fetch of shape {a.shape} inside a "
                f"forbid_row_fetches scope — the device-resident path must "
                f"not stage rows through the host")
        return real_asarray(a, *args, **kwargs)

    np.asarray = guarded
    try:
        yield
    finally:
        np.asarray = real_asarray
