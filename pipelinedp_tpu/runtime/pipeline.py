"""Device-resident streaming executor: overlapped ingest -> aggregate -> drain.

A bare kernel ran some 200x faster than the end-to-end job, and the gap
is NOT the DP math: host-side encode, per-call dispatch/compile round
trips and serialized engine stages dominate the warm path. This module is
the engine's answer — the pieces that turn ``DPEngine.aggregate`` into a
device-resident pipeline instead of one serial batch call:

  * **Bounded staging queue + encode thread pool** (``map_overlapped``) —
    chunk *k+1* parses/factorizes on a small host thread pool while chunk
    *k*'s columns land on device. The window is bounded by the shared
    ``PIPELINE_DEPTH`` (the same depth that bounds the blocked drivers'
    in-flight block kernels and staged drains), so host memory holds
    O(depth) chunks, never the whole stream. Backpressure is a
    semaphore: a stalled consumer stops the producer from pulling new
    chunks. The consumer's waits heartbeat the active watchdog and run
    under ``pipeline_wait`` guards, so a stalled queue (a hung producer,
    a wedged encode worker) surfaces as a BlockTimeoutError instead of a
    silent hang.
  * **Device-resident chunk accumulator** (``DeviceRowAccumulator``) —
    encoded chunks append into persistent device buffers sized to
    power-of-two row buckets (``executor.row_bucket``, the same buckets
    ``pad_rows`` uses), with the previous buffer DONATED to XLA on every
    append so steady-state appends reuse device memory instead of
    allocating per chunk. ``finalize()`` returns buffers bit-identical
    to ``executor.pad_rows`` over the concatenated rows — pipelined and
    serial execution therefore feed the fused kernel the exact same
    arrays and release the exact same noise.
  * **Dense staging** (``stage_host_rows``) — a host ``EncodedData``
    reaches the device in slabs of ``DENSE_SLAB_BYTES`` through two
    reused host scratch buffers, appended (the accumulator's donated
    append) into device buffers that were built at the row bucket's
    length with the pad values: the pad exists on the device only, no
    host array of a column's length is made in a job, and slab *k+1* is
    narrowed on the host while slab *k* crosses the link.
  * **ChunkSource** — the engine-level chunked entry: wrap an iterable of
    ``(pid_raw, pk_raw, values)`` column chunks and hand it to
    ``DPEngine.aggregate`` / ``select_partitions`` in place of a row
    collection; the executor routes it through the pipelined
    ``ingest.stream_encode_columns`` under the backend's
    ``encode_threads`` / ``pipeline_depth`` knobs.
    ``ChunkSource.from_netflix_file``'s items are byte ranges of a text
    file, each read and parsed by the encode worker that then encodes it,
    so the feeder only counts ranges.
  * **Overlapped drain** (``copy_to_host_async``) — the shared
    async-copy helper (moved here from parallel/large_p.py so the
    executor's dense drain can use it without an import cycle): result
    columns start their device->host copies together and block only at
    the final materialization barrier.
  * **Kept-prefix drain** (``KeptPrefix``, ``drain_bucket``) — what every
    ``[:k]`` of a kept-first compacted release goes through, on every
    route: the device slice's length comes from a short ladder of buckets,
    never from the kept count, so no job builds a program for a count the
    process has not seen; the cut to k happens on the host copy.

Failure semantics compose with the rest of the runtime: encode-worker
exceptions re-raise in the consumer (the original exception, so
``nonfinite="error"`` still surfaces as ValueError), an OOM mid-pipeline
(hooked for fault injection at the append site) aborts the stream before
any DP release — re-running under the same ``noise_seed`` replays the
identical release with zero duplicate budget registrations, because
mechanisms register at graph-build time and noise keys derive from the
seed, never from execution history.

Static discipline: this module is covered by staticcheck's host-transfer
rule (like parallel/ and ops/) — staging-queue consumers must route any
device->host fetch through ``mesh.host_fetch``; the module itself
performs one, suppressed with its reason: ``KeptPrefix.host``, the
O(kept) barrier of a release drain (chunks flow host->device only).
"""

import contextlib
import functools
import logging
import math
import queue
import threading
from concurrent import futures as _futures
from typing import Any, Callable, Iterable, Iterator, Optional

from pipelinedp_tpu.runtime import faults as rt_faults
from pipelinedp_tpu.runtime import telemetry as rt_telemetry
from pipelinedp_tpu.runtime import trace as rt_trace
from pipelinedp_tpu.runtime import watchdog as rt_watchdog
from pipelinedp_tpu.runtime.concurrency import guarded_by

# One shared depth for every async pipeline in the package: the blocked
# drivers keep at most this many block kernels in flight and this many
# blocks' drains staged (parallel/large_p.py re-exports it), and the
# streaming ingest keeps at most this many encoded chunks in its staging
# window. The residency reasoning (host and HBM both hold O(depth)
# intermediates, never O(stream)) only holds while these agree — derive
# all of them from here, never tune one alone.
PIPELINE_DEPTH = 8

# Device-append batch size (rows) of the streaming ingest: encoded
# chunks stage host-side until this many rows accumulate, then land on
# device as ONE jit append instead of one per chunk — a fine-grained
# 4K-row stream goes from hundreds of pipeline_append dispatches to a
# handful (the e2e_dispatch_count receipt), with bit-identical final
# buffers (append order and pad values are unchanged; the accumulator
# reproduces executor.pad_rows either way). 0 disables batching (the
# per-chunk comparison baseline).
APPEND_BATCH_ROWS = 1 << 16

# Slab size of the dense route's host -> device staging
# (stage_host_rows): bytes of one slab in the device dtypes over all four
# row columns (pid, pk, values, valid), converted to rows from the
# columns' widths. A job smaller than one slab goes up as one slab. Large
# enough that a job is a handful of dispatches, small enough that the two
# host scratch buffers a process keeps and the two slabs in flight on the
# device stay a small share of either memory.
DENSE_SLAB_BYTES = 128 << 20

_POLL_S = 0.05


def default_encode_threads() -> int:
    """Auto thread count for the host encode pool: enough to overlap
    parse/factorize with device work without oversubscribing a small
    host (the bench host has one core; encode is numpy/pandas C code
    that releases the GIL, so even one worker overlaps the consumer's
    device appends)."""
    import os
    return max(1, min(4, os.cpu_count() or 1))


class ChunkSource:
    """Marks an iterable of ``(pid_raw, pk_raw, values)`` column chunks as
    a streaming input for ``DPEngine.aggregate`` / ``select_partitions``.

    The executor routes a ChunkSource through the pipelined
    ``ingest.stream_encode_columns`` (host thread-pool encode, bounded
    staging queue, device-resident accumulation) under the backend's
    ``encode_threads`` / ``pipeline_depth`` knobs — the bulk-file
    counterpart of handing the engine Python rows, minus the serial
    encode stall.

    nonfinite: per-chunk NaN/Inf value policy ("error" | "drop"), the
        same semantics as ``ingest.stream_encode_columns``.
    encode_mode: "host" | "hash_device" | None. None (the default)
        defers to the backend's ``encode_mode`` knob; an explicit value
        here overrides it per source. "hash_device" routes through the
        on-device hash factorization (``device_encode.py``) — chunk
        workers only hash, codes are assigned inside jit, partition-key
        decode is deferred to DP-selected indices.
    """

    def __init__(self, chunks: Iterable, nonfinite: str = "error",
                 encode_mode: Optional[str] = None):
        if nonfinite not in ("error", "drop"):
            raise ValueError(
                f"nonfinite must be error|drop, got {nonfinite!r}")
        if encode_mode is not None:
            from pipelinedp_tpu import input_validators
            input_validators.validate_encode_mode(encode_mode,
                                                  "ChunkSource")
        self.chunks = chunks
        self.nonfinite = nonfinite
        self.encode_mode = encode_mode
        # None, or the function from one item of `chunks` to its columns,
        # run on the encode worker: set by from_netflix_file alone.
        self._load: Optional[Callable[[Any], Any]] = None

    @classmethod
    def from_netflix_file(cls, path, range_bytes: int = 1 << 24,
                          encode_mode: Optional[str] = None):
        """A Netflix Prize format text file (``<movie_id>:`` header lines,
        ``<user_id>,<rating>,<date>`` data lines) as (user, movie, rating)
        rows. The items are the file's byte ranges of `range_bytes`; each
        is read and parsed (``textfile.read_netflix_range``: the movie
        sections whose header starts in the range) on the encode worker
        that encodes it. The rows arrive in file order, so the release is
        the one an in-memory ChunkSource over the parsed columns gives."""
        from pipelinedp_tpu import textfile
        ranges = textfile.NetflixFileRanges(path, range_bytes)
        source = cls(ranges.ranges, encode_mode=encode_mode)
        source._load = ranges.load
        return source


def _validate_window(encode_threads: int, depth: int) -> None:
    if not isinstance(encode_threads, int) or isinstance(
            encode_threads, bool) or encode_threads < 1:
        raise ValueError(f"encode_threads must be an integer >= 1 inside "
                         f"the pipeline, got {encode_threads!r}")
    if not isinstance(depth, int) or isinstance(depth,
                                                bool) or depth < 1:
        raise ValueError(
            f"pipeline_depth must be an integer >= 1, got {depth!r}")


def _staged_get(q: "queue.Queue", idx: int):
    """Queue pop under the active watchdog (if any): a stalled staging
    queue expires the ``pipeline_wait`` guard and surfaces as a
    BlockTimeoutError instead of wedging the consumer."""
    wd = rt_watchdog.active()
    if wd is None:
        return q.get()
    with wd.guard("pipeline_wait", idx) as g:
        while True:
            try:
                return q.get(timeout=_POLL_S)
            except queue.Empty:
                g.raise_if_expired()


def _staged_result(fut: "_futures.Future", idx: int):
    """Future wait under the active watchdog (see _staged_get); worker
    exceptions re-raise here as their original type."""
    wd = rt_watchdog.active()
    if wd is None:
        return fut.result()
    with wd.guard("pipeline_wait", idx) as g:
        while True:
            try:
                return fut.result(timeout=_POLL_S)
            except _futures.TimeoutError:
                g.raise_if_expired()


def map_overlapped(items: Iterable,
                   fn,
                   encode_threads: int,
                   depth: Optional[int] = None) -> Iterator[Any]:
    """Ordered overlapped map: yields ``fn(item)`` in input order while up
    to ``depth`` items are in flight across ``encode_threads`` workers.

    The staging discipline of the streaming executor:

      * a feeder thread pulls from ``items`` and submits encode tasks,
        blocking on a depth-bounded semaphore (backpressure: a slow
        consumer stops the producer — host memory holds O(depth) chunks);
      * results are consumed strictly in submission order, so downstream
        sequential state (the incremental vocabulary merge) sees chunks
        exactly as a serial loop would — pipelined and serial encode are
        bit-identical by construction;
      * consumer waits heartbeat the active watchdog and run under
        ``pipeline_wait`` guards (a stalled queue raises
        BlockTimeoutError at the deadline);
      * a worker exception re-raises in the consumer as its original
        type as soon as its chunk's turn comes; a producer (iterator)
        exception re-raises likewise.
    """
    depth = PIPELINE_DEPTH if depth is None else depth
    _validate_window(encode_threads, depth)
    q: "queue.Queue" = queue.Queue()
    slots = threading.BoundedSemaphore(depth)
    stop = threading.Event()
    pool = _futures.ThreadPoolExecutor(max_workers=encode_threads,
                                       thread_name_prefix="pdp-encode")

    # Captured at submission (this generator's first pull runs on the
    # consumer, inside its `ingest` span): a worker's pipeline_encode
    # span names the span that caused it, and so its job and agg.
    cause = rt_trace.current()

    def encode(idx, item):
        with rt_trace.span("pipeline_encode", parent=cause, chunk=idx):
            return fn(item)

    def feed():
        try:
            idx = 0
            for item in items:
                while not slots.acquire(timeout=_POLL_S):
                    if stop.is_set():
                        return
                if stop.is_set():
                    slots.release()
                    return
                q.put(("chunk", idx, pool.submit(encode, idx, item)))
                idx += 1
            q.put(("end", idx, None))
        except BaseException as e:  # noqa: BLE001 - producer failures must surface in the consumer, not die silently on the feeder thread
            q.put(("producer_error", -1, e))

    feeder = threading.Thread(target=feed, name="pdp-pipeline-feed",
                              daemon=True)
    feeder.start()
    n_consumed = 0
    try:
        while True:
            # The consumer's blocking wait on the staging queue: long
            # waits mean encode starves it, short ones that the consumer
            # itself (merge, append) is the serial ceiling.
            with rt_trace.span("ingest.wait", chunk=n_consumed):
                tag, idx, payload = _staged_get(q, n_consumed)
                if tag == "end":
                    return
                if tag == "producer_error":
                    raise payload
                try:
                    result = _staged_result(payload, idx)
                finally:
                    slots.release()
            wd = rt_watchdog.active()
            if wd is not None:
                wd.beat("pipeline")
            rt_telemetry.record("pipeline_chunks", chunk=idx)
            # Post-pop staging depth: what a mid-run scrape sees. A
            # persistently full gauge (== depth) means the device side
            # is the bottleneck; persistently 0 means encode is.
            rt_telemetry.set_gauge("pipeline_queue_depth", q.qsize())
            n_consumed += 1
            yield result
    finally:
        stop.set()
        pool.shutdown(wait=False, cancel_futures=True)


# --- Overlapped device->host drains ----------------------------------------

# Platforms without async device->host copies warn once, not per array.
_async_copy_unsupported = False


def copy_to_host_async(arr) -> None:
    """Starts an async host copy where the platform supports it.

    Shared by the blocked drivers' staged drains (parallel/large_p.py)
    and the dense executor's result drain: starting every output
    column's copy before the first blocking materialization turns N
    serial device->host round trips into one overlapped batch fetched at
    the final barrier.

    Only the unsupported-platform signatures (missing or unimplemented
    method) are swallowed — a real runtime failure here is the same
    failure the blocking materialization would hit and must stay visible
    there, not vanish into a blanket except.
    """
    global _async_copy_unsupported
    if _async_copy_unsupported:
        return
    try:
        arr.copy_to_host_async()
    except (AttributeError, NotImplementedError) as e:
        _async_copy_unsupported = True
        logging.warning(
            "copy_to_host_async is unsupported on this platform (%s: %s); "
            "device->host drains will block at materialization instead of "
            "overlapping. Warning once.", type(e).__name__, e)


# The ladder of device-prefix lengths a drain slices a kept-first
# compacted release to. A slice on the device is a program per (column
# shape, dtype, length): cut to the kept count itself, every job whose
# count the process had not seen built one per dtype (34-50 ms each on a
# TPU host, and a stalled build failed jobs: PERF.md §6, PR 34). Cut to
# a power of two at or above the kept count, a process builds one per
# dtype and BUCKET — the first job does, every later one dispatches.
# The shortest bucket is 4,096 rows, and it is also the length at or
# under which a column goes whole: below it a slice's dispatch costs more
# than the padding bytes it keeps off the link, and a finer ladder only
# adds boundaries for a kept count to straddle (keys-1e7's fullest block
# keeps 495-525 partitions, either side of 512: with a floor of 8 rows a
# window of its jobs still built six programs; my chip run, PR 34). What
# crosses stays small: at most twice the kept rows or 4,096 of them,
# tens of KB a drain.
DRAIN_MIN_ROWS = 4096


def drain_bucket(k: int, length: int) -> int:
    """Rows of a kept-first compacted column of `length` rows that cross
    to the host for its `k` kept ones: none for none, else the next power
    of two at or above max(k, DRAIN_MIN_ROWS), capped at the column —
    where that is the whole column no program is dispatched at all. Its
    only inputs are k and the length, so every route and every cell takes
    the same rule."""
    if k <= 0:
        return 0
    return min(length, max(DRAIN_MIN_ROWS, _pow2_at_least(k)))


class KeptPrefix:
    """The first `k` rows of a compacted release's arrays (the kept ids
    and each released column, kept-first, behind an n_kept sync) on their
    way to the host — what every `[:k]` of such an array goes through.

    Constructing it slices each device array to `drain_bucket(k, length)`
    rows and starts every host copy, so the transfers overlap each other
    and whatever the device still runs; `host()` is the one barrier and
    hands back host arrays of exactly k rows, in the arrays' order. A host
    array (a batched lane's copy) is only cut.

    Release discipline: rows k..bucket of a prefix are compaction
    leftovers — noised values and ids of partitions the selection
    DROPPED. They cross the link and end here: `host()` copies the k kept
    rows out of the fetched buffer and lets go of it, so nothing it
    returns holds a row at or beyond k, and no caller may read a prefix
    any other way. They must never reach executor._decode_rows' loop, a
    journal record or a log (the drains' release-taint notes rest on
    this).

    `rows` / `nbytes`: rows of the device prefix fetched (bucket length,
    or the column's where it went whole) and the bytes that cross.
    Telemetry: `drain_bucket_rows` += rows once a fetch, `d2h_bytes` +=
    nbytes at the barrier."""

    def __init__(self, arrays, k: int):
        import jax
        import numpy as np
        self.k = max(int(k), 0)
        self.rows = 0
        self.nbytes = 0
        self._prefixes = []
        for arr in arrays:
            if not isinstance(arr, jax.Array):
                self._prefixes.append(arr)
                continue
            rows = drain_bucket(self.k, arr.shape[0])
            if not rows:  # nothing kept: nothing is dispatched or fetched
                self._prefixes.append(
                    np.empty((0,) + tuple(arr.shape[1:]), arr.dtype))
                continue
            prefix = (arr if rows == arr.shape[0] else
                      jax.lax.slice_in_dim(arr, 0, rows))
            copy_to_host_async(prefix)
            self._prefixes.append(prefix)
            self.rows = max(self.rows, rows)
            self.nbytes += int(prefix.nbytes)
        if self.rows:
            rt_telemetry.record("drain_bucket_rows", self.rows)

    def host(self) -> list:
        """Blocks until the prefixes are on the host; each array's k kept
        rows, and nothing else of it."""
        import numpy as np
        kept = []
        for prefix in self._prefixes:
            fetched = np.asarray(prefix)  # staticcheck: disable=host-transfer — the O(kept) drain barrier itself: at most max(2k, DRAIN_MIN_ROWS) rows per array, gated by the caller's n_kept sync; every copy was started async in __init__
            kept.append(fetched if fetched.shape[0] == self.k else
                        fetched[:self.k].copy())
        self._prefixes = []
        if self.nbytes:
            rt_telemetry.record("d2h_bytes", self.nbytes)
        return kept


def fetch_kept(arrays, k: int) -> list:
    """Host copies of the first k rows of each kept-first compacted
    array, all in flight before the one barrier (KeptPrefix)."""
    return KeptPrefix(arrays, k).host()


# --- Device-resident chunk accumulation ------------------------------------


def _donation_supported() -> bool:
    """Buffer donation is a no-op (with a warning) on the CPU backend;
    the accumulator then stages chunks and concatenates once instead of
    copying the whole buffer on every append."""
    import jax
    return jax.default_backend() != "cpu"


@functools.lru_cache(maxsize=None)
def _append_fn():
    """Jitted chunk append: writes one chunk into the persistent buffers
    at a traced row offset, column by column, however many columns the
    caller carries (the accumulator's three bucket-padded ones, the
    dense staging's four slab columns with ``valid``). The previous
    buffers are donated to XLA, so the append updates device memory in
    place instead of allocating a fresh copy per chunk."""
    import jax

    def _append_impl(bufs, chunk, offset):
        def upd(buf, part):
            # A part that came up flat (the dense staging's [m, d]
            # columns, see stage_host_rows) takes its rows' shape here,
            # on the device. For d under the chip's 128 lanes the
            # compiler pads the reshaped rows to 128 lanes on the way
            # (512 B a slab row of f32, whatever d is: 2.4 GB for a
            # 128 MiB slab of five f32 columns), as a temporary of this
            # program and beside nothing but the job's buffers.
            part = part.reshape((-1,) + buf.shape[1:])
            start = (offset,) + (0,) * (buf.ndim - 1)
            return jax.lax.dynamic_update_slice(buf, part, start)

        return tuple(upd(b, c) for b, c in zip(bufs, chunk))

    jitted = jax.jit(_append_impl, donate_argnums=(0,))
    return rt_trace.probe_jit("pipeline_append", jitted)


@functools.lru_cache(maxsize=None)
def _grow_fn(fills: tuple = (0, -1, 0)):
    """Jitted buffer growth to a larger power-of-two bucket; pad rows
    carry the accumulator's pad values (the executor.pad_rows pid 0 /
    pk -1 / values 0 on the host-encoded route, hash sentinels on the
    hash-device route) so the tail is indistinguishable from a fresh
    pad. Not donating: a larger output can never alias the smaller
    input (the chip's compiler reports such donated buffers unusable),
    and the old buffers are released when the accumulator rebinds.
    Grown from zero-row buffers it builds the dense staging's
    bucket-length buffers of pad values alone (stage_host_rows, fills
    (0, -1, 0, False) for the fourth column, ``valid``)."""
    import jax
    import jax.numpy as jnp

    def _grow_impl(bufs, new_cap: int):
        def grown(buf, fill):
            out = jnp.full((new_cap,) + buf.shape[1:], fill, buf.dtype)
            return jax.lax.dynamic_update_slice(out, buf,
                                                (0,) * buf.ndim)

        return tuple(grown(b, f) for b, f in zip(bufs, fills))

    jitted = jax.jit(_grow_impl, static_argnames=("new_cap",))
    return rt_trace.probe_jit("pipeline_grow", jitted)


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class DeviceRowAccumulator:
    """Device-resident row columns appended chunk by chunk.

    Two modes, bit-identical results:

      * **donating** (accelerators): persistent (pid, pk, values)
        buffers sized to power-of-two row buckets; every append
        donates the previous buffers to XLA so device memory is reused
        across chunks instead of reallocated. Appended chunks must
        arrive bucket-padded with the pad_rows pad values (pid 0, pk -1,
        values 0) — the pad tail of chunk *k* is overwritten by chunk
        *k+1* and the final tail IS the pad.
      * **staged** (CPU, where donation is a warned no-op and an
        in-place append would copy the whole buffer per chunk): chunks
        stage as individual device arrays and ``finalize`` concatenates
        once.

    ``finalize()`` returns ``(pid, pk, values)`` buffers bit-identical to
    ``executor.pad_rows`` over the concatenated chunk rows: same
    power-of-two capacity (``executor.row_bucket``), same pad values —
    so the fused kernel compiled for the serial path is hit, not
    retraced, and pipelined noise is the serial noise.

    Who pads where: a streamed input is padded HERE, chunk by chunk on
    the host (each chunk to its own bucket) and by the buffers' tail on
    the device; a host ``EncodedData`` on the one-chip dense route is
    padded on the device by ``stage_host_rows`` below, which shares this
    class's append and grow programs and carries ``valid`` as a fourth
    column; ``executor.pad_rows`` pads on the host only for the callers
    that hand padded host arrays on (an offered batched launch, the
    meshed dense route, one-chip select_partitions) and on the device
    for a device-resident input that is no power of two long.
    """

    def __init__(self, donate: Optional[bool] = None,
                 fills: tuple = (0, -1, 0), batch_rows: int = 0):
        self.donating = _donation_supported() if donate is None else donate
        # Per-column pad values. The default is the executor.pad_rows
        # convention (pid 0, pk -1, values 0); the hash-device encode
        # route accumulates raw hash rows instead and pads with the
        # uint32 sentinel so pad rows can never alias a real key hash.
        self.fills = tuple(fills)
        # batch_rows > 0: host-numpy chunks stage in a host-side batch
        # until this many rows accumulate, then land as ONE device
        # append — dozens of per-chunk jit dispatches collapse to a
        # handful, with bit-identical final buffers (same row order,
        # same pad values). The streaming ingest passes
        # APPEND_BATCH_ROWS; 0 keeps the historical per-chunk appends.
        self.batch_rows = int(batch_rows)
        self._batch = []  # host-staged (pid, pk, values) chunk slices
        self._batch_n = 0
        self._n = 0  # real rows accumulated
        self._bufs = None  # donating mode: (pid, pk, values)
        self._staged = []  # staged mode: (pid, pk, values, n_real)
        self._accounted_bytes = 0

    @property
    def n_rows(self) -> int:
        return self._n + self._batch_n

    def _refresh_accounting(self) -> None:
        """Folds this accumulator's device footprint into the byte
        accountant (runtime/observability.py) — the array-shape fallback
        that gives CPU runs (no platform memory stats) a watermark. The
        donating path's transient donated-in/out pair is not modeled;
        the steady-state buffer footprint is."""
        from pipelinedp_tpu.runtime import observability
        if self.donating:
            now = (sum(int(b.nbytes) for b in self._bufs)
                   if self._bufs is not None else 0)
        else:
            now = sum(int(p.nbytes) + int(k.nbytes) + int(v.nbytes)
                      for p, k, v, _ in self._staged)
        delta = now - self._accounted_bytes
        if delta > 0:
            observability.account_bytes(delta)
        elif delta < 0:
            observability.release_bytes(-delta)
        self._accounted_bytes = now

    def append(self, pid, pk, values, n_real: int, chunk: int = 0) -> None:
        """Appends one encoded chunk (host numpy arrays; in donating mode
        already padded to a row bucket, with ``n_real`` true rows)."""
        # Fault-injection hook: an OOM mid-pipeline aborts the stream
        # before any DP release — the failed run registered mechanisms at
        # graph-build time only, so a rerun replays the same release.
        rt_faults.maybe_fail("oom", chunk)
        if n_real == 0 and pid.shape[0] == 0:
            return
        import numpy as _np
        if self.batch_rows and isinstance(pid, _np.ndarray):
            # Host-side batch staging: trim each chunk to its real rows
            # (batched chunks re-pad once at flush) and land the batch
            # as one device append when it crosses the row threshold.
            self._batch.append(
                (pid[:n_real], pk[:n_real], values[:n_real]))
            self._batch_n += n_real
            if self._batch_n >= self.batch_rows:
                self._flush_batch(chunk)
            return
        self._flush_batch(chunk)
        self._append_now(pid, pk, values, n_real, chunk)

    def _flush_batch(self, chunk: int) -> None:
        """Lands the host-staged batch as one device append (no-op when
        nothing is staged)."""
        if not self._batch:
            return
        import numpy as _np
        n = self._batch_n
        # The host copies before the upload: concatenate (a copy even of
        # a batch of one chunk) and the pad to the row bucket.
        with rt_trace.span("ingest.stage", chunk=chunk, rows=n,
                           chunks=len(self._batch)):
            pid = _np.concatenate([c[0] for c in self._batch])
            pk = _np.concatenate([c[1] for c in self._batch])
            values = _np.concatenate([c[2] for c in self._batch])
            self._batch = []
            self._batch_n = 0
            if self.donating:
                # Re-pad the batch to its row bucket with this
                # accumulator's pad values — byte-identical to what the
                # per-chunk path would have left in the buffer tail.
                from pipelinedp_tpu import executor
                cap = executor.row_bucket(n)
                pad = cap - n
                if pad:
                    f0, f1, f2 = self.fills
                    pid = _np.concatenate(
                        [pid,
                         _np.full((pad,) + pid.shape[1:], f0, pid.dtype)])
                    pk = _np.concatenate(
                        [pk, _np.full((pad,) + pk.shape[1:], f1, pk.dtype)])
                    values = _np.concatenate(
                        [values,
                         _np.full((pad,) + values.shape[1:], f2,
                                  values.dtype)])
        self._append_now(pid, pk, values, n, chunk)

    def _append_now(self, pid, pk, values, n_real: int, chunk: int) -> None:
        import jax.numpy as jnp
        import numpy as np
        nbytes = int(pid.nbytes) + int(pk.nbytes) + int(values.nbytes)
        if not isinstance(pid, jnp.ndarray):
            rt_telemetry.record("h2d_bytes", nbytes)
        with rt_trace.span("pipeline_append", chunk=chunk, rows=n_real,
                           bytes=nbytes):
            if not self.donating:
                self._staged.append((jnp.asarray(pid), jnp.asarray(pk),
                                     jnp.asarray(values), n_real))
                self._n += n_real
                self._refresh_accounting()
                return
            # A host column of rows wider than one value (the hash route's
            # (n, 3) hash rows) goes up flat and takes its rows' shape on
            # the device, in the append (_append_fn) or below: the
            # transfer of a two-dimensional host array lays it out into
            # the chip's layout on the host, in pieces, each of them a
            # profiler event (PERF.md section 5).
            chunk_bufs = tuple(
                jnp.asarray(a.reshape(-1))
                if isinstance(a, np.ndarray) and a.ndim > 1 else
                jnp.asarray(a) for a in (pid, pk, values))
            if self._bufs is None:
                # The first bucket-padded chunk IS the buffer.
                self._bufs = tuple(b.reshape(a.shape) for a, b in
                                   zip((pid, pk, values), chunk_bufs))
                self._n = n_real
                self._refresh_accounting()
                return
            cap = self._bufs[0].shape[0]
            need = self._n + pid.shape[0]
            if need > cap:
                self._bufs = _grow_fn(self.fills)(
                    self._bufs, new_cap=_pow2_at_least(need))
            self._bufs = _append_fn()(self._bufs, chunk_bufs, self._n)
            self._n += n_real
            self._refresh_accounting()

    def finalize(self):
        """Returns (pid, pk, values) device buffers holding the
        concatenated rows padded to ``executor.row_bucket(n)`` — the
        exact arrays ``executor.pad_rows`` would produce; ``n_rows``
        holds the real row count. Returns None when nothing was
        appended (the caller emits its empty-stream encoding)."""
        import jax.numpy as jnp

        self._flush_batch(0)

        # Lazy: the executor imports this module at load; the bucket
        # arithmetic lives with pad_rows so the two can never drift.
        from pipelinedp_tpu import executor
        if self._n == 0:
            return None
        target = executor.row_bucket(self._n)
        if self.donating:
            pid, pk, values = self._bufs
            if pid.shape[0] > target:
                # A small tail chunk's bucket can overshoot the total's
                # bucket by one step; one slice restores the pad_rows
                # shape so the serial-path compile cache is hit.
                pid, pk, values = (pid[:target], pk[:target],
                                   values[:target])
            return pid, pk, values
        pad = target - self._n
        # Chunks arrive unpadded in staged mode; slice only a chunk that
        # was handed over padded (a forced-donate caller), so the common
        # path concatenates the staged arrays without an extra copy.
        trim = lambda a, n: a if a.shape[0] == n else a[:n]
        pids = [trim(p, n) for p, _, _, n in self._staged]
        pks = [trim(k, n) for _, k, _, n in self._staged]
        vals = [trim(v, n) for _, _, v, n in self._staged]
        if pad:
            f0, f1, f2 = self.fills
            pids.append(
                jnp.full((pad,) + pids[0].shape[1:], f0, pids[0].dtype))
            pks.append(
                jnp.full((pad,) + pks[0].shape[1:], f1, pks[0].dtype))
            vals.append(
                jnp.full((pad,) + vals[0].shape[1:], f2, vals[0].dtype))
        return (jnp.concatenate(pids), jnp.concatenate(pks),
                jnp.concatenate(vals))


# --- Dense staging: a host EncodedData to the device, slab by slab ---------

# executor.pad_rows' pad values for (pid, pk, values, valid).
_PAD_ROW_FILLS = (0, -1, 0, False)

# Host scratch of the dense staging: (shape, dtype) -> two rotating
# buffers, kept for the life of the process so that a job maps and
# first-touches no new host pages. Keyed by shape and dtype alone: what a
# job leaves in them means nothing to the next one. One job stages at a
# time (the lock is held from its first slab to its last) — service/ runs
# jobs on threads, and they share the one link anyway.
_scratch_lock = threading.Lock()
_scratch: dict = {}
_GUARDED_BY = guarded_by("_scratch_lock", "_scratch")


def _slab_scratch(shape, dtype):  # staticcheck: disable=lock-discipline — caller holds _scratch_lock
    import numpy as np
    key = (shape, np.dtype(dtype).str)
    pair = _scratch.get(key)
    if pair is None:
        pair = _scratch[key] = (np.empty(shape, dtype),
                                np.empty(shape, dtype))
    return pair


def stage_host_rows(pid, pk, values, valid=None):
    """Host row columns -> ``(pid, pk, values, valid)`` device buffers of
    ``executor.row_bucket(n)`` rows: bit-identical to ``jnp.asarray`` of
    ``executor.pad_rows``' host copies, without making them.

    The device buffers are built at the bucket's length holding the
    pad_rows pad values (span ``dense.pad``, when n is no power of two);
    the real rows are written into them slab by slab (span
    ``dense.upload``) with the accumulator's donated append at a traced
    offset, so the tail IS the pad and no pad row crosses the link. A
    column already in its device dtype goes up as views of the caller's
    array; one that is not (float64 values with x64 off) is narrowed a
    slab at a time, by numpy's rounding as ``jnp.asarray`` applies it,
    into two rotating scratch buffers the process keeps. ``valid=None``
    derives each slab's flags from ``pk`` as ``EncodedData.valid``
    defines them (pk >= 0), into scratch too.

    Slab k's transfer and append run while the host narrows slab k+1; a
    scratch buffer is written again only after the append that read it
    is done. ``dense.upload`` closes when the rows ARE on the device.
    Every program's shape follows (bucket, slab rows, column widths)
    alone, the last, shorter slab being one more; nothing made from the
    rows outlives the call but the returned buffers."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from pipelinedp_tpu import executor

    n = pid.shape[0]
    cap = executor.row_bucket(n)
    sources = [pid, pk, values, pk if valid is None else valid]
    widths = [c.shape[1:] for c in sources]
    dtypes = [jax.dtypes.canonicalize_dtype(c.dtype) for c in sources]

    def narrow(out, part):
        np.copyto(out, part, casting="same_kind")

    def flags(out, part):
        np.greater_equal(part, 0, out=out)

    # How a column's slab is made ready on the host: None sends views of
    # the caller's array, anything else fills scratch first.
    fill = [narrow if c.dtype != dt else None
            for c, dt in zip(sources, dtypes)]
    if valid is None:
        dtypes[3], fill[3] = np.dtype(bool), flags
    row_bytes = sum(dt.itemsize * math.prod(w)
                    for dt, w in zip(dtypes, widths))
    slab_rows = max(1, DENSE_SLAB_BYTES // row_bytes)
    n_slabs = -(-n // slab_rows)

    def host_slab(k, scratch):
        a = k * slab_rows
        b = min(n, a + slab_rows)
        with rt_trace.span("dense.narrow", slab=k, rows=b - a):
            slab = [c[a:b] for c in sources]
            for i, pair in enumerate(scratch):
                if pair is not None:
                    out = pair[k % 2][:b - a]
                    fill[i](out, slab[i])
                    slab[i] = out
            # An [m, d] column goes up flat and takes its shape in the
            # append. The chip keeps the long dimension of a narrow
            # [n, d] array minor, and the transfer transposes a
            # two-dimensional host array into that layout on the HOST, a
            # few rows at a time (4.2 M pieces a 60 M-row job of five
            # columns, each an event while a profiler is on: 2.3 s and
            # 6 GB of host memory a traced job). A flat array is copied
            # as it is, and the device lays the rows out instead.
            slab = [c.reshape(-1) for c in slab]
        return a, slab

    with _scratch_lock:
        # Scratch rows stop at the bucket, so that small jobs keep small
        # scratch and the keys stay few.
        scratch = [
            _slab_scratch((min(slab_rows, cap),) + w, dt) if f else None
            for f, w, dt in zip(fill, widths, dtypes)]
        empty = tuple(jnp.asarray(np.empty((0,) + w, dt))
                      for w, dt in zip(widths, dtypes))
        with (rt_trace.span("dense.pad", rows=n, padded=cap) if cap != n
              else contextlib.nullcontext()):
            bufs = _grow_fn(_PAD_ROW_FILLS)(empty, new_cap=cap)
        with rt_trace.span("dense.upload", rows=n, slabs=n_slabs):
            nxt = host_slab(0, scratch) if n_slabs else None
            for k in range(n_slabs):
                offset, slab = nxt
                slab = tuple(jnp.asarray(c) for c in slab)
                if k:
                    # Append k-1 read the scratch slab k+1 is narrowed
                    # into; its output is still ours to wait on (append k
                    # donates it next). Transfer k is already queued.
                    with rt_trace.span("dense.wait", slab=k - 1):
                        jax.block_until_ready(bufs)
                bufs = _append_fn()(bufs, slab, offset)
                rt_telemetry.record("dense_stage_slabs")
                rt_telemetry.record("h2d_bytes",
                                    sum(int(c.nbytes) for c in slab))
                if k + 1 < n_slabs:
                    nxt = host_slab(k + 1, scratch)
            with rt_trace.span("dense.wait", slab=n_slabs - 1):
                bufs = jax.block_until_ready(bufs)
    return bufs
