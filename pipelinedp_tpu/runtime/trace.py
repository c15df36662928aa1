"""Span-based pipeline tracing: where the wall clock of a run goes.

A bare-kernel rate and a file-to-release rate can differ by orders of
magnitude, and a flat counter bag plus coarse min/max/sum phase timings
cannot *prove where* the difference goes — no causality, no per-block
timeline, no transfer or compile attribution. This module turns every
run into an exportable, attributable trace:

  * **Spans** — ``with trace.span("drain", block=b):`` records one timed,
    nested, thread- and job-scoped interval. Spans carry arbitrary
    attributes (set at creation or via ``sp.set(bytes=n)`` on the yielded
    token), nest naturally per thread, and self-account exclusive time
    (inclusive minus the time spent in child spans) at close — so a
    summary needs no tree reconstruction. Every span has an ``id`` and
    the ``parent`` id of the span that caused it: the span open on the
    thread's stack, or — where work crosses threads — a token captured
    with ``trace.current()`` and handed over as ``span(name,
    parent=token)``. A child inherits its parent's ``agg``, the request
    identifier the executor's root ``aggregate`` span stamps on every
    span of one materialised aggregation (``next_agg()``), and, where
    its own thread has no job scope, the parent's ``job``. When tracing
    is disabled, ``span()`` returns a shared null token and ``current()``
    None: one module-global bool check and no allocation — near-zero
    cost on the hot block stream (tests/test_trace.py guards the
    disabled overhead).
  * **One clock with the device trace** — while tracing is enabled a
    span also enters ``jax.profiler.TraceAnnotation("rt:" + name)``, so
    an ``.xplane.pb`` taken by anyone carries the program's spans on the
    profiler's host plane beside ``XLA Ops``. The annotation class is
    imported at ``enable()``, so this module still imports without JAX.
  * **Instants** — ``trace.instant(name, **attrs)`` marks a point event.
    telemetry.record() forwards every counter increment here, so every
    runtime incident the counters already record (retry, timeout, OOM
    degradation, journal replay/quarantine, device loss, mesh rebuild,
    budget registration) lands on the timeline automatically.
  * **jit probe** — ``probe_jit(name, jitted_fn)`` wraps a jit entry
    point: each traced call records a ``jit:<name>`` span, and a call
    that grows the jit cache is counted as a compile (cache miss) with
    its wall seconds attributed to that entry point — the
    dispatch-vs-compile attribution the device-resident-pipeline
    refactor will be judged against.
  * **Export** — ``dump(path)`` writes Chrome/Perfetto trace-event JSON
    (load in ui.perfetto.dev or chrome://tracing); ``trace_summary()``
    returns the in-memory rollup: top spans by inclusive/exclusive wall
    time, instant counts, transferred bytes (the sum of ``bytes=`` span
    attributes — host_fetch and the reshard staging set them) and
    per-entry-point compile stats. Both reach operators through
    ``TPUBackend.dump_trace(path)`` / ``TPUBackend.trace_summary()``;
    the benchmark's per-layer metrics read the same summary.

Epoch discipline: buffers are process-wide and bounded (``buffer_limit``
events; excess events are counted in ``dropped_events``, never silently
lost). telemetry.reset() clears them together with counters, timings and
health states so long-running processes and tests cannot mix epochs.
"""

import contextlib
import functools
import itertools
import json
import logging
import os
import threading
import time
from typing import Any, Dict, Optional

from pipelinedp_tpu.runtime.concurrency import guarded_by

# Module-global fast path: span()/instant() check this one bool before
# doing anything else, so disabled tracing costs a dict-free function
# call per call site and nothing more.
_enabled = False

_lock = threading.Lock()
_events: list = []
_buffer_limit = 1_000_000
_dropped = 0
_t0 = time.perf_counter()
_PID = os.getpid()
# entry point -> [cache misses, compile seconds] (probe_jit).
_compile: Dict[str, list] = {}

_local = threading.local()

# Span ids and aggregation sequence numbers: next() on an
# itertools.count is one C call under the GIL, so neither needs the lock.
_span_ids = itertools.count(1)
_agg_ids = itertools.count(1)

# jax.profiler.TraceAnnotation, bound by enable() (None until then, and
# where JAX is not installed): an enabled span enters "rt:<name>" so the
# profiler's host plane carries the program's spans on its own clock.
_annotation = None

# Optional per-span memory sampler (runtime/observability.py installs
# memory_watermark here via enable_memory_sampling): when set, every
# span close attaches mem_live_bytes/mem_peak_bytes attrs so the
# Perfetto timeline carries the device-memory watermark per phase. A
# module-global callable keeps the disabled path at one None check.
_memory_sampler = None

# Spans close on driver/worker threads while exporters read; staticcheck
# enforces the declaration. `_enabled` (the disabled-path bool) and
# `_t0` (monotonic epoch base, re-set only under the lock, read
# tear-free as a float) are deliberately lock-free publishes.
_GUARDED_BY = guarded_by("_lock", "_events", "_compile", "_dropped",
                         "_buffer_limit")


def enabled() -> bool:
    return _enabled


def enable(buffer_limit: int = 1_000_000) -> None:
    """Turns span/instant recording on (process-wide)."""
    global _enabled, _buffer_limit, _t0, _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            pass
        else:
            _annotation = TraceAnnotation
    with _lock:
        _buffer_limit = int(buffer_limit)
        if not _events:
            _t0 = time.perf_counter()
    _enabled = True  # staticcheck: disable=thread-escape — deliberately lock-free single-writer monotonic bool publish (see runtime/concurrency.py): a reader that observes the stale False merely skips one event, it never tears state


def disable() -> None:
    """Stops recording; buffered events stay exportable until reset()."""
    global _enabled
    _enabled = False


def reset() -> None:
    """Drops all buffered events and compile stats (epoch boundary).

    Called by telemetry.reset() so one coordinated reset clears counters,
    timings, health states and trace buffers together.
    """
    global _dropped, _t0
    with _lock:
        _events.clear()
        _compile.clear()
        _dropped = 0
        _t0 = time.perf_counter()


def _current_job() -> Optional[str]:
    # Lazy import: health -> telemetry -> trace is the module order; the
    # reverse edge must not run at import time.
    from pipelinedp_tpu.runtime import health
    h = health.current()
    return h.job_id if h is not None else None


def _append(event: tuple) -> None:
    global _dropped
    with _lock:
        if len(_events) >= _buffer_limit:
            _dropped += 1
            first_drop = _dropped == 1
            limit = _buffer_limit
        else:
            _events.append(event)
            return
    # Buffer overflow is a DECLARED incident, not a silent truncation:
    # the counter makes trace_summary's under-reporting visible in every
    # receipt, and the warning fires once per epoch. The reentrancy flag
    # stops the counter's own instant event from re-entering the full
    # buffer (record -> instant -> _append -> drop -> record ...).
    if getattr(_local, "noting_drop", False):
        return
    _local.noting_drop = True
    try:
        if first_drop:
            logging.warning(
                "trace: event buffer full (%d events) — further events "
                "are dropped and counted in trace_dropped_events; "
                "trace_summary will flag this epoch as truncated. Raise "
                "trace.enable(buffer_limit=...) or reset() between runs.",
                limit)
        from pipelinedp_tpu.runtime import telemetry
        telemetry.record("trace_dropped_events")
    finally:
        _local.noting_drop = False


class _NullSpan:
    """Shared no-op token returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """One open span on the current thread (returned by span()); also
    the token current() hands out for span(name, parent=token)."""

    __slots__ = ("name", "attrs", "id", "parent", "job", "agg", "_cause",
                 "_start", "_child_s", "_tid", "_ann")

    def __init__(self, name: str, attrs: Optional[dict], cause=None):
        self.name = name
        self.attrs = attrs or None
        self._cause = cause

    def set(self, **attrs) -> None:
        """Attaches/overwrites attributes on the open span (e.g. a byte
        count known only once the transfer finished)."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        # The span that caused this one: the token handed over (work that
        # crossed threads), else the span open on this thread.
        cause = self._cause if self._cause is not None else (
            stack[-1] if stack else None)
        self.id = next(_span_ids)
        self.job = _current_job()
        if cause is None:
            self.parent = self.agg = None
        else:
            self.parent, self.agg = cause.id, cause.agg
            if self.job is None:
                self.job = cause.job
        if self.attrs is not None and "agg" in self.attrs:
            self.agg = self.attrs["agg"]  # the root stamps its own
        self._cause = None  # a token must not keep its ancestors alive
        self._tid = threading.get_ident()
        self._child_s = 0.0
        stack.append(self)
        self._ann = None
        if _annotation is not None:
            # id (and agg) ride along as the event's stats, so a reader of
            # the .xplane.pb can join an annotation to the exported span.
            stats = {"id": self.id}
            if self.agg is not None:
                stats["agg"] = self.agg
            self._ann = _annotation("rt:" + self.name, **stats)
            self._ann.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._start
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = getattr(_local, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        if stack:
            stack[-1]._child_s += dur
        exclusive = max(dur - self._child_s, 0.0)
        if _memory_sampler is not None:
            try:
                self.set(**_memory_sampler())
            except Exception:  # noqa: BLE001 - a failed memory sample must never fail the traced operation; the span simply lacks the mem attrs
                pass
        _append(("X", self.name, self._tid, self.job, self._start, dur,
                 exclusive, self.attrs, self.id, self.parent, self.agg))
        return False


def span(name: str, parent=None, **attrs):
    """Context manager timing one nested, attributed interval.

    ``with trace.span("drain", block=b, rows=n) as sp: ...`` — the token
    supports ``sp.set(**attrs)`` for values known only at close.
    ``parent`` is a token from ``current()`` captured on the thread that
    caused this work; without it the parent is the span open on this
    thread. Returns a shared no-op token when tracing is disabled.
    """
    if not _enabled:
        return _NULL_SPAN
    return _Span(name, attrs or None, parent)


def current():
    """The span open on this thread, as a token for ``span(name,
    parent=token)`` on another thread; None when tracing is disabled or
    no span is open."""
    if not _enabled:
        return None
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


def next_agg() -> int:
    """The next process-wide aggregation sequence number: the executor's
    root ``aggregate`` span takes one as ``agg=``, and every span it
    causes inherits it — the request identifier of one job's spans."""
    return next(_agg_ids)


def instant(name: str, **attrs) -> None:
    """Records a point event (a runtime incident) on the timeline."""
    if not _enabled:
        return
    if getattr(_local, "noting_drop", False):
        # The trace_dropped_events counter's own forwarded instant:
        # the buffer is full by definition, so buffering it is
        # impossible and counting it as another drop would double-count.
        return
    _append(("i", name, threading.get_ident(), _current_job(),
             time.perf_counter(), attrs or None))


def set_memory_sampler(fn) -> None:
    """Installs (or, with None, removes) the per-span memory sampler.

    ``fn()`` must return a dict of span attributes (observability.py
    passes {"mem_live_bytes": ..., "mem_peak_bytes": ...}); it runs at
    every span close while installed, so it must be cheap and must not
    raise for control flow. Use observability.enable_memory_sampling()
    rather than calling this directly.
    """
    global _memory_sampler
    _memory_sampler = fn


def probe_jit(name: str, fn):
    """Wraps a jitted entry point with dispatch/compile attribution.

    Traced calls record a ``jit:<name>`` span; a call that grew the jit
    cache is a compile (cache miss): its wall seconds accumulate under
    `name` in compile_stats(), a ``jit_compile:<name>`` instant lands on
    the timeline, and the ``jit_cache_misses`` telemetry counter
    increments. With tracing disabled the wrapper is one bool check and
    a tail call, so this attribution exists in traced runs only; the
    count that works with tracing off is telemetry's ``backend_compiles``
    (one jax.monitoring listener, every program of the process). The
    underlying jit attributes (clear_cache, lower, _cache_size) are
    re-exposed on the wrapper.
    """
    cache_size = getattr(fn, "_cache_size", None)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if not _enabled:
            return fn(*args, **kwargs)
        before = cache_size() if cache_size is not None else -1
        start = time.perf_counter()
        with span("jit:" + name):
            out = fn(*args, **kwargs)
        if cache_size is not None and cache_size() > before:
            dt = time.perf_counter() - start
            with _lock:
                entry = _compile.setdefault(name, [0, 0.0])
                entry[0] += 1
                entry[1] += dt
            instant("jit_compile:" + name, seconds=round(dt, 6))
            from pipelinedp_tpu.runtime import telemetry
            telemetry.record("jit_cache_misses")
        return out

    for attr in ("_cache_size", "clear_cache", "lower"):
        if hasattr(fn, attr):
            setattr(wrapper, attr, getattr(fn, attr))
    return wrapper


def note_compile(name: str, seconds: float) -> None:
    """Records one compile (with its wall seconds) under ``name`` in
    compile_stats() — the attribution hook ahead-of-time lowering
    (runtime/aot.py) shares with probe_jit, so a ``.lower().compile()``
    executable's build cost shows up in the same per-entry-point compile
    table (and on the timeline) as a traced jit cache miss would."""
    if not _enabled:
        return
    with _lock:
        entry = _compile.setdefault(name, [0, 0.0])
        entry[0] += 1
        entry[1] += seconds
    instant("jit_compile:" + name, seconds=round(seconds, 6))


def compile_stats() -> Dict[str, Dict[str, float]]:
    """{entry point: {"misses": n, "compile_s": seconds}} from probe_jit
    and note_compile: traced runs only (see probe_jit)."""
    with _lock:
        return {
            name: {"misses": entry[0], "compile_s": round(entry[1], 6)}
            for name, entry in _compile.items()
        }


def _snapshot_events(job_id: Optional[str] = None) -> list:
    with _lock:
        events = list(_events)
    if job_id is None:
        return events
    return [ev for ev in events if ev[3] == job_id]


def trace_summary(job_id: Optional[str] = None) -> Dict[str, Any]:
    """In-memory rollup: top spans by inclusive/exclusive wall time.

    Returns {"spans": {name: {count, inclusive_s, exclusive_s, max_s}}
    ordered by inclusive time descending, "instants": {name: count},
    "transfer_bytes": total of ``bytes=`` attributes, "compile":
    compile_stats(), "n_events", "dropped_events", "truncated"}. With a
    job_id, only events recorded while that job's scope was current.
    ``truncated`` is True when ANY event of the epoch was dropped on the
    full buffer: the rollup (and every job filter of it — drops are not
    attributable to a job) under-reports, and readers must treat counts
    and times as lower bounds rather than totals.
    """
    spans: Dict[str, list] = {}
    instants: Dict[str, int] = {}
    transfer_bytes = 0
    events = _snapshot_events(job_id)
    for ev in events:
        if ev[0] == "X":
            _, name, _tid, _job, _start, dur, excl, attrs = ev[:8]
            entry = spans.setdefault(name, [0, 0.0, 0.0, 0.0])
            entry[0] += 1
            entry[1] += dur
            entry[2] += excl
            entry[3] = max(entry[3], dur)
        else:
            _, name, _tid, _job, _ts, attrs = ev
            instants[name] = instants.get(name, 0) + 1
        if attrs and isinstance(attrs.get("bytes"), int):
            transfer_bytes += attrs["bytes"]
    ordered = dict(
        sorted(spans.items(), key=lambda kv: -kv[1][1]))
    with _lock:
        dropped = _dropped
    return {
        "spans": {
            name: {
                "count": entry[0],
                "inclusive_s": round(entry[1], 6),
                "exclusive_s": round(entry[2], 6),
                "max_s": round(entry[3], 6),
            }
            for name, entry in ordered.items()
        },
        "instants": dict(sorted(instants.items())),
        "transfer_bytes": transfer_bytes,
        "compile": compile_stats(),
        "n_events": len(events),
        "dropped_events": dropped,
        "truncated": dropped > 0,
    }


def to_trace_events(job_id: Optional[str] = None,
                    pid: Optional[int] = None,
                    process_name: Optional[str] = None) -> Dict[str, Any]:
    """The buffered events as a Chrome/Perfetto trace-event JSON object
    ({"traceEvents": [...], "displayTimeUnit": "ms"}).

    ``pid``/``process_name`` override the track identity: the
    cross-process rollup (runtime/observability.py) exports each
    controller's buffer under its jax process index so the merged pod
    trace reads as one timeline with one named track group per
    controller, instead of OS pids that collide across hosts.
    """
    track_pid = _PID if pid is None else int(pid)
    out = [{
        "name": "process_name",
        "ph": "M",
        "pid": track_pid,
        "tid": 0,
        "ts": 0,
        "args": {"name": process_name or "pipelinedp-tpu"},
    }]
    for ev in _snapshot_events(job_id):
        if ev[0] == "X":
            (_, name, tid, job, start, dur, excl, attrs, span_id, parent,
             agg) = ev
            args = dict(attrs) if attrs else {}
            if job is not None:
                args["job"] = job
            args["id"] = span_id
            if parent is not None:
                args["parent"] = parent
            if agg is not None:
                args["agg"] = agg
            args["exclusive_us"] = round(excl * 1e6, 3)
            out.append({
                "name": name,
                "cat": "span",
                "ph": "X",
                "pid": track_pid,
                "tid": tid,
                "ts": round((start - _t0) * 1e6, 3),
                "dur": round(dur * 1e6, 3),
                "args": args,
            })
        else:
            _, name, tid, job, ts, attrs = ev
            args = dict(attrs) if attrs else {}
            if job is not None:
                args["job"] = job
            out.append({
                "name": name,
                "cat": "instant",
                "ph": "i",
                "s": "t",
                "pid": track_pid,
                "tid": tid,
                "ts": round((ts - _t0) * 1e6, 3),
                "args": args,
            })
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def dump(path: str, job_id: Optional[str] = None) -> str:
    """Writes the buffered trace as Chrome/Perfetto trace-event JSON.

    Load the file in ui.perfetto.dev or chrome://tracing. Returns the
    path. Atomic (write-then-rename) so a crash mid-dump never leaves a
    half-written file where a trace was expected.
    """
    payload = to_trace_events(job_id)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    return path


@contextlib.contextmanager
def scoped(buffer_limit: int = 1_000_000):
    """Enables tracing for the scope, restoring the prior state on exit
    (the dryrun/tests convenience; buffers are NOT cleared on exit)."""
    was = _enabled
    enable(buffer_limit)
    try:
        yield
    finally:
        if not was:
            disable()
