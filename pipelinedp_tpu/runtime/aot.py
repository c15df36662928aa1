"""Ahead-of-time executable cache: the warm path without Python retraces.

PR 6's compile attribution proved the warm end-to-end gap is host-side
dispatch, not device math: every warm call re-enters ``jax.jit``'s
Python dispatch across ~16 separate probed entry points, and a second
job with an identical spec still pays the tracing-cache lookup (and, in
a fresh thread of a resident service, the lock contention around it)
per call. This module makes the warm path a handful of *pre-compiled*
dispatches instead:

  * **ExecutableCache** — one process-wide cache of
    ``jitted.lower(...).compile()`` executables, keyed by
    (entry point, static-config fingerprint — the KernelConfig /
    SelectionParams / mesh geometry repr — and the dynamic arguments'
    shape/dtype/weak-type/sharding fingerprint). The key is exactly
    what XLA specializes on, so a hit is always safe to execute and a
    second identical-spec tenant of ``DPAggregationService`` executes
    with ZERO Python retraces on its own job record
    (``aot_cache_misses`` attributes per job through the health scope,
    like ``jit_cache_misses``).
  * **aot_probe(name, jitted_fn, static_argnames)** — the probe_jit-
    equivalent wrapper for AOT entry points (staticcheck's jit-boundary
    rule accepts it as attribution, and conversely flags any bare
    ``.lower().compile()`` outside this module). Disabled (the
    default), it is exactly ``trace.probe_jit``: one bool check and a
    tail call. Enabled (``TPUBackend(aot=True)``, thread-scoped via
    ``activate()``), calls route through the cache: a miss lowers +
    compiles once (``aot_cache_misses``, compile seconds attributed via
    ``trace.note_compile``), every later call invokes the compiled
    executable directly (``aot_cache_hits``) — no tracing-cache lookup,
    no retrace, bit-identical results (the executable IS the program
    jit would have dispatched).

Fallback discipline: exactly two cases degrade to the probed jit path,
each counted in the ``aot_fallbacks`` telemetry counter (0 on a healthy
run — the chip smoke asserts it): an argument mix ``.lower()`` rejects
with a TypeError (the entry is disabled for the process, one warning),
and a cached executable rejecting its arguments at the call boundary
(TypeError/ValueError from the compiled object's input type/sharding
check — a key dimension the fingerprint missed). Everything else — a
compile error, a runtime failure of the executable — raises: the jit
path would run the same program and fail the same way, and a service
that silently runs none of its AOT cache must not look healthy.
"""

import collections
import contextlib
import functools
import inspect
import logging
import threading
import time
from typing import Any, Dict, Optional, Tuple

from pipelinedp_tpu.runtime import trace as rt_trace
from pipelinedp_tpu.runtime.concurrency import guarded_by

# Process default; per-thread overrides via activate(). The executor
# activates the backend's `aot` knob around its device work, so service
# worker threads running different backends never leak the flag into
# each other.
_default_enabled = False
_tls = threading.local()


def enabled() -> bool:
    """Whether AOT routing is on for the current thread."""
    stack = getattr(_tls, "stack", None)
    if stack:
        return stack[-1]
    return _default_enabled


def enable(flag: bool = True) -> None:
    """Sets the process-wide default (tests/benches; backends should use
    the thread-scoped activate())."""
    global _default_enabled
    _default_enabled = bool(flag)


@contextlib.contextmanager
def activate(flag: Optional[bool]):
    """Thread-scoped AOT enable/disable; None inherits the current state
    (so a backend without the knob changes nothing)."""
    if flag is None:
        yield
        return
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append(bool(flag))
    try:
        yield
    finally:
        stack.pop()


class ExecutableCache:
    """Process-wide map of AOT keys -> compiled executables.

    Reads/writes race between service worker threads; compilation
    happens OUTSIDE the lock (an XLA compile can take seconds — holding
    the lock would serialize every concurrent tenant on it), so two
    threads racing on one cold key may both compile; the second store
    wins and both results are the same program.
    """

    _GUARDED_BY = guarded_by("_lock", "_entries", "_hits", "_misses")

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[Any, Any] = {}
        self._hits: "collections.Counter[str]" = collections.Counter()
        self._misses: "collections.Counter[str]" = collections.Counter()

    def lookup(self, name: str, key) -> Optional[Any]:
        with self._lock:
            executable = self._entries.get(key)
            if executable is not None:
                self._hits[name] += 1
            return executable

    def store(self, name: str, key, executable) -> None:
        with self._lock:
            self._entries[key] = executable
            self._misses[name] += 1

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> Dict[str, Any]:
        """{"entries", "hits", "misses", "per_entry": {name: {hits,
        misses}}} — the receipt-friendly rollup."""
        with self._lock:
            names = set(self._hits) | set(self._misses)
            return {
                "entries": len(self._entries),
                "hits": sum(self._hits.values()),
                "misses": sum(self._misses.values()),
                "per_entry": {
                    name: {
                        "hits": self._hits.get(name, 0),
                        "misses": self._misses.get(name, 0),
                    }
                    for name in sorted(names)
                },
            }

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._hits.clear()
            self._misses.clear()


_global_cache = ExecutableCache()


def global_cache() -> ExecutableCache:
    """THE process-wide executable cache (shared by every backend view,
    which is what makes cross-tenant reuse work)."""
    return _global_cache


def _leaf_sig(x) -> Tuple:
    """Compilation-relevant signature of one pytree leaf: shape, dtype,
    weak-type and sharding for arrays (XLA specializes on all four),
    scalar kind for Python/numpy scalars. Values never enter the key —
    they are traced, and two calls differing only in values must hit
    the same executable."""
    shape = getattr(x, "shape", None)
    if shape is not None:
        dtype = str(getattr(x, "dtype", ""))
        weak = bool(getattr(x, "weak_type", False))
        sharding = getattr(x, "sharding", None)
        return ("a", shape, dtype, weak,
                str(sharding) if sharding is not None else "")
    if x is None:
        return ("-",)
    if isinstance(x, (bool, int, float, complex)):
        return ("s", type(x).__name__)
    return ("o", type(x).__name__)


def fingerprint(dyn_kwargs: Dict[str, Any]):
    """Hashable fingerprint of the dynamic arguments (structure + leaf
    signatures)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(dyn_kwargs)
    return (treedef, tuple(_leaf_sig(leaf) for leaf in leaves))


def _has_tracer(tree) -> bool:
    """Whether any argument leaf is a jax Tracer — i.e. the call sits
    inside another jit/vmap/shard_map trace (e.g.
    select_kept_pair_stream called from the sharded pass-1 body). A
    compiled executable cannot consume tracers; the inner call inlines
    into the outer program via the jit path instead."""
    import jax
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(tree))


def aot_probe(name: str, jitted_fn, static_argnames: Tuple[str, ...] = (),
              signature_from=None):
    """Wraps a jitted entry point with AOT routing + probe attribution.

    The probe_jit contract holds verbatim when AOT is disabled (same
    spans, same compile accounting, same re-exposed jit attributes).
    With AOT enabled, the call binds its arguments against the entry's
    signature, splits static from dynamic, and executes the cached
    ``.lower().compile()`` executable for its key — compiling it once
    per (static fingerprint, dynamic fingerprint, backend) on first
    use. static_argnames must name EXACTLY the jit's static arguments:
    they are baked into the executable and excluded from the call.
    """
    probed = rt_trace.probe_jit(name, jitted_fn)
    statics = frozenset(static_argnames)
    sig = inspect.signature(
        signature_from if signature_from is not None else jitted_fn)
    failed = []  # [True] once lowering failed; disables AOT per entry

    @functools.wraps(jitted_fn)
    def wrapper(*args, **kwargs):
        if not enabled() or failed or _has_tracer((args, kwargs)):
            return probed(*args, **kwargs)
        from pipelinedp_tpu.runtime import telemetry
        import jax
        bound = sig.bind(*args, **kwargs)
        bound.apply_defaults()
        static_kw = {k: v for k, v in bound.arguments.items()
                     if k in statics}
        dyn_kw = {k: v for k, v in bound.arguments.items()
                  if k not in statics}
        key = (name,
               tuple((k, repr(v)) for k, v in sorted(static_kw.items())),
               fingerprint(dyn_kw), jax.default_backend())
        cache = _global_cache
        executable = cache.lookup(name, key)
        if executable is None:
            t0 = time.perf_counter()
            with rt_trace.span("aot_compile:" + name):
                try:
                    lowered = jitted_fn.lower(**static_kw, **dyn_kw)
                except TypeError as e:
                    # The one expected lowering degrade: an argument
                    # mix .lower() cannot express. Compile errors
                    # propagate.
                    failed.append(True)
                    telemetry.record("aot_fallbacks", entry=name,
                                     stage="lower")
                    logging.warning(
                        "aot: lowering %s failed (%s: %s); this entry "
                        "point falls back to the traced jit path for the "
                        "rest of the process. Warning once.", name,
                        type(e).__name__, e)
                    return probed(*args, **kwargs)
                executable = lowered.compile()
            cache.store(name, key, executable)
            dt = time.perf_counter() - t0
            rt_trace.note_compile("aot:" + name, dt)
            telemetry.record("aot_cache_misses", entry=name)
        else:
            telemetry.record("aot_cache_hits", entry=name)
        try:
            with rt_trace.span("aot:" + name):
                return executable(**dyn_kw)
        except (TypeError, ValueError) as e:
            # The compiled object's own input check (argument
            # types/shardings differ from what it was compiled for): a
            # key dimension the fingerprint missed. Runtime failures of
            # the executable are neither type and propagate.
            telemetry.record("aot_fallbacks", entry=name, stage="execute")
            logging.warning(
                "aot: the cached %s executable rejected its arguments "
                "(%s: %s); retrying through the traced jit path.", name,
                type(e).__name__, e)
            return probed(*args, **kwargs)

    for attr in ("_cache_size", "clear_cache", "lower"):
        if hasattr(jitted_fn, attr):
            setattr(wrapper, attr, getattr(jitted_fn, attr))
    wrapper.__wrapped_aot__ = name
    return wrapper
