"""Numeric armor: typed numeric-failure errors and the fail-closed
release sentinel.

The chaos/fleet arcs hardened the stack against *crashing* faults; this
module guards the *numeric* seams extreme inputs attack at scale. A
release that is bit-exactly reproducible but numerically wrong (a wrapped
count, an f32 sum that went Inf, a NaN that poisoned a partition) is the
worst failure mode: it passes every replay/determinism gate. The
discipline here is fail closed — if a released column carries a numeric
sentinel value, NOTHING is released, the job fails with a typed error,
and the budget is forfeited conservatively like every other pre-release
failure (the mechanisms were already registered at graph-build time, so
privacy is never under-counted).

Two layers:

  * `ReleaseIntegrityError` / `NumericOverflowError`: the typed error
    vocabulary. Both are terminal — runtime/retry.is_transient does not
    recognize them, so no retry loop ever re-dispatches a numerically
    poisoned release.
  * `check_release(...)`: the post-kernel, pre-decode sentinel every
    release driver runs (dense solo/meshed, blocked solo/meshed, and the
    megabatched service lanes through the same dense seam). One tiny jit
    program reduces every released column to a single uint32 flag word on
    device — NaN, ±Inf and near-dtype-max saturation bits, masked to the
    partitions the DP selection actually kept — and the host fetches ONE
    scalar (no O(rows) transfer) to decide pass/fail.

Flag classification by `numeric_mode`:

  * "fast" (default): NaN/Inf trip `ReleaseIntegrityError`; the
    saturation bit alone is advisory (legitimate workloads may release
    finite values near the clip bound, and the default mode must keep
    pre-existing releases bit-identical AND behavior-identical).
  * "safe": Inf or saturation trips `NumericOverflowError` (counted in
    `numeric_overflows`), NaN trips `ReleaseIntegrityError` — overflow
    is refused before it rounds to a finite-but-wrong release.

Every trip increments `release_sentinel_trips`; health marks the job
FAILED through the ordinary job_scope discipline when the typed error
escapes, and the chaos invariant checker treats these as typed driver
errors (never a lost job).
"""

import jax
import jax.numpy as jnp
import numpy as np

from pipelinedp_tpu.runtime import telemetry as rt_telemetry
from pipelinedp_tpu.runtime import trace as rt_trace


class ReleaseIntegrityError(RuntimeError):
    """A released column failed the numeric release sentinel.

    Fail closed: nothing was released for this job; the budget grant is
    forfeited conservatively (mechanisms were registered at graph time).
    Not transient — retrying would recompute the same poisoned bits.
    """


class NumericOverflowError(ReleaseIntegrityError):
    """An accumulator overflowed (Inf) or saturated near the dtype max.

    Raised in numeric_mode="safe" instead of wrapping/rounding: the job
    fails typed with zero partial release and zero duplicate budget
    registrations (execution runs under no_new_mechanisms).
    """


_FLAG_NAN = 1
_FLAG_INF = 2
_FLAG_SAT = 4

# A finite released magnitude at or beyond half the dtype max is one
# addition away from Inf — treat it as saturation, not data.
SATURATION_LIMIT = float(np.finfo(np.float32).max) / 2


def _column_flags(col, gate):
    """uint32 flag word for one released column under a bool[P] gate."""
    g = gate if col.ndim == 1 else gate[:, None]
    limit = jnp.asarray(jnp.finfo(col.dtype).max / 2, col.dtype)
    nan = jnp.isnan(col) & g
    inf = jnp.isinf(col) & g
    sat = jnp.isfinite(col) & (jnp.abs(col) >= limit) & g
    z = jnp.uint32(0)
    return (jnp.where(jnp.any(nan), jnp.uint32(_FLAG_NAN), z)
            | jnp.where(jnp.any(inf), jnp.uint32(_FLAG_INF), z)
            | jnp.where(jnp.any(sat), jnp.uint32(_FLAG_SAT), z))


@jax.jit
def _flags_from_kept(cols, n_kept):
    """Sentinel flags for kept-first compacted columns ([:n_kept] live)."""
    p = next(iter(cols.values())).shape[0]
    gate = jnp.arange(p, dtype=jnp.int32) < n_kept.astype(jnp.int32)
    flags = jnp.uint32(0)
    for name in sorted(cols):
        flags = flags | _column_flags(cols[name], gate)
    return flags


# Compile/dispatch attribution: the sentinel reductions are tiny, but a
# retrace storm here would still be invisible without the probes.
_flags_from_kept = rt_trace.probe_jit("_flags_from_kept", _flags_from_kept)


def release_flag_bits(flags: int):
    """Human-readable names of the tripped sentinel bits."""
    names = []
    if flags & _FLAG_NAN:
        names.append("NaN")
    if flags & _FLAG_INF:
        names.append("Inf")
    if flags & _FLAG_SAT:
        names.append("saturation")
    return names


def check_release(outputs, *, n_kept, numeric_mode: str = "fast",
                  context: str = "release") -> None:
    """Fail-closed sentinel over released columns; raises typed on trip.

    The columns are kept-first compacted ([:n_kept] live), as every
    driver releases them. The device program reduces every floating
    column to one uint32 flag word; the single scalar fetch here is the
    only host transfer.
    """
    cols = {
        name: col
        for name, col in outputs.items()
        if jnp.issubdtype(jnp.asarray(col).dtype, jnp.floating)
    }
    if not cols:
        return
    cols = {name: jnp.asarray(col) for name, col in cols.items()}
    flags = int(_flags_from_kept(cols, jnp.asarray(n_kept)))
    if not flags:
        return
    overflow = bool(flags & (_FLAG_INF | _FLAG_SAT))
    poisoned = bool(flags & _FLAG_NAN)
    if numeric_mode == "safe":
        trip_overflow = overflow
        trip_poison = poisoned
    else:
        # Default mode: only non-values (NaN / Inf) trip; finite
        # saturation is advisory so legitimate extreme-but-finite
        # workloads keep their pre-existing behavior bit-for-bit.
        trip_overflow = bool(flags & _FLAG_INF)
        trip_poison = poisoned
        if not (trip_overflow or trip_poison):
            return
    bits = ", ".join(release_flag_bits(flags))
    rt_telemetry.record("release_sentinel_trips")
    msg = (f"release sentinel tripped at {context}: released columns "
           f"carry {bits} (numeric_mode={numeric_mode!r}). Failing "
           f"closed: nothing released, budget forfeited conservatively. "
           f"Columns checked: {sorted(cols)}.")
    if numeric_mode == "safe" and trip_overflow and not trip_poison:
        rt_telemetry.record("numeric_overflows")
        raise NumericOverflowError(
            msg + " Overflow-safe accumulation detected saturation/Inf "
            "before release; reduce input magnitude or clip bounds.")
    raise ReleaseIntegrityError(msg)
