"""Bounded-backoff retry + OOM degradation for block dispatch.

Why naive re-execution is not an option here: redrawing fresh noise for a
partition whose noisy value was already computed is a SECOND DP release of
the same statistic, and re-running the graph-build (which is where
mechanisms register) would double-spend the epsilon ledger. The retry
discipline therefore has two halves:

  * retry_call re-invokes the same dispatch closure. Every blocked driver
    derives its block key as fold_in(final_key, b) — a pure function of
    the run key and the block index — so the retried kernel redraws
    bit-identical noise: the retry is a replay of the SAME release.
    (JAX-Privacy's deterministic step-keyed noise is the same foundation.)
  * OOM-classified failures are never retried at the same shape (the same
    allocation would fail again); they surface as BlockOOMError so
    run_with_degradation can halve the partition block capacity and
    re-plan the REMAINING partition range. Re-planned blocks draw fresh
    keys — sound, because the OOM'd dispatch never produced (let alone
    released) an output for those partitions.

Error classification is by marker substrings over the PJRT/XLA exception
text (the installed runtime raises one JaxRuntimeError type for every
status; the status code is only in its message) plus the injection
harness's typed exceptions.
"""

import contextlib
import dataclasses
import logging
import threading
import time
from typing import Callable, List, Optional

import numpy as np

from pipelinedp_tpu.runtime import faults
from pipelinedp_tpu.runtime import health as health_lib
from pipelinedp_tpu.runtime import journal as journal_lib
from pipelinedp_tpu.runtime import telemetry
from pipelinedp_tpu.runtime import watchdog as watchdog_lib
from pipelinedp_tpu.runtime.concurrency import guarded_by

# PJRT status markers of failures worth re-dispatching: the runtime came
# back (or will), the program itself is fine.
_TRANSIENT_MARKERS = (
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "ABORTED",
    "CANCELLED",
    "connection reset",
    "socket closed",
    "Broken pipe",
    "preempted",
)

# Markers of allocation failure: retrying the identical shape re-fails.
_OOM_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "Resource exhausted",
    "out of memory",
    "OOM",
    "Out of memory",
)

# Markers of DEVICE-FATAL runtime failures: a chip dropped off the slice
# (died, was fenced, lost its ICI links). Neither a retry on the same
# mesh nor a capacity halving can succeed — the program's mesh contains
# a dead device — so these route to the elastic degradation loop
# (run_with_mesh_degradation), which rebuilds a smaller mesh from the
# survivors. Checked BEFORE the transient markers: real device-loss
# status text often also carries UNAVAILABLE/ABORTED.
_DEVICE_FATAL_MARKERS = (
    "DEVICE_LOST",
    "device is lost",
    "Device lost",
    "device failed",
    "device halted",
    "hardware error",
    "uncorrectable ECC",
    "HBM is unhealthy",
    "chip has been disabled",
)


class BlockOOMError(RuntimeError):
    """A block kernel needs re-planning at a smaller capacity: it either
    exceeded device memory, or exceeded its deadline through the whole
    retry budget (halving the block shrinks the allocation AND the
    per-block work, so both failure classes degrade identically).

    `block` is the index of the failed block within the current plan; all
    earlier blocks of the plan were consumed (their results drained and,
    when journaling, recorded) before this was raised, so the driver can
    re-plan from exactly this block's base partition.
    """

    def __init__(self, block: int, cause: BaseException):
        super().__init__(f"block {block} kernel needs re-planning at a "
                         f"smaller capacity: "
                         f"{type(cause).__name__}: {cause}")
        self.block = block
        self.cause = cause


class MeshDegradationError(RuntimeError):
    """Device losses exhausted the elastic floor: fewer live devices
    remain than `min_devices` allows (or none at all). The run cannot
    continue in this process; the message names the job_id and journal
    needed to resume elsewhere."""


class HostEvacuatedError(MeshDegradationError):
    """A whole-host loss left THIS controller with no addressable devices
    in the rebuilt mesh: the job continues bit-identically on the
    surviving hosts (block keys are geometry-independent), but this
    process can no longer participate — it holds no shard of the mesh to
    drive. Raised instead of silently idling so the launcher can reap
    the evacuated controller; the surviving processes complete the run
    and their journals/health carry the degradation record."""


class MeshGrowthSignal(RuntimeError):
    """Control-flow signal of an elastic SCALE-UP: a join announcement
    (announce_join) matched the current block boundary, so the running
    driver must unwind — draining every in-flight block into the journal
    on the way out, exactly like the shrink path — and let
    run_with_mesh_elasticity rebuild the mesh over the larger device
    set. Never an error: is_transient/is_oom/is_device_fatal all
    classify it false, so it propagates straight to the elastic loop.

    Bit-identity is preserved by construction: block keys are
    fold_in(final_key, b) — pure functions of the run key and block
    index, independent of mesh geometry — so the re-entered run replays
    journaled blocks and re-derives the same keys for the rest."""

    def __init__(self, devices=None, n_devices: Optional[int] = None,
                 block: int = 0):
        super().__init__(
            f"mesh growth admitted at block boundary {block} "
            f"(join announcement matched)")
        self.devices = devices
        self.n_devices = n_devices
        self.block = block


class _JoinRegistry:
    """Process-wide registry of announced join candidates.

    A scale-UP is initiated from OUTSIDE the running driver (a cluster
    manager noticing healthy spare hosts), so announcements land in a
    shared registry and the driver polls it at block boundaries
    (maybe_grow, hooked into retry_call's dispatch sequence). Tickets
    are consumed once — matched at the first dispatched block >= the
    ticket's block (None = the very next boundary); every controller of
    a pod announces the same ticket from the same recipe, so all of
    them grow at the same boundary to the same device set."""

    _GUARDED_BY = guarded_by("_lock", "_tickets")

    def __init__(self):
        self._lock = threading.Lock()
        self._tickets: List[dict] = []

    def announce(self, devices=None, n_devices: Optional[int] = None,
                 block: Optional[int] = None) -> None:
        if devices is None and n_devices is None:
            raise ValueError(
                "announce_join needs devices= (explicit joining device "
                "objects) or n_devices= (target total, resolved against "
                "jax.devices() at admit time)")
        with self._lock:
            self._tickets.append({
                "devices": None if devices is None else list(devices),
                "n_devices": None if n_devices is None else int(n_devices),
                "block": None if block is None else int(block),
            })

    def take(self, block: int) -> Optional[dict]:
        with self._lock:
            for i, t in enumerate(self._tickets):
                if t["block"] is None or block >= t["block"]:
                    return self._tickets.pop(i)
        return None

    def pending(self) -> int:
        with self._lock:
            return len(self._tickets)

    def clear(self) -> None:
        with self._lock:
            self._tickets.clear()


_joins = _JoinRegistry()


def announce_join(devices=None, n_devices: Optional[int] = None,
                  block: Optional[int] = None) -> None:
    """Announces devices/hosts wanting to JOIN the next elastic run's
    mesh at a block boundary: either explicit device objects, or a
    target total `n_devices` resolved against jax.devices() at admit
    time (mesh.join_candidates). `block` defers the admit to the first
    dispatched block >= block (None = the very next boundary). Only
    drivers running under run_with_mesh_elasticity consume
    announcements; plain and shrink-only-elastic runs ignore them."""
    _joins.announce(devices=devices, n_devices=n_devices, block=block)


def pending_joins() -> int:
    """Announced join tickets not yet consumed by an elastic run."""
    return _joins.pending()


def clear_joins() -> None:
    """Drops every pending join announcement (test isolation)."""
    _joins.clear()


# Growth is opt-in per DRIVER INVOCATION, not per process: only the
# thread actively inside run_with_mesh_elasticity's run() treats a
# pending join ticket as a grow signal. Thread-local depth counter —
# cheap, and re-entrant in case an elastic driver composes another.
_growth = threading.local()


@contextlib.contextmanager
def _growth_scope():
    _growth.depth = getattr(_growth, "depth", 0) + 1
    try:
        yield
    finally:
        _growth.depth -= 1


def maybe_grow(block: int = 0) -> None:
    """Block-boundary hook (retry_call): raises MeshGrowthSignal when a
    join announcement matches and the thread is inside an elasticity
    scope. A no-op everywhere else — announcements never perturb runs
    that did not opt into growing."""
    if getattr(_growth, "depth", 0) <= 0:
        return
    ticket = _joins.take(block)
    if ticket is None:
        return
    raise MeshGrowthSignal(devices=ticket["devices"],
                           n_devices=ticket["n_devices"], block=block)


def is_device_fatal(exc: BaseException) -> bool:
    """Whether the failure means a device dropped off the mesh.

    Device-fatal failures are never transient and never OOM-degradable:
    the compiled program's mesh contains a dead chip, so only rebuilding
    a smaller mesh from the survivors (run_with_mesh_degradation) can
    make progress.
    """
    if isinstance(exc, MeshGrowthSignal):
        return False
    if isinstance(exc, faults.InjectedDeviceLossError):
        return True
    if isinstance(exc, faults.InjectedFault):
        return False
    msg = str(exc)
    return any(marker in msg for marker in _DEVICE_FATAL_MARKERS)


def is_oom(exc: BaseException) -> bool:
    if isinstance(exc, (faults.InjectedOOMError, MemoryError)):
        return True
    if isinstance(exc, faults.InjectedFault):
        return False
    if is_device_fatal(exc):
        return False
    msg = str(exc)
    return any(marker in msg for marker in _OOM_MARKERS)


def is_transient(exc: BaseException) -> bool:
    """Whether re-dispatching the same program can plausibly succeed."""
    if isinstance(exc, MeshGrowthSignal):
        return False
    if isinstance(exc,
                  (faults.InjectedDispatchError, faults.InjectedConsumeError,
                   faults.InjectedCollectiveError)):
        return True
    # A deadline expiry is transient BY DESIGN: the retried block
    # re-derives the same fold_in key (bit-identical noise), and the
    # dispatcher escalates exhausted timeouts into OOM-style degradation.
    if isinstance(exc, watchdog_lib.BlockTimeoutError):
        return True
    if isinstance(exc, faults.InjectedFault):  # oom / fatal / device loss
        return False
    # Device loss first: its status text often also says UNAVAILABLE, but
    # re-dispatching onto a dead chip cannot succeed.
    if is_device_fatal(exc):
        return False
    if is_oom(exc):
        return False
    msg = str(exc)
    return any(marker in msg for marker in _TRANSIENT_MARKERS)


def is_timeout(exc: BaseException) -> bool:
    """Whether the failure is a deadline expiry (watchdog verdict or the
    runtime's own DEADLINE_EXCEEDED). Timeouts are transient — but when
    one survives the whole retry budget, the dispatcher degrades the
    block capacity exactly as it would for OOM: a smaller block is
    likelier to finish inside the deadline, and nothing was released for
    the timed-out block, so the re-plan draws fresh keys soundly."""
    if isinstance(exc, watchdog_lib.BlockTimeoutError):
        return True
    if isinstance(exc, faults.InjectedFault):
        return False
    return "DEADLINE_EXCEEDED" in str(exc)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded exponential backoff: base * multiplier^attempt, capped.

    max_retries bounds retries PER OPERATION (one block dispatch, one
    host fetch); max_total_retries additionally caps the job's TOTAL
    transient retries across every seam — dispatch retries, reshard
    host-path fallbacks, host-fetch retries — so composed faults (a
    chaos campaign's specialty) cannot spiral one job into an unbounded
    retry storm of individually-within-budget retries. None disables
    the job-wide cap. The budget is threaded through the entry wrapper
    (retry_budget_scope) rather than stored here mutably: the policy
    stays frozen and shareable across jobs.
    """
    max_retries: int = 3
    base_delay: float = 0.05
    multiplier: float = 2.0
    max_delay: float = 2.0
    max_total_retries: Optional[int] = None

    def delay(self, attempt: int) -> float:
        return min(self.base_delay * self.multiplier**attempt,
                   self.max_delay)


DEFAULT_POLICY = RetryPolicy()


class RetryBudgetExhaustedError(RuntimeError):
    """The job's total transient-retry budget (RetryPolicy.
    max_total_retries) is spent. NOT transient — is_transient never
    matches it, so it propagates straight out of every retry loop and
    fails the job with a typed error instead of letting composed faults
    grind on. Recovery is a resume (journaled blocks replay; block keys
    are fold_in(final_key, b), so the resumed run is a replay of the
    same release)."""


# Per-job retry-budget scope, threaded by runtime/entry.py from the
# retry policy's max_total_retries. Thread-local like the fetch-retry
# scope (parallel/mesh.fetch_retry_scope): the driver thread owns the
# job, so its transient retries all decrement one counter.
_budget = threading.local()


@contextlib.contextmanager
def retry_budget_scope(max_total_retries: Optional[int]):
    """Scopes the job's total transient-retry budget onto this thread
    (None = unlimited, the default). Nesting restores the outer budget
    on exit."""
    if max_total_retries is not None:
        max_total_retries = int(max_total_retries)
        if max_total_retries < 0:
            raise ValueError(
                f"retry_budget_scope: max_total_retries must be "
                f"non-negative or None, got {max_total_retries}")
    prev = getattr(_budget, "left", None)
    _budget.left = max_total_retries
    try:
        yield
    finally:
        _budget.left = prev


def consume_retry_budget(what: str = "operation") -> None:
    """Decrements the job's total retry budget before a transient retry
    is attempted; raises RetryBudgetExhaustedError when it hits zero.
    Called at every transient-retry decision point (retry_call, the
    reshard host fallback, host_fetch) — a no-op without a scope."""
    left = getattr(_budget, "left", None)
    if left is None:
        return
    if left <= 0:
        telemetry.record("retry_budget_exhausted", what=what)
        raise RetryBudgetExhaustedError(
            f"retry budget exhausted: the job's max_total_retries cap "
            f"is spent and {what} wants another transient retry. The "
            f"job fails typed instead of retry-storming; resume replays "
            f"journaled blocks under the same keys.")
    _budget.left = left - 1


def retry_call(fn: Callable,
               policy: Optional[RetryPolicy] = None,
               *,
               block: int = 0,
               what: str = "block dispatch",
               counter: str = "block_retries",
               sleep: Callable[[float], None] = time.sleep):
    """Calls fn(), retrying transient failures with bounded backoff.

    Consults the fault-injection hooks before each attempt (so scheduled
    dispatch faults and slow blocks fire here). Non-transient errors —
    OOMs included — propagate to the caller immediately.
    """
    policy = policy or DEFAULT_POLICY
    attempt = 0
    while True:
        try:
            # Scale-UP poll first: a block boundary is the only safe
            # point to grow (nothing of this block has dispatched yet,
            # so the re-entered run re-derives its key unchanged).
            maybe_grow(block)
            faults.maybe_fail("fatal", block)
            faults.maybe_fail("device_loss", block, point="dispatch")
            faults.maybe_fail("oom", block)
            faults.maybe_fail("dispatch", block)
            faults.maybe_sleep(block)
            # Each attempt runs under its own watchdog deadline (when a
            # watchdog is active on this thread): an expiry cancels the
            # injected hang / surfaces as BlockTimeoutError, lands in the
            # transient branch below, and re-dispatches the same key.
            with watchdog_lib.guard("dispatch", block):
                faults.maybe_hang(block, point="dispatch")
                return fn()
        except Exception as e:  # noqa: BLE001 - classified below
            if not is_transient(e) or attempt >= policy.max_retries:
                raise
            # The job-wide budget is spent LAST, once this retry is
            # otherwise certain: exhaustion raises typed from here.
            consume_retry_budget(what)
            delay = policy.delay(attempt)
            attempt += 1
            if is_timeout(e):
                telemetry.record("block_timeouts", block=block)
            telemetry.record(counter, block=block, what=what)
            logging.warning(
                "%s failed transiently at block %d (%s: %s); retry %d/%d "
                "in %.2fs — the retried kernel re-derives the same block "
                "key, so noise is bit-identical (no second release)", what,
                block, type(e).__name__,
                str(e).splitlines()[0][:160], attempt, policy.max_retries,
                delay)
            sleep(delay)


# Journal key of the per-job plan-history record (flattened
# [base, capacity, generation] triples in BlockRecord.ids). Defined in
# journal.py (compact() interprets it there); re-exported for callers.
PLAN_KEY = journal_lib.PLAN_KEY


def _load_plan(journal, job_id: str,
               block_partitions: int) -> List[List[int]]:
    if journal is None:
        return [[0, block_partitions, 0]]
    record = journal.get(job_id, PLAN_KEY)
    if record is None or record.ids.size == 0:
        return [[0, block_partitions, 0]]
    ranges = [
        list(map(int, triple))
        for triple in np.asarray(record.ids).reshape(-1, 3)
    ]
    if ranges[0][1] != block_partitions:
        logging.warning(
            "journaled plan starts at block capacity %d; overriding "
            "block_partitions=%d so the resumed run replays the exact "
            "geometry (and keys) of the interrupted one.", ranges[0][1],
            block_partitions)
    return ranges


def _save_plan(journal, job_id: str, ranges: List[List[int]]) -> None:
    if journal is None:
        return
    journal.put(
        job_id, PLAN_KEY,
        journal_lib.BlockRecord(ids=np.asarray(ranges,
                                               dtype=np.int64).reshape(-1),
                                outputs={}))


def run_with_degradation(run_range: Callable[[int, int, int, int], None],
                         n_partitions: int,
                         block_partitions: int,
                         min_block_partitions: int = 8,
                         journal=None,
                         job_id: Optional[str] = None) -> int:
    """Drives a blocked pass with OOM-halving re-planning.

    run_range(base, capacity, generation, end) must process partitions
    [base, end) in blocks of `capacity`, raising BlockOOMError (with the
    failed in-plan block index) after consuming every block that
    completed before the failure. On OOM the capacity halves and the
    remaining range re-plans under the next generation — generation feeds
    the block key derivation so a re-planned block never reuses a key a
    differently-shaped block already consumed.

    The plan history (the (base, capacity, generation) ranges entered) is
    itself journaled BEFORE each degraded range runs: a run that degrades
    and then crashes resumes under the exact degraded geometry —
    journaled blocks replay by their (base, capacity) keys, unjournaled
    blocks dispatch with the very keys the interrupted run would have
    used. Without this, a resume would re-plan from scratch and redraw
    noise for partitions whose finer-geometry results were already
    consumed — a second release. Ranges other than the last are fully
    journaled by construction (every block consumed before an OOM is
    recorded first). Undegraded runs save no plan record — the default
    single-range plan is what a resume reconstructs anyway.

    Returns the final block capacity (== block_partitions when no
    degradation happened and no degraded plan was resumed).
    """
    ranges = _load_plan(journal, job_id, block_partitions)
    idx = 0
    while idx < len(ranges):
        base, capacity, generation = ranges[idx]
        last = idx + 1 >= len(ranges)
        end = n_partitions if last else ranges[idx + 1][0]
        try:
            run_range(base, capacity, generation, end)
        except BlockOOMError as e:
            if not last:
                # Historical ranges replay from the journal and cannot
                # legitimately OOM; degrading here would fork the
                # already-released geometry.
                raise
            new_base = base + e.block * capacity
            if capacity // 2 < min_block_partitions:
                raise
            capacity //= 2
            # The degradation event carries the device-memory watermark
            # that triggered it (platform memory stats, or the byte-
            # accounted fallback): an operator reading the timeline sees
            # HOW FULL the device was when the halving fired, not just
            # that it fired. Lazy import: observability sits above retry.
            from pipelinedp_tpu.runtime import observability
            wm = observability.memory_watermark()
            telemetry.record("block_oom_degradations", block=e.block,
                             capacity=capacity,
                             mem_live_bytes=wm["live_bytes"],
                             mem_peak_bytes=wm["peak_bytes"],
                             mem_source=wm["source"])
            logging.warning(
                "block kernel OOM (or exhausted deadline) at partition "
                "base %d; halving partition "
                "block capacity to %d and re-planning the remaining "
                "%d partitions (generation %d). Already-consumed blocks "
                "keep their drained results; re-planned partitions draw "
                "fresh noise keys (nothing was released for them).",
                new_base, capacity, n_partitions - new_base,
                generation + 1)
            ranges.append([new_base, capacity, generation + 1])
            _save_plan(journal, job_id, ranges)
        idx += 1
    return ranges[-1][1]


def run_with_mesh_degradation(run: Callable,
                              mesh,
                              *,
                              fallback: Optional[Callable] = None,
                              min_devices: int = 1,
                              job_id: str = "",
                              journal=None):
    """Drives a meshed driver with elastic device-loss degradation.

    run(mesh) executes the full driver on the given mesh; fallback()
    (when provided) executes the unsharded driver — the floor the mesh
    degrades onto when only one device remains (or when the caller
    passed a 1-device mesh to begin with).

    On a device-fatal failure (is_device_fatal: an injected device_loss
    fault, or an XLA/PJRT error whose status text names a lost chip),
    the loop probes the current mesh's devices for liveness
    (parallel/mesh.probe_live_devices), rebuilds a mesh over the largest
    supported device count <= D-1 that the survivors allow, and
    re-enters the driver. Privacy makes this safe, not just availability:
    block noise/selection keys are fold_in(final_key, b) — pure
    functions of the run key and block index, independent of mesh
    geometry — so the re-entered run replays journaled blocks from the
    host record and re-draws bit-identical noise for every block it
    re-dispatches. A degraded run is a replay of the same release on
    fewer chips, never a second release.

    Losses past the floor — fewer survivors than max(min_devices, 1) —
    raise MeshDegradationError naming the job_id and the journal path a
    resume needs; the job's health record reports FAILED.

    Multi-controller meshes extend the same loop to WHOLE-HOST loss: a
    controller process whose every device dropped is counted as a host
    loss (host_losses telemetry), the mesh rebuilds over the surviving
    hosts' devices, and the run re-enters bit-identically — while a
    controller left with no addressable devices in the rebuilt mesh
    raises HostEvacuatedError (it cannot drive a mesh it cannot
    address; the surviving processes carry the run).

    Returns whatever run()/fallback() returns.
    """
    return _elastic_loop(run, mesh, grow=False, fallback=fallback,
                         min_devices=min_devices, job_id=job_id,
                         journal=journal)


def run_with_mesh_elasticity(run: Callable,
                             mesh,
                             *,
                             fallback: Optional[Callable] = None,
                             min_devices: int = 1,
                             job_id: str = "",
                             journal=None):
    """run_with_mesh_degradation's full-fleet counterpart: the same
    shrink-on-device-loss loop, PLUS elastic scale-UP.

    While the driver runs, announce_join tickets (new hosts/devices
    probed healthy and wanting in) are polled at every block boundary
    (retry_call's maybe_grow hook). When one matches, the driver unwinds
    via MeshGrowthSignal — draining in-flight blocks into the journal
    exactly like the shrink path — the candidates are resolved
    (mesh.join_candidates) and probed (mesh.probe_live_devices), and the
    mesh rebuilds over the LARGER device set: current devices first, in
    their existing order, admitted joiners appended. The re-entered run
    replays journaled blocks and re-derives fold_in(final_key, b) keys
    for the rest — geometry-independent, so the grown run's releases are
    bit-identical to the fixed-geometry run's by construction.

    A failed admit — an injected host_join_failure, a joiner failing its
    liveness probe, or a current device dying mid-admit — ABORTS the
    grow: the ticket is spent, the old mesh (still fully live) carries
    on, and the job records the aborted REJOINING event. Growth never
    wedges a healthy run.

    Shrink behavior, floors, whole-host loss and HostEvacuatedError are
    exactly run_with_mesh_degradation's.
    """
    return _elastic_loop(run, mesh, grow=True, fallback=fallback,
                         min_devices=min_devices, job_id=job_id,
                         journal=journal)


def _admit_joiners(current, signal: MeshGrowthSignal, job_id: str):
    """Resolves and probes a grow ticket's join candidates against the
    CURRENT mesh. Returns the admitted device list (empty = abort the
    grow). Any admit failure aborts rather than propagates: the old
    mesh is still fully live, and the joiners were never part of any
    dispatched program, so nothing needs recovery beyond dropping the
    ticket."""
    from pipelinedp_tpu.parallel import mesh as mesh_lib
    joining = mesh_lib.join_candidates(current, devices=signal.devices,
                                       n_devices=signal.n_devices)
    if not joining:
        return []
    try:
        # Fault-injection hook: a joining host dying exactly mid-admit.
        faults.maybe_fail("host_join_failure", signal.block)
        live = mesh_lib.probe_live_devices(
            list(current.devices.flat) + list(joining))
        live_ids = {getattr(d, "id", d) for d in live}
        if any(getattr(d, "id", d) not in live_ids
               for d in current.devices.flat):
            raise RuntimeError(
                "a device of the CURRENT mesh failed its liveness probe "
                "mid-admit; growing onto a set containing it would wedge "
                "the run")
        return [d for d in joining if getattr(d, "id", d) in live_ids]
    except Exception as e:  # noqa: BLE001 - any admit failure aborts the grow
        logging.warning(
            "elastic scale-UP for job %r aborted at block %d: %s: %s — "
            "the join ticket is dropped and the run continues on the "
            "old %d-device mesh (still fully live; the joiners never "
            "carried any dispatched work).", job_id, signal.block,
            type(e).__name__,
            str(e).splitlines()[0][:160], int(current.devices.size))
        return []


def _elastic_loop(run: Callable,
                  mesh,
                  *,
                  grow: bool,
                  fallback: Optional[Callable] = None,
                  min_devices: int = 1,
                  job_id: str = "",
                  journal=None):
    """The shared elastic engine: shrink on device loss (always), grow
    on join announcements (grow=True). Both directions re-enter run()
    on a rebuilt mesh and rely on the same invariant — block keys are
    geometry-independent, so every re-entry is a replay of the same
    release, never a second one."""
    from pipelinedp_tpu.parallel import mesh as mesh_lib

    current = mesh
    planned = int(mesh.devices.size)
    floor = max(int(min_devices), 1)
    health = health_lib.current()
    if health is not None:
        health.note_mesh(planned, planned)
    if grow:
        telemetry.set_gauge("mesh_target_devices", planned,
                            job_id=job_id or None)
    while True:
        n_live = int(current.devices.size)
        try:
            if n_live <= 1 and fallback is not None:
                logging.warning(
                    "elastic mesh floor reached for job %r: running the "
                    "unsharded driver on the single remaining device "
                    "(results are identical — block keys are independent "
                    "of mesh geometry).", job_id)
                return fallback()
            if grow:
                with _growth_scope():
                    return run(current)
            return run(current)
        except MeshGrowthSignal as sig:
            admitted = _admit_joiners(current, sig, job_id)
            if not admitted:
                if health is not None:
                    health.note_fleet_event(
                        "REJOINING",
                        f"scale-UP aborted at block {sig.block}: join "
                        f"candidates failed the admit; continuing on "
                        f"{n_live} device(s)")
                continue
            current = mesh_lib.make_mesh(
                devices=list(current.devices.flat) + list(admitted))
            planned = int(current.devices.size)
            telemetry.record("mesh_expansions", block=sig.block,
                             devices=planned)
            telemetry.set_gauge("mesh_target_devices", planned,
                                job_id=job_id or None)
            if health is not None:
                health.note_mesh(planned, planned)
                health.note_fleet_event(
                    "REJOINING",
                    f"admitted {len(admitted)} joining device(s) at "
                    f"block {sig.block}; mesh grew {n_live} -> {planned}")
            logging.warning(
                "elastic scale-UP for job %r: admitted %d joining "
                "device(s) at block boundary %d; rebuilding a %d-device "
                "mesh and re-entering the driver — journaled blocks "
                "replay, the rest re-derive the same fold_in(final_key, "
                "b) keys, so the grown run is bit-identical to the "
                "fixed-geometry run.", job_id, len(admitted), sig.block,
                planned)
        except Exception as e:  # noqa: BLE001 - classified below
            if not is_device_fatal(e):
                raise
            telemetry.record("device_losses")
            live = mesh_lib.probe_live_devices(list(current.devices.flat))
            # Whole-host accounting: a controller process whose every
            # device dropped is a HOST loss (power/network/runtime death
            # takes all its chips together) — surfaced distinctly so
            # operators can tell one dead chip from one dead machine.
            procs_before = set(mesh_lib.mesh_processes(current))
            procs_alive = {mesh_lib.device_process(d) for d in live}
            dead_procs = sorted(procs_before - procs_alive)
            if dead_procs:
                telemetry.record("host_losses", len(dead_procs))
                logging.warning(
                    "whole-host loss for job %r: controller process(es) "
                    "%s lost every device; the mesh rebuilds over the "
                    "surviving host(s) and the run continues "
                    "bit-identically (block keys are geometry-"
                    "independent).", job_id, dead_procs)
            # Shrink by at least one even if every device answers the
            # probe (transiently-wedged chips can ack a trivial program):
            # the failed dispatch names this geometry as unusable.
            target = min(len(live), n_live - 1)
            if health is not None:
                health.note_mesh(planned, max(target, 0))
            if target < floor:
                journal_hint = (
                    f"journal at {journal.directory!r}"
                    if getattr(journal, "directory", None) else
                    "no journal configured — pair journal=BlockJournal(dir) "
                    "with a fixed noise_seed so a resume replays consumed "
                    "blocks")
                raise MeshDegradationError(
                    f"job {job_id!r}: device losses exhausted the elastic "
                    f"floor ({len(live)} live devices < "
                    f"min_devices={floor}, planned {planned}). Resume on a "
                    f"healthy slice with the same job_id={job_id!r} and "
                    f"the same inputs/seed ({journal_hint}); consumed "
                    f"blocks replay, the rest re-derive the same "
                    f"fold_in keys.") from e
            telemetry.record("mesh_degradations")
            if grow:
                telemetry.set_gauge("mesh_target_devices", target,
                                    job_id=job_id or None)
            survivors = live[:target]
            me = mesh_lib.process_index()
            if (len(procs_before) > 1 and
                    all(mesh_lib.device_process(d) != me
                        for d in survivors)):
                # This controller's own host lost its devices: the
                # surviving processes rebuild without it, and a mesh this
                # process cannot address is a mesh it cannot drive.
                raise HostEvacuatedError(
                    f"job {job_id!r}: whole-host loss evacuated this "
                    f"controller (process {me}) — none of the {target} "
                    f"surviving devices are addressable here. The job "
                    f"continues on the surviving host(s); this process "
                    f"should exit and be reaped by the launcher.") from e
            logging.warning(
                "device loss for job %r (%s: %s); rebuilding a %d-device "
                "mesh from %d survivors (planned %d) and re-entering the "
                "driver — journaled blocks replay, re-dispatched blocks "
                "re-derive the same fold_in(final_key, b) keys, so the "
                "degraded run is a replay of the same release.", job_id,
                type(e).__name__,
                str(e).splitlines()[0][:160], target, len(live), planned)
            current = mesh_lib.make_mesh(devices=survivors)
