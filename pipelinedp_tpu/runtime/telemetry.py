"""Process-wide metrics registry, counters and phase timings.

A flat Counter rather than per-run stats objects: the drivers that
increment these live several layers below the entry points that want to
report them (the benchmark's per-layer lines, the dryrun), and
threading a stats dict through every signature would couple all of them
to the runtime. Counters
are monotonically increasing per process; callers that want per-run deltas
snapshot() before and after.

Every metric is DECLARED in REGISTRY (name, kind, help text) — counters
(monotonic, record()) and gauges (point-in-time levels, set_gauge():
queue depth, live devices, health state, remaining budget, memory
watermarks). Both entry points validate name AND kind, so a typo'd or
mis-kinded metric is a loud error at the call site instead of a
silently forked metric; staticcheck's registry-drift rule proves both
directions for both kinds over the source tree. Gauges are keyed by
(name, job_id) — set under a job_scope they belong to that job, and
the Prometheus exporter (runtime/observability.py) renders them with a
job_id label so two jobs in one process never mix levels. The full
table is rendered in README "Observability".

Timings (record_duration) aggregate per-phase wall time as
(count, min, max, sum); the watchdog and the blocked drivers feed them
so a report can show where a job's wall clock went. Every counter
increment and duration is also forwarded to the current job's health
state machine (runtime/health.py) when one is tracked, and durations
are ADDITIONALLY aggregated under the current job's id — the same
job_scope discipline counter forwarding uses — so timing_snapshot(job)
/ job_timing_snapshot() report one job's phases without mixing in
another job run in the same process. With tracing enabled
(runtime/trace.py), every record() additionally lands as an instant
event on the trace timeline, so runtime incidents (retries, timeouts,
degradations, replays, device losses, budget registrations) appear in
causal order between the spans they interrupted.
"""

import collections
import logging
import threading
from typing import Any, Dict

from pipelinedp_tpu.runtime import trace
from pipelinedp_tpu.runtime.concurrency import guarded_by

Metric = collections.namedtuple("Metric", ["name", "kind", "help"])


def _counter(name: str, help_text: str) -> Metric:
    return Metric(name, "counter", help_text)


def _gauge(name: str, help_text: str) -> Metric:
    """A point-in-time level (set_gauge), not a monotonic count: queue
    depths, live device counts, health states, remaining budget. Gauges
    are scrapeable mid-run through runtime/observability.py's Prometheus
    endpoint; staticcheck's registry-drift rule enforces declaration in
    both directions exactly as it does for counters."""
    return Metric(name, "gauge", help_text)


# The declared metrics registry: every record() name must appear here.
REGISTRY: Dict[str, Metric] = {
    m.name: m
    for m in (
        _counter("block_retries",
                 "transient dispatch/sync failures retried"),
        _counter("block_timeouts",
                 "blocks whose deadline expired (watchdog verdict or "
                 "runtime DEADLINE_EXCEEDED surfaced)"),
        _counter("block_oom_degradations",
                 "partition block capacity halvings after OOM (or after "
                 "repeated deadline expiries)"),
        _counter("reshard_host_fallbacks",
                 "device collective reshard -> host permutation"),
        _counter("journal_replays",
                 "blocks served from the journal instead of "
                 "re-dispatching"),
        _counter("journal_quarantined",
                 "corrupt/truncated journal records renamed aside and "
                 "never replayed"),
        _counter("journal_compacted",
                 "superseded journal records dropped by "
                 "BlockJournal.compact()"),
        _counter("watchdog_timeouts",
                 "deadline expiries observed by the monitor"),
        _counter("watchdog_late_completions",
                 "guarded operations that completed after their deadline "
                 "had already expired"),
        _counter("host_fetch_retries",
                 "transient control-table fetch failures retried"),
        _counter("device_losses",
                 "device-fatal failures observed (a chip dropped off the "
                 "mesh)"),
        _counter("host_losses",
                 "whole-host losses observed (a controller process lost "
                 "every one of its devices at once)"),
        _counter("mesh_degradations",
                 "elastic mesh rebuilds onto fewer devices after a "
                 "device loss"),
        _counter("reshard_capacity_reuse",
                 "collective reshard exchanges that reused a cached "
                 "padded capacity for their geometry (the stats fetch "
                 "overlapped the exchange instead of gating it)"),
        _counter("injected_faults",
                 "faults raised by the injection harness"),
        _counter("budget_registrations",
                 "mechanisms registered with a BudgetAccountant ledger "
                 "(graph-build time only; execution-time registrations "
                 "are the double-spend bug no_new_mechanisms guards)"),
        _counter("jit_cache_misses",
                 "probed jit entry-point calls that compiled (grew the "
                 "jit cache) instead of hitting it — counted only while "
                 "rt_trace is enabled (trace.probe_jit's per-entry-point "
                 "attribution); backend_compiles is the count that works "
                 "with tracing off"),
        _counter("backend_compiles",
                 "programs the backend compiled, or loaded from the "
                 "persistent compilation cache, in this process: one per "
                 "jax.monitoring backend_compile_duration event, tracing "
                 "on or off (install_compile_listener); never fires on a "
                 "dispatch of a program already built"),
        _counter("backend_compile_ms",
                 "milliseconds (rounded per event) those backend "
                 "compiles or cache loads took"),
        _counter("h2d_bytes",
                 "bytes of row data copied host->device on the release "
                 "path, from nbytes where they cross: the ingest "
                 "accumulator's appends, blocked pass 1's padded rows or "
                 "chunk inputs and its re-upload of the merged survivors, "
                 "the dense route's slabs of host columns (dense.upload, "
                 "pipeline.stage_host_rows: the real rows only, the pad is "
                 "written on the device)"),
        _counter("dense_stage_slabs",
                 "slabs of host row columns the dense route sent up and "
                 "appended into its bucket-length device buffers "
                 "(pipeline.stage_host_rows): ceil(rows / slab rows) a "
                 "job, the slab rows being pipeline.DENSE_SLAB_BYTES over "
                 "the bytes of a row in the device dtypes"),
        _counter("d2h_bytes",
                 "bytes copied device->host on the release path, from "
                 "nbytes where they cross: control-table host_fetch, and "
                 "every kept prefix a drain fetched (pipeline.KeptPrefix: "
                 "pass 1's survivors, the blocked staged drains and "
                 "journal records, the dense release's ids and columns) "
                 "at the length that crossed, the bucket's, not the kept "
                 "count's"),
        _counter("drain_bucket_rows",
                 "rows of the device prefix each drain of a kept-first "
                 "compacted release fetched (pipeline.KeptPrefix), once a "
                 "fetch whatever its number of columns: the bucket's "
                 "length (pipeline.drain_bucket: the next power of two at "
                 "or above the kept count, 4,096 rows at least), or the "
                 "column's where it went whole; a fetch of nothing kept "
                 "adds nothing. Beside the "
                 "kept count it says how often the ladder engaged and "
                 "what it over-fetched"),
        _counter("pass1_device_resident",
                 "aggregate_blocked calls whose pass 1 stayed "
                 "device-resident: the rows fit the device's row budget "
                 "(large_p._pass1_row_budget) or the explicit row_chunk, "
                 "so no host sort, no survivor round trip; a call that "
                 "took the host-staged branch does not count"),
        _counter("value_columns",
                 "scalar value columns bounded in one pass, added once per "
                 "materialised aggregation: len(AggregateParams."
                 "value_columns), 1 for a one-column job"),
        _counter("quantile_row_passes",
                 "passes over the bounded row stream that the quantile "
                 "trees of one launch take (executor.quantile_row_passes, "
                 "from the static config: 1 on either path — the lazy "
                 "descent's one sort by (partition, leaf), the one-chunk "
                 "dense histogram's one scatter-add), added once per "
                 "materialised aggregation that has percentiles"),
        _counter("quantile_node_searches",
                 "boundary positions the lazy quantile descent looks up in "
                 "the sorted rows in one launch "
                 "(executor.quantile_node_searches, from the static config: "
                 "quantiles x tree height x partitions x (branching - 1)), "
                 "added once per materialised aggregation whose percentiles "
                 "take the lazy descent; not recorded where the one-chunk "
                 "dense histogram runs"),
        _counter("quantile_trees",
                 "partitions a materialised aggregation with percentiles "
                 "built quantile trees for (the launch's n_partitions), "
                 "added once per such aggregation"),
        _counter("selection_pairs",
                 "(privacy id, partition) pairs that survived dedupe and "
                 "l0 bounding in a blocked standalone selection "
                 "(large_p.select_partitions_blocked), read once a job "
                 "from the last block offset the host fetches anyway: "
                 "what pass 2's blocks have to count"),
        _counter("selection_block_rows",
                 "rows the selection block programs of a blocked "
                 "standalone selection gathered and scattered: the "
                 "range's shared row capacity (large_p._range_row_cap, "
                 "the largest block's pairs rounded up) times the block "
                 "programs dispatched. Beside selection_pairs it says "
                 "what the shared capacity costs on skewed keys"),
        _counter("pass2_rows",
                 "bounded survivors that pass 2 of a blocked aggregation "
                 "bins (large_p.aggregate_blocked and its meshed twin), "
                 "read once a job from the last block offset the host "
                 "fetches anyway (summed over shards on a mesh)"),
        _counter("pass2_block_rows",
                 "rows the block programs of a blocked aggregation "
                 "gathered: the range's shared row capacity "
                 "(large_p._range_row_cap) times the block programs "
                 "dispatched, times the shards on a mesh. Beside "
                 "pass2_rows it says what the shared capacity costs on "
                 "skewed keys"),
        _counter("aot_cache_hits",
                 "warm-path dispatches served by an ahead-of-time "
                 "compiled executable from the process-wide "
                 "ExecutableCache (runtime/aot.py) — zero Python "
                 "retracing, zero jit cache lookup"),
        _counter("aot_cache_misses",
                 "AOT entry-point calls that lowered and compiled a new "
                 "executable (first call for a (spec, shape, mesh, "
                 "dtype) key; 0 on a second identical-spec job is the "
                 "cross-job reuse proof)"),
        _counter("aot_fallbacks",
                 "AOT entry-point calls that degraded to the traced jit "
                 "path: .lower() rejected the argument mix, or a cached "
                 "executable rejected its arguments at the call "
                 "boundary. 0 on a healthy run — anything else means "
                 "the AOT cache is not serving the dispatches it "
                 "claims"),
        _counter("release_dispatches",
                 "device program launches plus blocking host "
                 "materializations on the executor/driver release path "
                 "(kernel dispatches, per-block drain syncs, decode "
                 "barriers) — the per-aggregation dispatch bill the "
                 "fused release kernels exist to shrink"),
        _counter("pipeline_chunks",
                 "chunks streamed through the ingest staging queue "
                 "(runtime/pipeline.map_overlapped)"),
        _counter("pipeline_device_encode_chunks",
                 "chunks accumulated through the hash-device encode "
                 "route (raw hash columns streamed host->device; codes "
                 "assigned on device by device_encode.factorize_codes)"),
        _counter("ingest_hash_collisions",
                 "64-bit key-hash collisions the hash-device encode "
                 "detector caught (each one fell back to the exact host "
                 "encoder or raised HashCollisionError)"),
        _counter("trace_dropped_events",
                 "trace events dropped because the bounded trace buffer "
                 "was full (trace_summary flags the epoch as truncated)"),
        _counter("service_jobs_admitted",
                 "jobs a DPAggregationService worker picked up and "
                 "started executing (admission passed, queue wait over)"),
        _counter("service_jobs_queued",
                 "jobs accepted by DPAggregationService.submit into the "
                 "admission queue (every admitted job passes through it; "
                 "admitted + shed + still-queued partitions this count)"),
        _counter("service_batch_launches",
                 "megabatched release launches dispatched by the "
                 "service's coalescing tier (one vmapped device program "
                 "per >= 2-lane batch: N jobs per launch)"),
        _counter("service_jobs_batched",
                 "jobs whose release executed as one lane of a "
                 "megabatched launch (increments by the lane count per "
                 "batch; jobs_admitted minus this is the solo-path "
                 "traffic)"),
        _counter("service_jobs_shed",
                 "service submissions refused by load shedding: the "
                 "device-memory watermark crossed the shed fraction at "
                 "submit, or a queued job outlived queue_timeout_s "
                 "(typed AdmissionRejectedError with retry-after; "
                 "tenant-budget refusals are NOT sheds and raise "
                 "TenantBudgetExceededError uncounted here)"),
        _counter("mesh_expansions",
                 "elastic mesh rebuilds onto MORE devices after admitting "
                 "joining devices/hosts at a block boundary "
                 "(run_with_mesh_elasticity scale-UP)"),
        _counter("job_migrations",
                 "jobs whose journal records were adopted into a new "
                 "controller's scope (BlockJournal.adopt_job — the "
                 "drain-and-migrate resume path)"),
        _counter("rolling_restarts",
                 "controller/service bounces performed under the rolling-"
                 "restart discipline (each bounce reloads persisted "
                 "ledgers and resumes journaled work)"),
        _counter("service_jobs_cancelled",
                 "jobs settled CANCELLED (JobHandle.cancel() or a "
                 "deadline_s expiry): reservation released, nothing "
                 "charged, result withheld at the service boundary "
                 "(typed JobCancelledError)"),
        _counter("storage_disk_full",
                 "journal persists refused with ENOSPC (disk full): the "
                 "tmp write failed closed — no rewrite attempted, the "
                 "previous record stays the durable truth"),
        _counter("storage_fsync_failures",
                 "journal fsyncs the kernel refused: fsyncgate "
                 "discipline unlinked the tmp and rewrote once on a "
                 "fresh fd (never re-fsync a failed fd)"),
        _counter("storage_io_errors",
                 "EIO-class I/O failures at the journal's storage seams "
                 "(record reads routed to quarantine, tmp writes that "
                 "failed before fsync)"),
        _counter("storage_unavailable",
                 "journal persists that failed CLOSED after the storage "
                 "discipline was exhausted (StorageUnavailableError: "
                 "ENOSPC, or a rewrite that stayed sick) — each one "
                 "surfaces as a typed shed, never a lost trail"),
        _counter("retry_budget_exhausted",
                 "jobs whose total transient-retry budget "
                 "(RetryPolicy.max_total_retries) ran out: the next "
                 "would-be retry raised RetryBudgetExhaustedError "
                 "instead of spiralling into a retry storm"),
        _counter("chaos_trials",
                 "chaos-campaign trials executed (runtime/chaos.py: one "
                 "seeded composed-fault schedule run under the full "
                 "invariant suite per trial)"),
        _counter("release_sentinel_trips",
                 "releases refused by the fail-closed numeric sentinel "
                 "(pipelinedp_tpu/numeric.check_release): a released "
                 "column carried NaN/Inf/saturation and the job failed "
                 "typed (ReleaseIntegrityError) with nothing released"),
        _counter("numeric_overflows",
                 "sentinel trips classified as accumulator overflow in "
                 "numeric_mode='safe' (Inf or near-dtype-max saturation "
                 "-> typed NumericOverflowError instead of a wrapped or "
                 "rounded release)"),
        _counter("snapped_releases",
                 "values released through the floating-point-safe "
                 "discrete/snapped host mechanisms (geometric counts, "
                 "snapped Laplace/Gaussian sums — "
                 "dp_computations.create_discrete_mechanism)"),
        _counter("pld_compositions",
                 "batched one-shot PLD compositions run by the "
                 "frequency-domain engine (accounting/compose.py: one "
                 "increment per compose_plds call, however many "
                 "mechanisms it folded)"),
        _counter("pld_cache_hits",
                 "mechanism-PLD spectrum-cache lookups served without "
                 "re-discretizing (key: mechanism kind, normalized "
                 "scale, sensitivity, discretization — repeat tenants "
                 "and repeated binary-search probes land here)"),
        _counter("pld_cache_misses",
                 "spectrum-cache lookups that discretized a mechanism "
                 "CDF onto the loss grid (first sighting of a "
                 "(kind, scale, sensitivity, discretization) key)"),
        _counter("chaos_invariant_failures",
                 "chaos trials that FAILED an invariant (lost/duplicated "
                 "jobs, ledger mismatch, double-spend, nondeterminism, "
                 "wedged threads, unexplained counters) — nonzero means "
                 "a reproducer schedule was minimized and reported"),
        _gauge("pipeline_queue_depth",
               "encoded chunks currently staged between the host encode "
               "pool and the device accumulator (bounded by "
               "pipeline_depth)"),
        _gauge("live_devices",
               "devices currently live in the elastic mesh of the "
               "gauge's job (== planned until a device loss shrinks it)"),
        _gauge("mesh_target_devices",
               "device count the elastic runtime currently targets for "
               "the gauge's job (== planned at entry; grows on scale-UP "
               "admissions, shrinks on degradations)"),
        _gauge("job_health_state",
               "numeric health state of a job (0 HEALTHY, 1 DEGRADED, "
               "2 STALLED, 3 FAILED — runtime/health.HealthState)"),
        _gauge("budget_epsilon_remaining",
               "total_epsilon minus the epsilon already apportioned to "
               "registered mechanisms (the odometer's spent-vs-remaining "
               "view; equals 0 once a finalized ledger spent its budget)"),
        _gauge("device_memory_live_bytes",
               "bytes currently live on the local devices (JAX device "
               "memory stats where available, the byte-accounted "
               "fallback elsewhere)"),
        _gauge("device_memory_peak_bytes",
               "peak device-memory watermark observed this epoch (same "
               "sources as device_memory_live_bytes)"),
        _gauge("service_active_jobs",
               "jobs currently executing on the DPAggregationService "
               "worker pool (bounded by max_concurrent_jobs)"),
        _gauge("service_queue_depth",
               "jobs waiting in the service admission queue (admitted "
               "but not yet picked up by a worker)"),
        _gauge("tenant_pld_epsilon_saved",
               "naive-composition spend minus PLD-composed spend for "
               "the gauge's tenant (job_id label = tenant id): the "
               "epsilon the tenant's budget got back by admitting "
               "against the composed number; refreshed whenever the "
               "ledger rebuilds its composed spend"),
        _gauge("service_batch_occupancy",
               "lane count of the most recent megabatched launch (how "
               "full the batch window ran; 1-lane windows fall through "
               "to the solo path and never set this)"),
    )
}


def counter_names() -> "tuple[str, ...]":
    """Declared counter names, for receipt builders that want them all."""
    return tuple(m.name for m in REGISTRY.values() if m.kind == "counter")


_lock = threading.Lock()
counters: "collections.Counter[str]" = collections.Counter()
# name -> [count, min, max, sum] of recorded durations.
_timings: Dict[str, list] = {}
# job_id -> {name -> [count, min, max, sum]}: the same stats scoped to
# the job that was current (health.job_scope) when they were recorded.
_job_timings: Dict[str, Dict[str, list]] = {}
# (gauge name, job_id or None) -> last set value. Gauges are levels:
# set_gauge overwrites, snapshots read the latest, reset clears.
_gauges: Dict[tuple, float] = {}
# Drivers record from worker threads while the watchdog monitor and
# receipt builders read; staticcheck's lock-discipline rule enforces the
# declaration (readers use snapshot()/delta(), never the bare maps).
_GUARDED_BY = guarded_by("_lock", "counters", "_timings", "_job_timings",
                         "_gauges", "_compile_listener_installed")

# Sentinel distinguishing "no job_id passed" (attribute to the current
# job scope) from an explicit job_id=None (process-level gauge).
_CURRENT_JOB = object()


def record(name: str, n: int = 1, **attrs) -> None:
    """Increments a DECLARED counter (REGISTRY membership is enforced).

    Extra keyword attributes (e.g. block=b) attach to the instant event
    emitted on the trace timeline when tracing is enabled; they are not
    stored in the counter itself.
    """
    if name not in REGISTRY:
        raise ValueError(
            f"telemetry.record({name!r}): not a declared metric. Declare "
            f"it in telemetry.REGISTRY (name, kind, help) first — "
            f"undeclared counters silently fork the metric namespace. "
            f"Declared: {sorted(REGISTRY)}")
    if REGISTRY[name].kind != "counter":
        raise ValueError(
            f"telemetry.record({name!r}): declared as a "
            f"{REGISTRY[name].kind}, not a counter — levels are set with "
            f"set_gauge(), record() increments monotonic counters only.")
    with _lock:
        counters[name] += n
    if trace.enabled():
        trace.instant(name, **attrs)
    # Forward to the current job's health state machine (lazy import:
    # health imports telemetry for durations, so the top-level import
    # would be circular; the hook only fires on failure-path events).
    from pipelinedp_tpu.runtime import health
    health.observe_counter(name, n)


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_listener_installed = False


def _on_jax_duration(event: str, duration_secs: float, **_) -> None:
    if event == _COMPILE_EVENT:
        record("backend_compiles")
        record("backend_compile_ms", int(round(duration_secs * 1e3)))


def install_compile_listener() -> None:
    """Registers the ONE process-wide jax.monitoring listener behind
    backend_compiles / backend_compile_ms (idempotent). Called where the
    runtime is first used (TPUBackend, the driver entry wrapper), not at
    import: importing this module touches neither JAX nor its listeners.
    JAX calls the listener on compiles and cache loads only, on the
    thread that built the program."""
    global _compile_listener_installed
    with _lock:
        if _compile_listener_installed:
            return
        _compile_listener_installed = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def set_gauge(name: str, value, job_id=_CURRENT_JOB) -> None:
    """Sets a DECLARED gauge to a point-in-time level.

    Gauges overwrite (a level, not a count) and are keyed by job: with
    the default job_id the current job scope (health.job_scope) owns the
    value; pass job_id=None for an explicitly process-level gauge, or a
    string to attribute to a job from outside its scope (the elastic
    runtime does this for live_devices). Gauges do not forward to the
    trace timeline — a queue-depth gauge updates per chunk, and flooding
    the bounded buffer with level samples would evict the causal
    incidents instants exist for.
    """
    metric = REGISTRY.get(name)
    if metric is None:
        raise ValueError(
            f"telemetry.set_gauge({name!r}): not a declared metric. "
            f"Declare it with _gauge(name, help) in telemetry.REGISTRY "
            f"first. Declared gauges: "
            f"{sorted(m.name for m in REGISTRY.values() if m.kind == 'gauge')}")
    if metric.kind != "gauge":
        raise ValueError(
            f"telemetry.set_gauge({name!r}): declared as a "
            f"{metric.kind}, not a gauge — counters increment via "
            f"record(), set_gauge() sets levels only.")
    if job_id is _CURRENT_JOB:
        from pipelinedp_tpu.runtime import health
        h = health.current()
        job_id = h.job_id if h is not None else None
    with _lock:
        _gauges[(name, job_id)] = float(value)


def gauge_snapshot() -> Dict[str, Dict[str, float]]:
    """{gauge name: {job_id or "": value}} for every gauge set this
    epoch. The empty-string key is the process-level (job-less) value —
    JSON-safe, and the Prometheus renderer maps it to a label-less
    sample."""
    with _lock:
        items = list(_gauges.items())
    out: Dict[str, Dict[str, float]] = {}
    for (name, job), value in items:
        out.setdefault(name, {})[job if job is not None else ""] = value
    return out


def _fold_timing(store: Dict[str, list], name: str, seconds: float) -> None:
    entry = store.get(name)
    if entry is None:
        store[name] = [1, seconds, seconds, seconds]
    else:
        entry[0] += 1
        entry[1] = min(entry[1], seconds)
        entry[2] = max(entry[2], seconds)
        entry[3] += seconds


def record_duration(name: str, seconds: float) -> None:
    """Aggregates one phase wall-time observation (min/max/sum/count),
    process-wide and under the current job's id (when a job_scope is
    active) so per-job snapshots never mix two jobs' phases. Timing
    names are free-form (phases are dynamic: watchdog_<phase>, driver
    kinds) — only counters validate against the registry."""
    seconds = float(seconds)
    from pipelinedp_tpu.runtime import health
    h = health.current()
    job = h.job_id if h is not None else None
    with _lock:
        _fold_timing(_timings, name, seconds)
        if job is not None:
            _fold_timing(_job_timings.setdefault(job, {}), name, seconds)
    health.observe_duration(name, seconds)


def _stats(store: Dict[str, list]) -> Dict[str, Dict[str, float]]:
    return {
        name: {
            "count": entry[0],
            "min": entry[1],
            "max": entry[2],
            "sum": entry[3],
        }
        for name, entry in store.items()
    }


def timing_snapshot(
        job_id: "str | None" = None) -> Dict[str, Dict[str, float]]:
    """Per-phase wall-time stats recorded via record_duration. With no
    job_id, the process-wide aggregate (every job plus unattributed
    phases); with one, only the phases recorded while that job's
    job_scope was current — two jobs in one process never mix."""
    with _lock:
        if job_id is None:
            return _stats(_timings)
        return _stats(_job_timings.get(job_id, {}))


def job_timing_snapshot() -> Dict[str, Dict[str, Dict[str, float]]]:
    """{job_id: timing_snapshot(job_id)} for every job that recorded a
    duration — the receipt-friendly per-job view."""
    with _lock:
        return {job: _stats(store) for job, store in _job_timings.items()}


def snapshot() -> Dict[str, int]:
    """Counter values only — a flat {name: int} safe to feed delta()."""
    with _lock:
        return dict(counters)


def full_snapshot() -> Dict[str, Any]:
    """Counters AND timing stats in one structured snapshot:
    {"counters": {name: int}, "gauges": gauge_snapshot(),
    "timings": timing_snapshot(), "job_timings": job_timing_snapshot()}.
    Use snapshot() when the result feeds delta(), which subtracts
    integer counters only."""
    return {
        "counters": snapshot(),
        "gauges": gauge_snapshot(),
        "timings": timing_snapshot(),
        "job_timings": job_timing_snapshot(),
    }


def delta(before: Dict[str, int]) -> Dict[str, int]:
    """Counter increments since a snapshot() (zero-valued keys omitted)."""
    now = snapshot()
    out = {k: now.get(k, 0) - before.get(k, 0)
           for k in set(now) | set(before)}
    return {k: v for k, v in out.items() if v}


def reset(force: bool = False) -> None:
    """Coordinated epoch reset: counters, gauges, timings, job timings,
    trace buffers, per-job health states, memory watermarks AND the
    budget odometer clear together, so test isolation and long-running
    processes can never mix epochs (a counter from one epoch attributed
    to another job's health, or a stale trace buffer leaking into the
    next run's export).

    Guarded under a resident service: resetting while any job_scope is
    active on some thread would wipe a LIVE job's health record,
    counters and odometer records out from under it — mid-run scrapes
    would report a healthy empty epoch and the job's ledger records
    would vanish before its teardown persisted them. With active scopes
    the reset therefore warns and no-ops; pass force=True to reset
    anyway (the concurrency-safety stress test does, deliberately)."""
    # Lazy import (health imports telemetry at module load).
    from pipelinedp_tpu.runtime import health as _health
    if not force:
        active = _health.active_job_scopes()
        if active:
            logging.warning(
                "telemetry.reset(): %d job_scope(s) are active — a "
                "process-wide epoch reset would corrupt live jobs' "
                "health/odometer state, so the reset is skipped. Wait "
                "for the jobs to finish (or pass force=True if you "
                "really mean it).", active)
            return
    with _lock:
        counters.clear()
        _timings.clear()
        _job_timings.clear()
        _gauges.clear()
    # Lazy imports: health imports telemetry at module load, and
    # observability's epoch state (memory accounting, odometer) sits a
    # layer above both.
    from pipelinedp_tpu.runtime import health
    from pipelinedp_tpu.runtime import observability
    health.reset()
    trace.reset()
    observability.reset_epoch()
