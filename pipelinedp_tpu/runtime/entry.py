"""Shared runtime-entry discipline for every meshed/blocked driver.

One decorator gives the four meshed drivers (sharded_aggregate_arrays,
sharded_select_partitions, aggregate_blocked_sharded,
select_partitions_blocked_sharded) and the two unsharded blocked drivers
a single API boundary for the runtime knobs:

  * validation: every runtime knob (job_id, timeout_s, retry, journal,
    watchdog, elastic, elastic_grow, min_devices) is rejected with an
    actionable
    message HERE, through input_validators, before any device work —
    tests/test_knob_validation.py greps this module to prove no knob
    can skip it.
  * health scope: the run executes inside its job's health scope
    (telemetry counter/duration forwarding + completion/failure
    accounting) and under thread-local watchdog activation, so
    retry_call, the drain guards, host_fetch heartbeats and the
    device-reshard collective deadline all see them without signature
    threading. The backend RetryPolicy's max_retries is also scoped onto
    host_fetch (mesh.fetch_retry_scope), so the retry= knob governs
    control-plane fetches too.
  * elastic mesh degradation (meshed drivers only — the ones
    constructed with a `fallback`): elastic=True wraps the run in
    runtime/retry.run_with_mesh_degradation. A device-fatal failure
    rebuilds a smaller mesh from the surviving devices and re-enters the
    driver; at the one-device floor the unsharded fallback runs instead;
    losses past min_devices raise MeshDegradationError with a resume
    pointer. Block keys are fold_in(final_key, b) — independent of mesh
    geometry — so every re-entry replays the same release. On
    multi-controller meshes the same loop covers whole-host loss: the
    mesh rebuilds over the surviving hosts, and an evacuated controller
    (no addressable device left) raises HostEvacuatedError.
    elastic_grow=True upgrades the loop to full fleet elasticity
    (run_with_mesh_elasticity): announced join candidates
    (retry.announce_join) are admitted at block boundaries and the mesh
    rebuilds over the LARGER device set — shrink tolerance included, so
    elastic_grow implies elastic.
  * multi-controller coordination (meshed drivers on a mesh that is not
    fully addressable): the journal knob is automatically scoped to this
    controller's process index (BlockJournal.scoped_to_process) so
    co-hosted processes sharing a journal directory never collide or
    cross-replay, and the driver span carries the process index.

timeout_s: per-operation deadline in seconds. Shorthand for
    watchdog=Watchdog(timeout_s=...); with neither, no deadlines are
    enforced. Passing a Watchdog without timeout_s auto-derives
    deadlines as a multiple of the pass-1 profiled time.
"""

import functools
import logging
import time
from typing import Callable, Optional

from pipelinedp_tpu import input_validators
from pipelinedp_tpu.runtime import health as rt_health
from pipelinedp_tpu.runtime import retry as rt_retry
from pipelinedp_tpu.runtime import telemetry as rt_telemetry
from pipelinedp_tpu.runtime import trace as rt_trace
from pipelinedp_tpu.runtime import watchdog as rt_watchdog


def runtime_entry(kind: str, fallback: Optional[Callable] = None):
    """Decorator for a driver entry point (see module docstring).

    kind: default job id + the duration-stat name of the driver.
    fallback: meshed drivers only — fallback(args, kwargs, job_id) runs
        the unsharded equivalent when elastic degradation reaches the
        one-device floor (args are the driver's positional args, mesh
        first). Its presence marks the driver as meshed.
    """
    meshed = fallback is not None

    def deco(fn):

        @functools.wraps(fn)
        def wrapper(*args,
                    timeout_s: Optional[float] = None,
                    watchdog: Optional[rt_watchdog.Watchdog] = None,
                    job_id: Optional[str] = None,
                    elastic: bool = False,
                    elastic_grow: bool = False,
                    min_devices: int = 1,
                    **kwargs):
            job = job_id or kind
            input_validators.validate_job_id(job, kind)
            rt_telemetry.install_compile_listener()
            if timeout_s is not None:
                input_validators.validate_timeout_s(timeout_s, kind)
            if kwargs.get("retry") is not None:
                input_validators.validate_retry_policy(kwargs["retry"], kind)
            if kwargs.get("journal") is not None:
                input_validators.validate_journal(kwargs["journal"], kind)
            if watchdog is not None:
                input_validators.validate_watchdog(watchdog, kind)
            if "overlap" in kwargs:
                input_validators.validate_overlap_drain(
                    kwargs["overlap"], kind)
            input_validators.validate_elastic(elastic, kind)
            input_validators.validate_elastic_grow(elastic_grow, kind)
            input_validators.validate_min_devices(min_devices, kind)
            if elastic and not meshed:
                # The unsharded drivers have no mesh to degrade; the knob
                # is accepted (one backend config drives every route) and
                # simply has nothing to do.
                logging.debug(
                    "%s: elastic=True ignored — the unsharded driver "
                    "already runs at the one-device floor.", kind)
            wd = watchdog
            if wd is None and timeout_s is not None:
                wd = rt_watchdog.Watchdog(timeout_s=timeout_s)
            elif wd is not None and timeout_s is not None:
                wd.timeout_s = timeout_s
            # Lazy: parallel imports runtime; the reverse edge must not
            # run at import time.
            from pipelinedp_tpu.parallel import mesh as mesh_lib
            fetch_retries = getattr(kwargs.get("retry"), "max_retries",
                                    None)
            # The job-wide transient-retry budget (None = uncapped):
            # scoped here so every retry seam the run passes through —
            # dispatch retry, reshard host fallback, host fetch — draws
            # from ONE per-job pool.
            total_retries = getattr(kwargs.get("retry"),
                                    "max_total_retries", None)
            span_attrs = {"job": job}
            if meshed and not mesh_lib.is_fully_addressable(args[0]):
                # Multi-controller mesh: per-process coordination. The
                # journal (when present) is scoped to this controller so
                # co-hosted processes sharing one directory can never
                # collide, cross-replay or quarantine each other's
                # records; health snapshots and spans carry the process
                # index for the same (job_id, process_index) keying.
                pi = mesh_lib.process_index()
                span_attrs["process"] = pi
                journal = kwargs.get("journal")
                if journal is not None and \
                        getattr(journal, "process_index", None) is None and \
                        callable(getattr(journal, "scoped_to_process",
                                         None)):
                    kwargs["journal"] = journal.scoped_to_process(pi)
                    logging.debug(
                        "%s: journal scoped to controller process %d "
                        "(multi-controller mesh).", kind, pi)
            t0 = time.perf_counter()
            with rt_health.job_scope(job), rt_watchdog.activate(wd), \
                    mesh_lib.fetch_retry_scope(fetch_retries), \
                    rt_retry.retry_budget_scope(total_retries), \
                    rt_trace.span(kind, **span_attrs):
                if meshed and (elastic or elastic_grow):
                    # elastic_grow implies shrink tolerance: the full-
                    # fleet loop (run_with_mesh_elasticity) is the shrink
                    # loop plus join admission, so the strongest knob
                    # picks the engine.
                    elastic_runner = (rt_retry.run_with_mesh_elasticity
                                      if elastic_grow else
                                      rt_retry.run_with_mesh_degradation)
                    result = elastic_runner(
                        lambda m: fn(m, *args[1:], job_id=job, **kwargs),
                        args[0],
                        fallback=lambda: fallback(args, kwargs, job),
                        min_devices=min_devices,
                        job_id=job,
                        journal=kwargs.get("journal"))
                else:
                    result = fn(*args, job_id=job, **kwargs)
                rt_telemetry.record_duration(kind,
                                             time.perf_counter() - t0)
            if kwargs.get("journal") is not None:
                # Teardown audit persist: the ordered budget-odometer
                # trail rides the journal's durability (CRC, fsync-then-
                # rename) and process scoping, so a resume — or an
                # auditor — replays mechanism provenance from the same
                # store the block results live in. Best-effort: a failed
                # persist must not fail a completed run.
                from pipelinedp_tpu.runtime import observability
                try:
                    observability.persist_odometer(kwargs["journal"], job)
                except Exception as e:  # noqa: BLE001 - audit persistence is an observer; the run's results are already safe
                    logging.warning(
                        "%s: odometer persist to journal failed (%s: "
                        "%s); the in-memory audit trail is unaffected.",
                        kind, type(e).__name__, e)
            return result

        return wrapper

    return deco
