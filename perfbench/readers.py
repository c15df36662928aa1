"""The reader kinds of per-layer metrics.

A per-layer metric is one file, perfbench/layer_metrics/<metric>.json,
that names one of these readers and what it reads. A reader gets the
traced run's `observed` (below) and returns the metric's value, or None
when it finds nothing to read — the harness then leaves the metric out of
the result line. A share of a roofline is never reported as 0.

observed = {
  "jobs":        jobs completed in the measured window,
  "spans":       {name: {"count", "inclusive_s", "exclusive_s"}} — the
                 program's rt_trace spans over the window,
  "counters":    {name: delta} — the program's telemetry counters,
  "window_builds": small programs built inside the window (run.py's
                 ProgramsBuilt, from jax.monitoring),
  "trace":       the reduction of the profiler trace (trace_reduce.
                 reduce_trace): busy_s, window_s, jobs (traced), ...
  "min_bytes_per_job": the least bytes one job has to move through device
                 memory, as the configuration's law counts them,
  "device_kind": as JAX reports it (the key of trace_reduce.PEAKS)
}
"""

from perfbench import trace_reduce


def span_ms_per_job(spec, observed):
    """Sum of the named rt_trace spans' time over the window ÷ jobs.
    `time`: "inclusive" (a span's own wall time) or "exclusive" (less its
    child spans — use it when the named spans nest in one another)."""
    field = {"inclusive": "inclusive_s",
             "exclusive": "exclusive_s"}[spec.get("time", "inclusive")]
    found = [observed["spans"][name] for name in spec["spans"]
             if name in observed["spans"]]
    if not found or not observed["jobs"]:
        return None
    return 1e3 * sum(s[field] for s in found) / observed["jobs"]


def counter_per_job(spec, observed):
    """A telemetry counter's increase over the window ÷ jobs."""
    delta = observed["counters"].get(spec["counter"])
    if not delta or not observed["jobs"]:
        return None
    return delta / observed["jobs"]


def window_builds_per_job(spec, observed):
    """Small programs built inside the window ÷ jobs (the benchmark's own
    jax.monitoring count; see run.ProgramsBuilt). Zero is a reading here:
    a program that builds nothing per job reports 0."""
    if not observed["jobs"]:
        return None
    return observed["window_builds"] / observed["jobs"]


def _busy_s_per_job(observed):
    trace = observed.get("trace")
    if not trace or not trace["jobs"] or trace["busy_s"] <= 0:
        return None
    return trace["busy_s"] / trace["jobs"]


def device_busy_ms_per_job(spec, observed):
    """Union of the device-op intervals in the traced window ÷ traced
    jobs: by interval, whatever the ops are called."""
    busy = _busy_s_per_job(observed)
    return None if busy is None else 1e3 * busy


def device_idle_pct(spec, observed):
    """1 − busy ÷ traced window."""
    trace = observed.get("trace")
    if not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def min_bytes_roofline_pct(spec, observed):
    """Least time for the job's bytes (its law's `min_bytes`) at the peak
    HBM rate of every device the trace shows working ÷ their mean busy
    time per job."""
    busy = _busy_s_per_job(observed)
    if busy is None:
        return None
    return trace_reduce.min_bytes_roofline_pct(
        observed["min_bytes_per_job"], busy, observed["device_kind"],
        observed["trace"]["devices"])


READERS = {
    "span_ms_per_job": span_ms_per_job,
    "counter_per_job": counter_per_job,
    "window_builds_per_job": window_builds_per_job,
    "device_busy_ms_per_job": device_busy_ms_per_job,
    "device_idle_pct": device_idle_pct,
    "min_bytes_roofline_pct": min_bytes_roofline_pct,
}


def read(spec, observed):
    reader = READERS.get(spec["reader"])
    if reader is None:
        raise KeyError(f"metric {spec['name']}: unknown reader "
                       f"{spec['reader']!r}; readers.py has {sorted(READERS)}")
    return reader(spec, observed)
