"""From a jax.profiler trace (.xplane.pb) to device busy/idle, per-op time
and attributed idle gaps; the table of peaks; the roofline's share (the
bytes themselves travel with the deployment's law: laws/<law>.min_bytes).

Read with nothing but JAX (`jax.profiler.ProfileData`). What a trace of
this system looks like on a TPU v5e (looked at by hand, PERF.md §5):
planes `/device:TPU:<n>` carry a line `XLA Ops` with one event per HLO
operation the device ran (control-flow ops contain their bodies' events,
nested on the same line; `XLA Modules` has one event per program and
`Async XLA Ops` the device-side copies, neither read here); the plane `/host:CPU` carries one line per host thread, on which
`jax.profiler.TraceAnnotation` spans appear under their names. Device and
host events are on one clock, to about a millisecond (fixtures/).

Busy time is the union of the device-op intervals (by interval, not by
name: it reads the same work whatever implements it); idle is the traced
window less that. A gap is attributed to the innermost benchmark
annotation (`pb:*`, written by perfbench/traffic.py around each job and
each call it makes) that covers its midpoint.

A CPU rehearsal has no device plane: its trace reduces to no device time
at all, and the readers of device metrics then report nothing.
"""

import glob
import os

# One table, keyed by device_kind as JAX reports it. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per
# chip). An unknown kind is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}

ANNOTATION_PREFIX = "pb:"
SHORT_GAP_NS = 10_000
SHORT_GAPS = "(gaps under 10 us, between ops)"
DEVICE_OP_LINE = "XLA Ops"


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add a row "
                       f"to perfbench/trace_reduce.py PEAKS with its source")
    return PEAKS[device_kind]


def min_bytes_roofline_pct(min_bytes, busy_s_per_job, device_kind, devices):
    """Least time for a job's bytes at the peak HBM rate of ALL the devices
    that worked on it, as a share of the time they were busy for it.
    `min_bytes`: the whole job's least bytes (its law's `min_bytes`);
    `busy_s_per_job`: busy time per job averaged over the `devices` the
    trace shows working (reduce_trace's busy_s), so a job spread over four
    chips is held to four chips' bandwidth. None when nothing ran."""
    if not busy_s_per_job or busy_s_per_job <= 0:
        return None
    least_s = min_bytes / (devices * peaks_for(device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / busy_s_per_job


# ---------------------------------------------------------------------------
# Interval arithmetic (pure; checked by the selftest)
# ---------------------------------------------------------------------------


def union(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return sum(e - s for s, e in intervals)


def gaps(busy, lo, hi):
    """The complement of a disjoint sorted `busy` inside [lo, hi]."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events):
    """Per-name self time of (name, start, end) events of ONE line, where a
    control-flow op's event contains its body's: an event's self time is its
    duration less its direct children's."""
    out = {}
    stack = []  # [name, end, start, time covered by direct children]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, start, child = stack.pop()
            out[name] = out.get(name, 0) + (end - start) - child
            if stack:
                stack[-1][3] += end - start
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([name, end, start, 0])
    close(float("inf"))
    return out


def attribute_gaps(idle, annotations, shortest=SHORT_GAP_NS):
    """Idle time by what the host was doing: each gap goes to the
    innermost annotation (shortest span) that covers its midpoint, or to
    `(outside any job)`; gaps under `shortest` (the device stepping from
    one op to the next) are lumped under one name. `annotations` are
    (name, start, end). Returns (time by name, longest gap by name)."""
    by_name, longest = {}, {}
    short = sum(e - s for s, e in idle if e - s < shortest)
    if short:
        by_name[SHORT_GAPS] = short
        longest[SHORT_GAPS] = max(e - s for s, e in idle if e - s < shortest)
    for s, e in idle:
        if e - s < shortest:
            continue
        mid = 0.5 * (s + e)
        covering = [a for a in annotations if a[1] <= mid <= a[2]]
        name = (min(covering, key=lambda a: a[2] - a[1])[0]
                if covering else "(outside any job)")
        by_name[name] = by_name.get(name, 0) + (e - s)
        longest[name] = max(longest.get(name, 0), e - s)
    return by_name, longest


# ---------------------------------------------------------------------------
# xplane -> events
# ---------------------------------------------------------------------------


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_events(path):
    """{"devices": [per device: [per line: [(name, start_ns, end_ns)]]],
        "annotations": [(name, start_ns, end_ns), ...],
        "planes": {plane: {line: n_events}}}"""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, annotations = [], []
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        is_device = plane.name.startswith("/device:TPU:") and \
            "SparseCore" not in plane.name
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            lines[line.name] = len(events)
            if is_device and line.name == DEVICE_OP_LINE:
                devices.append([events])
            elif plane.name == "/host:CPU":
                annotations += [e for e in events
                                if e[0].startswith(ANNOTATION_PREFIX)]
    return {"devices": devices, "annotations": annotations, "planes": planes}


def short_name(hlo):
    """`%sort.32 = (s32[16777216]{0:T(1024)}, ...) sort(...)` ->
    `%sort.32 (s32[16777216]`: the op's own name and its (first) output
    shape. A TPU trace names each op by its whole HLO line."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:64]
    return f"{head} {rest.split('{')[0].split(' ')[0]}"[:64]


def reduce_trace(path, top=10, host_spans=(), first_job_start_s=None):
    """The traced window's numbers. The window is the span of the
    benchmark's `pb:job` annotations (first start to last end); seconds
    throughout. busy_s is averaged over the devices that ran anything.

    `host_spans`: the program's own spans, (name, start_s, end_s) on the
    clock on which the first traced job started at `first_job_start_s`
    (time.perf_counter): they are moved onto the trace's clock by that one
    point and join the annotations the idle gaps are attributed to, as
    `span:<name>` — the engine is lazy, so without them every gap falls in
    `pb:materialise`."""
    ev = read_events(path)
    jobs = sorted((a for a in ev["annotations"]
                   if a[0] == ANNOTATION_PREFIX + "job"), key=lambda a: a[1])
    if not jobs:
        raise ValueError("the trace holds no pb:job annotation")
    lo, hi = jobs[0][1], max(j[2] for j in jobs)
    ns = 1e-9
    if host_spans and first_job_start_s is not None:
        shift = lo - first_job_start_s / ns
        ev["annotations"] = ev["annotations"] + [
            ("span:" + name, start / ns + shift, end / ns + shift)
            for name, start, end in host_spans]
    busy_each, op_self = [], {}
    idle_by, idle_longest = {}, {}
    for device in ev["devices"]:
        busy = clip(union((s, e) for line in device for _, s, e in line),
                    lo, hi)
        if not busy:
            continue
        busy_each.append(total(busy) * ns)
        for line in device:
            inside = [(n, max(s, lo), min(e, hi)) for n, s, e in line
                      if min(e, hi) > max(s, lo)]
            for name, t in self_times(inside).items():
                name = short_name(name)
                op_self[name] = op_self.get(name, 0) + t * ns
        by, longest = attribute_gaps(gaps(busy, lo, hi), ev["annotations"])
        for name, t in by.items():
            idle_by[name] = idle_by.get(name, 0) + t * ns
            idle_longest[name] = max(idle_longest.get(name, 0),
                                     longest[name] * ns)
    n_dev = max(len(busy_each), 1)

    def top_of(table):
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[name, seconds / n_dev] for name, seconds in ranked]

    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy_each) / n_dev if busy_each else 0.0,
        "jobs": len(jobs),
        "devices": len(busy_each),
        "device_ops": top_of(op_self),
        "idle_gaps": top_of(idle_by),
        "longest_gap_s": max(idle_longest.values()) if idle_longest else 0.0,
        "planes": ev["planes"],
    }
