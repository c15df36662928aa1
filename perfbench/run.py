"""perfbench.run — one cell, once: set-up, window, comparison, one line.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. Finds the cell, its configuration and its
per-layer metrics BY FILE NAME from what BENCHMARK.json lists, and through
them the deployment's generator, job form and law (perfbench.find), so a
later PR adds any of them as files plus entries (README.md). Raises, and prints
no result, when JAX reports anything but a TPU or fewer chips than the
cell asks for; `--rehearse` relaxes that for a run on the CPU at the
configuration's `rehearsal` sizes, says so in `device`, and is never a
measurement. `--selftest` checks the benchmark's own arithmetic and exits.

The last line of stdout is the result: correct, attempted, failed,
metrics, device, (breakdown,) and `compared` last — each number the
comparison made beside its limit, which are also the last lines of stderr.
"""

import time

T0 = time.perf_counter()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "perfbench")
TRACE_DIR = os.path.join(ROOT, ".perfbench_trace")  # fixed, git-ignored


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload):
    """(cell file, configuration file, per-layer specs, end-to-end entries)
    of one cell, each found by its name."""
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"BENCHMARK.json has no workload {workload!r}; it "
                         f"has {[w['name'] for w in bench['workloads']]}")
    cell = load_json(HERE, "workloads", workload + ".json")
    listed = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(ROOT, listed["file"])
    for key, mine, theirs in (("config", cell["config"], entry["config"]),
                              ("chips", cell["chips"], entry["chips"]),
                              ("traffic", cell["traffic"]["name"],
                               entry["traffic"])):
        if mine != theirs:
            raise SystemExit(f"{workload}: the cell's file says {key} = "
                             f"{mine!r}, BENCHMARK.json says {theirs!r}")

    def in_cell(metric):
        return workload in metric.get("workloads", [workload])

    layers = [dict(load_json(HERE, "layer_metrics", m["name"] + ".json"),
                   unit=m["unit"])
              for m in bench["per_layer"] if in_cell(m)]
    end_to_end = [m for m in bench["end_to_end"] if in_cell(m)]
    return cell, config, layers, end_to_end


def enable_compile_cache(jax):
    """The repo's one cache rule (benchmarks/_common.enable_compile_cache):
    where JAX_COMPILATION_CACHE_DIR is set nothing is set in code, else
    <checkout>/.jax_cache — a fixed path, since the path is in the key.
    JAX's own threshold stays (PERSIST_S): what compiles faster is never
    kept, so every run builds the same small programs and the cache a
    check fills never makes a later run's window cheaper than an earlier
    one's. It is five times SMALL_S on purpose: a 40 ms slice program that
    a loaded host once took 0.2 s over must not be kept, or every later
    job with that kept count would load it and count as failed."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", PERSIST_S)
    return jax.config.jax_compilation_cache_dir


PERSIST_S = 1.0  # JAX's default


# The line between a small program and a large one, in seconds of backend
# compile. The drain's `col[:k]` slice programs measured 46 ms each on the
# chip tool's host (PERF.md §6); a kernel of the release path takes from
# seconds (block kernel, 24 s) to minutes.
SMALL_S = 0.2
# What one drain can explain: the program slices the released columns to
# the kept count on the device, two `dynamic_slice` programs for a kept
# count the process has not seen (measured: 0 or 2 per drain). A cell on
# a route whose slices build otherwise states its own count, with the
# measurement, as `traffic.small_programs_per_drain`.
SMALL_PER_DRAIN = 2


def small_allowed(cell):
    """Small programs one job of the cell may build: what its drains can
    explain."""
    spec = cell["traffic"]
    return int(spec.get("small_programs_per_drain", SMALL_PER_DRAIN)) * int(
        spec["drains_per_job"])


class ProgramsBuilt:
    """The benchmark's own record, through jax.monitoring, of programs
    built in this process — it works with rt_trace off, which the
    program's `jit_cache_misses` does not.

    `large`: a backend compile of SMALL_S or more, or a hit in the
    persistent cache (only programs of PERSIST_S or more are in it).
    Set-up's warm-up job builds or loads every one of them; one inside a
    window fails the job it fell in.
    `small`: backend compiles under SMALL_S. The program slices each
    released column to the job's kept count on the device
    (executor.decode_release_results, `col[:k]`), a new `dynamic_slice`
    program for every kept count not yet seen in the process — no warm-up
    can cover them, every user's job pays them, and they are never in the
    persistent cache. A job may build SMALL_PER_DRAIN of them (or the
    cell's own `traffic.small_programs_per_drain`) for each drain it makes
    (the cell's `traffic.drains_per_job`); more fails the job, whatever
    they are. They are counted, per job, as the per-layer
    metric `window_builds_per_job`.
    `seconds`: every build's duration in order, a cache load negative, so
    that the result's `run` notes show what was built where."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.large = self.small = 0
        self.seconds = []
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **_):
        if name == self.CACHE_HIT:  # its backend_compile span follows
            self._hit = True

    def _on_span(self, name, seconds, **_):
        if name == self.COMPILE:
            if self._hit or seconds >= SMALL_S:
                self.large += 1
            else:
                self.small += 1
            self.seconds.append(-seconds if self._hit else seconds)
            self._hit = False


def device_stamp(jax, rehearsal):
    devices = jax.devices()
    stamp = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if rehearsal:
        stamp["rehearsal"] = True  # a CPU run at toy sizes: no measurement
    return stamp


def require_device(jax, chips, rehearse):
    """The device stamp — or no run: anything but a TPU with `chips` chips
    raises, unless this is a rehearsal."""
    stamp = device_stamp(jax, rehearse)
    if rehearse:
        return stamp
    if stamp["platform"] != "tpu":
        raise SystemExit(f"perfbench needs a TPU; JAX reports platform "
                         f"{stamp['platform']!r} ({stamp['kind']}). There is "
                         f"no CPU fallback (--rehearse is not a measurement).")
    if stamp["count"] < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s); JAX reports "
                         f"{stamp['count']}")
    return stamp


def sized(cell, config, rehearse):
    """(configuration, rows per job) as they are run. Rows: the cell's own
    `traffic.rows_per_job` where it states one (a cut or growth its file
    lists under `reduced`), else the configuration's. With `rehearse`, the
    configuration's `rehearsal` sizes."""
    if not rehearse:
        return config, int(cell["traffic"].get(
            "rows_per_job", config["scale"]["rows_per_job"]))
    toy = config["rehearsal"]
    generator = dict(config["generator"], args=toy["generator_args"])
    config = dict(config, generator=generator,
                  encoded=toy.get("encoded", config.get("encoded")))
    return config, int(toy["rows_per_job"])


class TracedWindow:
    """The profiler over the first whole jobs of a traced run's window: on
    from the first job's start to the first job boundary `seconds` later."""

    def __init__(self, jax, seconds):
        self.jax, self.seconds = jax, seconds
        self.started = None
        self.on = False

    def on_job_start(self, _index):
        now = time.perf_counter()
        if self.started is None:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = self.jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            self.jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            self.started, self.on = now, True
        elif self.on and now - self.started >= self.seconds:
            self.stop()

    def stop(self):
        if self.on:
            self.jax.profiler.stop_trace()
            self.on = False


def memory_peak_bytes(jax):
    """The peak on the fullest chip, from the device itself; None where
    the backend reports none (the CPU of a rehearsal)."""
    peaks = []
    for device in jax.devices():
        stats = device.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


ANCHOR = "perfbench_anchor"


def program_spans(rt_trace, anchor_s):
    """The program's rt_trace spans as (name, start_s, end_s) on
    time.perf_counter: its exported timestamps are relative to an epoch of
    its own, which the ANCHOR instant (recorded at `anchor_s`) locates."""
    events = rt_trace.to_trace_events()["traceEvents"]
    anchor_us = next(e["ts"] for e in events if e["name"] == ANCHOR)
    return [(e["name"], anchor_s + (e["ts"] - anchor_us) * 1e-6,
             anchor_s + (e["ts"] + e["dur"] - anchor_us) * 1e-6)
            for e in events if e.get("ph") == "X"]


def release_arrays(release):
    """A job's release dict (never empty: such a job failed) as (keys,
    values[n, released columns]) for the comparison."""
    import numpy as np
    keys = np.fromiter(release.keys(), dtype=np.int64, count=len(release))
    values = np.array(list(release.values()), dtype=np.float64).reshape(
        len(release), len(next(iter(release.values()))))
    return keys, values


def execute(args):
    """One run of one cell; returns the result line as a dict."""
    cell, config, layers, end_to_end = load_cell(args.workload)
    import jax

    stamp = require_device(jax, cell["chips"], args.rehearse)
    cache_dir = enable_compile_cache(jax)
    built = ProgramsBuilt(jax)

    import numpy as np
    import pipelinedp_tpu  # noqa: F401 - the system under test; absent, the run fails here
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry
    from pipelinedp_tpu.runtime import trace as rt_trace
    from perfbench import data, readers, reference, trace_reduce, traffic

    # ---- set-up: rows from the seed, one warm-up job --------------------
    config, rows_per_job = sized(cell, config, args.rehearse)
    law = reference.law_of(config)
    t = time.perf_counter()
    imports_s = t - T0  # python, jax, the device, the program's modules
    columns = data.generate(config["generator"], rows_per_job, args.seed)
    generate_s = time.perf_counter() - t
    job = traffic.build_job(cell, config, columns)
    t = time.perf_counter()
    if not job(traffic.noise_seed(args.seed, -1)):  # compiles or loads all
        raise SystemExit("the warm-up job released nothing")
    warm_s = time.perf_counter() - t
    setup_large, setup_small = built.large, built.small
    tracing = bool(args.trace)
    profiler = anchor_s = None
    if tracing:
        profiler = TracedWindow(jax, float(cell.get("traced_seconds", 5)))
        rt_trace.enable()
        rt_trace.instant(ANCHOR)  # ties rt_trace's clock to perf_counter
        anchor_s = time.perf_counter()
    counters_before = rt_telemetry.snapshot()
    setup_s = time.perf_counter() - T0

    # ---- the measured window ------------------------------------------
    allowed = small_allowed(cell)
    builds_before = len(built.seconds)
    records, (w_start, w_end) = traffic.closed_loop(
        job, args.seed, args.seconds, rows_per_job, built, allowed,
        profiler.on_job_start if tracing else None)

    # ---- what the program and the device recorded -----------------------
    spans, host_spans = {}, []
    if tracing:
        profiler.stop()
        spans = rt_trace.trace_summary()["spans"]
        host_spans = program_spans(rt_trace, anchor_s)
        rt_trace.disable()
    counters = rt_telemetry.delta(counters_before)
    device = dict(stamp, memory_peak_bytes=memory_peak_bytes(jax))
    window_s = w_end - w_start
    done = [r for r in records if not r["failed"]]
    for r in records:
        if r["failed"]:
            print(f"[perfbench] job {r['index']} failed: error={r['error']} "
                  f"large programs built={r['programs_built']} small="
                  f"{r['small_programs_built']} (allowed {allowed})",
                  file=sys.stderr)

    # ---- the comparison, on what the window's jobs released -------------
    g = config["guarantees"]
    t = time.perf_counter()
    expect = law.expectations(*columns, g)
    releases = [release_arrays(r["release"]) for r in done]
    correct, compared = reference.decide(law.compare(expect, releases),
                                         cell["limits"])
    reference_s = time.perf_counter() - t

    # ---- the metrics ------------------------------------------------------
    result = {"correct": correct and bool(done), "attempted": len(records),
              "failed": len(records) - len(done), "metrics": {},
              "device": device}
    traced_jobs = 0
    if not tracing:
        values = {"rows_per_s": sum(r["rows"] for r in done) / window_s,
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in end_to_end}
    else:
        xplane = trace_reduce.find_xplane(TRACE_DIR)
        reduced = trace_reduce.reduce_trace(
            xplane, host_spans=host_spans,
            first_job_start_s=records[0]["start"])
        if args.debug_dir:
            os.makedirs(args.debug_dir, exist_ok=True)
            kept = os.path.join(args.debug_dir, args.workload)
            shutil.copy(xplane, kept + ".xplane.pb")
            with open(kept + ".reduced.json", "w") as f:
                json.dump(reduced, f, indent=1)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        kept_per_job = (float(np.mean([len(r["release"]) for r in done]))
                        if done else 0.0)
        observed = {
            "jobs": len(records), "spans": spans, "counters": counters,
            "window_builds": built.small - setup_small, "trace": reduced,
            "min_bytes_per_job": law.min_bytes(rows_per_job, kept_per_job, g),
            "device_kind": stamp["kind"],
        }
        for spec in layers:
            value = readers.read(spec, observed)
            if value is not None:
                result["metrics"][spec["name"]] = {"value": value,
                                                   "unit": spec["unit"]}
        if reduced["devices"]:  # a rehearsal's trace has no device plane
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        traced_jobs = reduced["jobs"]

    result["run"] = {  # the harness's own notes; the driver ignores them
        "workload": args.workload, "seed": args.seed, "trace": args.trace,
        "jobs": len(done), "window_s": window_s,
        "job_s": [r["end"] - r["start"] for r in records],
        "job_small_builds": [r["small_programs_built"] for r in records],
        "job_span_s": {name: [sum(e - s for n, s, e in host_spans
                                  if n == name and r["start"] <= s <= r["end"])
                              for r in records]
                       for name in ("ingest", "post_process")}
        if tracing else {},
        "span_ms_per_job": {name: 1e3 * row["inclusive_s"] / len(records)
                            for name, row in spans.items()},
        "kept": [len(r["release"]) for r in done][:4],
        "imports_s": imports_s, "generate_s": generate_s,
        "warm_job_s": warm_s,
        "reference_s": reference_s,
        "programs_built_in_setup": [setup_large, setup_small],
        "programs_built_in_window": [built.large - setup_large,
                                     built.small - setup_small],
        "build_s_in_setup": [round(x, 4) for x in
                             built.seconds[:builds_before]],
        "build_s_in_window": [round(x, 4) for x in
                              built.seconds[builds_before:]][:64],
        "compile_cache_dir": cache_dir, "traced_jobs": traced_jobs,
    }
    result["compared"] = {name: [row["value"], row["limit"]]
                          for name, row in compared.items()}
    return result


def run(args):
    result = execute(args)
    print(json.dumps(result), flush=True)
    for name, (value, limit) in result["compared"].items():
        held = ("not held" if limit is None else
                f"limit {limit:.6g}  {'ok' if value <= limit else 'OVER'}")
        print(f"[perfbench] compared {name} = {value:.6g}  {held}",
              file=sys.stderr)
    print(f"[perfbench] correct = {result['correct']}", file=sys.stderr,
          flush=True)
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="allow a CPU, at the configuration's rehearsal "
                             "sizes; stamps the result; never a measurement")
    parser.add_argument("--selftest", action="store_true")
    parser.add_argument("--debug-dir", default=None,
                        help="traced run: keep the .xplane.pb and a summary "
                             "of its planes here (for looking at by hand)")
    args = parser.parse_args(argv)
    if args.selftest:
        from perfbench import selftest
        return selftest.main()
    if not args.workload:
        parser.error("--workload is required")
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
