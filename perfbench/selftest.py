"""`python3 -m perfbench.run --selftest`: the benchmark's own arithmetic,
checked without a chip — the interval reduction on hand-made intervals
and on the recorded trace in fixtures/, the roofline's byte count against
hand-computed values on one chip and on four, the closed forms of the
reference against brute force, the reference against its own simulator
(sound: passes; each break: caught), and that every generator, form and
law a listed file names is there to be found."""

import json
import math
import os

import numpy as np

import perfbench
from perfbench import reference, trace_reduce
from perfbench.laws import bounded_laplace_geometric as law

HERE = os.path.dirname(os.path.abspath(__file__))


def check(name, ok, detail=""):
    print(f"[selftest] {'ok  ' if ok else 'FAIL'} {name} {detail}")
    return bool(ok)


def intervals():
    u = trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    g = trace_reduce.gaps(u, -1, 12)
    s = trace_reduce.self_times([("while", 0, 10), ("sort", 1, 4),
                                 ("sort", 5, 9), ("add", 12, 13)])
    by, longest = trace_reduce.attribute_gaps(
        [(0, 100), (200, 50_200), (60_000, 90_000)],
        [("pb:job", 0, 100_000), ("pb:aggregate", 100, 55_000)],
        shortest=1_000)
    return all([
        check("union", u == [(0, 3), (5, 8)], str(u)),
        check("total", trace_reduce.total(u) == 6),
        check("clip", trace_reduce.clip(u, 2, 6) == [(2, 3), (5, 6)]),
        check("gaps", g == [(-1, 0), (3, 5), (8, 12)], str(g)),
        check("self_times", s == {"while": 3, "sort": 7, "add": 1}, str(s)),
        check("attribute_gaps",
              by == {trace_reduce.SHORT_GAPS: 100, "pb:aggregate": 50_000,
                     "pb:job": 30_000} and longest["pb:job"] == 30_000,
              str(by)),
    ])


def fixture():
    """The recorded TPU trace reduces to the numbers it reduced to when it
    was recorded (fixtures/small.expected.json), and those hang together."""
    path = os.path.join(HERE, "fixtures", "small.xplane.pb")
    with open(os.path.join(HERE, "fixtures", "small.expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce_trace(path)
    ok = True
    for key in ("window_s", "busy_s", "jobs", "devices", "longest_gap_s"):
        same = (math.isclose(got[key], want[key], rel_tol=1e-9)
                if isinstance(want[key], float) else got[key] == want[key])
        ok &= check(f"fixture {key}", same, f"{got[key]} vs {want[key]}")
    ok &= check("fixture device_ops",
                [n for n, _ in got["device_ops"]] ==
                [n for n, _ in want["device_ops"]])
    idle = sum(t for _, t in got["idle_gaps"])
    ok &= check("fixture busy + idle = window",
                math.isclose(got["busy_s"] + idle, got["window_s"],
                             rel_tol=1e-6), f"{got['busy_s']} + {idle}")
    ok &= check("fixture is a TPU trace", got["devices"] == 1 and
                got["jobs"] == 3 and 0 < got["busy_s"] < got["window_s"])
    # By hand: the toy program sorts 4 times in a loop, in each of 3 jobs;
    # the loop's own time is what its sorts leave of it.
    events = trace_reduce.read_events(path)
    ops = events["devices"][0][0]
    sorts = [e for e in ops if e[0].startswith("%sort")]
    loops = [e for e in ops if e[0].startswith("%while")]
    # (The device's clock runs about a millisecond ahead of the host's in
    # this recording, so the first job's sorts fall before the window the
    # host's `pb:job` spans define and are clipped away: 8 of 12 count.)
    jobs = [a for a in events["annotations"] if a[0] == "pb:job"]
    lo, hi = min(j[1] for j in jobs), max(j[2] for j in jobs)
    sort_s = sum(max(0, min(e, hi) - max(s, lo)) for _, s, e in sorts) * 1e-9
    reduced = dict(got["device_ops"])
    ok &= check("fixture holds 12 sorts in 3 loops",
                len(sorts) == 12 and len(loops) == 3)
    ok &= check("fixture sort self time",
                math.isclose(reduced["%sort.9 (f32[4096]"], sort_s,
                             rel_tol=1e-9), f"{sort_s}")
    ok &= check("fixture loop self time is what its body leaves",
                reduced["%while (s32[]"] < 0.01 * sort_s)
    return ok


def roofline():
    """By hand: 2^24 rows x 13 B = 218,103,808 B, + 17,000 kept x 2 columns
    x 4 B = 136,000 B; at 819 GB/s that is 0.26647 ms, so 0.1 % of a job
    whose device time is 266.47 ms. 2^22 rows x 13 B = 54,525,952 B, + 3,000
    kept x 8 B."""
    two = {"metrics": ["count", "sum"]}
    a = law.min_bytes(1 << 24, 17_000, two)
    b = law.min_bytes(1 << 22, 3_000, two)
    pct = trace_reduce.min_bytes_roofline_pct(a, 0.26647, "TPU v5 lite", 1)
    # Four chips, each busy the same 266.47 ms for a job of 2^26 rows: four
    # times the bytes over four times the bandwidth, the same 0.1 %.
    pct4 = trace_reduce.min_bytes_roofline_pct(
        law.min_bytes(1 << 26, 68_000, two), 0.26647, "TPU v5 lite", 4)
    unknown = False
    try:
        trace_reduce.peaks_for("TPU v9")
    except KeyError:
        unknown = True
    return all([
        check("min_bytes dense", a == 218_103_808 + 136_000, str(a)),
        check("min_bytes blocked", b == 54_525_952 + 24_000, str(b)),
        check("roofline share", abs(pct - 0.1) < 1e-4, str(pct)),
        check("roofline share over four chips", abs(pct4 - 0.1) < 1e-4,
              str(pct4)),
        check("unknown device kind is an error", unknown),
        check("nothing ran -> no share", trace_reduce.min_bytes_roofline_pct(
            a, 0.0, "TPU v5 lite", 1) is None),
    ])


GUARANTEES = {"epsilon": 1.0, "delta": 1e-6, "l0": 2, "linf": 1,
              "min_value": 1.0, "max_value": 5.0,
              "metrics": ["count", "sum", "privacy_id_count"],
              "noise": "laplace", "selection": "truncated_geometric"}


def selection():
    """π(n) by the recurrence it is defined by (Desfontaines et al.):
    π(n) = min(e^ε' π(n−1) + δ', 1 − e^{−ε'}(1 − π(n−1) − δ'), 1)."""
    b = law.budgets(GUARANTEES)
    sel = law.TruncatedGeometric(b["select_eps"], b["select_delta"], 2)
    e, d = sel.eps1, sel.delta1
    pi, worst = 0.0, 0.0
    for n in range(1, 600):
        pi = min(math.exp(e) * pi + d, 1 - math.exp(-e) * (1 - pi - d), 1.0)
        got = float(sel.keep_probability(n))
        worst = max(worst, abs(got - pi) / max(pi, 1e-300))
    scales = b["scales"]
    return all([
        check("budget split", math.isclose(scales["count"], 8.0) and
              math.isclose(scales["sum"], 40.0) and
              math.isclose(scales["privacy_id_count"], 8.0) and
              math.isclose(b["select_eps"], 1 / 4)),
        check("truncated geometric closed form", worst < 1e-9, f"{worst:.3g}"),
    ])


def small_rows(seed=11):
    """Rows on which every guarantee binds: ids with more than l0
    partitions, pairs with more than linf rows, values outside the clamp."""
    rng = np.random.default_rng(seed)
    n = 1 << 19
    pid = (rng.random(n)**2 * 60_000).astype(np.int64)
    pk = (rng.random(n)**4 * 3_000).astype(np.int64)
    values = rng.choice(np.arange(0, 8, dtype=np.float32), n)
    return pid, pk, values


def reference_against_itself():
    """The simulator's releases pass the comparison; each break of a
    guarantee fails it. Limits as wide as any cell's."""
    limits = {"unknown_keys": 0, "sure_missing": 0, "kept_z": 6,
              "count_bias_z": 6, "sum_bias_z": 6, "ids_bias_z": 6,
              "count_spread": 0.1, "sum_spread": 0.1, "ids_spread": 0.1,
              "count_noise": 0.4, "sum_noise": 0.4, "ids_noise": 0.4,
              "max_abs_z": 14}
    rows = small_rows()
    expect = law.expectations(*rows, GUARANTEES)
    pairs = law.Pairs(*rows, GUARANTEES)
    ok = True
    for broken in (None,) + law.BREAKS:
        rng = np.random.default_rng(5)
        releases = [law.simulate_release(pairs, GUARANTEES, rng, broken)
                    for _ in range(12)]
        correct, table = reference.decide(
            law.compare(expect, releases), limits)
        over = [k for k, row in table.items() if not row["ok"]]
        ok &= check(f"simulator {broken or 'sound'}",
                    correct == (broken is None), f"over: {over}")
    return ok


def found_by_name():
    """Every generator, form and law that a configuration or a cell of
    BENCHMARK.json names is a file under perfbench/, with what the harness
    calls on it; a name that is not there is an error that lists what is."""
    from perfbench import run as perfbench_run
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    ok = True
    for name in cells:  # every configuration is some cell's
        cell, config, _, _ = perfbench_run.load_cell(name)
        generator = perfbench.find("generators", config["generator"]["name"])
        form = perfbench.find("forms", cell["traffic"]["input_form"])
        law_found = reference.law_of(config)
        ok &= check(f"{name}: generator, form and law",
                    callable(generator.generate) and
                    callable(form.build_job) and all(
                        callable(getattr(law_found, part, None)) for part in
                        ("expectations", "compare", "min_bytes",
                         "simulate_release", "Pairs")))
    missing = ""
    try:
        perfbench.find("forms", "no_such_form")
    except SystemExit as e:
        missing = str(e)
    return ok & check("a missing name lists what exists",
                      all(name in missing
                          for name in perfbench.names("forms")), missing)


def main():
    parts = [intervals(), roofline(), selection(), reference_against_itself(),
             fixture(), found_by_name()]
    print(f"[selftest] {'passed' if all(parts) else 'FAILED'}")
    return 0 if all(parts) else 1
