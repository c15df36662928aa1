"""Reads what the limits of a cell's comparison are set from (PERF.md §2):

  lower readings  the program, on `--seeds` seeds, a window of `--seconds`
                  each, in ONE process (one set-up): the numbers of
                  the law's `compare` for each seed;
  upper readings  the controls, on the first `--control-seeds` of those
                  seeds at the cell's own size: the plain reference put in
                  the program's place (the `simulate_release` of the law
                  the configuration names), sound and with each guarantee
                  of the cell's `controls` broken, `--control-jobs` jobs
                  each.

Every reading goes through the harness's own comparison (reference.decide
at the cell's limits) and carries its verdict: `correct`, and the numbers
that were over.

    python3 -m perfbench.tools.calibrate --workload <cell> --out <dir> \
        [--seeds 12] [--seconds 20] [--control-seeds 3] [--control-jobs 4]

Writes <dir>/<cell>.calibrate.jsonl, one line per reading. By hand, on the
chip; not part of a benchmark run. `--controls-only` touches no chip (the
control is numpy alone), so it can run beside a `--program-only` process
in one call; `--rehearse` is for the sandbox. The chips are the cell's
(`chips` in its file): run a four-chip cell's program on the four-chip
host.
"""

import argparse
import json
import os
import time

import numpy as np

from perfbench import data, reference, traffic
from perfbench import run as perfbench_run


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=2_147_500_000)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--control-jobs", type=int, default=4)
    parser.add_argument("--controls-only", action="store_true")
    parser.add_argument("--program-only", action="store_true")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    cell, config, _, _ = perfbench_run.load_cell(args.workload)
    config, rows_per_job = perfbench_run.sized(cell, config, args.rehearse)
    generator, g = config["generator"], config["guarantees"]
    law = reference.law_of(config)
    small_allowed = perfbench_run.small_allowed(cell)

    def verdict(numbers):
        correct, table = reference.decide(numbers, cell["limits"])
        return {"numbers": numbers, "correct": correct,
                "over": [k for k, row in table.items() if not row["ok"]]}
    built = None
    if not args.controls_only:
        import jax
        perfbench_run.require_device(jax, cell["chips"], args.rehearse)
        perfbench_run.enable_compile_cache(jax)
        built = perfbench_run.ProgramsBuilt(jax)
    os.makedirs(args.out, exist_ok=True)
    part = ("controls" if args.controls_only else
            "program" if args.program_only else "calibrate")
    path = os.path.join(args.out, f"{args.workload}.{part}.jsonl")
    with open(path, "a") as out:
        def emit(record):
            out.write(json.dumps(record) + "\n")
            out.flush()
            print(json.dumps(record), flush=True)

        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            columns = data.generate(generator, rows_per_job, seed)
            t = time.perf_counter()
            expect = law.expectations(*columns, g)
            expect_s = time.perf_counter() - t
            if not args.controls_only:
                job = traffic.build_job(cell, config, columns)
                builds_before = len(built.seconds)
                job(traffic.noise_seed(seed, -1))  # every seed's own warm-up: a program may be cut to its data
                warm_builds = [round(x, 2) for x in
                               built.seconds[builds_before:] if abs(x) >= 1]
                records, (start, end) = traffic.closed_loop(
                    job, seed, args.seconds, rows_per_job, built,
                    small_allowed)
                done = [r for r in records if not r["failed"]]
                releases = [perfbench_run.release_arrays(r["release"])
                            for r in done]
                emit(dict(verdict(law.compare(expect, releases)),
                          kind="program", seed=seed, jobs=len(done),
                          failed=len(records) - len(done),
                          rows_per_s=len(done) * rows_per_job / (end - start),
                          kept=[len(r["release"]) for r in done][:3],
                          small_builds=[r["small_programs_built"]
                                        for r in records],
                          warm_up_builds_s=warm_builds,
                          expect_s=expect_s, sure=int(expect["sure"].sum())))
                del job, records, done, releases
            if i < args.control_seeds and not args.program_only:
                pairs = law.Pairs(*columns, g)
                for broken in [None] + list(cell["controls"]):
                    rng = np.random.default_rng(seed)
                    t = time.perf_counter()
                    releases = [law.simulate_release(pairs, g, rng, broken)
                                for _ in range(args.control_jobs)]
                    emit(dict(verdict(law.compare(expect, releases)),
                              kind="control", broken=broken or "sound",
                              seed=seed, jobs=args.control_jobs,
                              simulate_s=time.perf_counter() - t))


if __name__ == "__main__":
    main()
