#!/usr/bin/env python
"""Benchmark: DP SUM+COUNT throughput at eps=1 on one chip.

Measures the fused columnar kernel (contribution bounding + per-(pid,pk)
aggregation + private partition selection + noise) end-to-end on synthetic
movie_view_ratings-shaped data (BASELINE.json configs[1]/[3] shape), and
prints ONE JSON line:

    {"metric": ..., "value": N, "unit": "records/sec/chip", "vs_baseline": N,
     "platform": ..., "device_kind": ..., "device_count": N}

vs_baseline is value / north_star (50M records/sec/chip, BASELINE.json).

Runs on the accelerator JAX finds and exits non-zero when there is none;
`--cpu` is the explicit CPU debug run (its line says platform "cpu" and
is never a device number). A failing section fails the run.

Data is generated directly as columnar arrays (the large-scale ingestion
path — string-key vocab encoding is a host concern benchmarked separately),
streamed through the kernel in chunks that fit HBM.
"""

import argparse
import json
import sys
import time

import numpy as np

NORTH_STAR_RECORDS_PER_SEC = 50e6


def require_device(allow_cpu):
    """The device this run measures. No accelerator and no --cpu is an
    error, never a CPU fallback: a CPU number printed under a per-chip
    metric name is worse than no number."""
    import jax
    device = jax.devices()[0]
    if device.platform == "cpu" and not allow_cpu:
        raise SystemExit(
            "bench.py: JAX found no accelerator (platform=cpu). Pass "
            "--cpu for an explicit CPU debug run.")
    return device


def _bench_eps_sweep(jax, jnp, on_tpu):
    """BASELINE config 5: 64-parameter-config utility-analysis ε-sweep,
    vmapped over the config axis in one jit-compiled program
    (analysis/kernels.sweep_kernel)."""
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu.analysis import error_model as em
    from pipelinedp_tpu.analysis import kernels as analysis_kernels

    n_rows = 2**21 if on_tpu else 2**17
    n_partitions = 2**14 if on_tpu else 2**10
    l0_grid = [1, 2, 4, 8, 16, 32, 64, 128]
    linf_grid = [1, 2, 4, 8, 16, 32, 64, 128]
    configs = [
        pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                            noise_kind=pdp.NoiseKind.GAUSSIAN,
                            max_partitions_contributed=l0,
                            max_contributions_per_partition=linf)
        for l0 in l0_grid for linf in linf_grid
    ]
    noise_stds = np.array([[
        em.config_noise_std(p, pdp.Metrics.COUNT, 1.0, 1e-6)
    ] for p in configs])
    cfg = analysis_kernels.build_config_arrays(configs, [pdp.Metrics.COUNT],
                                               noise_stds, (1.0, 1e-6))
    rng = np.random.default_rng(11)
    counts = rng.integers(1, 16, n_rows).astype(np.float64)
    sums = rng.random(n_rows) * 5.0
    contributed = rng.integers(1, 256, n_rows).astype(np.float64)
    pk_idx = rng.integers(0, n_partitions, n_rows).astype(np.int32)

    def run():
        out = analysis_kernels.sweep_kernel(
            counts,
            sums,
            contributed,
            pk_idx,
            cfg,
            n_partitions_total=n_partitions,
            metric_codes=(analysis_kernels.METRIC_CODES[pdp.Metrics.COUNT],),
            public=False,
            return_per_partition=False)
        return float(np.asarray(out["bucket_rows"]).sum())

    run()  # compile
    start = time.perf_counter()
    checksum = run()
    elapsed = time.perf_counter() - start
    del checksum
    return {
        "eps_sweep_configs": len(configs),
        "eps_sweep_rows": n_rows,
        "eps_sweep_partitions": n_partitions,
        "eps_sweep_sec": round(elapsed, 4),
        "eps_sweep_config_rows_per_sec": round(
            len(configs) * n_rows / elapsed),
    }


def _bench_large_p(jax, on_tpu):
    """10^7-partition aggregation in bounded memory via the blocked
    partition-axis path (parallel/large_p.py). Spec + data shared with the
    standalone benchmarks (benchmarks/_common.py) so the numbers stay
    comparable."""
    from benchmarks import _common
    from pipelinedp_tpu.parallel import large_p

    P = 10_000_000
    n = 2**22 if on_tpu else 2**18
    _, cfg, stds, (min_v, max_v, min_s, max_s, mid) = _common.build_spec(P)
    pid, pk, values, valid = _common.zipfish_data(n, P)

    def run(key_seed):
        return large_p.aggregate_blocked(pid,
                                         pk,
                                         values,
                                         valid,
                                         min_v,
                                         max_v,
                                         min_s,
                                         max_s,
                                         mid,
                                         stds,
                                         jax.random.PRNGKey(key_seed),
                                         cfg,
                                         block_partitions=1 << 20)

    run(8)  # warm the jit caches (bounded-rows + block kernels)
    start = time.perf_counter()
    kept, _ = run(9)
    elapsed = time.perf_counter() - start

    # Device-resident regime: rows already in HBM (the streamed-ingest
    # case) — isolates compute+dispatch from the host->device upload the
    # host-staged number includes (roofline term 3 vs 4,
    # benchmarks/README.md).
    dev = jax.block_until_ready(
        [jax.device_put(c) for c in (pid, pk, values, valid)])

    def run_dev(key_seed):
        return large_p.aggregate_blocked(*dev, min_v, max_v, min_s, max_s,
                                         mid, stds,
                                         jax.random.PRNGKey(key_seed), cfg,
                                         block_partitions=1 << 20)

    run_dev(8)
    start = time.perf_counter()
    kept_dev, _ = run_dev(9)
    dev_elapsed = time.perf_counter() - start
    if len(kept_dev) != len(kept):
        raise AssertionError(
            f"large_p kept-count mismatch under the same key: host-staged "
            f"{len(kept)} vs device-resident {len(kept_dev)}")
    return {
        "large_p_partitions": P,
        "large_p_rows": n,
        "large_p_sec": round(elapsed, 3),
        "large_p_rows_per_sec": round(n / elapsed),
        "large_p_device_resident_sec": round(dev_elapsed, 3),
        "large_p_device_resident_rows_per_sec": round(n / dev_elapsed),
        "large_p_kept": int(len(kept)),
        "large_p_kept_device_resident": int(len(kept_dev)),
    }


def _bench_meshed_reshard(on_tpu):
    """Host-staged vs collective (all_to_all) reshard on the 8-device CPU
    mesh (benchmarks/bench_reshard.py in a subprocess: the virtual-device
    mesh needs XLA_FLAGS set before backend init, which this process has
    already done). The child is forced to CPU explicitly — this process
    holds the chip, and a child that reached for it would fail or hang.
    Its numbers are CPU rehearsal figures, labelled by their own
    meshed_reshard_platform key (chip_smoke.py --chips 4 drives the real
    four-chip reshard); see benchmarks/README.md for what they do and do
    not bound. A failing child fails the run."""
    import os
    import subprocess
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmarks", "bench_reshard.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # let the script set its own device count
    rows = 2**20 if on_tpu else 2**18
    r = subprocess.run([sys.executable, script, "--rows", str(rows)],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    if r.returncode != 0:
        raise RuntimeError(
            f"bench_reshard.py exited {r.returncode}: "
            f"{(r.stderr or '').strip()[-400:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def _bench_multihost():
    """multihost_* receipt keys (runtime/multihost.multihost_receipt):
    the controller topology this receipt was produced under — process
    count, per-process ingest overlap factor, and the cross-host share
    of the traced collective-reshard exchange bytes. A single-controller
    bench reports processes=1 / 0 cross-host bytes; a pod launcher
    running this same benchmark under jax.distributed gets the real
    numbers with no bench changes. The 2-process correctness gate lives
    in tier-1 (tests/test_multihost.py), not here."""
    from pipelinedp_tpu.runtime import multihost as rt_multihost
    return rt_multihost.multihost_receipt()


def _bench_service(on_tpu):
    """`service` receipt key: the resident multi-tenant session layer
    driven end to end — one warm job compiles the shared entry points,
    then 3 tenants fan 8 identical-spec jobs over one backend. Reports
    jobs/sec and job-latency percentiles (queue wait included), the jit
    cache misses the REUSE jobs added (0 = every tenant after the first
    hit the warm compile cache), and whether every tenant's ledger
    reconciles bit-exactly with its jobs' accountants."""
    import numpy as np

    import pipelinedp_tpu as pdp
    from pipelinedp_tpu.runtime import trace as rt_trace
    from pipelinedp_tpu.service import DPAggregationService, JobSpec

    rng = np.random.default_rng(11)
    n_rows, n_partitions = 20_000, 256
    rows = list(zip(rng.integers(0, 2_000, n_rows).tolist(),
                    rng.integers(0, n_partitions, n_rows).tolist(),
                    rng.uniform(0.0, 5.0, n_rows).tolist()))
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=4,
        max_contributions_per_partition=8,
        min_value=0.0, max_value=5.0)

    def spec(seed):
        return JobSpec(params=params, epsilon=1.0, delta=1e-6,
                       noise_seed=seed)

    was_traced = rt_trace.enabled()
    rt_trace.enable()  # the jit probe behind the reuse counts
    try:
        # aot=True: the warm jobs dispatch through the process-wide
        # executable cache — service_aot_retraces measures the AOT
        # compiles the identical-spec REUSE jobs added on their own
        # health records (0 = every tenant after the warm job
        # executed with zero Python retraces).
        with DPAggregationService(pdp.TPUBackend(aot=True),
                                  max_concurrent_jobs=4,
                                  queue_timeout_s=600.0) as svc:
            # Warm job: compiles the shared entry points once.
            svc.submit("tenant-0", spec(0), rows).result(timeout=600)
            handles = []
            start = time.perf_counter()
            for j in range(8):
                handles.append(
                    svc.submit(f"tenant-{j % 3}", spec(j + 1), rows))
            for handle in handles:
                handle.result(timeout=600)
            elapsed = time.perf_counter() - start
            latencies = sorted(h.latency_s for h in handles)
            reuse_misses = sum(h.jit_cache_misses or 0
                               for h in handles)
            from pipelinedp_tpu.runtime import health as rt_health
            aot_retraces = sum(
                rt_health.for_job(h.job_id).snapshot()
                ["counters"].get("aot_cache_misses", 0)
                for h in handles)
            reconciled = svc.ledgers_reconciled()
    finally:
        if not was_traced:
            rt_trace.disable()
    return {
        "service": {
            "service_jobs_per_sec": round(len(handles) / elapsed, 2),
            "service_p50_job_latency_s": round(
                latencies[len(latencies) // 2], 4),
            "service_p99_job_latency_s": round(
                latencies[min(len(latencies) - 1,
                              int(len(latencies) * 0.99))], 4),
            "service_compile_reuse_misses": reuse_misses,
            # AOT compiles added by the 8 identical-spec reuse jobs
            # on their own job records (the warm job paid them all).
            "service_aot_retraces": aot_retraces,
            "service_ledger_reconciled": reconciled,
            "service_jobs": len(handles) + 1,
            "service_tenants": 3,
        }
    }


def _bench_megabatch(on_tpu):
    """`megabatch` receipt key: the coalescing execution tier under a
    sustained open-loop micro-job load — the regime the per-job path is
    worst at (many small identical-spec jobs, per-launch overhead
    dominating compute). The load is N pre-encoded 64-row columnar
    micro-jobs (a serving front-end hands the service ready payloads;
    `columnar.encode` passes EncodedData through untouched), all with
    one spec fingerprint and one shape class so the coalescer can fill
    whole lane buckets. The same saturated queue drains twice over the
    same worker pool: per-job (batching=False, N release launches) and
    megabatched (batching=True, ~N/max_batch_jobs launches); each path
    takes its best of three trials — on a shared box the open-loop
    drain rate is scheduler-noisy and the max is the honest capacity
    figure. The receipt reports jobs/sec and p50/p99 job latency for
    both paths, the speedup, mean batch occupancy, release launches per
    N jobs, and the single-row-job floor (the latency of the smallest
    possible warm solo job — the fixed cost a batch lane amortizes).

    Note the CPU-backend caveat: with XLA on host cores, kernel
    *execution* releases the GIL and overlaps the host-side work of
    other workers in BOTH paths, so the measured speedup reflects only
    the amortized per-launch dispatch CPU, not the launch-rate ceiling
    a device-queue backend sees. On a real TPU the per-launch cost the
    batch amortizes (dispatch + device round-trip) is the dominant term
    this bench is sized to expose.
    """
    import numpy as np

    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import columnar
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry
    from pipelinedp_tpu.service import DPAggregationService, JobSpec

    n_jobs, n_rows, workers, lanes, trials = 96, 64, 16, 16, 3
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=4,
        max_contributions_per_partition=8,
        min_value=0.0, max_value=5.0)

    def job_cols(seed):
        # Every job covers the same 48 partition keys (plus a
        # random tail) so all jobs share one distinct-partition
        # bucket: the timed region re-dispatches ONE compiled
        # program instead of compiling per partition-count.
        r = np.random.default_rng(seed)
        pk = np.concatenate(
            [np.arange(48), r.integers(0, 48, n_rows - 48)])
        pid = np.concatenate(
            [np.arange(48) % 200, r.integers(0, 200, n_rows - 48)])
        return columnar.encode_columns(
            pid, pk, r.uniform(0.0, 5.0, n_rows))

    # Payloads are pre-encoded OUTSIDE the timed region: the bench
    # measures the service drain rate, not numpy data generation.
    data = {i: job_cols(i) for i in range(n_jobs)}
    warm_data = {i: job_cols(10_000 + i) for i in range(workers)}

    def spec(seed):
        return JobSpec(params=params, epsilon=1.0, delta=1e-6,
                       noise_seed=seed)

    def run_load(batching):
        with DPAggregationService(pdp.TPUBackend(),
                                  max_concurrent_jobs=workers,
                                  queue_timeout_s=600.0,
                                  batching=batching,
                                  batch_window_ms=100.0,
                                  max_batch_jobs=lanes) as svc:
            # Warm round: compiles the (lane-stacked) kernels for
            # this shape class so the timed trials measure steady
            # state, not first-compile. The batched warm round
            # fills a whole lane bucket.
            warm = [svc.submit(f"w{i}", spec(900 + i), warm_data[i])
                    for i in range(workers if batching else 2)]
            for h in warm:
                h.result(timeout=600)
            best = None
            for trial in range(trials):
                before = rt_telemetry.snapshot()
                start = time.perf_counter()
                # Open loop: the whole load submitted up front — a
                # saturated admission queue; jobs/sec is the drain
                # rate.
                handles = [svc.submit(f"tenant-{i % 3}",
                                      spec(trial * 1000 + i),
                                      data[i])
                           for i in range(n_jobs)]
                for h in handles:
                    h.result(timeout=600)
                elapsed = time.perf_counter() - start
                delta = rt_telemetry.delta(before)
                jps = n_jobs / elapsed
                if best is None or jps > best[0]:
                    best = (jps, delta,
                            sorted(h.latency_s for h in handles))
            reconciled = svc.ledgers_reconciled()
        jps, delta, latencies = best
        batch_launches = delta.get("service_batch_launches", 0)
        jobs_batched = delta.get("service_jobs_batched", 0)
        return {
            "jobs_per_sec": round(jps, 2),
            "p50_s": round(latencies[len(latencies) // 2], 4),
            "p99_s": round(latencies[min(len(latencies) - 1,
                                         int(len(latencies) * 0.99))],
                           4),
            # Per-N-jobs release launches: batched lanes share one,
            # unbatched jobs pay their own.
            "launches": batch_launches + (n_jobs - jobs_batched),
            "batch_launches": batch_launches,
            "jobs_batched": jobs_batched,
            "occupancy": round(jobs_batched / batch_launches, 2)
                         if batch_launches else 0.0,
            "reconciled": reconciled,
        }

    per_job = run_load(batching=False)
    batched = run_load(batching=True)

    # The floor: a warm single-row job, solo — the fixed per-job
    # cost (admission, graph build, encode, ONE launch, decode,
    # ledger) that megabatching amortizes across lanes.
    with DPAggregationService(pdp.TPUBackend(),
                              max_concurrent_jobs=1,
                              queue_timeout_s=600.0) as svc:
        one_row = [(0, 1, 1.0)]
        svc.submit("floor", spec(7001), one_row).result(timeout=600)
        h = svc.submit("floor", spec(7002), one_row)
        h.result(timeout=600)
        floor_s = h.latency_s

    return {
        "megabatch": {
            "service_jobs_per_sec": batched["jobs_per_sec"],
            "service_p50_job_latency_s": batched["p50_s"],
            "service_p99_job_latency_s": batched["p99_s"],
            "service_jobs_per_sec_per_job_path":
                per_job["jobs_per_sec"],
            "service_p50_job_latency_s_per_job_path":
                per_job["p50_s"],
            "service_p99_job_latency_s_per_job_path":
                per_job["p99_s"],
            "megabatch_speedup": round(
                batched["jobs_per_sec"] /
                max(per_job["jobs_per_sec"], 1e-9), 2),
            "megabatch_occupancy_mean": batched["occupancy"],
            "megabatch_jobs_batched": batched["jobs_batched"],
            # N jobs -> how many release launches each path paid.
            "launches_per_%d_jobs_batched" % n_jobs:
                batched["launches"],
            "launches_per_%d_jobs_per_job_path" % n_jobs:
                per_job["launches"],
            "single_row_job_floor_s": round(floor_s, 4),
            "megabatch_ledgers_reconciled": (per_job["reconciled"]
                                             and
                                             batched["reconciled"]),
            "megabatch_jobs": n_jobs,
            "megabatch_lane_cap": lanes,
        }
    }


def _bench_numeric(on_tpu):
    """`numeric` receipt key: the numeric-armor arc priced.

    Three figures: the warm fused-release cost of numeric_mode="safe"
    relative to the default path on identical rows (what compensated
    accumulation charges); the accumulation error against a float64
    oracle on a 1M-row integer-valued stream — sequential f32, XLA's
    log-depth f32 scan, and the compensated scan, in f32 ULPs at the
    oracle; and the per-draw cost of the floating-point-safe noise
    (snapped Laplace + geometric). Correctness gates live in tier-1
    (tests/test_numeric_armor.py); this receipt says what the armor
    costs."""
    import dataclasses
    import time

    import numpy as np

    import jax
    import jax.numpy as jnp

    from benchmarks import _common
    from pipelinedp_tpu import dp_computations as dp
    from pipelinedp_tpu import executor
    from pipelinedp_tpu.ops import segment_ops

    # --- safe vs fast: the dense fused release, warm. ---
    n = 2**20 if on_tpu else 2**17
    n_partitions = 1 << 12
    _, cfg, stds, (min_v, max_v, min_s, max_s, mid) = \
        _common.build_spec(n_partitions)
    pid, pk, values, valid = _common.zipfish_data(n, n_partitions)
    key = jax.random.PRNGKey(3)

    def run(cfg_):
        out = executor.aggregate_release_kernel(
            pid, pk, values, valid, min_v, max_v, min_s, max_s, mid,
            stds, key, cfg_)
        return jax.block_until_ready(out)

    def timed(cfg_):
        run(cfg_)  # compile
        start = time.perf_counter()
        run(cfg_)
        return time.perf_counter() - start

    fast_s = timed(cfg)
    safe_s = timed(dataclasses.replace(cfg, numeric_mode="safe"))

    # --- accumulation error vs a float64 oracle at 1M rows:
    # sequential f32 (the classic running accumulator), XLA's
    # log-depth f32 scan (the fast path's shape), and the
    # compensated scan (the safe path). ULPs at the oracle. ---
    m = 1 << 20
    rng = np.random.default_rng(7)
    x = rng.integers(0, 1 << 22, m).astype(np.float32)
    xj = jnp.asarray(x)
    oracle = float(np.cumsum(x.astype(np.float64))[-1])
    seq = float(np.cumsum(x)[-1])
    xla = float(np.asarray(jnp.cumsum(xj, dtype=xj.dtype))[-1])
    hi, lo = segment_ops.compensated_cumsum(xj)
    starts = jnp.asarray([0, m], dtype=jnp.int32)
    comp = float(np.asarray(
        segment_ops.compensated_segment_diff(hi, lo, starts))[0])
    ulp = float(np.spacing(np.float32(oracle)))

    # --- floating-point-safe noise draw cost (threefry-keyed,
    # scalar release path — the per-draw price the host pays). ---
    draws = 500
    snap = dp.SnappedLaplaceMechanism(1.0, 1.0,
                                      key=jax.random.PRNGKey(9))
    start = time.perf_counter()
    for v in range(draws):
        snap.add_noise(float(v))
    snap_s = time.perf_counter() - start
    geo = dp.GeometricMechanism(1.0, 1, key=jax.random.PRNGKey(10))
    start = time.perf_counter()
    for v in range(draws):
        geo.add_noise(v)
    geo_s = time.perf_counter() - start

    return {"numeric": {
        "rows": n,
        "fast_sec": round(fast_s, 4),
        "safe_sec": round(safe_s, 4),
        "safe_vs_fast": round(safe_s / fast_s, 3),
        "cumsum_rows": m,
        "sequential_f32_error_ulps": round(abs(seq - oracle) / ulp, 1),
        "xla_scan_f32_error_ulps": round(abs(xla - oracle) / ulp, 2),
        "compensated_error_ulps": round(abs(comp - oracle) / ulp, 2),
        "snap_grid": snap.grid,
        "snapped_laplace_draws_per_sec": round(draws / snap_s),
        "geometric_draws_per_sec": round(draws / geo_s),
    }}


def _bench_pld(on_tpu):
    """`pld` receipt key: the fast-composition engine priced.

    Four figures: the one-shot batched frequency-domain composition vs
    the sequential pairwise chain at k=1000 heterogeneous mechanisms
    (compositions/sec both ways — the >=10x acceptance bar); the
    epsilon a tenant gets back from PLD composition at k=100 identical
    Gaussian jobs (naive sum / composed epsilon); the spectrum-cache
    hit rate over a 3-tenant identical-spec run; and the admission
    capacity multiplier — jobs admitted on ONE fixed tenant budget
    under pld vs naive accounting. Correctness gates live in tier-1
    (tests/test_pld_compose.py); this receipt says what the engine
    buys."""
    import time

    import numpy as np

    from pipelinedp_tpu import dp_computations as dpc
    from pipelinedp_tpu.accounting import compose as eng
    from pipelinedp_tpu.accounting import pld as pldlib
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry
    from pipelinedp_tpu.runtime.journal import BlockJournal
    from pipelinedp_tpu.service.errors import TenantBudgetExceededError
    from pipelinedp_tpu.service.ledger import TenantLedger

    # --- batched vs sequential pairwise at k=1000 heterogeneous
    # mechanisms (8 distinct Gaussian scales x 125 each; 1e-2 grid
    # keeps the sequential chain's quadratic cost sufferable). ---
    disc = 1e-2
    scales = [0.8 + 0.15 * i for i in range(8)]
    plds = [pldlib.from_gaussian_mechanism(s, disc) for s in scales]
    counts = [125] * len(scales)
    k_total = sum(counts)
    start = time.perf_counter()
    batched = eng.compose_plds(plds, counts)
    batched_s = time.perf_counter() - start
    start = time.perf_counter()
    seq = None
    for p, c in zip(plds, counts):
        for _ in range(c):
            seq = p if seq is None else seq.compose(p)
    sequential_s = time.perf_counter() - start
    parity = float(np.max(np.abs(batched.probs - seq.probs)))

    # --- epsilon saved at k=100 identical Gaussian jobs: the naive
    # sum of shares vs the composed epsilon at the same delta. ---
    eps_j, delta_j = 0.05, 1e-8
    std = dpc.gaussian_sigma(eps_j, delta_j, 1.0)
    record = {"mechanism_kind": "MechanismType.GAUSSIAN",
              "eps": eps_j, "delta": delta_j, "sensitivity": 1.0,
              "count": 1, "noise_std": std}
    composed_eps, _ = eng.composed_epsilon_from_records(
        [record] * 100, discretization=1e-3)
    saved_ratio = (100 * eps_j) / composed_eps

    # --- spectrum-cache hit rate over a 3-tenant identical-spec
    # run: each tenant charges the same mechanism spec, so only the
    # first rebuild discretizes. ---
    eng.CACHE.clear()  # hit rate measured from a cold cache
    before = rt_telemetry.snapshot()
    for tenant in ("bench-t1", "bench-t2", "bench-t3"):
        led = TenantLedger(tenant, 10.0, BlockJournal(None),
                           accounting_mode="pld",
                           pld_discretization=1e-3)
        for i in range(4):
            job = f"{tenant}--j{i + 1}"
            led.reserve(job, eps_j)
            led.charge(job, [dict(record, seq=0, job_id=None,
                                  metric="count", weight=1.0,
                                  process_index=0)])
        led.pld_spent_epsilon()
    diff = rt_telemetry.delta(before)
    hits = diff.get("pld_cache_hits", 0)
    misses = diff.get("pld_cache_misses", 0)
    hit_rate = hits / (hits + misses) if hits + misses else 0.0

    # --- admission capacity multiplier: jobs admitted on one fixed
    # budget, naive vs pld (capped — the pld ledger would admit far
    # past the floor the receipt needs to show). ---
    budget, cap = 2.0, 200

    def admitted(mode):
        led = TenantLedger(f"bench-cap-{mode}", budget,
                           BlockJournal(None), accounting_mode=mode,
                           pld_discretization=1e-3)
        n = 0
        while n < cap:
            job = f"bench-cap-{mode}--j{n + 1}"
            try:
                led.reserve(job, eps_j)
            except TenantBudgetExceededError:
                break
            led.charge(job, [dict(record, seq=0, job_id=None,
                                  metric="count", weight=1.0,
                                  process_index=0)])
            n += 1
        return n

    n_naive = admitted("naive")
    n_pld = admitted("pld")

    return {"pld": {
        "k_mechanisms": k_total,
        "batched_sec": round(batched_s, 4),
        "sequential_sec": round(sequential_s, 4),
        "pld_compositions_per_sec": {
            "batched": round(k_total / batched_s),
            "sequential": round(k_total / sequential_s),
        },
        "batched_speedup": round(sequential_s / batched_s, 1),
        "batched_vs_pairwise_parity": parity,
        "pld_epsilon_saved_ratio": round(saved_ratio, 3),
        "pld_cache_hit_rate": round(hit_rate, 3),
        "jobs_admitted_naive": n_naive,
        "jobs_admitted_pld": n_pld,
        "pld_admission_capacity_multiplier": round(n_pld / n_naive, 2),
    }}


def _bench_select_partitions(jax, on_tpu):
    """Standalone DP partition selection at P = 10^7 via the O(kept)
    blocked route (parallel/large_p.select_partitions_blocked): neither a
    dense count vector nor a bool[P] keep vector exists on device or
    host."""
    from benchmarks import _common
    from pipelinedp_tpu.parallel import large_p

    P = 10_000_000
    n = 2**22 if on_tpu else 2**18
    params, _, _, _ = _common.build_spec(P)
    selection = _common.build_selection(params)
    pid, pk, _, valid = _common.zipfish_data(n, P)

    def run(seed):
        return large_p.select_partitions_blocked(
            pid, pk, valid, jax.random.PRNGKey(seed),
            params.max_partitions_contributed, P, selection,
            block_partitions=1 << 20)

    run(8)  # warm the pass-1 + block kernels
    start = time.perf_counter()
    kept = run(9)
    elapsed = time.perf_counter() - start
    return {
        "select_partitions_p": P,
        "select_partitions_rows": n,
        "select_partitions_sec": round(elapsed, 3),
        "select_partitions_rows_per_sec": round(n / elapsed),
        "select_partitions_kept": int(len(kept)),
    }


def _device_zipfish(jax, jnp, n, n_partitions, n_users):
    """Device-side synthetic rows: exponentially-tilted partition
    popularity, uniform users — benchmarks/_common.zipfish_data's
    on-device twin, generated in HBM so device benchmarks never pay a
    host upload. Returns a jitted key -> (pid, pk, values, valid)."""

    @jax.jit
    def make(k):
        kp, ku, kv = jax.random.split(k, 3)
        u = jax.random.uniform(kp, (n,))
        pk = (jnp.power(u, 3.0) * n_partitions).astype(jnp.int32)
        pid = jax.random.randint(ku, (n,), 0, n_users, dtype=jnp.int32)
        values = jax.random.uniform(kv, (n,), minval=0.0, maxval=5.0)
        return pid, pk, values, jnp.ones((n,), bool)

    return make


def _bench_baseline_configs(jax, jnp, on_tpu):
    """BASELINE.md configs 1-3, measured (the reference publishes no
    numbers — BASELINE.json `published: {}` — so these are the reference
    points its table lists as 'TBD (measure)').

    Config 1: movie_view_ratings-shaped COUNT on LocalBackend, the
    reference's own host execution model
    (/root/reference/examples/movie_view_ratings/run_without_frameworks.py:1).
    Config 2: SUM+MEAN, Gaussian mechanism, public partitions.
    Config 3: CompoundCombiner COUNT+SUM+PRIVACY_ID_COUNT, private
    selection (/root/reference/pipeline_dp/combiners.py CompoundCombiner).
    """
    import pipelinedp_tpu as pdp
    from benchmarks import _common
    from pipelinedp_tpu import executor
    detail = {}

    # --- Config 1: LocalBackend COUNT (the CPU ground-truth engine). ----
    n1 = 200_000 if on_tpu else 50_000
    rng = np.random.default_rng(0)
    rows = list(
        zip(rng.integers(0, 10_000, n1).tolist(),
            rng.integers(0, 500, n1).tolist()))
    acc = pdp.NaiveBudgetAccountant(total_epsilon=1.0, total_delta=1e-6)
    engine = pdp.DPEngine(acc, pdp.LocalBackend())
    params1 = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                                  noise_kind=pdp.NoiseKind.LAPLACE,
                                  max_partitions_contributed=4,
                                  max_contributions_per_partition=8)
    extractors = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                    partition_extractor=lambda r: r[1],
                                    value_extractor=lambda r: 1.0)
    start = time.perf_counter()
    result = engine.aggregate(rows, params1, extractors)
    acc.compute_budgets()
    kept1 = sum(1 for _ in result)
    elapsed = time.perf_counter() - start
    detail["config1_local_count_rows"] = n1
    detail["config1_local_count_rows_per_sec"] = round(n1 / elapsed)
    detail["config1_local_count_kept"] = kept1

    # --- Configs 2 and 3: device kernel variants on shared data. --------
    P = 4096
    n = 2**24 if on_tpu else 2**18
    key = jax.random.PRNGKey(0)
    data = jax.block_until_ready(
        _device_zipfish(jax, jnp, n, P, 1_000_000)(key))

    def timed_kernel(metrics, noise_kind, private, tag):
        _, cfg, stds, (min_v, max_v, min_s, max_s, mid) = \
            _common.build_spec(P, metrics=metrics, noise_kind=noise_kind,
                               private=private)

        def step(k):
            return executor.aggregate_kernel(*data, min_v, max_v, min_s,
                                             max_s, mid, jnp.asarray(stds),
                                             k, cfg)

        jax.block_until_ready(step(jax.random.fold_in(key, 1)))  # warm
        start = time.perf_counter()
        outputs, keep, _ = jax.block_until_ready(
            step(jax.random.fold_in(key, 2)))
        elapsed = time.perf_counter() - start
        detail[f"{tag}_rows"] = n
        detail[f"{tag}_rows_per_sec"] = round(n / elapsed)
        detail[f"{tag}_outputs"] = sorted(outputs)

    timed_kernel([pdp.Metrics.SUM, pdp.Metrics.MEAN],
                 pdp.NoiseKind.GAUSSIAN, False,
                 "config2_gaussian_public_sum_mean")
    timed_kernel([pdp.Metrics.COUNT, pdp.Metrics.SUM,
                  pdp.Metrics.PRIVACY_ID_COUNT],
                 pdp.NoiseKind.LAPLACE, True,
                 "config3_compound_private")
    return detail


# Span names whose exclusive time is device-side work (or the wait for
# it): the fused-kernel dispatch/drain pair, the streaming accumulator's
# append/grow, and every probed jit entry point.
_DEVICE_SPANS = ("dispatch", "drain", "pipeline_append", "pipeline_grow")


def _probed_dispatches(summary):
    """Device-dispatch events in a trace summary: every jit:* (traced
    dispatch) and aot:* (cached-executable dispatch) entry-point call,
    plus every pipeline_append (one host->device chunk landing — the
    staged CPU accumulator dispatches transfers, not jit calls, so the
    probe alone would under-count the ingest half). THE dispatch bill
    of a warm run — what the fused release kernels, the batched appends
    and the AOT cache exist to shrink."""
    return sum(stats["count"]
               for name, stats in summary.get("spans", {}).items()
               if name.startswith(("jit:", "aot:")) or
               name == "pipeline_append")


def _overlap_efficiency(summary, total_s):
    """Device-busy fraction of a pipelined run, from span exclusive
    times: the share of total wall time spent in device-side spans
    (dispatch/drain/append/grow + jit:* probes). 1.0 means the device
    never waited on host encode — the streaming executor's target; the
    serial path's value is bounded by the host-encode share. Worker
    -thread encode spans run on their own threads, so they do NOT
    deflate this figure — overlap shows up as device spans covering
    wall time that a serial run would spend blocked in `ingest`."""
    if not total_s:
        return None
    busy = sum(stats["exclusive_s"]
               for name, stats in summary["spans"].items()
               if name in _DEVICE_SPANS or name.startswith("jit:"))
    return round(min(busy / total_s, 1.0), 4)


def _phase_breakdown(summary, total_s):
    """e2e phase breakdown from a trace summary: exclusive (self) wall
    seconds per span name. Every span in the traced run nests under the
    e2e root span, so the exclusive times PARTITION the root's inclusive
    time — the per-phase seconds reconcile against total wall time by
    construction (the residual is host time between instrumented
    stages, reported as unattributed_s, plus clock skew)."""
    phases = {
        name: round(stats["exclusive_s"], 4)
        for name, stats in summary["spans"].items()
    }
    attributed = sum(phases.values())
    return {
        "total_wall_s": round(total_s, 4),
        "phases": phases,
        "attributed_s": round(attributed, 4),
        "unattributed_s": round(max(total_s - attributed, 0.0), 4),
        "attributed_frac": (round(attributed / total_s, 4)
                            if total_s else None),
        "transfer_bytes": summary["transfer_bytes"],
        "compile": summary["compile"],
    }


def _bench_end_to_end(on_tpu):
    """File -> DP result on the Netflix-format path: chunked parse ->
    incremental factorize -> overlapped upload (pipelinedp_tpu.ingest) ->
    fused kernel. The honest whole-pipeline number the kernel-only figure
    above excludes (host encode at ~3.5M rows/s on the 1-core host bounds
    it; the overlap hides the device-transfer term).

    The WARM run executes with tracing enabled under an "e2e" root span:
    the receipt gains e2e_phase_breakdown (per-phase exclusive seconds
    that reconcile against total wall time, with transfer-byte and jit
    compile attribution) and trace_summary, and the full Perfetto trace
    is dumped next to the system tempdir — the decomposition of the
    kernel-vs-end-to-end gap the ROADMAP's engine-pipeline refactor will
    be judged against."""
    import os
    import tempfile

    import pipelinedp_tpu as pdp
    from examples.movie_view_ratings import netflix_format
    from pipelinedp_tpu import ingest
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry
    from pipelinedp_tpu.runtime import trace as rt_trace

    n = 8_000_000 if on_tpu else 400_000
    path = os.path.join(tempfile.mkdtemp(), "views.txt")
    netflix_format.generate_file(path, n, n_users=200_000, n_movies=4000)

    params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT,
                                          pdp.Metrics.SUM],
                                 noise_kind=pdp.NoiseKind.LAPLACE,
                                 max_partitions_contributed=4,
                                 max_contributions_per_partition=8,
                                 min_value=0.0,
                                 max_value=5.0)
    extractors = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                    partition_extractor=lambda r: r[1],
                                    value_extractor=lambda r: r[2])

    def run_once():
        start = time.perf_counter()
        chunk_iter = ((u, m, r.astype(np.float32)) for u, m, r in
                      netflix_format.parse_file_chunks(path))
        encoded = ingest.stream_encode_columns(chunk_iter)
        accountant = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                               total_delta=1e-6)
        engine = pdp.DPEngine(accountant, pdp.TPUBackend(noise_seed=13))
        result = engine.aggregate(encoded, params, extractors)
        accountant.compute_budgets()
        n_kept = sum(1 for _ in result)
        return time.perf_counter() - start, n_kept

    # Cold includes jit compilation of every kernel shape (minutes per
    # sort-bearing program on the chip's compiler); warm re-runs the
    # identical shapes against the compile cache and is the steady-state
    # number a long-running pipeline sees.
    cold_sec, n_kept = run_once()
    # Warm run under a fresh trace epoch: spans attribute the steady-state
    # wall time; tracing is restored to its prior state afterwards so the
    # remaining benchmarks measure the untraced hot path.
    rt_trace.reset()
    with rt_trace.scoped():
        with rt_trace.span("e2e"):
            warm_sec, n_kept_warm = run_once()
        summary = rt_trace.trace_summary()
        trace_path = os.path.join(tempfile.gettempdir(),
                                  "pipelinedp_tpu_e2e_trace.json")
        rt_trace.dump(trace_path)
    breakdown = _phase_breakdown(summary, warm_sec)
    rt_trace.reset()

    # --- Pipelined end-to-end: the device-resident streaming executor
    # (ChunkSource -> thread-pool encode -> bounded staging queue ->
    # donated device accumulator). The serial warm number above stays in
    # the receipt as the comparison baseline. Two warm runs: the first
    # warms the pipeline-specific jit entries (append/grow), the second
    # measures steady state AND proves the persistent compile cache —
    # its jit_cache_misses delta must be 0 (bucketed padding lands every
    # row shape on the bucket the serial warm run already compiled).
    def run_pipelined():
        start = time.perf_counter()
        chunks = ((u, m, r.astype(np.float32)) for u, m, r in
                  netflix_format.parse_file_chunks(path))
        accountant = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                               total_delta=1e-6)
        engine = pdp.DPEngine(
            accountant,
            pdp.TPUBackend(noise_seed=13, encode_threads=2))
        result = engine.aggregate(pdp.ChunkSource(chunks), params,
                                  extractors)
        accountant.compute_budgets()
        n_kept = sum(1 for _ in result)
        return time.perf_counter() - start, n_kept

    with rt_trace.scoped():
        pipelined_warm1_sec, _ = run_pipelined()
    rt_trace.reset()
    misses_before = rt_telemetry.snapshot()
    with rt_trace.scoped():
        with rt_trace.span("e2e_pipelined"):
            pipelined_sec, n_kept_pipelined = run_pipelined()
        pipelined_summary = rt_trace.trace_summary()
    second_warm_misses = rt_telemetry.delta(misses_before).get(
        "jit_cache_misses", 0)
    rt_trace.reset()

    # --- Device-resident encode (encode_mode="hash_device") vs the
    # host encoder, same data, both warm. The netflix shape above is
    # the wrong comparator for ENCODE work (integer keys factorize at
    # memcpy speed and file parsing dominates its wall), so this
    # section uses the heavy host-encode shape the streaming dryrun
    # established — composite string keys, a ~300K-entry user
    # vocabulary, fine-grained 4K-row chunks (network-granularity
    # streaming): there the host route's sequential vocabulary stitch
    # (per-chunk remap + index rebuild over the full vocabulary) is the
    # wall the ROADMAP names, and the hash route replaces it with
    # vectorized hashing + in-jit code assignment. Byte-arrival
    # boundary: chunks are pre-materialized raw columns, so both modes
    # time exactly "everything after byte arrival".
    n_de = 800_000 if not on_tpu else 8_000_000
    de_chunk = 4_000
    rng_de = np.random.default_rng(23)
    de_pid = np.char.add(
        np.char.add("user_",
                    rng_de.integers(0, 300_000, n_de).astype(str)),
        np.char.add("_sess", rng_de.integers(0, 3, n_de).astype(str)))
    de_pk = np.char.add("movie_",
                        rng_de.integers(0, 2_000, n_de).astype(str))
    de_vals = rng_de.uniform(0, 5, n_de)

    def de_chunks():
        return [(de_pid[i:i + de_chunk], de_pk[i:i + de_chunk],
                 de_vals[i:i + de_chunk])
                for i in range(0, n_de, de_chunk)]

    def run_encode_mode(mode):
        start = time.perf_counter()
        accountant = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                               total_delta=1e-6)
        engine = pdp.DPEngine(
            accountant,
            pdp.TPUBackend(noise_seed=13, encode_threads=2,
                           encode_mode=mode))
        result = engine.aggregate(pdp.ChunkSource(de_chunks()), params,
                                  extractors)
        accountant.compute_budgets()
        n_kept = sum(1 for _ in result)
        return time.perf_counter() - start, n_kept

    run_encode_mode("host")  # compiles for this shape
    host_encode_sec, n_kept_host_enc = run_encode_mode("host")
    run_encode_mode("hash_device")  # warm the hash-route kernels
    misses_before = rt_telemetry.snapshot()
    with rt_trace.scoped():
        with rt_trace.span("e2e_device_encode"):
            device_sec, n_kept_device = run_encode_mode("hash_device")
        device_summary = rt_trace.trace_summary()
    device_second_warm_misses = rt_telemetry.delta(misses_before).get(
        "jit_cache_misses", 0)
    device_breakdown = _phase_breakdown(device_summary, device_sec)
    rt_trace.reset()
    assert n_kept_device == n_kept_host_enc, (
        "device-encode release diverged from the host encode")

    # --- Single-dispatch warm path (PR 14) over the same fine-grained
    # 4K-chunk stream (the shape where per-dispatch overhead is
    # visible). Three warm configurations, identical released bytes
    # (bit-identity asserted in tests/test_aot.py + the dryrun):
    #   legacy    — unfused release, serial drain, per-chunk appends
    #               (the pre-PR14 path; the comparison baseline),
    #   traced    — the default warm path (fused release + overlap +
    #               batched appends) through jit's Python dispatch,
    #   aot       — the default warm path through the AOT executable
    #               cache (.lower().compile(), zero retraces).
    # e2e_dispatch_count counts probed jit:/aot: entry-point calls per
    # warm run; e2e_aot_speedup is traced/aot wall on identical work.
    from pipelinedp_tpu.runtime import pipeline as rt_pipeline_mod

    n_wp = min(n_de, 200_000)
    wp_chunks = [(de_pid[i:i + de_chunk], de_pk[i:i + de_chunk],
                  de_vals[i:i + de_chunk]) for i in range(0, n_wp, de_chunk)]

    def run_warm_path(label, batch_rows, **kw):
        prev_batch = rt_pipeline_mod.APPEND_BATCH_ROWS
        rt_pipeline_mod.APPEND_BATCH_ROWS = batch_rows
        try:
            accountant = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                                   total_delta=1e-6)
            engine = pdp.DPEngine(
                accountant,
                pdp.TPUBackend(noise_seed=13, encode_threads=2, **kw))
            start = time.perf_counter()
            result = engine.aggregate(pdp.ChunkSource(iter(wp_chunks)),
                                      params, extractors)
            accountant.compute_budgets()
            n_kept = sum(1 for _ in result)
            return time.perf_counter() - start, n_kept
        finally:
            rt_pipeline_mod.APPEND_BATCH_ROWS = prev_batch

    warm_path = {}
    kept_counts = set()
    for label, batch_rows, kw in (
            ("legacy", 0, dict(fused_release=False)),
            ("traced", rt_pipeline_mod.APPEND_BATCH_ROWS,
             dict(overlap_drain=True)),
            ("aot", rt_pipeline_mod.APPEND_BATCH_ROWS,
             dict(aot=True, overlap_drain=True))):
        run_warm_path(label, batch_rows, **kw)  # warm compiles/cache
        with rt_trace.scoped():
            with rt_trace.span("e2e_warm_" + label):
                sec, kept = run_warm_path(label, batch_rows, **kw)
            warm_path[label] = (sec, _probed_dispatches(
                rt_trace.trace_summary()))
        rt_trace.reset()
        kept_counts.add(kept)
    assert len(kept_counts) == 1, (
        f"warm-path configurations diverged: {kept_counts}")
    dispatch_reduction = (warm_path["legacy"][1] /
                          max(warm_path["aot"][1], 1))
    os.unlink(path)
    # Note for cross-round comparisons: rounds <= 4 reported a single
    # compile-inclusive "end_to_end_sec"; that old key corresponds to
    # end_to_end_sec_cold here.
    return {
        "end_to_end_rows": n,
        "end_to_end_sec_cold": round(cold_sec, 3),
        "end_to_end_rows_per_sec_cold": round(n / cold_sec),
        "end_to_end_sec_warm": round(warm_sec, 3),
        "end_to_end_rows_per_sec_warm": round(n / warm_sec),
        "end_to_end_kept_partitions": n_kept_warm,
        "e2e_sec_pipelined": round(pipelined_sec, 3),
        "e2e_sec_pipelined_first_warm": round(pipelined_warm1_sec, 3),
        "e2e_rows_per_sec_pipelined": round(n / pipelined_sec),
        "e2e_overlap_efficiency": _overlap_efficiency(pipelined_summary,
                                                      pipelined_sec),
        "e2e_pipelined_kept_partitions": n_kept_pipelined,
        # 0 == every row shape of the second warm pipelined call hit the
        # persistent compile cache (the bucketed-padding guarantee).
        "e2e_pipelined_second_warm_jit_cache_misses": second_warm_misses,
        # Device-resident ingest (encode_mode="hash_device") vs the
        # host encoder over the SAME heavy-encode stream (composite
        # string keys, 300K-entry vocabulary, 4K-row chunks), both
        # warm; the device-mode phase breakdown shows host
        # encode/factorize is no longer the dominant phase (no host
        # factorization runs at all — "ingest" is hashing + upload,
        # "ingest.device_codes" the in-jit code assignment).
        "e2e_device_encode_rows": n_de,
        "e2e_sec_host_encode": round(host_encode_sec, 3),
        "e2e_rows_per_sec_host_encode": round(n_de / host_encode_sec),
        "e2e_sec_device_encode": round(device_sec, 3),
        "e2e_rows_per_sec_device_encode": round(n_de / device_sec),
        "e2e_device_encode_speedup": round(
            host_encode_sec / device_sec, 2),
        "e2e_device_encode_kept_partitions": n_kept_device,
        "e2e_device_encode_second_warm_jit_cache_misses":
            device_second_warm_misses,
        "e2e_device_encode_phase_breakdown": device_breakdown,
        # Single-dispatch warm path: probed jit:/aot: entry-point calls
        # per warm run over the 4K-chunk stream (legacy = pre-PR14
        # unfused/serial/per-chunk-append path), and the warm wall-clock
        # ratio of the traced vs AOT-executable dispatch of the SAME
        # fused path. Identical released bytes in all three modes.
        "e2e_dispatch_count": {
            "legacy": warm_path["legacy"][1],
            "fused": warm_path["traced"][1],
            "fused_aot": warm_path["aot"][1],
            "reduction": round(dispatch_reduction, 2),
        },
        "e2e_sec_warm_legacy": round(warm_path["legacy"][0], 3),
        "e2e_sec_warm_fused": round(warm_path["traced"][0], 3),
        "e2e_sec_warm_aot": round(warm_path["aot"][0], 3),
        "e2e_aot_speedup": round(
            warm_path["traced"][0] / max(warm_path["aot"][0], 1e-9), 3),
        "e2e_warm_path_speedup": round(
            warm_path["legacy"][0] / max(warm_path["aot"][0], 1e-9), 3),
        "e2e_phase_breakdown": breakdown,
        "trace_summary": {
            "spans": dict(list(summary["spans"].items())[:12]),
            "instants": summary["instants"],
            "n_events": summary["n_events"],
            "dropped_events": summary["dropped_events"],
        },
        "trace_file": trace_path,
    }


def _bench_ingest():
    """Host ingest throughput: raw key columns -> vocab-encoded int arrays
    (columnar.encode_columns, the 1B-row bottleneck flagged in round 2)."""
    from pipelinedp_tpu import columnar
    n = 4_000_000
    rng = np.random.default_rng(3)
    pids = rng.integers(0, 1_000_000, n)
    pks = np.char.add("movie_", rng.integers(0, 100_000, n).astype(str))
    vals = rng.random(n)
    start = time.perf_counter()
    encoded = columnar.encode_columns(pids, pks, vals)
    elapsed = time.perf_counter() - start

    # Fallback path (pandas masked): the vectorized searchsorted remap in
    # ChunkedVocabEncoder, measured host-side on the same columns.
    from pipelinedp_tpu import ingest as ingest_mod
    saved = ingest_mod._pd, columnar._pd
    ingest_mod._pd = columnar._pd = None
    try:
        start = time.perf_counter()
        enc_pid = ingest_mod.ChunkedVocabEncoder()
        enc_pk = ingest_mod.ChunkedVocabEncoder()
        chunk = 1 << 19
        for i in range(0, n, chunk):
            enc_pid.encode(pids[i:i + chunk])
            enc_pk.encode(pks[i:i + chunk])
        fb_elapsed = time.perf_counter() - start
    finally:
        ingest_mod._pd, columnar._pd = saved

    # Device-resident encode: the same columns through the hash-device
    # route (host work = hashing only; factorization runs inside jit).
    # Warm once so the factorize-kernel compile does not bill the
    # steady-state number, then time a full encode to device arrays.
    import jax

    chunk = 1 << 19

    def dev_chunks():
        return [(pids[i:i + chunk], pks[i:i + chunk], vals[i:i + chunk])
                for i in range(0, n, chunk)]

    ingest_mod.stream_encode_columns(dev_chunks(),
                                     encode_mode="hash_device",
                                     encode_threads=2)
    start = time.perf_counter()
    dev_encoded = ingest_mod.stream_encode_columns(
        dev_chunks(), encode_mode="hash_device", encode_threads=2)
    jax.block_until_ready((dev_encoded.pid, dev_encoded.pk))
    dev_elapsed = time.perf_counter() - start
    return {
        "ingest_rows": n,
        "ingest_rows_per_sec": round(n / elapsed),
        "ingest_fallback_rows_per_sec": round(n / fb_elapsed),
        "ingest_device_rows_per_sec": round(n / dev_elapsed),
        "ingest_partitions": encoded.n_partitions,
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=200_000_000,
                        help="total synthetic rows to push through")
    parser.add_argument("--chunk", type=int, default=0,
                        help="rows per device chunk (0 = auto)")
    parser.add_argument("--partitions", type=int, default=4096)
    parser.add_argument("--users", type=int, default=1_000_000)
    parser.add_argument("--cpu", action="store_true",
                        help="explicit CPU debug run (never a device "
                        "number); without it, no accelerator is an error")
    args = parser.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp

    # Persistent compilation cache: one rule, one helper, shared with
    # chip_smoke.py and the benchmarks/ scripts.
    from benchmarks import _common
    _common.enable_compile_cache()

    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import combiners, executor
    from pipelinedp_tpu.aggregate_params import MechanismType
    from pipelinedp_tpu.ops import selection_ops

    device = require_device(allow_cpu=args.cpu)
    stamp = _common.device_stamp()
    on_tpu = device.platform != "cpu"
    # 2^24 rows per launch on an accelerator (not measured on today's
    # code; PERF.md holds the compile and memory figures for this bucket).
    chunk = args.chunk or (2**24 if on_tpu else 2**20)
    chunk = min(chunk, args.rows)

    # --- Aggregation spec: SUM+COUNT, eps=1, private partition selection. ---
    params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
                                 noise_kind=pdp.NoiseKind.LAPLACE,
                                 max_partitions_contributed=4,
                                 max_contributions_per_partition=8,
                                 min_value=0.0,
                                 max_value=5.0)
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                           total_delta=1e-6)
    compound = combiners.create_compound_combiner(params, accountant)
    selection_budget = accountant.request_budget(MechanismType.GENERIC)
    accountant.compute_budgets()
    selection = selection_ops.selection_params_from_host(
        params.partition_selection_strategy, selection_budget.eps,
        selection_budget.delta, params.max_partitions_contributed, None)
    cfg = executor.make_kernel_config(params, compound, args.partitions,
                                      private_selection=True,
                                      selection_params=selection)
    stds = executor.compute_noise_stds(compound, params)
    min_v, max_v, min_s, max_s, mid = executor.kernel_scalars(params)

    # --- Synthetic data: zipf-ish partition popularity, uniform users. ---
    key = jax.random.PRNGKey(0)
    make_chunk = _device_zipfish(jax, jnp, chunk, args.partitions,
                                 args.users)

    def step(k):
        pid, pk, values, valid = make_chunk(jax.random.fold_in(k, 1))
        return executor.aggregate_kernel(pid, pk, values, valid, min_v, max_v,
                                         min_s, max_s, mid, jnp.asarray(stds),
                                         jax.random.fold_in(k, 2), cfg)

    # Warmup / compile.
    outputs, keep, _ = jax.block_until_ready(step(key))

    n_chunks = max(1, args.rows // chunk)
    start = time.perf_counter()
    results = []
    for i in range(n_chunks):
        results.append(step(jax.random.fold_in(key, i)))
    jax.block_until_ready(results)
    outputs, keep, _ = results[-1]
    elapsed = time.perf_counter() - start

    total_rows = n_chunks * chunk
    records_per_sec = total_rows / elapsed

    # --- BASELINE config 5: 64-config ε-sweep as ONE compiled program. ---
    sweep_detail = _bench_eps_sweep(jax, jnp, on_tpu)

    # --- Host ingest: vectorized vocab factorization (columnar.encode). ---
    ingest_detail = _bench_ingest()

    # --- End-to-end: Netflix-format file -> DP result, overlapped ingest. ---
    e2e_detail = _bench_end_to_end(on_tpu)

    # --- 10^7-partition blocked aggregation (bounded memory). ---
    large_p_detail = _bench_large_p(jax, on_tpu)

    # --- 10^7-partition standalone selection, O(kept) transfers. ---
    select_detail = _bench_select_partitions(jax, on_tpu)

    # --- Meshed reshard: host-staged vs collective on the CPU mesh. ---
    reshard_detail = _bench_meshed_reshard(on_tpu)

    # --- Multi-host topology: process count, per-process ingest overlap,
    # cross-host exchange volume (0 on a single-controller run). ---
    multihost_detail = _bench_multihost()

    # --- Resident multi-tenant service: jobs/sec, latency percentiles,
    # compile reuse across tenants, ledger reconciliation. ---
    service_detail = _bench_service(on_tpu)

    # --- Megabatched serving: saturated open-loop micro-job load,
    # per-job path vs the coalescing tier (jobs/sec, p50/p99, batch
    # occupancy, launches per N jobs, the single-row-job floor). ---
    megabatch_detail = _bench_megabatch(on_tpu)

    # --- Numeric armor: safe-vs-fast release cost, compensated-vs-naive
    # accumulation error in ULPs, snapped/geometric noise draw rates. ---
    numeric_detail = _bench_numeric(on_tpu)

    # --- PLD fast composition: batched-vs-sequential compositions/sec,
    # epsilon saved at k=100, cache hit rate, admission capacity. ---
    pld_detail = _bench_pld(on_tpu)

    # --- BASELINE configs 1-3 (LocalBackend ref, Gaussian+public,
    # compound combiner). ---
    baseline_detail = _bench_baseline_configs(jax, jnp, on_tpu)

    # Noise-distribution fidelity: KS statistic of 1M device noise draws
    # vs the CPU reference distribution at the same calibrated stddev
    # (BASELINE.json metric "noise-dist KS-stat vs CPU ref").
    from scipy import stats as scipy_stats
    from pipelinedp_tpu.ops import noise as noise_ops
    sum_std = float(stds[1])
    draws = np.asarray(
        noise_ops.laplace_noise(jax.random.PRNGKey(7), (1_000_000,),
                                jnp.float32(sum_std)))
    ks = float(
        scipy_stats.kstest(draws,
                           scipy_stats.laplace(scale=sum_std /
                                               np.sqrt(2.0)).cdf).statistic)
    # Fault-tolerance counters accumulated across every benchmark above:
    # a healthy run records zeros; nonzero retries/fallbacks/degradations
    # in a receipt flag the run as having survived adversity (and explain
    # any throughput dip) instead of silently hiding it.
    from pipelinedp_tpu.runtime import health as rt_health
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry
    # Every declared counter (telemetry.REGISTRY is the single source of
    # truth), not a hand-maintained list that drifts as counters grow.
    fault_counters = {
        name: rt_telemetry.counters.get(name, 0)
        for name in rt_telemetry.counter_names()
    }
    # Per-phase wall-time stats (telemetry.record_duration) and the
    # health state machine's per-job verdicts: a receipt that stalled,
    # degraded or quarantined says so — and says where the time went.
    # Timings are scoped by job (the same job_scope discipline counter
    # forwarding uses), so a receipt covering several jobs run in this
    # process never mixes their phases; "_process" is the unscoped
    # aggregate for phases recorded outside any job.
    def _rounded(stats_by_name):
        return {
            name: {k: round(v, 4) for k, v in stats.items()}
            for name, stats in stats_by_name.items()
        }

    phase_timings = {
        job: _rounded(stats)
        for job, stats in rt_telemetry.job_timing_snapshot().items()
    }
    phase_timings["_process"] = _rounded(rt_telemetry.timing_snapshot())
    job_health = {
        job: {
            "state": snap["state"],
            "counters": snap["counters"],
            "journal_quarantined": snap["journal_quarantined"],
            **({"planned_devices": snap["planned_devices"],
                "live_devices": snap["live_devices"]}
               if snap.get("planned_devices") is not None else {}),
        }
        for job, snap in rt_health.snapshot_all().items()
    }
    # Fleet observability keys: the device-memory watermark the run
    # peaked at (platform memory stats on TPU, the byte-accounted
    # fallback on CPU), and the privacy-budget odometer reconciled
    # against the headline accountant's ledger — a receipt whose
    # odometer does not reconcile is flagging a registration that
    # bypassed the audit trail.
    from pipelinedp_tpu.runtime import observability as rt_obs
    memory_watermarks = rt_obs.memory_watermark()
    odo = rt_obs.odometer_report(accountant=accountant)
    odometer_detail = {
        "mechanisms": odo["mechanisms"],
        "spent_epsilon": round(odo["spent_epsilon"], 8),
        "total_epsilon": odo["total_epsilon"],
        "remaining_epsilon": round(odo["remaining_epsilon"], 8),
        "reconciled": odo["reconciled"],
        "by_metric": {
            metric: sum(1 for r in odo["records"]
                        if (r["metric"] or "?") == metric)
            for metric in sorted({r["metric"] or "?"
                                  for r in odo["records"]})
        },
    }
    print(
        json.dumps({
            "metric": "DP SUM+COUNT records/sec/chip (eps=1, private "
                      "partition selection, fused kernel)",
            "value": round(records_per_sec),
            "unit": "records/sec/chip",
            "vs_baseline": round(records_per_sec / NORTH_STAR_RECORDS_PER_SEC,
                                 4),
            **stamp,
            "detail": {
                "rows": total_rows,
                "chunk": chunk,
                "partitions": args.partitions,
                "users": args.users,
                "elapsed_sec": round(elapsed, 3),
                "device": str(device),
                "kept_partitions": int(np.asarray(keep).sum()),
                "noise_ks_stat_vs_cpu_ref": round(ks, 5),
                **sweep_detail,
                **ingest_detail,
                **e2e_detail,
                **large_p_detail,
                **select_detail,
                **reshard_detail,
                **multihost_detail,
                **service_detail,
                **megabatch_detail,
                **numeric_detail,
                **pld_detail,
                **baseline_detail,
                "runtime_fault_counters": fault_counters,
                "runtime_phase_timings": phase_timings,
                "runtime_job_health": job_health,
                "memory_watermarks": memory_watermarks,
                "odometer": odometer_detail,
            },
        }))


if __name__ == "__main__":
    main()
