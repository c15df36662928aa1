#!/usr/bin/env python
"""chip_smoke.py — the served DP-aggregation path, once, on the attached TPU.

The quickest proof that the system still starts on the chip: one process,
the regime a user gets there (no JAX_PLATFORMS, x64 at its default), data
made from --seed, every phase through the public API and checked. It fails
with a traceback the moment JAX reports anything but a TPU and never
retries on CPU.

    python chip_smoke.py             # one chip: dense, parity, blocked, service
    python chip_smoke.py --chips 4   # four chips: the meshed path only

Every phase prints one JSON line; the LAST line of stdout is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.

Why the phases start together: each sort-bearing program costs minutes of
TPU compile whatever its row count (PERF.md, item-6 table), every compile
is one host thread, and a cold run has ~10 of them — so the phases' first
calls run on their own threads (the compiles overlap on the host's
cores) and their identical second calls run one after another, timed,
and must compile nothing. Timings printed here are smoke timings, not
benchmark metrics.
"""

import argparse
import json
import logging
import math
import os
import sys
import tempfile
import threading
import time
import traceback
import types

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# Widths come from the sources and are never cut; row counts are the cut
# and every phase states its own as `reduced`.
SIZES = types.SimpleNamespace(
    # Netflix Prize (ROADMAP R1): 100,480,507 ratings, 480,189 users,
    # 17,770 movies, ratings 1-5.
    netflix_rows=100_480_507, users=480_189, movies=17_770,
    dense_rows=1 << 24,  # one launch bucket
    chunk_rows=1 << 20,
    file_rows=(1 << 23) + (1 << 17),  # lands in the dense phase's bucket
    parity_rows=200_000, parity_users=20_000, parity_movies=2_000,
    large_p=10_000_000, large_p_rows=1 << 22, large_p_users=1_000_000,
    ks_draws=1_000_000,
    micro_jobs=8, micro_rows=128, micro_lanes=4,
    mid_rows=20_000, mid_users=5_000, mid_partitions=16,
    # --chips 4: the blocked rows are sized so LPT (reshard="host") and
    # hash (reshard="device") staging round to ONE per-shard capacity,
    # i.e. one meshed pass-1 program for both.
    mesh_rows=1 << 16, mesh_users=6_000, mesh_large_p_rows=4 * 15_565,
)

# The repo's huge-eps parity contract (tests/test_sharded.py), with room
# for the sensitivity the heavy-rater tail's natural bounds imply.
HUGE_EPS = 1e9

# Probed entry points whose programs carry a lax.sort (the minutes-long
# compiles); the smoke prints how many distinct ones it compiled.
SORT_BEARING = (
    "aggregate_release_kernel", "batched_aggregate_release_kernel",
    "select_partitions_release_kernel", "device_factorize",
    "blocked_bound_compact", "blocked_block_kernel",
    "select_kept_pair_stream", "selection_block_kernel",
    "sharded_release_kernel", "reshard_exchange", "sharded_bound_compact",
    "sharded_block_kernel", "sharded_select_compact",
    "sharded_selection_block")


def emit(record):
    print(json.dumps(record), flush=True)


class WarningLog(logging.Handler):
    """Collects WARNING+ records so a degrade that only logs still fails
    the run (aot fall-backs, copy_to_host_async, megabatch fallback)."""

    FATAL = ("aot:", "copy_to_host_async is unsupported",
             "megabatched dispatch failed", "native DP primitives",
             "device collective reshard failed")

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())

    def fatal(self):
        return [m for m in self.messages
                if any(marker in m for marker in self.FATAL)]


# ---------------------------------------------------------------------------
# Data (all from --seed)
# ---------------------------------------------------------------------------


def netflix_columns(n, users, movies, seed):
    """movie_view_ratings columns at the dataset's widths: heavy-rater
    tail over users, popularity tilt over movies (the tilt of
    netflix_format.generate_file), ratings skewed high."""
    rng = np.random.default_rng(seed)
    user = (np.power(rng.random(n), 2.0) * users).astype(np.int64) * 5 + 6
    movie = (np.power(rng.random(n), 2.5) * movies).astype(np.int64) + 1
    rating = rng.choice(np.arange(1, 6, dtype=np.float32), n,
                        p=[0.05, 0.1, 0.2, 0.35, 0.3])
    return user, movie, rating


def chunked(columns, chunk_rows):
    n = len(columns[0])
    return [tuple(c[i:i + chunk_rows] for c in columns)
            for i in range(0, n, chunk_rows)]


def _pairs(pid, pk):
    """Distinct (partition, id) pairs as sorted packed int64 keys plus
    their row counts (both columns are non-negative and < 2^31)."""
    packed = (pk.astype(np.int64) << 32) | pid.astype(np.int64)
    return np.unique(packed, return_counts=True)


def natural_bounds(pid, pk):
    """(l0, linf) that bind nothing on these rows: the most partitions
    any id touches and the most rows any (id, partition) pair holds."""
    pairs, per_pair = _pairs(pid, pk)
    _, per_id = np.unique(pairs & 0xFFFFFFFF, return_counts=True)
    return int(per_id.max()), int(per_pair.max())


def group_by(pid, pk, values):
    """The plain reference: per-partition count, sum and distinct ids."""
    order = np.argsort(pk, kind="stable")
    keys, starts, counts = np.unique(pk[order], return_index=True,
                                     return_counts=True)
    sums = np.add.reduceat(values[order].astype(np.float64), starts)
    pairs, _ = _pairs(pid, pk)
    _, ids = np.unique(pairs >> 32, return_counts=True)
    return keys, counts, sums, ids


# ---------------------------------------------------------------------------
# Engine calls (the public API, nothing else)
# ---------------------------------------------------------------------------


def count_sum_params(pdp, l0=4, linf=8, max_value=5.0):
    """COUNT+SUM, Laplace, l0=4, linf=8: the spec of the seed's headline
    and BASELINE.json configs 1/3."""
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=l0,
        max_contributions_per_partition=linf,
        min_value=0.0, max_value=max_value)


def tuple_extractors(pdp):
    return pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                              partition_extractor=lambda r: r[1],
                              value_extractor=lambda r: r[2])


def aggregate(pdp, backend, source, params, eps, delta=1e-6):
    """DPEngine.aggregate -> compute_budgets -> full materialization."""
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=eps,
                                           total_delta=delta)
    engine = pdp.DPEngine(accountant, backend)
    result = engine.aggregate(source, params, tuple_extractors(pdp))
    accountant.compute_budgets()
    return {key: (float(m.count), float(m.sum)) for key, m in result}


def select_partitions(pdp, backend, source, l0, eps, delta=1e-6):
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=eps,
                                           total_delta=delta)
    engine = pdp.DPEngine(accountant, backend)
    result = engine.select_partitions(
        source, pdp.SelectPartitionsParams(max_partitions_contributed=l0),
        tuple_extractors(pdp))
    accountant.compute_budgets()
    return sorted(result)


def assert_finite_release(release, universe, what):
    assert release, f"{what}: empty release"
    values = np.asarray(list(release.values()), dtype=np.float64)
    assert np.isfinite(values).all(), f"{what}: non-finite released value"
    assert set(release) <= universe, f"{what}: released an unknown key"


def assert_same_release(a, b, what, rel=0.0, abs_tol=0.0):
    assert set(a) == set(b), (
        f"{what}: kept sets differ ({len(set(a) ^ set(b))} keys of "
        f"{len(a)}/{len(b)})")
    if not a:
        return 0.0
    keys = sorted(a)
    va = np.asarray([a[k] for k in keys], np.float64)
    vb = np.asarray([b[k] for k in keys], np.float64)
    diff = np.abs(va - vb)
    bound = abs_tol + rel * np.abs(vb)
    assert (diff <= bound).all(), (
        f"{what}: values differ, max |a-b| = {diff.max():.6g} "
        f"(allowed abs {abs_tol} + rel {rel})")
    return float(diff.max())


# ---------------------------------------------------------------------------
# Harness: cold calls together, warm calls in turn
# ---------------------------------------------------------------------------


class Phase:
    """One checked public-API call, run twice: `call()` must be
    deterministic (fixed noise_seed), so the warm result equals the
    cold one exactly."""

    def __init__(self, name, call, check=None, info=None):
        self.name, self.call, self.check = name, call, check
        self.info = info or {}
        self.cold = self.cold_s = self.error = None

    def run_cold(self):
        start = time.perf_counter()
        try:
            self.cold = self.call()
        except BaseException as e:  # noqa: BLE001 - re-raised on the main thread by run_together; a phase thread must not die silently
            self.error = e
        self.cold_s = time.perf_counter() - start


def device_peak_bytes(jax):
    """peak_bytes_in_use per device (process-wide high-water marks)."""
    peaks = []
    for device in jax.devices():
        stats = device.memory_stats()
        assert stats and "peak_bytes_in_use" in stats, (
            f"{device}: memory_stats() reports no peak_bytes_in_use")
        peaks.append(int(stats["peak_bytes_in_use"]))
    return peaks


def run_together(phases):
    """Every phase's first call on its own thread (their compiles
    overlap); raises the first failure once all threads have ended."""
    threads = [threading.Thread(target=p.run_cold, name=f"smoke-{p.name}")
               for p in phases]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    failed = [p for p in phases if p.error is not None]
    for p in failed:  # every failure is shown; the first one is raised
        print(f"[chip_smoke] phase {p.name} failed:", file=sys.stderr)
        traceback.print_exception(p.error, file=sys.stderr)
    if failed:
        raise RuntimeError(
            f"phase(s) {[p.name for p in failed]} failed") from failed[0].error


def run_warm(jax, phases, rt_telemetry, counters):
    """Each phase's identical second call, in turn: timed, counted, and
    required to compile nothing; one record per phase."""
    for p in phases:
        before = rt_telemetry.snapshot()
        start = time.perf_counter()
        warm = p.call()
        warm_s = time.perf_counter() - start
        delta = rt_telemetry.delta(before)
        extra = p.check(p.cold, warm) or {}
        misses = delta.get("jit_cache_misses", 0) + delta.get(
            "aot_cache_misses", 0)
        assert misses == 0, (
            f"phase {p.name}: the second identical call compiled "
            f"{misses} program(s)")
        emit({"phase": p.name, **p.info, **extra,
              "cold_s": round(p.cold_s, 3), "warm_s": round(warm_s, 3),
              "compile_s": round(max(p.cold_s - warm_s, 0.0), 3),
              "second_call_compiled_nothing": True,
              **{name: delta.get(name, 0) for name in counters},
              "peak_bytes_in_use": max(device_peak_bytes(jax))})


def compile_report(rt_trace):
    """Distinct compiled programs per probed entry point (jit and AOT
    routes of one entry are one name), sort-bearing ones counted."""
    stats = {}
    for name, entry in rt_trace.compile_stats().items():
        base = name[4:] if name.startswith("aot:") else name
        row = stats.setdefault(base, {"programs": 0, "compile_s": 0.0})
        row["programs"] += entry["misses"]
        row["compile_s"] = round(row["compile_s"] + entry["compile_s"], 1)
    return {"compiled_programs": sum(r["programs"] for r in stats.values()),
            "sort_bearing_programs": sum(
                r["programs"] for name, r in stats.items()
                if name in SORT_BEARING),
            "by_entry": stats}


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------


def dense_phases(pdp, sizes, seed, tmpdir):
    """The upstream movie_view_ratings deployment at Netflix Prize
    widths: raw (user, movie, rating) chunks -> ChunkSource -> release."""
    from examples.movie_view_ratings import netflix_format
    from pipelinedp_tpu import executor, ingest
    from pipelinedp_tpu.runtime import pipeline as rt_pipeline

    columns = netflix_columns(sizes.dense_rows, sizes.users, sizes.movies,
                              seed)
    chunks = chunked(columns, sizes.chunk_rows)
    movies = set(np.unique(columns[1]).tolist())
    params = count_sum_params(pdp)
    info = {"rows": sizes.dense_rows,
            "row_bucket": executor.row_bucket(sizes.dense_rows),
            "users": int(len(np.unique(columns[0]))),
            "partitions": len(movies),
            "reduced": f"{sizes.dense_rows} of the dataset's "
                       f"{sizes.netflix_rows} rows: one launch bucket; the "
                       f"cold compile of its release program is the "
                       f"phase's compile_s and is flat in the row count "
                       f"(PERF.md, item-6 table)"}
    releases = {}

    def run(mode):
        backend = pdp.TPUBackend(noise_seed=seed)
        return aggregate(pdp, backend,
                         pdp.ChunkSource(chunks, encode_mode=mode), params,
                         eps=1.0)

    def check_host(cold, warm):
        assert_finite_release(cold, movies, "dense host")
        assert cold == warm, "dense host: same seed, different release"
        releases["host"] = warm
        return {"kept": len(warm), "encode_mode": "host",
                "sentinel": "numeric.check_release passed (engine-side)"}

    # hash_device adds ONE sort-bearing program, device_factorize. Its
    # cold call is the public streaming ingest alone (what ChunkSource
    # routes to), so that compile overlaps the release program's; the
    # engine call through ChunkSource(encode_mode="hash_device") is the
    # warm call and must release what the host encode released.
    def hash_ingest():
        encoded = ingest.stream_encode_columns(
            chunks, encode_threads=rt_pipeline.default_encode_threads(),
            encode_mode="hash_device")
        return int(encoded.n_partitions), int(encoded.n_privacy_ids)

    hash_cold = Phase("dense_hash_device_ingest", hash_ingest)

    def hash_release():
        release = run("hash_device")
        assert hash_cold.cold == (len(movies), info["users"]), (
            f"hash_device factorize found {hash_cold.cold}, expected "
            f"{(len(movies), info['users'])}")
        return release

    def check_hash(cold, warm):
        assert cold == warm, "dense hash_device: not deterministic"
        diff = assert_same_release(warm, releases["host"],
                                   "hash_device vs host encode")
        return {"kept": len(warm), "encode_mode": "hash_device",
                "max_abs_diff_vs_host_encode": diff}

    # A file -> release pass, as examples/movie_view_ratings drives it,
    # in the dense phase's bucket (no new program).
    path = os.path.join(tmpdir, "movie_views.txt")

    file_written = Phase(
        "dense_file_write",
        lambda: netflix_format.generate_file(
            path, sizes.file_rows, n_users=sizes.users,
            n_movies=sizes.movies, seed=seed))

    def file_release():
        source = pdp.ChunkSource.from_netflix_file(path)
        return aggregate(pdp, pdp.TPUBackend(noise_seed=seed), source,
                         params, eps=1.0)

    def check_file(cold, warm):
        assert_finite_release(
            warm, set(range(1, sizes.movies + 1)), "dense file")
        assert cold == warm, "dense file: same seed, different release"
        return {"kept": len(warm), "file_bytes": os.path.getsize(path)}

    file_info = {"rows": sizes.file_rows,
                 "row_bucket": executor.row_bucket(sizes.file_rows),
                 "partitions": sizes.movies,
                 "reduced": "a file in the dense phase's row bucket, so "
                            "the parser is covered at no new compile"}
    cold_stage = [Phase("dense_batch", lambda: run("host"), check_host,
                        info),
                  hash_cold, file_written]
    warm_only = [Phase("dense_batch_hash_device", hash_release, check_hash,
                       info),
                 Phase("dense_file", file_release, check_file, file_info)]
    return cold_stage, warm_only


def parity_phases(pdp, sizes, seed):
    """TPUBackend vs LocalBackend on the same rows at huge eps with
    bounds that bind nothing: same kept set, values to f32 tolerance —
    in the default and the "safe" numeric mode. (This is as large as
    "safe" goes here: its compensated scan unrolls with the row count,
    and a blocked "safe" block program at 3.1M rows took 844 s and
    194 MB of code to compile for the chip — PERF.md.)"""
    user, movie, rating = netflix_columns(sizes.parity_rows,
                                          sizes.parity_users,
                                          sizes.parity_movies, seed + 1)
    l0, linf = natural_bounds(user, movie)
    rows = list(zip(user.tolist(), movie.tolist(), rating.tolist()))
    params = count_sum_params(pdp, l0=l0, linf=linf)
    reference = {}

    def local():
        reference["release"] = aggregate(pdp, pdp.LocalBackend(seed=seed),
                                         rows, params, eps=HUGE_EPS)
        return len(reference["release"])

    def check(mode):
        def go(cold, warm):
            assert cold == warm, f"parity {mode}: not deterministic"
            diff = assert_same_release(warm, reference["release"],
                                       f"TPUBackend({mode}) vs LocalBackend",
                                       rel=1e-5, abs_tol=0.05)
            return {"kept": len(warm), "numeric_mode": mode,
                    "max_abs_diff_vs_local": diff}
        return go

    info = {"rows": sizes.parity_rows, "partitions": sizes.parity_movies,
            "eps": HUGE_EPS, "l0": l0, "linf": linf}
    phases = [
        Phase(f"parity_{mode}",
              lambda mode=mode: aggregate(
                  pdp, pdp.TPUBackend(noise_seed=seed, numeric_mode=mode),
                  rows, params, eps=HUGE_EPS),
              check(mode), info)
        for mode in ("fast", "safe")]
    return phases, local


def blocked_phases(pdp, sizes, seed):
    """The engine entry above large_partition_threshold: Zipf rows over
    a 10^7 partition space as encoded columns, so the routing takes
    large_p.aggregate_blocked / select_partitions_blocked at the
    default C = 2^20. Huge eps, bounds that bind nothing, kept ids and
    values checked against a numpy group-by."""
    from benchmarks import _common
    from pipelinedp_tpu import columnar

    P = sizes.large_p
    pid, pk, values, _ = _common.zipfish_data(
        sizes.large_p_rows, P, n_users=sizes.large_p_users, seed=seed + 2)
    values = values.astype(np.float32)
    l0, linf = natural_bounds(pid, pk)
    keys, counts, sums, ids = group_by(pid, pk, values)
    # Truncated-geometric selection at huge eps keeps >= 2 ids and drops
    # 1 id except with probability ~delta; allow a handful of those.
    sure = set(keys[ids >= 2].tolist())
    possible = set(keys.tolist())
    truth = {int(k): (float(c), float(s))
             for k, c, s in zip(keys, counts, sums)}
    params = count_sum_params(pdp, l0=l0, linf=linf)

    def encoded():
        return columnar.EncodedData(pid=pid, pk=pk, values=values,
                                    partition_vocab=range(P),
                                    n_privacy_ids=sizes.large_p_users)

    def check_kept(kept, what):
        kept = set(kept)
        assert sure <= kept <= possible, (
            f"{what}: kept set disagrees with the numpy group-by "
            f"({len(sure - kept)} missing, {len(kept - possible)} unknown)")
        assert len(kept - sure) <= 8, (
            f"{what}: {len(kept - sure)} single-id partitions kept at "
            f"huge eps")

    # In f32 (x64 off) the default numeric_mode="fast" takes segment sums
    # as differences of running prefix sums, so EVERY partition's sum
    # carries an absolute error of about one f32 ulp of the whole
    # launch's running total, whatever its own size (PERF.md). The
    # release is held to two of those.
    prefix_ulp = float(np.spacing(np.float32(values.sum(dtype=np.float64))))
    abs_tol = 0.05 + 2.0 * prefix_ulp

    def check_aggregate(cold, warm):
        assert cold == warm, "blocked aggregate: not deterministic"
        check_kept(warm, "blocked aggregate")
        diff = assert_same_release(
            warm, {k: truth[k] for k in warm},
            "blocked aggregate vs numpy group-by", rel=1e-5,
            abs_tol=abs_tol)
        return {"kept": len(warm), "max_abs_diff_vs_numpy": diff,
                "abs_tolerance": abs_tol,
                "f32_ulp_of_running_total": prefix_ulp}

    def check_select(cold, warm):
        assert cold == warm, "blocked select: not deterministic"
        check_kept(warm, "blocked select_partitions")
        return {"kept": len(warm)}

    info = {"rows": sizes.large_p_rows, "partitions": P,
            "block_partitions": 1 << 20, "eps": HUGE_EPS, "l0": l0,
            "linf": linf,
            "reduced": f"{sizes.large_p_rows} Zipf rows "
                       f"(benchmarks/_common.zipfish_data) over the full "
                       f"partition space"}
    return [
        Phase("blocked_aggregate",
              lambda: aggregate(pdp, pdp.TPUBackend(noise_seed=seed),
                                encoded(), params, eps=HUGE_EPS),
              check_aggregate, info),
        Phase("blocked_select_partitions",
              lambda: select_partitions(pdp, pdp.TPUBackend(noise_seed=seed),
                                        encoded(), l0, eps=HUGE_EPS),
              check_select, info)]


def noise_ks(jax, jnp, sizes, seed):
    """The device sampler against the analytic Laplace CDF."""
    from scipy import stats as scipy_stats
    from pipelinedp_tpu.ops import noise as noise_ops
    std = 3.0
    draws = np.asarray(noise_ops.laplace_noise(
        jax.random.PRNGKey(seed), (sizes.ks_draws,), jnp.float32(std)))
    ks = float(scipy_stats.kstest(
        draws, scipy_stats.laplace(scale=std / math.sqrt(2.0)).cdf
    ).statistic)
    bound = 3.0 / math.sqrt(sizes.ks_draws)
    assert ks < bound, f"device Laplace KS {ks:.5f} >= {bound:.5f}"
    return {"phase": "noise_ks", "draws": sizes.ks_draws,
            "ks_statistic": ks, "bound": bound}


def service_phase(pdp, sizes, seed, tmpdir, rt_telemetry):
    """DPAggregationService over one TPUBackend (AOT cache and
    megabatching on): three tenants, a dozen jobs — identical-spec
    micro-jobs that must coalesce, same-spec mid-size jobs that must
    reuse one AOT executable, one standalone selection — ledgers
    reconciled, an over-budget submission refused."""
    from pipelinedp_tpu import columnar
    from pipelinedp_tpu.runtime import observability
    from pipelinedp_tpu.service import (DPAggregationService, JobSpec,
                                        TenantBudgetExceededError)

    rng = np.random.default_rng(seed + 3)
    params = count_sum_params(pdp)
    tenants = ("alpha", "beta", "gamma")

    micro_params = count_sum_params(pdp, l0=1, linf=1)

    def micro_payload(i):
        # Every micro-job is one shape class (same rows, same two
        # partition keys, one row per user), so all of them share one
        # compiled program and each keeps both partitions.
        r = np.random.default_rng(seed + 100 + i)
        n = sizes.micro_rows
        return columnar.encode_columns(np.arange(n) + 1000 * i,
                                       np.arange(n) % 2,
                                       r.uniform(0.0, 5.0, n))

    mid_rows = list(zip(
        rng.integers(0, sizes.mid_users, sizes.mid_rows).tolist(),
        rng.integers(0, sizes.mid_partitions, sizes.mid_rows).tolist(),
        rng.uniform(0.0, 5.0, sizes.mid_rows).tolist()))

    def spec(job_seed, p=params, eps=1.0):
        return JobSpec(params=p, epsilon=eps, delta=1e-6,
                       noise_seed=seed + job_seed)

    ledger_dir = os.path.join(tmpdir, "ledgers")
    before = rt_telemetry.snapshot()
    start = time.perf_counter()
    handles = []
    with DPAggregationService(pdp.TPUBackend(aot=True), ledger_dir,
                              max_concurrent_jobs=sizes.micro_lanes,
                              tenant_budget_epsilon=8.0,
                              queue_timeout_s=1500.0,
                              drain_timeout_s=1500.0,
                              batching=True, batch_window_ms=2000.0,
                              max_batch_jobs=sizes.micro_lanes) as svc:
        # Identical-spec micro-jobs, submitted together: they fill whole
        # lane buckets and run as lanes of one vmapped launch.
        micro = [svc.submit(tenants[i % 3], spec(i, p=micro_params),
                            micro_payload(i))
                 for i in range(sizes.micro_jobs)]
        for h in micro:
            h.result(timeout=1500)
        # A mid-size job and a standalone selection side by side (two
        # programs, two workers), then the same mid spec twice more:
        # those must be served by the executable the first one built.
        first_mid = svc.submit("alpha", spec(50), mid_rows)
        selection = svc.submit(
            "beta", spec(51, p=pdp.SelectPartitionsParams(
                max_partitions_contributed=4)), mid_rows)
        first_mid.result(timeout=1500)
        selection.result(timeout=1500)
        reuse = []
        for i, tenant in enumerate(("beta", "gamma")):
            h = svc.submit(tenant, spec(52 + i), mid_rows)
            h.result(timeout=1500)
            reuse.append(h)
        handles = micro + [first_mid, selection] + reuse
        try:
            svc.submit("gamma", spec(60, eps=100.0), mid_rows)
        except TenantBudgetExceededError:
            refused = True
        else:
            refused = False
        results = [h.result(timeout=1500) for h in handles]
        reconciled = svc.ledgers_reconciled()
        ledgers = svc.ledgers()
    elapsed = time.perf_counter() - start
    delta = rt_telemetry.delta(before)

    assert refused, "service: the over-budget submission was admitted"
    assert reconciled, "service: a ledger does not reconcile"
    assert len(handles) == 12 and all(len(r) > 0 for r in results), (
        "service: a job returned nothing")
    for tenant in tenants:
        spent = observability.fold_spend(
            h.spent_epsilon for h in handles if h.tenant_id == tenant)
        assert ledgers[tenant]["spent_epsilon"] == spent, (
            f"service: tenant {tenant} ledger "
            f"{ledgers[tenant]['spent_epsilon']} != its accountants' "
            f"{spent}")
    assert delta.get("aot_cache_hits", 0) + delta.get(
        "aot_cache_misses", 0) > 0, "service: aot is on and served nothing"
    assert delta.get("aot_fallbacks", 0) == 0, "service: an AOT call degraded"
    assert sum(h.jit_cache_misses or 0 for h in reuse) == 0, (
        "service: an identical-spec job compiled")
    assert delta.get("service_jobs_batched", 0) == sizes.micro_jobs, (
        f"service: {delta.get('service_jobs_batched', 0)} of "
        f"{sizes.micro_jobs} micro-jobs were coalesced")
    return {"phase": "service", "jobs": len(handles), "tenants": len(tenants),
            "rows": sizes.micro_jobs * sizes.micro_rows +
                    4 * sizes.mid_rows,
            "partitions": sizes.mid_partitions,
            "kept": sum(len(r) for r in results),
            "wall_s": round(elapsed, 3),
            "over_budget_refused": refused, "ledgers_reconciled": reconciled,
            "batch_launches": delta.get("service_batch_launches", 0),
            "jobs_batched": delta.get("service_jobs_batched", 0),
            # (only this phase's backend has aot on, so these deltas are
            # its own even while other phases run beside it)
            **{name: delta.get(name, 0)
               for name in ("aot_cache_hits", "aot_cache_misses",
                            "aot_fallbacks")}}


def one_chip(jax, jnp, pdp, sizes, seed, tmpdir):
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry
    from pipelinedp_tpu.runtime import trace as rt_trace

    counters = ("jit_cache_misses", "aot_cache_hits", "aot_cache_misses",
                "release_dispatches")
    dense_cold, dense_warm_only = dense_phases(pdp, sizes, seed, tmpdir)
    parity, local_reference = parity_phases(pdp, sizes, seed)
    blocked = blocked_phases(pdp, sizes, seed)
    service = Phase("service", lambda: service_phase(
        pdp, sizes, seed, tmpdir, rt_telemetry))
    cold_only = [service, Phase("local_reference", local_reference)]
    # Longest compile chains first.
    cold_stage = blocked + dense_cold + parity + cold_only
    start = time.perf_counter()
    run_together(cold_stage)
    emit({"stage": "cold", "wall_s": round(time.perf_counter() - start, 3),
          "cold_s": {p.name: round(p.cold_s, 3) for p in cold_stage},
          **compile_report(rt_trace)})
    emit(service.cold)
    # The phases that reuse the cold stage's programs, then every
    # phase's identical second call.
    for p in dense_warm_only:
        run_together([p])
    timed = [dense_cold[0]] + dense_warm_only + parity + blocked
    run_warm(jax, timed, rt_telemetry, counters)
    emit(noise_ks(jax, jnp, sizes, seed))
    emit({"stage": "all", **compile_report(rt_trace)})


# ---------------------------------------------------------------------------
# Four chips: the meshed path and what it is compared with, nothing else
# ---------------------------------------------------------------------------


def four_chips(jax, jnp, pdp, sizes, seed):
    from benchmarks import _common
    from pipelinedp_tpu import columnar
    from pipelinedp_tpu.parallel import make_mesh, reshard
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry
    from pipelinedp_tpu.runtime import trace as rt_trace

    devices = jax.devices()
    assert len(devices) >= 4, f"--chips 4 needs four chips, found {devices}"
    mesh = make_mesh(devices=devices[:4])
    peaks_before = device_peak_bytes(jax)[:4]

    # Dense: movie_view_ratings rows at Netflix widths, device-resident.
    user, movie, rating = netflix_columns(sizes.mesh_rows, sizes.mesh_users,
                                          sizes.movies, seed + 4)
    dense = columnar.encode_columns(user, movie, rating)
    dense_l0, dense_linf = natural_bounds(dense.pid, dense.pk)
    dense_params = count_sum_params(pdp, l0=dense_l0, linf=dense_linf)

    # Blocked: Zipf rows over the 10^7 partition space.
    P = sizes.large_p
    bpid, bpk, bvalues, _ = _common.zipfish_data(
        sizes.mesh_large_p_rows, P, n_users=sizes.mesh_users, seed=seed + 5)
    big_l0, big_linf = natural_bounds(bpid, bpk)
    big_params = count_sum_params(pdp, l0=big_l0, linf=big_linf)

    def on_device(pid, pk, values, vocab, n_ids):
        """Device-resident encoded columns (what streamed ingest hands
        the engine): uncommitted arrays land on device 0."""
        return columnar.EncodedData(
            pid=jnp.asarray(pid), pk=jnp.asarray(pk),
            values=jnp.asarray(values, dtype=jnp.float32),
            partition_vocab=vocab, n_privacy_ids=n_ids)

    def dense_input():
        return on_device(dense.pid, dense.pk, dense.values,
                         dense.partition_vocab, dense.n_privacy_ids)

    def big_input():
        return on_device(bpid, bpk, bvalues, range(P), sizes.mesh_users)

    # The one-device releases compile on their own thread (single-device
    # programs); meshed programs launch from this thread only.
    reference = {}

    def one_device():
        backend = pdp.TPUBackend(noise_seed=seed)
        reference["dense"] = aggregate(pdp, backend, dense_input(),
                                       dense_params, eps=HUGE_EPS)
        reference["blocked"] = aggregate(pdp, pdp.TPUBackend(noise_seed=seed),
                                         big_input(), big_params,
                                         eps=HUGE_EPS)

    ref = Phase("one_device_reference", one_device)
    ref_thread = threading.Thread(target=ref.run_cold, name="smoke-reference")
    ref_thread.start()

    # Every device must hold rows after either staging.
    cols = (jnp.asarray(dense.pid), jnp.asarray(dense.pk),
            jnp.asarray(dense.values, dtype=jnp.float32),
            jnp.asarray(dense.valid))
    placement = {}
    for mode in ("device", "host"):
        before = rt_telemetry.snapshot()
        staged = reshard.stage_rows_to_mesh(mesh, *cols, mode)
        valid_rows = {shard.device.id: int(np.asarray(shard.data).sum())
                      for shard in staged[3].addressable_shards}
        assert sorted(valid_rows) == sorted(d.id for d in devices[:4]), (
            f"reshard={mode}: shards on {sorted(valid_rows)}")
        assert all(n > 0 for n in valid_rows.values()), (
            f"reshard={mode}: a device holds no rows: {valid_rows}")
        assert sum(valid_rows.values()) == sizes.mesh_rows
        assert rt_telemetry.delta(before).get(
            "reshard_host_fallbacks", 0) == 0, (
            f"reshard={mode}: the collective degraded to the host path")
        placement[mode] = {"rows_per_device": valid_rows,
                           "per_shard_capacity": staged[0].shape[0] // 4}

    records = []
    for route, make_input, params in (("dense", dense_input, dense_params),
                                      ("blocked", big_input, big_params)):
        for mode in ("device", "host"):
            before = rt_telemetry.snapshot()
            start = time.perf_counter()
            release = aggregate(
                pdp, pdp.TPUBackend(mesh=mesh, reshard=mode,
                                    noise_seed=seed),
                make_input(), params, eps=HUGE_EPS)
            wall = time.perf_counter() - start
            delta = rt_telemetry.delta(before)
            assert delta.get("reshard_host_fallbacks", 0) == 0, (
                f"{route}/{mode}: the collective degraded to the host path")
            records.append((route, mode, release, wall, delta))

    ref_thread.join()
    if ref.error is not None:
        raise RuntimeError("one-device reference failed") from ref.error
    for route, mode, release, wall, delta in records:
        diff = assert_same_release(
            release, reference[route],
            f"{route} reshard={mode} on four chips vs one device",
            rel=1e-5, abs_tol=0.05)
        emit({"phase": f"mesh_{route}_{mode}", "devices": 4,
              "rows": sizes.mesh_rows if route == "dense"
              else sizes.mesh_large_p_rows,
              "partitions": len(dense.partition_vocab) if route == "dense"
              else P,
              "kept": len(release), "eps": HUGE_EPS,
              "parity_vs_one_device": True, "max_abs_diff": diff,
              "wall_s": round(wall, 3),
              "jit_cache_misses": delta.get("jit_cache_misses", 0),
              **({"placement": placement[mode]} if route == "dense"
                 else {})})
    peaks_after = device_peak_bytes(jax)[:4]
    assert all(a > b for a, b in zip(peaks_after, peaks_before)), (
        f"memory_stats() did not move on every device: "
        f"{peaks_before} -> {peaks_after}")
    emit({"stage": "all", "peak_bytes_in_use_per_device": peaks_after,
          "one_device_reference_s": round(ref.cold_s, 3),
          **compile_report(rt_trace)})


# ---------------------------------------------------------------------------


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=22)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke.py needs a TPU; JAX reports platform "
            f"{device.platform!r} ({device.device_kind}). No CPU fallback.")

    from benchmarks import _common
    cache_dir = _common.enable_compile_cache()
    warnings = WarningLog()
    logging.getLogger().addHandler(warnings)

    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import columnar, native
    from pipelinedp_tpu.runtime import observability
    from pipelinedp_tpu.runtime import pipeline as rt_pipeline
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry
    from pipelinedp_tpu.runtime import trace as rt_trace

    native_status = native.status()
    assert not native_status["build_failed"], (
        "the native library build was attempted and failed")
    emit({"stage": "start", "seed": args.seed, "chips": args.chips,
          "jax": jax.__version__, "x64": bool(jax.config.jax_enable_x64),
          "platform": device.platform, "device_kind": device.device_kind,
          "device_count": len(jax.devices()),
          "compile_cache_dir": cache_dir,
          "compile_cache_from_env": bool(
              os.environ.get("JAX_COMPILATION_CACHE_DIR")),
          "native_library": native_status,
          "host_vocab_encode": ("pandas.factorize"
                                if columnar._pd is not None else
                                "native.vocab_encode"
                                if native_status["in_use"] else "numpy"),
          "donating_accumulator": rt_pipeline._donation_supported(),
          "cpu_count": os.cpu_count()})

    rt_trace.enable()  # the jit probe behind every compile count below
    started = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmpdir:
        if args.chips == 4:
            four_chips(jax, jnp, pdp, SIZES, args.seed)
        else:
            one_chip(jax, jnp, pdp, SIZES, args.seed, tmpdir)

    assert observability.memory_watermark()["source"] == "device", (
        "memory watermarks did not come from device.memory_stats()")
    assert not rt_pipeline._async_copy_unsupported, (
        "copy_to_host_async degraded on the chip")
    assert rt_telemetry.snapshot().get("aot_fallbacks", 0) == 0
    assert not warnings.fatal(), f"degrade warnings fired: {warnings.fatal()}"
    emit({"stage": "end", "wall_s": round(time.perf_counter() - started, 3),
          "warnings": len(warnings.messages),
          "live_threads": sorted(t.name for t in threading.enumerate())})
    print(json.dumps({"ok": True,
                      "device": {"platform": device.platform,
                                 "kind": device.device_kind,
                                 "count": len(jax.devices())}}),
          flush=True)


if __name__ == "__main__":
    main()
