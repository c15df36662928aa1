"""End-to-end timing of large_p.aggregate_blocked at P = 10^7.

The blocked partition-axis path is the TPU counterpart of the reference's
unbounded-key shuffle regime (pipeline_dp/pipeline_backend.py:339-352);
this script times the full pass (bound+compact, block dispatch, O(kept)
result drains) on zipf-ish data over a 10^7-partition space.
"""
import os
import time

import _common

_common.path_setup()

import jax  # noqa: E402

from pipelinedp_tpu.parallel import large_p  # noqa: E402

P = int(os.environ.get("BENCH_P", 10_000_000))
n = int(os.environ.get("BENCH_ROWS", 2**22))

_, cfg, stds, (min_v, max_v, min_s, max_s, mid) = _common.build_spec(P)
pid, pk, values, valid = _common.zipfish_data(n, P)


def run(seed):
    return large_p.aggregate_blocked(pid, pk, values, valid, min_v, max_v,
                                     min_s, max_s, mid, stds,
                                     jax.random.PRNGKey(seed), cfg,
                                     block_partitions=1 << 20)


kept, _ = run(8)
print("warmup kept:", len(kept), flush=True)
t0 = time.perf_counter()
kept, outs = run(9)
t1 = time.perf_counter()
print(f"timed kept: {len(kept)}  {t1-t0:.3f}s  "
      f"{n/(t1-t0)/1e3:.0f}K rows/s", flush=True)

# --- Device-resident regime: rows already in HBM (streamed ingest). -------
# Isolates the path's compute+dispatch cost from the host->device upload
# the host-staged number includes (the roofline's term 3 vs term 4,
# benchmarks/README.md).
dev_cols = jax.block_until_ready(
    [jax.device_put(c) for c in (pid, pk, values, valid)])


def run_dev(seed):
    return large_p.aggregate_blocked(*dev_cols, min_v, max_v, min_s, max_s,
                                     mid, stds, jax.random.PRNGKey(seed), cfg,
                                     block_partitions=1 << 20)


kept, _ = run_dev(8)
print("device-resident warmup kept:", len(kept), flush=True)
t0 = time.perf_counter()
kept, outs = run_dev(9)
t1 = time.perf_counter()
print(f"device-resident kept: {len(kept)}  {t1-t0:.3f}s  "
      f"{n/(t1-t0)/1e3:.0f}K rows/s", flush=True)

# --- Standalone selection at the same P: O(kept) host transfer. -----------
params, _, _, _ = _common.build_spec(P)
selection = _common.build_selection(params)


def run_select(seed):
    return large_p.select_partitions_blocked(
        pid, pk, valid, jax.random.PRNGKey(seed),
        params.max_partitions_contributed, P, selection,
        block_partitions=1 << 20)


sel_kept = run_select(8)
print("select warmup kept:", len(sel_kept), flush=True)
t0 = time.perf_counter()
sel_kept = run_select(9)
t1 = time.perf_counter()
print(f"select_partitions kept: {len(sel_kept)}  {t1-t0:.3f}s  "
      f"{n/(t1-t0)/1e3:.0f}K rows/s", flush=True)
