"""Sweep block_partitions for the device-resident blocked path.

Fewer blocks mean fewer per-block n_kept sync round trips but a larger
per-block finalize; this measures where the trade lands at P = 10^7.
Not measured on today's code: C = 2^20 remains a CPU-chosen default
until a chip run lands a measurement. Every distinct C compiles its own
block program (~2 min each on the chip's compiler, PERF.md).
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
dev = jax.block_until_ready(
    [jax.device_put(c) for c in (pid, pk, values, valid)])

for C in (1 << 19, 1 << 20, 1 << 21, 1 << 22):

    def run(seed):
        return large_p.aggregate_blocked(*dev, min_v, max_v, min_s, max_s,
                                         mid, stds, jax.random.PRNGKey(seed),
                                         cfg, block_partitions=C)

    kept, _ = run(8)  # warm this C's block-kernel shapes
    t0 = time.perf_counter()
    kept, _ = run(9)
    t1 = time.perf_counter()
    print(f"C=2^{C.bit_length() - 1} blocks={-(-P // C)} kept={len(kept)} "
          f"{t1 - t0:.3f}s {n / (t1 - t0) / 1e3:.0f}K rows/s", flush=True)
