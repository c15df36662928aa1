"""Phase-level timing of the blocked large-P path — the REAL code path.

Runs large_p.aggregate_blocked with its phase_times profiling hook, so the
reported breakdown (pass-1 bound+compact, block-offset searchsorted, block
dispatch+drain) times the shipped implementation, not a replica.
"""
import os

import _common

_common.path_setup()


import jax  # noqa: E402

from pipelinedp_tpu.parallel import large_p  # noqa: E402

P = int(os.environ.get("BENCH_P", 10_000_000))
n = int(os.environ.get("BENCH_ROWS", 2**22))

_, cfg, stds, (min_v, max_v, min_s, max_s, mid) = _common.build_spec(P)
pid, pk, values, valid = _common.zipfish_data(n, P)

# Null dispatch round trip (shared helper, min-of-3): divide the
# per-block sync/drain phases below by this to count round trips rather
# than seconds.
print(f"null dispatch round trip: "
      f"{_common.null_roundtrip() * 1e3:.1f} ms", flush=True)


def run(seed, phase_times=None):
    kept, _ = large_p.aggregate_blocked(pid, pk, values, valid, min_v,
                                        max_v, min_s, max_s, mid, stds,
                                        jax.random.PRNGKey(seed), cfg,
                                        block_partitions=1 << 20,
                                        phase_times=phase_times)
    return kept


print("warmup kept:", len(run(8)), flush=True)
t = {}
kept = run(9, phase_times=t)
print("timed kept:", len(kept), flush=True)
for name, v in t.items():
    print(f"{name}: {v:.3f}" if isinstance(v, float) else f"{name}: {v}",
          flush=True)
print(f"rows/s: {n/t['total']/1e3:.0f}K", flush=True)
