#!/usr/bin/env python
"""Meshed reshard benchmark: host-staged permutation vs on-device
all_to_all collective.

Times the two row-staging paths every meshed aggregation starts with
(parallel/reshard.stage_rows_to_mesh):

  * host-staged — the exact load-balanced host permutation
    (sharded.shard_rows_by_pid: greedy-LPT heavy ids + serpentine tail)
    followed by the sharded upload; timed from host numpy columns.
  * collective — pid-hash bucketize + [D, D] count exchange + one padded
    jax.lax.all_to_all + shard-local compaction
    (reshard.device_reshard_rows_by_pid); timed from device-resident
    columns (the streamed-ingest regime), which never touch the host.

Runs on the 8-device virtual CPU mesh by default (set --devices / run
under real devices for pod numbers). On the CPU mesh the "exchange" is a
memcpy, so the numbers bound the host-side permutation + staging overhead
the collective path deletes — NOT ICI bandwidth; on a pod the gap widens
by the host link / ICI bandwidth ratio. Prints ONE JSON line of
`meshed_reshard_*` keys (merged into bench.py's receipt detail).
"""

import argparse
import json
import os
import sys
import time


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=1 << 20)
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("--users", type=int, default=200_000)
    parser.add_argument("--reps", type=int, default=3)
    args = parser.parse_args()

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags +
            f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
    # One chip cannot exchange with itself: this script is the virtual
    # CPU mesh rehearsal (chip_smoke.py --chips 4 drives the real
    # four-chip reshard). Export JAX_PLATFORMS to run it elsewhere.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _common  # noqa: E402  (sibling import when run as a script)
    _common.path_setup()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pipelinedp_tpu.parallel import make_mesh, reshard

    devices = jax.devices()
    n_devices = min(args.devices, len(devices))
    mesh = make_mesh(devices=devices[:n_devices])

    n = args.rows
    rng = np.random.default_rng(17)
    pid = rng.integers(0, args.users, n).astype(np.int32)
    pk = rng.integers(0, 4096, n).astype(np.int32)
    values = rng.uniform(0, 5, n).astype(np.float32)
    valid = np.ones(n, bool)

    def sync(cols):
        jax.block_until_ready(list(cols))

    # --- Host-staged: permute on host, upload sharded. -------------------
    def run_host():
        out = reshard.stage_rows_to_mesh(mesh, pid, pk, values, valid,
                                         "host")
        sync(out)
        return out

    run_host()  # warm any lazy imports / upload paths
    host_sec = float("inf")
    for _ in range(args.reps):
        t0 = time.perf_counter()
        run_host()
        host_sec = min(host_sec, time.perf_counter() - t0)

    # --- Collective: device-resident columns, all_to_all over the mesh. --
    dev_cols = (jnp.asarray(pid), jnp.asarray(pk), jnp.asarray(values),
                jnp.asarray(valid))
    sync(dev_cols)

    def run_device():
        with reshard.forbid_row_fetches():
            out = reshard.stage_rows_to_mesh(mesh, *dev_cols, "device")
        sync(out)
        return out

    run_device()  # compile (bucketize/count/exchange kernels)
    dev_sec = float("inf")
    for _ in range(args.reps):
        t0 = time.perf_counter()
        run_device()
        dev_sec = min(dev_sec, time.perf_counter() - t0)

    print(
        json.dumps({
            "meshed_reshard_devices": n_devices,
            "meshed_reshard_rows": n,
            "meshed_reshard_host_staged_sec": round(host_sec, 4),
            "meshed_reshard_host_staged_rows_per_sec": round(n / host_sec),
            "meshed_reshard_collective_sec": round(dev_sec, 4),
            "meshed_reshard_collective_rows_per_sec": round(n / dev_sec),
            "meshed_reshard_collective_speedup": round(host_sec / dev_sec,
                                                       2),
            "meshed_reshard_platform": devices[0].platform,
        }))


if __name__ == "__main__":
    sys.exit(main())
