"""Records the small profiler trace the selftest reads
(perfbench/fixtures/small.xplane.pb): three `pb:job`s of a toy program — a
sort inside a loop, a reduction, a host pause — each with the annotations
the traffic driver writes. Run once on the chip, by hand:

    python3 -m perfbench.tools.record_fixture <out_dir>

and copy the file it names into perfbench/fixtures/, with the numbers it
prints into fixtures/small.expected.json. Not part of a benchmark run.
"""

import json
import os
import shutil
import sys
import time


def main(out_dir):
    import jax
    import jax.numpy as jnp
    from perfbench import trace_reduce

    @jax.jit
    def toy(x):
        def body(_, v):
            return jnp.sort(v * 1.0001)
        return jax.lax.fori_loop(0, 4, body, x).sum()

    x = jnp.arange(1 << 12, dtype=jnp.float32)[::-1]
    annotate = jax.profiler.TraceAnnotation
    toy(x).block_until_ready()
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(3):
        with annotate("pb:job"):
            with annotate("pb:aggregate"):
                y = toy(x)
                y.block_until_ready()
            with annotate("pb:materialise"):
                time.sleep(0.002)
                float(y)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(trace_dir)
    kept = os.path.join(out_dir, "small.xplane.pb")
    shutil.copy(path, kept)
    reduced = trace_reduce.reduce_trace(kept)
    with open(os.path.join(out_dir, "small.expected.json"), "w") as f:
        json.dump(reduced, f, indent=1)
    print(kept, os.path.getsize(kept))
    print(json.dumps(reduced))


if __name__ == "__main__":
    main(sys.argv[1])
