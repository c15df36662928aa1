"""The benchmark's own tests: `python3 -m pytest perfbench/tests -q`.
CPU, x64 at its default (off), as a rehearsal runs; four virtual CPU
devices, so that a four-chip cell's mesh is a mesh; not part of tier-1."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
