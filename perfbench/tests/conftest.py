"""The benchmark's own tests: `python3 -m pytest perfbench/tests -q`.
CPU, x64 at its default (off), as a rehearsal runs; not part of tier-1."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
