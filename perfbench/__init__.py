"""perfbench — the benchmark of pipelinedp-tpu on the chip (BENCHMARK.json).

Everything that decides a number lives here, where a later PR that claims
a gain cannot change it: traffic, data generation, the plain reference and
the comparison that decides `correct`, the reduction from traces, spans and
counters to metrics, the table of peaks and the byte count of the roofline.
From the program it takes only the system under test (`pipelinedp_tpu`),
its `rt_trace` spans and its `telemetry` counters. See README.md.

What decides a deployment is found BY FILE NAME (`find`): its rows
(`generators/<name>.py`), its job (`forms/<input_form>.py`) and its law
(`laws/<law>.py`), as its cell, configuration and per-layer metrics are.
A later PR adds any of them as new files and edits none.
"""

import importlib
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))


def names(kind):
    """What perfbench/<kind>/ holds: its modules' names."""
    return sorted(f[:-3] for f in os.listdir(os.path.join(HERE, kind))
                  if f.endswith(".py") and not f.startswith("_"))


def find(kind, name):
    """The module perfbench/<kind>/<name>.py (`kind`: generators, forms or
    laws), for a `name` a data file states; what the directory holds is in
    the error when it has none."""
    if not re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9_]*", str(name)) or \
            name not in names(kind):
        raise SystemExit(f"perfbench/{kind}/ has no {name!r}: it has "
                         f"{names(kind)}. Add perfbench/{kind}/{name}.py "
                         f"(README.md); edit no file that is there.")
    return importlib.import_module(f"perfbench.{kind}.{name}")
