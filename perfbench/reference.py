"""Where `correct` is decided: the configuration names its law
(`guarantees.law`), the file perfbench/laws/<law>.py — the plain reference
of one family of guarantees, numpy only, importing nothing of the program:

    expectations(*columns, g)      what a sound release may say
    compare(expect, releases)      {number: value} over a window's releases,
                                   each (keys, values[n, released columns])
    min_bytes(rows, kept, g)       the roofline's least bytes for one job
    Pairs(*columns, g), simulate_release(pairs, g, rng, broken), BREAKS
                                   the reference put in the program's place
                                   and, broken, the control (tools/, tests/)

and `decide`, shared by every law, holds each number to the limit the
cell's file gives it.
"""

import numpy as np

import perfbench


def law_of(config):
    """The law module a configuration's guarantees name."""
    g = config["guarantees"]
    if "law" not in g:
        raise SystemExit(f"configuration {config.get('name')!r}: its "
                         f"guarantees name no `law`; perfbench/laws/ has "
                         f"{perfbench.names('laws')}")
    return perfbench.find("laws", g["law"])


def decide(numbers, limits):
    """`correct`, and each number beside its limit. Every number has to be
    named in the cell's `limits`: with a limit it is held to, or with null
    where PERF.md says why this cell's readings cannot hold it — it is then
    shown, not compared. A number the file does not name is an error."""
    table = {}
    correct = True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's file")
        if limits[name] is None:
            table[name] = {"value": value, "limit": None, "ok": True}
            continue
        limit = float(limits[name])
        ok = bool(np.isfinite(value)) and value <= limit
        correct = correct and ok
        table[name] = {"value": value, "limit": limit, "ok": ok}
    return correct, table
