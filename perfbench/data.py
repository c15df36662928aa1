"""Rows from --seed: every row of a run comes from the generator the
configuration's file names (`"generator": {"name": ..., "args": {...}}`),
which is the file perfbench/generators/<name>.py with

    generate(rows, seed, **args) -> the raw columns the job is given

as numpy arrays of one length (today: privacy id, partition key, value).
A deployment with another SHAPE of rows adds a generator file of its own;
one with other widths of a shape that is there names it with arguments of
its own. The seed only changes WHICH rows are drawn, never how many or
over what widths, so two seeds do the same amount of work.
"""

import perfbench


def generate(spec, rows, seed):
    """Calls the generator a configuration names, at `rows` rows."""
    generator = perfbench.find("generators", spec["name"])
    return generator.generate(rows=int(rows), seed=int(seed), **spec["args"])
