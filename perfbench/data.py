"""Seeded host generators: every row of a run comes from --seed.

A configuration's file names its generator and the arguments it is called
with (`"generator": {"name": ..., "args": {...}}`); a later PR adds a
deployment by adding a file that names one of these with arguments of its
own; a new SHAPE of data is a new generator here, which only a benchmark
PR can add. Each returns the three raw columns the engine is given:
(privacy id, partition key, value), as numpy arrays of one length.

Copy (the original stays where it is until ROADMAP D1 deletes it):
`netflix_columns` from chip_smoke.py. The seed only changes WHICH rows are
drawn, never how many or over what widths, so two seeds do the same amount
of work.
"""

import math

import numpy as np


def netflix_columns(rows, users, movies, seed):
    """movie_view_ratings columns at the Netflix Prize widths. The movie
    popularity tilt (u^2.5) and the rating shares are those of
    examples/movie_view_ratings/netflix_format.generate_file; that
    generator draws users uniformly, and the heavy-rater tilt over users
    (u^2, ids spaced like the dataset's sparse customer ids) is
    chip_smoke.py's. Ratings 1-5, all inside the configuration's clamp."""
    rng = np.random.default_rng(seed)
    user = (np.power(rng.random(rows), 2.0) * users).astype(np.int64) * 5 + 6
    movie = (np.power(rng.random(rows), 2.5) * movies).astype(np.int64) + 1
    rating = rng.choice(np.arange(1, 6, dtype=np.float32), rows,
                        p=[0.05, 0.1, 0.2, 0.35, 0.3])
    return user, movie, rating


def querylog_columns(rows, keys, users, repeat_share, no_click_share,
                     extra_click_ratio, seed):
    """A query log with the two marginals its source states: `rows` query
    instances over exactly `keys` distinct queries. Every key is asked at
    least once (the first `keys` rows, one each); the other rows fall on
    ranks drawn from a Zipf law of exponent 1 (rank = floor(keys^u), u
    uniform), so a short head carries most of them and most keys stay
    singletons. Privacy ids carry a heavy-searcher tilt (u^2 over
    `users`). A share `repeat_share` of those other rows then repeats the
    (privacy id, key) of a row drawn at random: a user asking one of their
    queries again. The value is the instance's click-throughs: 0 with
    probability `no_click_share`, else 1 + a geometric number of further
    clicks with ratio `extra_click_ratio` — so some lie above any small
    clamp. Rows are shuffled; pre-encoded int32 ids, float32 values."""
    rng = np.random.default_rng(seed)
    if rows < keys:
        raise ValueError("a query log has at least one row per key")
    extra = rows - keys
    pk = np.empty(rows, dtype=np.int32)
    pk[:keys] = np.arange(keys, dtype=np.int32)
    pk[keys:] = np.minimum(
        np.exp(rng.random(extra) * math.log(keys)).astype(np.int64) - 1,
        keys - 1)
    pid = (np.power(rng.random(rows), 2.0) * users).astype(np.int32)
    again = keys + np.arange(int(repeat_share * extra))  # never a key's one row
    first = rng.integers(0, rows, len(again))
    pk[again], pid[again] = pk[first], pid[first]
    order = rng.permutation(rows)
    pk, pid = pk[order], pid[order]
    clicked = rng.random(rows) >= no_click_share
    clicks = np.where(
        clicked, rng.geometric(1.0 - extra_click_ratio, rows), 0)
    return pid, pk, clicks.astype(np.float32)


GENERATORS = {
    "netflix_columns": netflix_columns,
    "querylog_columns": querylog_columns,
}


def generate(spec, rows, seed):
    """Calls the generator a configuration names, at `rows` rows."""
    fn = GENERATORS.get(spec["name"])
    if fn is None:
        raise KeyError(f"unknown generator {spec['name']!r}; "
                       f"perfbench/data.py has {sorted(GENERATORS)}")
    return fn(rows=int(rows), seed=int(seed), **spec["args"])
