"""A query log with the marginals its source states (AOL 2006).

The seed only changes WHICH rows are drawn, never how many or over what
widths, so two seeds do the same amount of work.
"""

import math

import numpy as np


def generate(rows, seed, keys, users, repeat_share, no_click_share,
             extra_click_ratio):
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

