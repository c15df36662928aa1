"""movie_view_ratings rows at the Netflix Prize widths.

Copy (the original stays where it is until ROADMAP D1 deletes it):
`netflix_columns` from chip_smoke.py. The seed only changes WHICH rows are
drawn, never how many or over what widths, so two seeds do the same amount
of work.
"""

import numpy as np


def generate(rows, seed, users, movies):
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

