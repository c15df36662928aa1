"""Netflix-prize file format IO (the reference's movie-view dataset shape).

File format (reference examples/movie_view_ratings/common_utils.py:33-60):

    <movie_id>:
    <user_id>,<rating>,<date>
    <user_id>,<rating>,<date>
    <next_movie_id>:
    ...

Parsing is the package's one reader of the format,
pipelinedp_tpu.textfile (numpy over the file's bytes, no Python object
per line); the functions here are the example's thin API over it. A
pipeline that aggregates a file hands the engine
``pdp.ChunkSource.from_netflix_file(path)`` instead, which parses the
file's byte ranges on the encode workers.
"""

import os
from dataclasses import dataclass
from typing import Iterator, Optional, Tuple

import numpy as np

from pipelinedp_tpu import textfile

Columns = textfile.Columns


@dataclass
class MovieView:
    user_id: int
    movie_id: int
    rating: int


def _no_header(filename: str) -> ValueError:
    return ValueError(f"{filename}: no 'movie_id:' header lines found")


def parse_file_columns(filename: str) -> Columns:
    """Parses a Netflix-format file into (user_ids, movie_ids, ratings)."""
    with open(filename, "rb") as f:
        data = f.read()
    # A file of no line but empty ones has no header; any other line is
    # a header or (before the first header) an error.
    if not data.strip(b"\r\n"):
        raise _no_header(filename)
    return textfile.parse_netflix(data, filename)


def parse_file_chunks(
        filename: str,
        chunk_bytes: int = 1 << 24,
        byte_range: Optional[Tuple[int, int]] = None) -> Iterator[Columns]:
    """Streams (user_ids, movie_ids, ratings) column chunks from a
    Netflix-format file in bounded memory: the rows of the movie sections
    whose header starts in each `chunk_bytes` of the file, so
    concatenating all chunks equals parse_file_columns.

    byte_range=(lo, hi) parses one HOST SHARD for multi-process ingest
    (ingest.encode_shard): the shard owns every movie section whose
    header line STARTS in [lo, hi) — it skips leading rating lines
    (they belong to the previous shard's last section) and reads past
    `hi` to the end of its own last section. Concatenating the shards
    of a contiguous cover of the file equals the whole-file parse, with
    every line parsed exactly once.
    """
    lo, hi = (0, os.path.getsize(filename)) if byte_range is None \
        else byte_range
    rows = 0
    for start in range(lo, hi, chunk_bytes):
        columns = textfile.read_netflix_range(
            filename, start, min(start + chunk_bytes, hi))
        rows += len(columns[0])
        if len(columns[0]):
            yield columns
    if byte_range is None and not rows:
        with open(filename, "rb") as f:
            if not f.read().strip(b"\r\n"):
                raise _no_header(filename)


def parse_file(filename: str):
    """Parses a Netflix-format file into MovieView rows (reference API)."""
    users, movies, ratings = parse_file_columns(filename)
    return [
        MovieView(int(u), int(m), int(r))
        for u, m, r in zip(users, movies, ratings)
    ]


def generate_file(filename: str,
                  n_rows: int,
                  n_users: int = 1000,
                  n_movies: int = 99,
                  seed: int = 0) -> None:
    """Writes a synthetic dataset in the Netflix file format (vectorized —
    no per-row Python loop, so multi-million-row bench inputs write in
    seconds)."""
    rng = np.random.default_rng(seed)
    if n_rows == 0:
        open(filename, "w").close()
        return
    # Zipf-ish movie popularity, uniform users, ratings skewed high.
    movies = (np.power(rng.random(n_rows), 2.5) * n_movies).astype(int) + 1
    users = rng.integers(0, n_users, n_rows)
    ratings = rng.choice([1, 2, 3, 4, 5], n_rows,
                         p=[0.05, 0.1, 0.2, 0.35, 0.3])
    order = np.argsort(movies, kind="stable")
    m_s, u_s, r_s = movies[order], users[order], ratings[order]
    data = np.char.add(
        np.char.add(u_s.astype(str), ","),
        np.char.add(np.char.add(r_s.astype(str), ","), "2023-01-01"))
    is_new = np.concatenate([[True], m_s[1:] != m_s[:-1]])
    # Interleave header lines before each movie's first row: row i lands at
    # slot i + (#headers at or before it); its header, when new, goes one
    # slot earlier.
    slot = np.arange(n_rows) + np.cumsum(is_new)
    out = np.empty(n_rows + int(is_new.sum()), dtype=object)
    out[slot] = data
    out[slot[is_new] - 1] = np.char.add(m_s[is_new].astype(str), ":")
    with open(filename, "w") as f:
        f.write("\n".join(out))
        f.write("\n")


def write_to_file(col, filename: str) -> None:
    with open(filename, "w") as out:
        out.write("\n".join(sorted(map(str, col))))
