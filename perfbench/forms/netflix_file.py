"""Input form `netflix_file`: the rows as the Netflix Prize's own text
file -> `ChunkSource.from_netflix_file` -> `DPEngine.aggregate` on one chip.

Set-up writes the generator's rows to one file in a temporary directory,
in the dataset's layout (`combined_data_*.txt`): the rows grouped by movie
in ascending order, each movie's section a `<movie>:` header line and then
one `<user>,<rating>,<YYYY-MM-DD>` line a row, the rating written as an
integer and the date drawn from the rows (and so from the seed) in the
dataset's 1999-11-11 ... 2005-12-31. The writer is this file's own numpy
code and imports nothing of the program, so a fault of the program's
reader cannot be cancelled by the same fault here: the law compares the
release with the generator's rows, not with the file.

Each job hands the engine `ChunkSource.from_netflix_file(path,
range_bytes=traffic.range_bytes, encode_mode="host")`: byte ranges that
the encode workers read and parse, then the host encode and the dense
release of `chunks_host`. A job whose reader parsed more or fewer rows
than the file's lines (the program's `file_rows_parsed` counter over the
job) raises, and so fails: a line lost or read twice is fatal at any size,
where the law alone would miss a loss of a few percent (PERF.md section
2). The file was written in set-up, so a job reads it from the page cache:
the warm-read case; a cold read from disk is not measured. The directory
goes when the job is dropped, or at exit."""

import os
import shutil
import tempfile
import weakref

import numpy as np

from perfbench import traffic

FIRST_DAY, LAST_DAY = np.datetime64("1999-11-11"), np.datetime64("2005-12-31")
# "YYYY-MM-DD" of each day of the span, by its index.
DATES = np.datetime_as_string(
    np.arange(FIRST_DAY, LAST_DAY + 1)).astype("S10").view(
        np.uint8).reshape(-1, 10)


def _digits(values):
    """Decimal digits of each non-negative integer."""
    count = np.ones(values.shape, np.int64)
    bound = 10
    while bound <= int(values.max(initial=0)):
        count += values >= bound
        bound *= 10
    return count


def _ascii(values, width):
    """Each non-negative integer as `width` ASCII digits, padded with '0's
    on the left: (rows, width) uint8, one column a digit position."""
    out = np.empty((len(values), width), np.uint8)
    rest = values.astype(np.min_scalar_type(int(values.max(initial=0))))
    ten = rest.dtype.type(10)
    for k in range(width - 1, -1, -1):
        rest, out[:, k] = np.divmod(rest, ten)
    out += ord("0")
    return out


def write_file(path, user, movie, rating, dates_seed):
    """The rows as a Netflix-format file, grouped by movie; returns its
    bytes. Each row's text is laid out at fixed width, its header (only
    where a movie's section starts) and its user id padded on the left,
    and one boolean compaction drops the padding."""
    n = len(user)
    # A stable sort of 16-bit keys is a radix sort: the movie ids fit.
    fits = n and movie.min() >= 0 and movie.max() <= 0xFFFF
    order = np.argsort(movie.astype(np.uint16) if fits else movie,
                       kind="stable")
    user, movie = user[order], movie[order]
    rating = np.rint(rating[order]).astype(np.int64)
    if n and not (rating.min() >= 0 and rating.max() <= 9):
        raise ValueError("ratings are written as one digit")
    day = np.random.default_rng(dates_seed).integers(0, len(DATES), n)
    new = np.ones(n, bool)
    new[1:] = movie[1:] != movie[:-1]
    heads = movie[new]
    head_len = np.zeros(n, np.int64)
    head_len[new] = _digits(heads) + 2  # "<movie>:\n"
    user_len = _digits(user)
    head_w = int(head_len.max(initial=3))
    user_w = int(user_len.max(initial=1))
    # A row's record: [header][user id],<rating>,<YYYY-MM-DD>\n
    line = np.empty((n, head_w + user_w + 14), np.uint8)
    line[new, :head_w - 2] = _ascii(heads, head_w - 2)
    line[:, head_w - 2] = ord(":")
    line[:, head_w - 1] = ord("\n")
    line[:, head_w:head_w + user_w] = _ascii(user, user_w)
    tail = line[:, head_w + user_w:]
    tail[:, 0] = ord(",")
    tail[:, 1] = rating + 48
    tail[:, 2] = ord(",")
    tail[:, 3:13] = DATES[day]
    tail[:, 13] = ord("\n")
    keep = np.ones(line.shape, bool)
    np.greater_equal(np.arange(head_w), (head_w - head_len)[:, None],
                     out=keep[:, :head_w])
    np.greater_equal(np.arange(user_w), (user_w - user_len)[:, None],
                     out=keep[:, head_w:head_w + user_w])
    out = line[keep]
    with open(path, "wb") as f:
        f.write(memoryview(out))
    return out.size


def checked(job, lines):
    """`job`, failing (it raises) where the program's reader parsed other
    than `lines` rows in it: its `file_rows_parsed` counter over the job."""
    from pipelinedp_tpu.runtime import telemetry

    def run(seed):
        before = telemetry.snapshot()
        release = job(seed)
        parsed = telemetry.delta(before).get("file_rows_parsed", 0)
        if parsed != lines:
            raise RuntimeError(f"the reader parsed {parsed} rows of the "
                               f"file's {lines} data lines")
        return release

    return run


def build_job(cell, config, columns):
    import pipelinedp_tpu as pdp

    from_file = pdp.ChunkSource.from_netflix_file  # absent: fail at once
    user, movie, rating = columns
    directory = tempfile.mkdtemp(prefix="perfbench-netflix-")
    path = os.path.join(directory, "combined_data.txt")
    write_file(path, user, movie, rating,
               dates_seed=[int(user.sum()), int(movie.sum())])
    range_bytes = int(cell["traffic"]["range_bytes"])
    engine_job = traffic.engine_job(
        config["guarantees"],
        source=lambda: from_file(path, range_bytes=range_bytes,
                                 encode_mode="host"))
    job = checked(engine_job, len(user))
    weakref.finalize(job, shutil.rmtree, directory, True)
    return job
