"""The Netflix Prize text format, parsed with numpy by byte ranges.

The dataset is distributed as text files (``combined_data_*.txt``) of
movie sections:

    <movie_id>:
    <user_id>,<rating>,<date>
    <user_id>,<rating>,<date>
    <next_movie_id>:
    ...

``parse_netflix`` reads a buffer of such lines into ``(user_ids int64,
movie_ids int64, ratings float32)`` without a Python object per line: it
finds the newlines, header lines and commas in ``np.frombuffer(...,
uint8)`` and reads each decimal field by digit arithmetic. The work is
uint8 / int64 array operations, which release the GIL, so
``ChunkSource.from_netflix_file`` parses byte ranges of one file on the
streaming executor's encode workers (``read_netflix_range`` is what a
worker runs), not on the thread that feeds them.

What a line means (PipelineDP's examples/movie_view_ratings/
common_utils.py, as this package has always read the format):

  * a line whose last character is ``:`` is a header, and its field is
    the movie id of every data line below it up to the next header;
  * ``\\r`` is ignored wherever it stands, and empty lines are skipped;
  * a data line's user id and rating are its first two comma-separated
    fields; the date, and anything after the second comma, is read over
    and not parsed (a line ``user,rating`` is a whole data line);
  * a last line without a newline is still a line;
  * a data line before the first header is a ValueError naming the file;
  * a movie id, user id or rating that is not a decimal integer of 1 to
    18 ASCII digits is a ValueError, never a silent 0.

Byte ranges: range ``[lo, hi)`` of a file owns every movie section whose
header line STARTS in it. A reader of the range skips the lines before its
first header (they belong to the section before) and reads past ``hi`` to
the end of its last section, so the ranges of a contiguous cover of the
file parse every line exactly once, a section longer than a range
included, and a range that holds no header yields no rows. The range at
byte 0 owns what precedes the file's first header too, so data there is
an error and not skipped.
"""

import os
from typing import List, Optional, Tuple

import numpy as np

from pipelinedp_tpu.runtime import telemetry as rt_telemetry
from pipelinedp_tpu.runtime import trace as rt_trace

Columns = Tuple[np.ndarray, np.ndarray, np.ndarray]

MAX_DIGITS = 18  # every such decimal fits int64
_NL, _CR, _COMMA, _COLON, _ZERO = 10, 13, 44, 58, 48
# Largest first read when looking for a header line; doubled while none
# is found.
_PROBE_BYTES = 1 << 16


def parse_netflix(data, context: str = "<bytes>") -> Columns:
    """Parses a buffer of whole Netflix-format lines (``bytes`` or a
    ``memoryview``, starting at a line start) into ``(user_ids int64,
    movie_ids int64, ratings float32)`` in line order. `context` names the
    source in errors."""
    buf = data if isinstance(data, bytes) else bytes(data)
    if b"\r" in buf:
        buf = buf.replace(b"\r", b"")
    a = np.frombuffer(buf, np.uint8)
    n = a.size
    if not n:
        none = np.zeros(0, np.int64)
        return none, none.copy(), np.zeros(0, np.float32)
    # Newlines and commas, in order; a virtual newline ends a last line
    # that has none. A line's delimiters run from the one after the
    # previous line's newline to its own.
    delim = np.flatnonzero((a == _NL) | (a == _COMMA))
    is_nl = a[delim] == _NL
    if n and a[-1] != _NL:
        delim = np.append(delim, n)
        is_nl = np.append(is_nl, True)
    last = np.flatnonzero(is_nl)  # each line's newline, as an index of delim
    ends = delim[last]
    starts = np.zeros_like(ends)
    starts[1:] = ends[:-1] + 1
    first = np.zeros_like(last)  # each line's first delimiter
    first[1:] = last[:-1] + 1
    kept = ends > starts
    if not kept.all():
        starts, ends, first, last = (starts[kept], ends[kept], first[kept],
                                     last[kept])
    header = a[ends - 1] == _COLON
    movie_of = np.cumsum(header) - 1
    rows = ~header
    if rows.any() and movie_of[np.argmax(rows)] < 0:
        line = _line(a, starts, ends, int(np.argmax(rows)))
        raise ValueError(f"{context}: data line {line!r} before the first "
                         f"'movie_id:' header")
    # A header's field ends before its trailing colons.
    field_end = ends[header] - 1
    colon = a[np.maximum(field_end - 1, 0)] == _COLON
    while colon.any():
        colon &= field_end > starts[header]
        field_end -= colon
        colon &= a[np.maximum(field_end - 1, 0)] == _COLON
    movies = _decimal(a, starts[header], field_end, starts[header],
                      ends[header], context, "movie id")
    starts, ends, first, last = (starts[rows], ends[rows], first[rows],
                                 last[rows])
    no_comma = first >= last
    if no_comma.any():
        line = _line(a, starts, ends, int(np.argmax(no_comma)))
        raise ValueError(f"{context}: data line {line!r} has no ',' after "
                         f"its user id")
    comma = delim[first]
    # The rating ends at the second comma, or at the line's end (delim at
    # `last`) where there is none.
    rating_end = delim[first + 1]
    users = _decimal(a, starts, comma, starts, ends, context, "user id")
    ratings = _decimal(a, comma + 1, rating_end, starts, ends, context,
                       "rating")
    return users, movies[movie_of[rows]], ratings.astype(np.float32)


def _line(a, starts, ends, i: int) -> str:
    return a[starts[i]:ends[i]].tobytes().decode("utf-8", "replace")


def _decimal(a, lo, hi, line_starts, line_ends, context: str,
             what: str) -> np.ndarray:
    """The decimal integers in a[lo[i]:hi[i]], by digit arithmetic; a
    field that is empty, longer than MAX_DIGITS or holds a byte that is no
    ASCII digit raises, naming its line."""
    width = hi - lo
    value = np.zeros(width.size, np.int64)
    if not width.size:
        return value
    bad = (width < 1) | (width > MAX_DIGITS)
    if not bad.any():
        # Every field read as `widest` digits ending at its end, the
        # positions before its start taken as leading zeros: one pass a
        # digit position, in place.
        widest = int(width.max())
        uniform = int(width.min()) == widest
        pos = hi - widest
        inside = np.ones(width.size, bool)
        for _ in range(widest):
            digit = a.take(pos, mode="clip")
            digit -= np.uint8(_ZERO)  # wraps below '0'
            if not uniform:
                np.greater_equal(pos, lo, out=inside)
                digit *= inside
            bad |= digit > 9
            value *= 10
            value += digit
            pos += 1
    if bad.any():
        i = int(np.argmax(bad))
        field = a[lo[i]:max(hi[i], lo[i])].tobytes().decode("utf-8",
                                                            "replace")
        raise ValueError(
            f"{context}: {what} {field!r} in line "
            f"{_line(a, line_starts, line_ends, i)!r} is not a decimal "
            f"integer of 1 to {MAX_DIGITS} digits")
    return value


def _pread(fd: int, n: int, offset: int) -> bytes:
    """n bytes at offset, or fewer at the end of the file."""
    parts = []
    while n > 0:
        part = os.pread(fd, n, offset)
        if not part:
            break
        parts.append(part)
        n -= len(part)
        offset += len(part)
    return b"".join(parts)


def _first_header_in(block: bytes) -> Optional[int]:
    """Index of the start of the first header line in `block`, which
    begins at a line start and ends at the end of a line."""
    i = 0
    while True:
        p = block.find(b":", i)
        if p < 0:
            return None
        q = p + 1
        while q < len(block) and block[q] == _CR:
            q += 1
        if q == len(block) or block[q] == _NL:
            return block.rfind(b"\n", 0, p) + 1
        i = p + 1


def _next_header(fd: int, x: int, limit: int, size: int) -> Optional[int]:
    """File offset of the first header line that starts at or after `x`
    and before `limit`; None where there is none."""
    # The first line start at or after x: x itself after a newline.
    pos = x
    if 0 < pos < limit and _pread(fd, 1, pos - 1) != b"\n":
        nl = -1
        while nl < 0 and pos < limit:
            nl = _pread(fd, _PROBE_BYTES, pos).find(b"\n")
            pos += _PROBE_BYTES if nl < 0 else nl + 1
    # A header that starts before `limit` ends within a short line of it.
    step = min(_PROBE_BYTES, limit - pos + 256)
    while pos < limit:
        block = _pread(fd, step, pos)
        if pos + len(block) < size:
            cut = block.rfind(b"\n") + 1  # whole lines only
            if not cut:
                step *= 2
                continue
            block = block[:cut]
        s = _first_header_in(block)
        if s is not None:
            return pos + s if pos + s < limit else None
        pos += len(block)
        step = min(2 * step, 1 << 24)
    return None


def read_netflix_range(path: str, lo: int, hi: int,
                       chunk: Optional[int] = None) -> Columns:
    """The rows of the movie sections whose header line starts in byte
    range [lo, hi) of a Netflix-format file (module docstring), read
    under span ``ingest.read`` (the read past `hi` included) and parsed
    under span ``ingest.parse``, both with ``chunk=`` as the ingest's
    other spans; counters ``file_bytes_parsed`` and ``file_rows_parsed``."""
    with rt_trace.span("ingest.read", chunk=chunk, lo=lo,
                       hi=hi) as read_span:
        with open(path, "rb") as f:
            fd = f.fileno()
            size = os.fstat(fd).st_size
            hi = min(hi, size)
            start = 0 if lo <= 0 else _next_header(fd, lo, hi, size)
            data = b""
            if start is not None and start < hi:
                end = _next_header(fd, hi, size, size)
                end = size if end is None else end
                data = _pread(fd, end - start, start)
        read_span.set(file_bytes=len(data))
    with rt_trace.span("ingest.parse", chunk=chunk,
                       file_bytes=len(data)) as parse_span:
        columns = parse_netflix(data, path)
        parse_span.set(rows=len(columns[0]))
    rt_telemetry.record("file_bytes_parsed", len(data))
    rt_telemetry.record("file_rows_parsed", len(columns[0]))
    return columns


class NetflixFileRanges:
    """A Netflix-format file as the items of a ChunkSource: `ranges`, the
    (lo, hi) byte ranges of `range_bytes` that cover it, and `load`, which
    reads and parses one of them (on the encode worker that encodes it)."""

    def __init__(self, path, range_bytes: int = 1 << 24):
        if not isinstance(range_bytes, int) or isinstance(
                range_bytes, bool) or range_bytes < 1:
            raise ValueError(
                f"range_bytes must be an integer >= 1, got {range_bytes!r}")
        self.path = os.fspath(path)
        self.range_bytes = range_bytes
        size = os.path.getsize(self.path)
        self.ranges: List[Tuple[int, int]] = [
            (lo, min(lo + range_bytes, size))
            for lo in range(0, size, range_bytes)]

    def load(self, item: Tuple[int, int]) -> Columns:
        lo, hi = item
        return read_netflix_range(self.path, lo, hi,
                                  chunk=lo // self.range_bytes)
