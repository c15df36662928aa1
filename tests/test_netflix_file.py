"""The package's Netflix-format reader (pipelinedp_tpu.textfile) against a
plain line-by-line reference, and the file source of ChunkSource.

The reference reads a file as upstream PipelineDP's
examples/movie_view_ratings/common_utils.py does, one Python str a line:
a line ending in ':' is a header, any other non-empty line is split on
',' and its first two fields are the user id and the rating. The reader
must give the same columns on seeded random files (ids of 1 to 18
digits, LF and CRLF endings, blank lines, a last line without a newline)
whatever the byte ranges it is read in; and a ChunkSource of the file
must release what an in-memory ChunkSource of the same columns releases,
bit for bit, under one noise seed, on both encode routes.
"""

import os

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from examples.movie_view_ratings import netflix_format
from pipelinedp_tpu import textfile
from pipelinedp_tpu.runtime import trace


def reference(path):
    users, movies, ratings = [], [], []
    movie = None
    with open(path, newline="") as f:
        for line in f.read().replace("\r", "").split("\n"):
            if not line:
                continue
            if line.endswith(":"):
                movie = int(line[:-1])
                continue
            fields = line.split(",")
            users.append(int(fields[0]))
            movies.append(movie)
            ratings.append(int(fields[1]))
    return (np.array(users, np.int64), np.array(movies, np.int64),
            np.array(ratings, np.float32))


def random_file(path, seed, crlf=False, blank_lines=False,
                last_newline=True, sections=40, rows=1500):
    """Movie sections with ids of 1 to 18 digits and dates read over."""
    rng = np.random.default_rng(seed)

    def number():
        return str(int(rng.integers(1, 10)) * 10**int(rng.integers(0, 18))
                   + int(rng.integers(0, 10**int(rng.integers(1, 9)))))

    lines = []
    for s in range(sections):
        lines.append(number() + ":")
        for _ in range(int(rng.integers(0, 2 * rows // sections))):
            lines.append(f"{number()},{int(rng.integers(1, 6))},"
                         f"200{int(rng.integers(0, 6))}-0{s % 9 + 1}-1"
                         f"{s % 10}")
            if blank_lines and rng.random() < 0.05:
                lines.append("")
    text = ("\r\n" if crlf else "\n").join(lines)
    if last_newline:
        text += "\r\n" if crlf else "\n"
    with open(path, "w", newline="") as f:
        f.write(text)
    return path


def assert_columns_equal(got, want):
    for g, w, dtype in zip(got, want, (np.int64, np.int64, np.float32)):
        assert g.dtype == dtype
        np.testing.assert_array_equal(g, w)


def by_ranges(path, range_bytes):
    ranges = textfile.NetflixFileRanges(path, range_bytes)
    parts = [ranges.load(item) for item in ranges.ranges]
    if not parts:
        return tuple(np.zeros(0, d) for d in (np.int64, np.int64,
                                              np.float32))
    return tuple(np.concatenate([p[i] for p in parts]) for i in range(3))


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("crlf", [False, True])
@pytest.mark.parametrize("blank_lines,last_newline", [(False, True),
                                                     (True, False)])
def test_the_reader_reads_what_the_reference_reads(tmp_path, seed, crlf,
                                                   blank_lines,
                                                   last_newline):
    path = random_file(str(tmp_path / "views.txt"), seed, crlf, blank_lines,
                       last_newline)
    want = reference(path)
    assert len(want[0]) > 500 and want[0].max() >= 10**16
    with open(path, "rb") as f:
        assert_columns_equal(textfile.parse_netflix(f.read(), path), want)
    assert_columns_equal(netflix_format.parse_file_columns(path), want)
    assert_columns_equal(by_ranges(path, 997), want)


@pytest.mark.parametrize("range_bytes", [1, 2, 3, 7, 64, 1000, 1 << 30])
@pytest.mark.parametrize("crlf", [False, True])
def test_every_range_size_reads_every_row_once(tmp_path, range_bytes, crlf):
    path = random_file(str(tmp_path / "views.txt"), 5, crlf,
                       blank_lines=True, last_newline=False, sections=12,
                       rows=300)
    assert_columns_equal(by_ranges(path, range_bytes), reference(path))


@pytest.mark.parametrize("range_bytes", [5, 333, 4096])
def test_one_section_over_many_ranges(tmp_path, range_bytes):
    path = str(tmp_path / "one.txt")
    with open(path, "w") as f:
        f.write("\n\n42:\n" + "".join(f"{u},{u % 5 + 1},2004-01-01\n"
                                      for u in range(3000)))
    ranges = textfile.NetflixFileRanges(path, range_bytes)
    assert len(ranges.ranges) > 10
    parts = [ranges.load(item) for item in ranges.ranges]
    # The range that holds the header reads the whole section; the others
    # hold no header and yield nothing.
    assert sorted(len(p[0]) for p in parts)[-2:] == [0, 3000]
    assert_columns_equal(by_ranges(path, range_bytes), reference(path))


@pytest.mark.parametrize("n_shards", [1, 2, 5, 13])
def test_byte_range_shards_equal_the_whole_file(tmp_path, n_shards):
    path = random_file(str(tmp_path / "views.txt"), 11, crlf=True)
    size = os.path.getsize(path)
    per = -(-size // n_shards)
    shards = [list(netflix_format.parse_file_chunks(
        path, chunk_bytes=301, byte_range=(h * per, min((h + 1) * per,
                                                       size))))
              for h in range(n_shards)]
    chunks = [c for shard in shards for c in shard]
    got = tuple(np.concatenate([c[i] for c in chunks]) for i in range(3))
    assert_columns_equal(got, reference(path))


def test_data_before_the_first_header_names_the_file(tmp_path):
    path = str(tmp_path / "headless.txt")
    with open(path, "w") as f:
        f.write("\n7,3,2005-01-01\n8:\n9,4,2005-01-02\n")
    with pytest.raises(ValueError, match="headless.txt.*before the first"):
        textfile.read_netflix_range(path, 0, 4)
    with pytest.raises(ValueError, match="headless.txt.*before the first"):
        by_ranges(path, 1)
    # A range that starts past the first line owns only what follows its
    # first header: no error there.
    users, _, _ = textfile.read_netflix_range(path, 5, 20)
    np.testing.assert_array_equal(users, [9])


@pytest.mark.parametrize("line", [
    "12a,3,2005-01-01", "12,x,2005-01-01", ",3,2005-01-01", "12,,2005",
    "12,3.5,2005-01-01", "-12,3,2005-01-01", " 12,3,2005-01-01",
    "1234567890123456789,3,2005-01-01", "12", "x:", ":"])
def test_a_field_that_is_not_a_decimal_integer_raises(tmp_path, line):
    path = str(tmp_path / "bad.txt")
    with open(path, "w") as f:
        f.write(f"5:\n1,2,2005-01-01\n{line}\n3,4,2005-01-01\n")
    with pytest.raises(ValueError, match="bad.txt"):
        textfile.read_netflix_range(path, 0, 1 << 20)


def test_headers_and_optional_fields(tmp_path):
    """A header's trailing colons are read over, as the reader has always
    done; a data line of two fields is whole; the date is not parsed."""
    path = str(tmp_path / "edge.txt")
    with open(path, "w") as f:
        f.write("000123::\n9,5\n10,1,not a date,extra\n4:\n")
    users, movies, ratings = textfile.read_netflix_range(path, 0, 100)
    np.testing.assert_array_equal(users, [9, 10])
    np.testing.assert_array_equal(movies, [123, 123])
    np.testing.assert_array_equal(ratings, [5.0, 1.0])


def test_an_empty_range_and_file(tmp_path):
    path = str(tmp_path / "empty.txt")
    open(path, "w").close()
    assert textfile.NetflixFileRanges(path).ranges == []
    users, movies, ratings = textfile.parse_netflix(b"")
    assert len(users) == len(movies) == len(ratings) == 0
    with pytest.raises(ValueError, match="header"):
        netflix_format.parse_file_columns(path)
    with pytest.raises(ValueError, match="range_bytes"):
        textfile.NetflixFileRanges(path, 0)


def _release(source, mode):
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM,
                 pdp.Metrics.PRIVACY_ID_COUNT],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=2,
        max_contributions_per_partition=1,
        min_value=1.0,
        max_value=5.0)
    ex = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                            partition_extractor=lambda r: r[1],
                            value_extractor=lambda r: r[2])
    acc = pdp.NaiveBudgetAccountant(total_epsilon=5.0, total_delta=1e-4)
    engine = pdp.DPEngine(acc, pdp.TPUBackend(noise_seed=17,
                                              encode_mode=mode))
    result = engine.aggregate(source, params, ex)
    acc.compute_budgets()
    return dict(result)


@pytest.mark.parametrize("mode", ["host", "hash_device"])
def test_the_file_source_releases_what_in_memory_chunks_release(tmp_path,
                                                               mode):
    path = str(tmp_path / "views.txt")
    netflix_format.generate_file(path, 6000, n_users=300, n_movies=40,
                                 seed=4)
    users, movies, ratings = netflix_format.parse_file_columns(path)
    chunks = [(users[i:i + 1000], movies[i:i + 1000], ratings[i:i + 1000])
              for i in range(0, len(users), 1000)]
    from_memory = _release(pdp.ChunkSource(chunks), mode)
    trace.reset()
    trace.enable()
    try:
        from_file = _release(
            pdp.ChunkSource.from_netflix_file(path, range_bytes=4096), mode)
        events = trace.to_trace_events()["traceEvents"]
    finally:
        trace.disable()
        trace.reset()
    assert from_memory and from_file == from_memory
    # Each range is read and parsed inside the encode worker's
    # pipeline_encode span, with the worker's chunk index.
    spans = [e for e in events if e.get("ph") == "X"]
    encode = {e["args"]["id"]: e for e in spans
              if e["name"] == "pipeline_encode"}
    n_ranges = -(-os.path.getsize(path) // 4096)
    for name in ("ingest.read", "ingest.parse"):
        mine = [e for e in spans if e["name"] == name]
        assert len(mine) == n_ranges
        for e in mine:
            worker = encode[e["args"]["parent"]]
            assert e["tid"] == worker["tid"]
            assert e["args"]["chunk"] == worker["args"]["chunk"]
