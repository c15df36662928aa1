"""Tests for the chunked overlapped ingest pipeline (pipelinedp_tpu.ingest)
and the Netflix-format chunked parser."""

import os
import sys

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import columnar, ingest

sys.path.insert(0,
                os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
from examples.movie_view_ratings import netflix_format  # noqa: E402

HUGE_EPS = 1e7


def _int_id_chunks(dtype, sparse=False, n=12_000, size=1_500):
    """Heavy-tailed integer ids in chunks; sparse: spaced like the Netflix
    Prize's customer ids (k * 5 + 6)."""
    rng = np.random.default_rng(2)
    ids = (rng.random(n)**2 * 3000).astype(np.int64)
    if sparse:
        ids = ids * 5 + 6
    ids = ids.astype(dtype)
    return [ids[i:i + size] for i in range(0, n, size)]


# Chunk streams for the integer code table, with the chunks it merges
# before a chunk leaves its rule and the pandas Index serves the rest.
CODE_TABLE_CASES = {
    "netflix_ids": (_int_id_chunks(np.int64, sparse=True), 8),
    "int32": (_int_id_chunks(np.int32), 8),
    "int64": (_int_id_chunks(np.int64), 8),
    "uint32": (_int_id_chunks(np.uint32), 8),
    "single_chunk": ([np.array([5, 5, 7, 5, 9, 7])], 1),
    "empty_chunks": ([np.zeros(0, np.int64), np.array([4, 2, 4]),
                      np.zeros(0, np.int64), np.array([2, 8])], 4),
    "table_grows": ([np.arange(10_000) % 100,
                     np.array([70_000, 5, 69_999])], 2),
    "key_beyond_limit": ([np.array([3, 1, 3]), np.array([1, 5_000_000, 2]),
                          np.array([2, 7])], 1),
    "negative_key": ([np.array([3, 1]), np.array([-1, 3]),
                      np.array([4, -1])], 1),
    "float_after_int": ([np.array([1, 3, 5]), np.array([3.0, 2.5, 1.0]),
                         np.array([5, 2])], 1),
    "string_after_int": ([np.array([1, 3]),
                          np.array(["3", "a", 1], dtype=object)], 1),
    "mixed_widths": ([np.zeros(0, np.int8), np.array([3, 1], np.int32),
                      np.array([1, 9], np.int64),
                      np.array([9, 2], np.uint8)], 4),
    "uint64_after_int64": ([np.array([1, 3]),
                            np.array([2, 3], np.uint64)], 1),
    # merge_host_vocabularies skips an empty host vocabulary.
    "host_vocabularies": ([np.array([11, 6, 21]), np.array([6, 16]),
                           np.zeros(0, np.int64), np.array([26, 11])], 3),
}


class TestChunkedVocabEncoder:

    @staticmethod
    def _table_run(case):
        """(codes of each chunk, vocabulary, len, code table merges)."""
        from pipelinedp_tpu.runtime import telemetry

        chunks = CODE_TABLE_CASES[case][0]
        before = telemetry.snapshot()
        if case == "host_vocabularies":
            vocab, codes = ingest.merge_host_vocabularies(chunks)
            n = len(vocab)
        else:
            enc = ingest.ChunkedVocabEncoder()
            codes = [enc.encode(chunk) for chunk in chunks]
            vocab, n = enc.vocabulary, len(enc)
        merges = telemetry.delta(before).get("vocab_table_merges", 0)
        return codes, vocab, n, merges

    @pytest.mark.parametrize("case", list(CODE_TABLE_CASES))
    def test_code_table_matches_pandas_path(self, monkeypatch, case):
        chunks, table_merges = CODE_TABLE_CASES[case]
        codes, vocab, n, merges = self._table_run(case)
        assert merges == table_merges
        monkeypatch.setattr(ingest.ChunkedVocabEncoder, "_fits_code_table",
                            lambda self, uniques: False)
        want_codes, want_vocab, want_n, merges = self._table_run(case)
        assert merges == 0
        for got, want in zip(codes, want_codes, strict=True):
            assert got.dtype == want.dtype == np.int32
            np.testing.assert_array_equal(got, want)
        assert vocab.dtype == want_vocab.dtype
        assert list(vocab) == list(want_vocab)
        assert n == want_n == len(vocab)
        flat_codes, flat_vocab = columnar.factorize(np.concatenate(chunks))
        np.testing.assert_array_equal(np.concatenate(codes), flat_codes)
        assert vocab.dtype == flat_vocab.dtype
        assert list(vocab) == list(flat_vocab)
        if case == "float_after_int":
            np.testing.assert_array_equal(codes[1], [1, 3, 0])

    def test_matches_global_factorize(self):
        rng = np.random.default_rng(0)
        raw = np.char.add("k", rng.integers(0, 500, 10_000).astype(str))
        expected_codes, expected_vocab = columnar.factorize(raw)
        enc = ingest.ChunkedVocabEncoder()
        got = np.concatenate([
            enc.encode(raw[i:i + 1234]) for i in range(0, len(raw), 1234)
        ])
        np.testing.assert_array_equal(got, expected_codes)
        assert list(enc.vocabulary) == list(expected_vocab)
        assert len(enc) == len(expected_vocab)

    def test_int_keys_and_single_chunk(self):
        raw = np.array([5, 5, 7, 5, 9, 7])
        enc = ingest.ChunkedVocabEncoder()
        codes = enc.encode(raw)
        np.testing.assert_array_equal(codes, [0, 0, 1, 0, 2, 1])
        assert list(enc.vocabulary) == [5, 7, 9]

    def test_composite_tuple_keys(self):
        # Tuple keys must stay single object elements (not explode into a
        # 2-D array) and encode consistently across chunks.
        chunk1 = [("a", 1), ("b", 2), ("a", 1)]
        chunk2 = [("b", 2), ("c", 3), ("a", 1)]
        enc = ingest.ChunkedVocabEncoder()
        c1 = enc.encode(chunk1)
        c2 = enc.encode(chunk2)
        np.testing.assert_array_equal(c1, [0, 1, 0])
        np.testing.assert_array_equal(c2, [1, 2, 0])
        assert list(enc.vocabulary) == [("a", 1), ("b", 2), ("c", 3)]

    @pytest.mark.parametrize("dtype", ["str", "int"])
    def test_fallback_matches_global_factorize_first_occurrence(
            self, monkeypatch, dtype):
        # With pandas masked out the chunk-local factorize can yield
        # SORTED uniques (np.unique branch); the encoder must still assign
        # global codes in first-occurrence order of the concatenation.
        rng = np.random.default_rng(1)
        ints = rng.integers(0, 500, 10_000)
        raw = (np.char.add("k", ints.astype(str)).astype(object)
               if dtype == "str" else ints)
        expected_codes, expected_vocab = columnar.factorize(
            columnar._as_key_array(raw))  # pandas path: first-occurrence
        monkeypatch.setattr(ingest, "_pd", None)
        monkeypatch.setattr(columnar, "_pd", None)
        enc = ingest.ChunkedVocabEncoder()
        got = np.concatenate([
            enc.encode(raw[i:i + 1234]) for i in range(0, len(raw), 1234)
        ])
        np.testing.assert_array_equal(got, expected_codes)
        assert list(enc.vocabulary) == list(expected_vocab)

    def test_fallback_dtype_widening(self, monkeypatch):
        # A later chunk with a wider string dtype must widen the stored
        # vocabulary, not truncate the new keys into it (np.insert would
        # silently cast 'hello' to 'he' in a '<U2' vocab).
        monkeypatch.setattr(ingest, "_pd", None)
        monkeypatch.setattr(columnar, "_pd", None)
        enc = ingest.ChunkedVocabEncoder()
        c1 = enc.encode(np.array(["ab", "cd"]))
        c2 = enc.encode(np.array(["hello", "ab"]))
        c3 = enc.encode(np.array(["hello", "cd"]))
        np.testing.assert_array_equal(c1, [0, 1])
        np.testing.assert_array_equal(c2, [2, 0])
        np.testing.assert_array_equal(c3, [2, 1])
        assert list(enc.vocabulary) == ["ab", "cd", "hello"]
        # Numeric widening: float keys against an int vocab must not be
        # floored into it.
        enc2 = ingest.ChunkedVocabEncoder()
        enc2.encode(np.array([1, 2]))
        c = enc2.encode(np.array([1.5, 1.0]))
        np.testing.assert_array_equal(c, [2, 0])
        assert list(enc2.vocabulary) == [1.0, 2.0, 1.5]

    def test_fallback_nan_keys_unify(self, monkeypatch):
        # All NaN keys share one code across chunks (pandas
        # use_na_sentinel=False semantics), and the NaN key never enters
        # the sorted vocab where it would corrupt binary searches.
        monkeypatch.setattr(ingest, "_pd", None)
        monkeypatch.setattr(columnar, "_pd", None)
        enc = ingest.ChunkedVocabEncoder()
        c1 = enc.encode(np.array([1.0, np.nan, 2.0]))
        c2 = enc.encode(np.array([np.nan, 1.0, 3.0]))
        np.testing.assert_array_equal(c1, [0, 1, 2])
        np.testing.assert_array_equal(c2, [1, 0, 3])
        vocab = enc.vocabulary
        assert len(vocab) == 4
        assert vocab[0] == 1.0 and np.isnan(vocab[1]) and vocab[2] == 2.0
        assert vocab[3] == 3.0
        # Keys larger than everything must still be found after NaN
        # appeared (NaN inside the sorted array would break the search).
        c3 = enc.encode(np.array([99.0, np.nan, 3.0]))
        np.testing.assert_array_equal(c3, [4, 1, 3])
        c4 = enc.encode(np.array([99.0]))
        np.testing.assert_array_equal(c4, [4])

    def test_fallback_mixed_number_string_chunks_spill(self, monkeypatch):
        # numpy silently PROMOTES numbers to strings instead of raising;
        # the encoder must detect the kind mismatch and spill to the dict
        # path where 1.5 and '1.5' stay distinct keys (pandas semantics).
        monkeypatch.setattr(ingest, "_pd", None)
        monkeypatch.setattr(columnar, "_pd", None)
        enc = ingest.ChunkedVocabEncoder()
        c1 = enc.encode(np.array(["ab", "cd"]))
        c2 = enc.encode(np.array([1.5, 2.5]))
        c3 = enc.encode(np.array([1.5, "1.5", "ab"], dtype=object))
        np.testing.assert_array_equal(c1, [0, 1])
        np.testing.assert_array_equal(c2, [2, 3])
        np.testing.assert_array_equal(c3, [2, 4, 0])
        assert list(enc.vocabulary) == ["ab", "cd", 1.5, 2.5, "1.5"]

    def test_fallback_nan_survives_dict_spill(self, monkeypatch):
        # The NaN code must keep matching after a spill to the dict path
        # (every float('nan') object is distinct under ==).
        monkeypatch.setattr(ingest, "_pd", None)
        monkeypatch.setattr(columnar, "_pd", None)
        enc = ingest.ChunkedVocabEncoder()
        c1 = enc.encode(np.array([1.0, np.nan]))
        c2 = enc.encode(np.array(["x", 2, np.nan], dtype=object))  # spills
        c3 = enc.encode(np.array([np.nan, 1.0]))
        np.testing.assert_array_equal(c1, [0, 1])
        np.testing.assert_array_equal(c2, [2, 3, 1])
        np.testing.assert_array_equal(c3, [1, 0])
        vocab = enc.vocabulary
        assert vocab[0] == 1.0 and np.isnan(vocab[1])

    def test_fallback_nan_with_string_vocab(self, monkeypatch):
        # A float NaN key alongside string keys: the vocabulary must hold
        # a REAL NaN (object dtype), not the string 'nan'.
        monkeypatch.setattr(ingest, "_pd", None)
        monkeypatch.setattr(columnar, "_pd", None)
        enc = ingest.ChunkedVocabEncoder()
        enc.encode(np.array(["a", "b"], dtype=object))
        enc.encode(np.array([np.nan], dtype=object))
        vocab = enc.vocabulary
        assert list(vocab[:2]) == ["a", "b"]
        assert isinstance(vocab[2], float) and np.isnan(vocab[2])
        # An int vocab with NaN promotes to float64, not to a string.
        enc2 = ingest.ChunkedVocabEncoder()
        enc2.encode(np.array([7, 9]))
        enc2.encode(np.array([np.nan]))
        vocab2 = enc2.vocabulary
        assert vocab2.dtype.kind in "fO"
        assert vocab2[0] == 7 and np.isnan(vocab2[2])

    def test_fallback_unorderable_keys_spill_to_dict(self, monkeypatch):
        # A chunk mixing unorderable key types mid-stream must spill to
        # the dict path without invalidating already-assigned codes.
        monkeypatch.setattr(ingest, "_pd", None)
        monkeypatch.setattr(columnar, "_pd", None)
        enc = ingest.ChunkedVocabEncoder()
        c1 = enc.encode(np.array(["x", "y", "x"], dtype=object))
        c2 = enc.encode(
            np.array(["y", ("tup", 1), 3, "z"], dtype=object))
        np.testing.assert_array_equal(c1, [0, 1, 0])
        np.testing.assert_array_equal(c2, [1, 2, 3, 4])
        assert list(enc.vocabulary) == ["x", "y", ("tup", 1), 3, "z"]
        # Codes keep accumulating on the dict path.
        c3 = enc.encode(np.array([3, "w"], dtype=object))
        np.testing.assert_array_equal(c3, [3, 5])


class TestNetflixChunkedParse:

    @pytest.mark.parametrize("chunk_bytes", [64, 1000, 1 << 20])
    def test_chunks_concat_equals_whole_parse(self, tmp_path, chunk_bytes):
        path = str(tmp_path / "views.txt")
        netflix_format.generate_file(path, 3000, n_users=50, n_movies=40,
                                     seed=3)
        users, movies, ratings = netflix_format.parse_file_columns(path)
        chunks = list(netflix_format.parse_file_chunks(path, chunk_bytes))
        assert len(chunks) >= (2 if chunk_bytes < 1000 else 1)
        np.testing.assert_array_equal(
            np.concatenate([c[0] for c in chunks]), users)
        np.testing.assert_array_equal(
            np.concatenate([c[1] for c in chunks]), movies)
        np.testing.assert_array_equal(
            np.concatenate([c[2] for c in chunks]), ratings)

    @pytest.mark.parametrize("n_shards", [1, 2, 3, 7])
    def test_byte_range_shards_cover_file_exactly(self, tmp_path, n_shards):
        # Host-shard semantics: a shard owns every movie section whose
        # header starts in its byte range; concatenating a contiguous
        # cover equals the whole-file parse, each line exactly once.
        path = str(tmp_path / "views.txt")
        netflix_format.generate_file(path, 5000, n_users=80, n_movies=60,
                                     seed=9)
        users, movies, ratings = netflix_format.parse_file_columns(path)
        size = os.path.getsize(path)
        per = -(-size // n_shards)
        got_u, got_m, got_r = [], [], []
        for h in range(n_shards):
            for u, m, r in netflix_format.parse_file_chunks(
                    path, chunk_bytes=997,
                    byte_range=(h * per, min((h + 1) * per, size))):
                got_u.append(u)
                got_m.append(m)
                got_r.append(r)
        np.testing.assert_array_equal(np.concatenate(got_u), users)
        np.testing.assert_array_equal(np.concatenate(got_m), movies)
        np.testing.assert_array_equal(np.concatenate(got_r), ratings)

    def test_byte_range_shards_crlf_file(self, tmp_path):
        # CRLF files: the binary byte accounting of the sharded reader
        # must line up with the binary header-probe offsets (text-mode
        # newline translation would undercount by one byte per line).
        path = str(tmp_path / "views_crlf.txt")
        lf = str(tmp_path / "views_lf.txt")
        netflix_format.generate_file(lf, 3000, n_users=50, n_movies=40,
                                     seed=3)
        with open(lf, "rb") as f:
            data = f.read()
        with open(path, "wb") as f:
            f.write(data.replace(b"\n", b"\r\n"))
        users, movies, ratings = netflix_format.parse_file_columns(lf)
        size = os.path.getsize(path)
        per = -(-size // 3)
        got_u, got_m = [], []
        for h in range(3):
            for u, m, _ in netflix_format.parse_file_chunks(
                    path, chunk_bytes=997,
                    byte_range=(h * per, min((h + 1) * per, size))):
                got_u.append(u)
                got_m.append(m)
        np.testing.assert_array_equal(np.concatenate(got_u), users)
        np.testing.assert_array_equal(np.concatenate(got_m), movies)

    def test_byte_range_shard_without_headers_is_empty(self, tmp_path):
        # A byte range holding only rating lines of an earlier section
        # yields nothing (and must not raise the no-header error).
        path = str(tmp_path / "views.txt")
        with open(path, "w") as f:
            f.write("7:\n" + "".join(f"{u},3,2020-01-01\n"
                                     for u in range(200)))
        mid = os.path.getsize(path) // 2
        out = list(
            netflix_format.parse_file_chunks(path, byte_range=(mid,
                                                               mid + 10)))
        assert out == []
        # And the owning shard (containing the header) reads to EOF.
        total = sum(
            len(u) for u, _, _ in netflix_format.parse_file_chunks(
                path, byte_range=(0, mid)))
        assert total == 200

    def test_generated_file_roundtrip(self, tmp_path):
        path = str(tmp_path / "views.txt")
        netflix_format.generate_file(path, 500, n_users=20, n_movies=10,
                                     seed=1)
        users, movies, ratings = netflix_format.parse_file_columns(path)
        assert len(users) == 500
        assert movies.min() >= 1 and movies.max() <= 10
        assert set(np.unique(ratings)) <= {1, 2, 3, 4, 5}

    def test_headerless_file_rejected(self, tmp_path):
        path = str(tmp_path / "bad.txt")
        with open(path, "w") as f:
            f.write("1,5,2023-01-01\n")
        with pytest.raises(ValueError, match="header"):
            list(netflix_format.parse_file_chunks(path))


class TestStreamEncodeEngine:

    @staticmethod
    def _chunks(pid, pk, values, size):
        for i in range(0, len(pid), size):
            yield pid[i:i + size], pk[i:i + size], values[i:i + size]

    def _data(self):
        rng = np.random.default_rng(7)
        pid = np.char.add("u", rng.integers(0, 80, 4000).astype(str))
        pk = np.char.add("m", rng.integers(0, 25, 4000).astype(str))
        values = rng.uniform(0, 5, 4000)
        return pid, pk, values

    def _aggregate(self, col, public=None, extractors=None):
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT,
                                              pdp.Metrics.SUM],
                                     max_partitions_contributed=25,
                                     max_contributions_per_partition=16,
                                     min_value=0.0,
                                     max_value=5.0)
        acc = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                        total_delta=1e-5)
        engine = pdp.DPEngine(acc, pdp.TPUBackend(noise_seed=11))
        if extractors is None:
            extractors = pdp.DataExtractors(
                privacy_id_extractor=lambda r: r[0],
                partition_extractor=lambda r: r[1],
                value_extractor=lambda r: r[2])
        result = engine.aggregate(col, params, extractors, public)
        acc.compute_budgets()
        return dict(result)

    def test_streamed_equals_row_input(self):
        pid, pk, values = self._data()
        encoded = ingest.stream_encode_columns(
            self._chunks(pid, pk, values, 700))
        streamed = self._aggregate(encoded)
        rows = list(zip(pid, pk, values))
        direct = self._aggregate(rows)
        assert set(streamed) == set(direct)
        for key in direct:
            assert streamed[key].count == pytest.approx(direct[key].count,
                                                        abs=0.05)
            assert streamed[key].sum == pytest.approx(direct[key].sum,
                                                      abs=0.1)

    def test_streamed_public_partitions(self):
        pid, pk, values = self._data()
        public = ["m0", "m1", "m_empty"]
        encoded = ingest.stream_encode_columns(
            self._chunks(pid, pk, values, 900), public_partitions=public)
        result = self._aggregate(encoded, public=public)
        assert set(result) == set(public)
        direct = self._aggregate(list(zip(pid, pk, values)), public=public)
        for key in public:
            assert result[key].count == pytest.approx(direct[key].count,
                                                      abs=0.05)

    def test_public_partition_mismatch_raises(self):
        pid, pk, values = self._data()
        encoded = ingest.stream_encode_columns(
            self._chunks(pid, pk, values, 900), public_partitions=["m0"])
        with pytest.raises(ValueError, match="same public partitions"):
            self._aggregate(encoded, public=["m0", "m1"])

    def test_code_table_release_is_bit_identical(self, monkeypatch):
        """A ChunkSource job on integer ids releases the same partitions
        and values, bit for bit under one noise seed, with the code table
        and without it. The table merges both key columns of every chunk,
        and no column of string keys."""
        from pipelinedp_tpu.runtime import telemetry

        rng = np.random.default_rng(12)
        n = 6000
        pid = (rng.random(n)**2 * 500).astype(np.int64) * 5 + 6
        pk = (rng.random(n)**2.5 * 40).astype(np.int64) + 1
        values = rng.choice(np.arange(1.0, 6.0), n)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=2,
            max_contributions_per_partition=1,
            min_value=1.0,
            max_value=5.0)
        extractors = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                        partition_extractor=lambda r: r[1],
                                        value_extractor=lambda r: r[2])

        def release(pid, pk):
            chunks = list(self._chunks(pid, pk, values, 1500))
            acc = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                            total_delta=1e-6)
            engine = pdp.DPEngine(acc, pdp.TPUBackend(noise_seed=13))
            before = telemetry.snapshot()
            result = engine.aggregate(
                pdp.ChunkSource(chunks, encode_mode="host"), params,
                extractors)
            acc.compute_budgets()
            result = dict(result)
            return result, telemetry.delta(before).get(
                "vocab_table_merges", 0)

        table, merges = release(pid, pk)
        assert merges == 2 * 4
        with monkeypatch.context() as patch:
            patch.setattr(ingest.ChunkedVocabEncoder, "_fits_code_table",
                          lambda self, uniques: False)
            index, merges = release(pid, pk)
        assert merges == 0
        assert table and list(table) == list(index)
        for key, row in table.items():
            assert tuple(row) == tuple(index[key]), key
        _, merges = release(pid.astype(str), pk.astype(str))
        assert merges == 0

    def test_empty_chunk_iter(self):
        encoded = ingest.stream_encode_columns(iter(()))
        assert encoded.n_rows == 0
        assert encoded.n_partitions == 0

    def test_file_to_result_end_to_end(self, tmp_path):
        path = str(tmp_path / "views.txt")
        netflix_format.generate_file(path, 4000, n_users=60, n_movies=30,
                                     seed=5)
        chunk_iter = ((u, m, r.astype(np.float32)) for u, m, r in
                      netflix_format.parse_file_chunks(path, 2048))
        encoded = ingest.stream_encode_columns(chunk_iter)
        result = self._aggregate(encoded)
        users, movies, ratings = netflix_format.parse_file_columns(path)
        direct = self._aggregate(list(zip(users, movies, ratings)))
        assert set(result) == set(direct)
        for key in direct:
            assert result[key].count == pytest.approx(direct[key].count,
                                                      abs=0.05)
            assert result[key].sum == pytest.approx(direct[key].sum,
                                                    abs=0.1)


class TestPreEncodedGuards:

    def _encoded(self, public=None):
        rng = np.random.default_rng(3)
        pid = np.char.add("u", rng.integers(0, 50, 2000).astype(str))
        pk = np.char.add("m", rng.integers(0, 12, 2000).astype(str))
        values = rng.uniform(0, 5, 2000)
        return ingest.stream_encode_columns(
            iter([(pid, pk, values)]), public_partitions=public)

    def test_public_encoded_without_public_raises(self):
        encoded = self._encoded(public=["m0", "m1"])
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                                     max_partitions_contributed=2,
                                     max_contributions_per_partition=2)
        acc = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                        total_delta=1e-5)
        engine = pdp.DPEngine(acc, pdp.TPUBackend(noise_seed=1))
        ext = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                 partition_extractor=lambda r: r[1],
                                 value_extractor=lambda r: r[2])
        result = engine.aggregate(encoded, params, ext)
        acc.compute_budgets()
        with pytest.raises(ValueError, match="public-partition vocabulary"):
            list(result)

    def test_select_partitions_does_not_destroy_values(self):
        encoded = self._encoded()
        ext = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                 partition_extractor=lambda r: r[1],
                                 value_extractor=lambda r: r[2])
        acc = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                        total_delta=1e-5)
        engine = pdp.DPEngine(acc, pdp.TPUBackend(noise_seed=1))
        sel = engine.select_partitions(
            encoded, pdp.SelectPartitionsParams(max_partitions_contributed=12),
            ext)
        agg = engine.aggregate(
            encoded,
            pdp.AggregateParams(metrics=[pdp.Metrics.SUM],
                                max_partitions_contributed=12,
                                max_contributions_per_partition=64,
                                min_value=0.0,
                                max_value=5.0), ext)
        acc.compute_budgets()
        assert len(list(sel)) == 12
        agg = dict(agg)
        # values column must have survived select_partitions: sums nonzero.
        assert encoded.values.shape == (2000,)
        assert sum(v.sum for v in agg.values()) > 100

    def test_device_resident_blocked_route(self):
        encoded = self._encoded()
        ext = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                 partition_extractor=lambda r: r[1],
                                 value_extractor=lambda r: r[2])
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT,
                                              pdp.Metrics.SUM],
                                     max_partitions_contributed=12,
                                     max_contributions_per_partition=64,
                                     min_value=0.0,
                                     max_value=5.0)

        def run(backend):
            acc = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                            total_delta=1e-5)
            engine = pdp.DPEngine(acc, backend)
            result = engine.aggregate(encoded, params, ext)
            acc.compute_budgets()
            return dict(result)

        blocked = run(pdp.TPUBackend(noise_seed=2,
                                     large_partition_threshold=4))
        dense = run(pdp.TPUBackend(noise_seed=2,
                                   large_partition_threshold=None))
        assert set(blocked) == set(dense)
        for k in dense:
            assert blocked[k].count == pytest.approx(dense[k].count,
                                                     abs=0.1)


def test_generate_file_zero_rows(tmp_path):
    path = str(tmp_path / "empty.txt")
    netflix_format.generate_file(path, 0)
    assert open(path).read() == ""


class TestMultiHostIngest:
    """Host-sharded ingest: encode_shard + vocabulary merge + remap."""

    @staticmethod
    def _raw(n=6000, seed=7):
        rng = np.random.default_rng(seed)
        pids = np.char.add("u", rng.integers(0, 500, n).astype(str))
        pks = np.char.add("pk", rng.integers(0, 60, n).astype(str))
        vals = rng.uniform(0, 5, n)
        return pids, pks, vals

    def _shard_chunks(self, pids, pks, vals, h, n_hosts, chunk=517):
        n = len(pids)
        per = -(-n // n_hosts)
        lo, hi = h * per, min((h + 1) * per, n)
        return [(pids[i:min(i + chunk, hi)], pks[i:min(i + chunk, hi)],
                 vals[i:min(i + chunk, hi)]) for i in range(lo, hi, chunk)]

    def test_merge_matches_single_process_factorize(self):
        pids, pks, vals = self._raw()
        n_hosts = 3
        shards = [
            ingest.encode_shard(self._shard_chunks(pids, pks, vals, h,
                                                   n_hosts))
            for h in range(n_hosts)
        ]
        merged = ingest.merge_shards(shards)
        expected = columnar.encode_columns(pids, pks, vals)
        np.testing.assert_array_equal(np.asarray(merged.pid), expected.pid)
        np.testing.assert_array_equal(np.asarray(merged.pk), expected.pk)
        assert list(merged.partition_vocab) == list(
            expected.partition_vocab)
        assert merged.n_privacy_ids == expected.n_privacy_ids
        np.testing.assert_allclose(np.asarray(merged.values),
                                   vals.astype(np.float32), rtol=1e-6)

    def test_merge_public_partitions(self):
        pids, pks, vals = self._raw(2000)
        public = [f"pk{i}" for i in range(40)]
        shards = [
            ingest.encode_shard(self._shard_chunks(pids, pks, vals, h, 2),
                                public_partitions=public)
            for h in range(2)
        ]
        merged = ingest.merge_shards(shards, public_partitions=public)
        expected = columnar.encode_columns(pids, pks, vals,
                                           public_partitions=public)
        np.testing.assert_array_equal(np.asarray(merged.pk), expected.pk)
        assert merged.public_encoded

    def test_merge_public_mismatch_raises(self):
        pids, pks, vals = self._raw(200)
        shard = ingest.encode_shard(self._shard_chunks(pids, pks, vals, 0, 1),
                                    public_partitions=["pk1"])
        with pytest.raises(ValueError, match="public"):
            ingest.merge_shards([shard])
        # Reverse direction: privately-encoded shard + public merge must
        # also raise (the pk codes index the wrong vocabulary).
        shard_priv = ingest.encode_shard(
            self._shard_chunks(pids, pks, vals, 0, 1))
        with pytest.raises(ValueError, match="without public"):
            ingest.merge_shards([shard_priv], public_partitions=["pk1"])

    def test_merge_fallback_no_pandas(self, monkeypatch):
        monkeypatch.setattr(ingest, "_pd", None)
        monkeypatch.setattr(columnar, "_pd", None)
        pids, pks, vals = self._raw(3000)
        pids = pids.astype(object)
        pks = pks.astype(object)
        shards = [
            ingest.encode_shard(self._shard_chunks(pids, pks, vals, h, 3))
            for h in range(3)
        ]
        merged = ingest.merge_shards(shards)
        monkeypatch.undo()
        expected = columnar.encode_columns(pids, pks, vals)
        np.testing.assert_array_equal(np.asarray(merged.pid), expected.pid)
        np.testing.assert_array_equal(np.asarray(merged.pk), expected.pk)

    def test_n_process_dryrun_and_engine(self, tmp_path):
        # REAL process isolation: each "host" encodes its shard in a
        # separate python process (no shared encoder state), the parent
        # merges and runs the engine — codes must equal the single-process
        # factorize and the DP result must match the row-input path.
        import pickle
        import subprocess

        pids, pks, vals = self._raw(4000)
        n_hosts = 3
        worker = tmp_path / "worker.py"
        worker.write_text(
            "import os, pickle, sys\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "sys.path.insert(0, %r)\n"
            "import numpy as np\n"
            "from pipelinedp_tpu import ingest\n"
            "with open(sys.argv[1], 'rb') as f:\n"
            "    chunks = pickle.load(f)\n"
            "shard = ingest.encode_shard(chunks)\n"
            "with open(sys.argv[2], 'wb') as f:\n"
            "    pickle.dump(shard, f)\n" %
            os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
        shards = []
        for h in range(n_hosts):
            inp, out = tmp_path / f"in{h}.pkl", tmp_path / f"out{h}.pkl"
            with open(inp, "wb") as f:
                pickle.dump(self._shard_chunks(pids, pks, vals, h, n_hosts),
                            f)
            subprocess.run([sys.executable, str(worker), str(inp), str(out)],
                           check=True, timeout=300)
            with open(out, "rb") as f:
                shards.append(pickle.load(f))
        merged = ingest.merge_shards(shards)
        expected = columnar.encode_columns(pids, pks, vals)
        np.testing.assert_array_equal(np.asarray(merged.pid), expected.pid)
        np.testing.assert_array_equal(np.asarray(merged.pk), expected.pk)

        rows = list(zip(pids, pks, vals))
        ex = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                partition_extractor=lambda r: r[1],
                                value_extractor=lambda r: float(r[2]))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=60,
            max_contributions_per_partition=30,
            min_value=0.0,
            max_value=5.0)

        def agg(data):
            acc = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                            total_delta=1e-6)
            engine = pdp.DPEngine(acc, pdp.TPUBackend(noise_seed=5))
            result = engine.aggregate(data, params, ex)
            acc.compute_budgets()
            return {k: round(v.count, 2) for k, v in dict(result).items()}

        assert agg(merged) == agg(rows)


class TestChunkedEncoderProperty:
    """Hypothesis: the no-pandas fallback encoder matches a global pandas
    factorize for ANY chunking over mixed key types (strings, ints,
    floats, NaN, tuples) — the contract every round-5 edge fix defends."""

    KEY_POOL = [
        "a", "bb", "ccc", "hello", "zz9", 1, 2, 37, 1.5, 2.5, 2.0,
        float("nan"), ("t", 1), ("t", 2)
    ]

    def test_random_chunkings_match_global_factorize(self):
        pytest.importorskip(
            "hypothesis",
            reason="property test needs hypothesis (absent in some images)")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=60, deadline=None)
        @given(
            st.lists(st.sampled_from(self.KEY_POOL), min_size=1,
                     max_size=60), st.integers(1, 7))
        def run(keys, chunk):
            arr = columnar._as_key_array(keys)
            expected, expected_vocab = columnar.factorize(arr)  # pandas
            saved = ingest._pd, columnar._pd
            ingest._pd = columnar._pd = None
            try:
                enc = ingest.ChunkedVocabEncoder()
                got = np.concatenate([
                    enc.encode(keys[i:i + chunk])
                    for i in range(0, len(keys), chunk)
                ])
                vocab = enc.vocabulary
            finally:
                ingest._pd, columnar._pd = saved
            np.testing.assert_array_equal(got, expected)
            assert len(vocab) == len(expected_vocab)
            for a, b in zip(vocab, expected_vocab):
                if isinstance(a, float) and np.isnan(a):
                    assert isinstance(b, float) and np.isnan(b)
                else:
                    assert a == b, (a, b)

        run()


class TestNonFiniteValueValidation:
    """NaN/Inf in the VALUE column survives jnp.clip and silently poisons
    sums; the ingest/columnar boundary must reject (default) or
    drop-with-warning."""

    def _cols(self):
        pids = np.array(["u1", "u2", "u3", "u4"])
        pks = np.array(["a", "a", "b", "b"])
        vals = np.array([1.0, np.nan, np.inf, 2.0])
        return pids, pks, vals

    def test_encode_columns_rejects_by_default(self):
        pids, pks, vals = self._cols()
        with pytest.raises(ValueError, match="non-finite"):
            columnar.encode_columns(pids, pks, vals)

    def test_encode_columns_drop_policy_invalidates_rows(self, caplog):
        pids, pks, vals = self._cols()
        with caplog.at_level("WARNING"):
            encoded = columnar.encode_columns(pids, pks, vals,
                                              nonfinite="drop")
        assert "dropping 2" in caplog.text
        np.testing.assert_array_equal(encoded.valid,
                                      [True, False, False, True])
        assert np.isfinite(encoded.values).all()

    def test_vector_values_any_bad_coordinate_drops_row(self):
        pids = np.array(["u1", "u2"])
        pks = np.array(["a", "b"])
        vals = np.array([[1.0, np.nan], [2.0, 3.0]])
        with pytest.raises(ValueError, match="non-finite"):
            columnar.encode_columns(pids, pks, vals)
        encoded = columnar.encode_columns(pids, pks, vals, nonfinite="drop")
        np.testing.assert_array_equal(encoded.valid, [False, True])

    def test_invalid_policy_rejected(self):
        with pytest.raises(ValueError, match="error|drop"):
            columnar.nonfinite_value_rows(np.array([1.0]), policy="ignore")

    def test_integer_values_never_checked(self):
        assert columnar.nonfinite_value_rows(np.array([1, 2, 3])) is None

    def test_stream_encode_rejects_and_drops(self):
        chunks = lambda: iter([(np.array(["u1", "u2"]), np.array(["a", "b"]),
                                np.array([1.0, np.inf]))])
        with pytest.raises(ValueError, match="non-finite"):
            ingest.stream_encode_columns(chunks())
        encoded = ingest.stream_encode_columns(chunks(), nonfinite="drop")
        np.testing.assert_array_equal(np.asarray(encoded.valid),
                                      [True, False])
        assert np.isfinite(np.asarray(encoded.values)).all()

    def test_encode_shard_rejects_and_drops(self):
        chunks = lambda: iter([(np.array(["u1", "u2"]), np.array(["a", "b"]),
                                np.array([np.nan, 5.0]))])
        with pytest.raises(ValueError, match="non-finite"):
            ingest.encode_shard(chunks())
        shard = ingest.encode_shard(chunks(), nonfinite="drop")
        np.testing.assert_array_equal(shard.pk, [-1, 1])
        assert np.isfinite(shard.values).all()

    def test_dropped_rows_do_not_poison_engine_results(self):
        # End to end: a poisoned row dropped at ingest leaves the other
        # partitions' noise-free sums intact.
        pids = np.array(["u%d" % (i % 30) for i in range(300)])
        pks = np.array(["p%d" % (i % 3) for i in range(300)])
        vals = np.ones(300)
        vals[7] = np.nan
        encoded = columnar.encode_columns(pids, pks, vals, nonfinite="drop")
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=3,
            max_contributions_per_partition=10,
            min_value=0.0,
            max_value=5.0)
        extractors = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                        partition_extractor=lambda r: r[1],
                                        value_extractor=lambda r: r[2])
        accountant = pdp.NaiveBudgetAccountant(total_epsilon=HUGE_EPS,
                                               total_delta=1e-6)
        engine = pdp.DPEngine(accountant, pdp.TPUBackend(noise_seed=3))
        result = engine.aggregate(encoded, params, extractors)
        accountant.compute_budgets()
        out = dict(result)
        assert len(out) == 3
        for pk, metrics in out.items():
            assert np.isfinite(metrics.sum)
            expected = 100.0 - (1.0 if pk == "p1" else 0.0)
            assert metrics.sum == pytest.approx(expected, abs=0.1)
