"""Device-resident hash ingest (encode_mode="hash_device").

Covers the four contracts of device_encode.py + the ingest hash route:

  * code parity — the device sort/unique factorize assigns EXACTLY the
    first-occurrence codes the host encoder assigns, so kernel inputs
    (and with them every DP release under the same noise keys) are
    bit-identical between the two encode modes;
  * collision safety — two raw keys colliding on the primary 64-bit
    hash trip the detector (secondary lane disagrees), increment the
    ``ingest_hash_collisions`` counter, and fall back to the exact host
    encoder bit-identically;
  * deferred decode — partition keys are looked up only at DP-selected
    indices (HashVocab.prefetch), with zero O(rows) host transfers
    under reshard.forbid_row_fetches;
  * end-to-end parity — all four meshed drivers and the engine release
    identical results from both encodings at mesh sizes 1/4/8 and
    pipeline depths 1/8, with equal budget-ledger mechanism counts.
"""

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import columnar, device_encode, executor, ingest
from pipelinedp_tpu import input_validators
from pipelinedp_tpu.parallel import reshard
from pipelinedp_tpu.parallel.mesh import make_mesh
from pipelinedp_tpu.runtime import pipeline as rt_pipeline
from pipelinedp_tpu.runtime import telemetry as rt_telemetry

import jax.numpy as jnp


def _stream(n=3000, n_users=250, n_parts=30, seed=5):
    rng = np.random.default_rng(seed)
    pids = np.char.add("u", rng.integers(0, n_users, n).astype(str))
    pks = np.char.add("p", rng.integers(0, n_parts, n).astype(str))
    vals = rng.integers(0, 10, n).astype(np.float64)
    return pids, pks, vals


def _chunks(pids, pks, vals, chunk=500):
    n = len(pids)
    return [(pids[i:i + chunk], pks[i:i + chunk], vals[i:i + chunk])
            for i in range(0, n, chunk)]


def _padded(encoded):
    return tuple(np.asarray(c) for c in executor.pad_rows(encoded))


def _assert_kernel_input_parity(host, dev):
    """Both encodings must feed the fused kernel bit-identical arrays."""
    hp = _padded(host)
    dp = _padded(dev)
    assert hp[0].shape == dp[0].shape
    for h, d, name in zip(hp, dp, ("pid", "pk", "values", "valid")):
        assert np.array_equal(h, d), f"{name} kernel inputs diverged"
    assert host.n_privacy_ids == dev.n_privacy_ids
    assert len(host.partition_vocab) == len(dev.partition_vocab)


# ---------------------------------------------------------------------------
# Host hash
# ---------------------------------------------------------------------------


def _fnv_per_column(raw):
    """The string hash in its plainest form: FNV-1a over EVERY column of
    the fixed-width character matrix, a zero code unit leaving the hash
    as it was — the reference the ragged form must equal."""
    n = len(raw)
    unit = np.uint32 if raw.dtype.kind == "U" else np.uint8
    mat = np.ascontiguousarray(raw).view(unit).reshape(n, -1)
    h0 = np.full(n, ingest._FNV_OFFSETS[0])
    h1 = np.full(n, ingest._FNV_OFFSETS[1])
    for j in range(mat.shape[1]):
        col = mat[:, j].astype(np.uint64)
        live = col != 0
        step0 = (h0 ^ (col + np.uint64(0x9E3779B9 * (j + 1)))) * \
            ingest._FNV_PRIME
        step1 = (h1 ^ (col + np.uint64(0xC2B2AE35 * (j + 2)))) * \
            ingest._FNV_PRIME
        h0 = np.where(live, step0, h0)
        h1 = np.where(live, step1, h1)
    return ingest._splitmix64(h0, 0), ingest._splitmix64(h1, 1)


class TestHashKeyColumn:

    def test_deterministic_and_lane_independent(self):
        raw = np.array(["a", "b", "a", "c"])
        h0 = ingest.hash_key_column(raw)
        h1 = ingest.hash_key_column(raw, lane=1)
        assert np.array_equal(h0, ingest.hash_key_column(raw))
        assert h0.dtype == np.uint64
        assert h0[0] == h0[2] and h1[0] == h1[2]
        assert not np.array_equal(h0, h1)

    def test_sentinel_hash_is_unreachable(self):
        h = ingest.hash_key_column(np.arange(1000))
        assert not (h == np.uint64(device_encode.HASH_SENTINEL)).any()

    def test_numeric_key_identity_matches_host_equality(self):
        # 3 (int) and 3.0 (float) are one key to the host encoder.
        assert ingest.hash_key_column(np.array([3]))[0] == \
            ingest.hash_key_column(np.array([3.0]))[0]
        assert ingest.hash_key_column(np.array([-0.0]))[0] == \
            ingest.hash_key_column(np.array([0.0]))[0]

    def test_nan_keys_share_one_hash(self):
        h = ingest.hash_key_column(
            np.array([float("nan"), np.nan, 1.0]))
        assert h[0] == h[1] and h[0] != h[2]

    def test_mixed_object_int_and_str_do_not_merge(self):
        # pandas hash_array silently stringifies mixed arrays; the
        # gated route must keep int 1 and "1" distinct keys.
        raw = np.empty(2, object)
        raw[0], raw[1] = 1, "1"
        h = ingest.hash_key_column(raw)
        assert h[0] != h[1]

    def test_hash_is_array_width_invariant(self):
        # The same key must hash identically whatever fixed width its
        # chunk's array carries (chunks of differing '<U_' widths are
        # one vocabulary to the host encoder).
        a = np.asarray(["ab", "c"], dtype="<U2")
        b = np.asarray(["ab", "c"], dtype="<U9")
        assert np.array_equal(ingest.hash_key_column(a),
                              ingest.hash_key_column(b))
        assert np.array_equal(ingest.hash_key_column(a, 1),
                              ingest.hash_key_column(b, 1))
        # And character ORDER still matters.
        assert ingest.hash_key_column(np.asarray(["ab"]))[0] != \
            ingest.hash_key_column(np.asarray(["ba"]))[0]

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_string_hash_is_the_per_column_fnv(self, seed):
        """The ragged hash (keys longest first, a position steps only the
        keys longer than it) gives what the loop over every column of
        the fixed-width matrix gave, on 'U', 'S' and object columns of
        str: interior NULs, empty keys, astral code points, lone
        surrogates, widths far past the longest key."""
        rng = np.random.default_rng(seed)
        alphabet = list("abcxyz \x00\u00e9\u6f22\U0001f600\ud800")
        keys = ["".join(rng.choice(alphabet, int(rng.integers(0, 40))))
                for _ in range(300)]
        objects = np.array(keys + [None], dtype=object)[:-1]
        fixed = objects.astype(np.str_)
        want = _fnv_per_column(fixed)
        for column in (fixed, fixed.astype("<U90"), objects):
            got = ingest.hash_key_column_pair(column)
            top = np.uint64((1 << 64) - 2)
            assert all(np.array_equal(g, np.minimum(w, top))
                       for g, w in zip(got, want))
        as_bytes = np.array([k.encode("utf-8", "surrogatepass")
                             for k in keys], dtype="S")
        assert all(np.array_equal(g, w) for g, w in zip(
            ingest._vector_hash_fixed_width(as_bytes),
            _fnv_per_column(as_bytes)))

    def test_composite_tuple_keys_stable(self):
        raw = np.empty(3, object)
        raw[0], raw[1], raw[2] = (1, "a"), (1, "a"), (2, "b")
        h = ingest.hash_key_column(raw)
        assert h[0] == h[1] and h[0] != h[2]

    def test_no_pandas_fallback_consistent(self, monkeypatch):
        raw_num = np.arange(50) % 7
        raw_str = np.char.add("k", (np.arange(50) % 9).astype(str))
        monkeypatch.setattr(ingest, "_pd", None)
        for raw in (raw_num, raw_str):
            h = ingest.hash_key_column(raw)
            assert np.array_equal(h, ingest.hash_key_column(raw))
            codes, n = device_encode.factorize_codes(
                jnp.asarray(device_encode.pack_hash_rows(h)))
            ref, uni = columnar.factorize(raw)
            assert int(n) == len(uni)


# ---------------------------------------------------------------------------
# Device factorize kernels
# ---------------------------------------------------------------------------


class TestFactorizeCodes:

    def test_first_occurrence_codes_match_host_factorize(self):
        rng = np.random.default_rng(3)
        raw = rng.integers(0, 97, 800)
        h = ingest.hash_key_column(raw)
        codes, n = device_encode.factorize_codes(
            jnp.asarray(device_encode.pack_hash_rows(h)))
        ref, uniques = columnar.factorize(raw)
        assert np.array_equal(np.asarray(codes), ref)
        assert int(n) == len(uniques)

    def test_sentinel_rows_code_to_minus_one(self):
        h = ingest.hash_key_column(np.array([7, 8, 7]))
        packed = device_encode.pack_hash_rows(h)
        packed = np.concatenate(
            [packed,
             np.full((3, 3), device_encode._U32_MAX, np.uint32)])
        codes, n = device_encode.factorize_codes(jnp.asarray(packed))
        assert np.array_equal(np.asarray(codes), [0, 1, 0, -1, -1, -1])
        assert int(n) == 2

    def test_invalid_rows_keep_vocabulary_slots(self):
        # An invalid (nonfinite-dropped) row's key still claims its
        # first-occurrence slot — codes after it must not shift.
        h = ingest.hash_key_column(np.array(["a", "b", "c", "b"]))
        valid = np.array([True, False, True, True])
        codes, n = device_encode.factorize_codes(
            jnp.asarray(device_encode.pack_hash_rows(h, valid)))
        assert np.array_equal(np.asarray(codes), [0, -1, 2, 1])
        assert int(n) == 3

    @pytest.mark.parametrize("n_devices", [1, 4, 8])
    def test_mesh_factorize_matches_single_device(self, n_devices):
        rng = np.random.default_rng(11)
        raw = rng.integers(0, 61, 512)
        h = device_encode.pack_hash_rows(ingest.hash_key_column(raw))
        mesh = make_mesh(n_devices=n_devices)
        codes, n = device_encode.mesh_factorize_codes(
            mesh, jnp.asarray(h))
        ref, uniques = columnar.factorize(raw)
        assert np.array_equal(np.asarray(codes), ref)
        assert n == len(uniques)


class TestMergeHashUniques:

    def test_dedupe_and_first_positions(self):
        h1 = [np.array([5, 9], np.uint64), np.array([9, 2], np.uint64)]
        h2 = [np.array([50, 90], np.uint64), np.array([90, 20], np.uint64)]
        keys = [np.array(["a", "b"], object), np.array(["b", "c"], object)]
        pos = [np.array([0, 1], np.int64), np.array([3, 2], np.int64)]
        s1, k, n, p = device_encode.merge_hash_uniques(h1, h2, keys, pos)
        assert list(s1) == [2, 5, 9]
        assert list(k) == ["c", "a", "b"]
        assert n == 3
        assert list(p) == [2, 0, 1]

    @pytest.mark.parametrize("seed", range(6))
    def test_one_sort_equals_the_lexsort(self, seed):
        """Chunk uniques in stream order, merged by the one stable sort
        of h1, give the table a lexsort of (h1, h2, pos) gives: each
        hash once, with its first key and position."""
        rng = np.random.default_rng(seed)
        n = 2000
        h1 = rng.integers(0, 300, n).astype(np.uint64) * np.uint64(2**40)
        h2 = h1 ^ np.uint64(12345)
        raw = np.array([f"k{int(h)}-{i}" for i, h in enumerate(h1)],
                       dtype=object)
        bounds = [0, 400, 900, 1500, n]
        chunks = [(ingest._hash_uniques(h1[a:b], h2[a:b], raw[a:b]), a)
                  for a, b in zip(bounds, bounds[1:])]
        parts = ([c[0] for c, _ in chunks], [c[1] for c, _ in chunks],
                 [c[2] for c, _ in chunks], [c[3] + a for c, a in chunks])
        got = device_encode.merge_hash_uniques(*parts)
        c1, c2, ck, cp = (np.concatenate(p) for p in parts)
        order = np.lexsort((cp, c2, c1))
        new = np.r_[True, c1[order][1:] != c1[order][:-1]]
        assert got[2] == int(new.sum()) == len(np.unique(h1))
        for g, w in ((got[0], c1), (got[1], ck), (got[3], cp)):
            assert np.array_equal(g, w[order][new])
        # The first occurrence: the key of each hash's lowest row.
        first = {}
        for i, h in enumerate(h1):
            first.setdefault(int(h), raw[i])
        assert list(got[1]) == [first[int(h)] for h in got[0]]

    def test_collision_inside_a_chunk_raises(self):
        h1 = np.array([5, 5, 7], np.uint64)
        h2 = np.array([1, 2, 3], np.uint64)
        u = ingest._hash_uniques(h1, h2, None)
        assert list(u[0]) == [5, 5, 7] and list(u[1]) == [1, 2, 3]
        with pytest.raises(device_encode.HashCollisionError,
                           match="primary hash 5"):
            device_encode.merge_hash_uniques([u[0]], [u[1]])
        # The two keys' rows interleaved: a pair is kept more than once,
        # and the merge still names the collision.
        u = ingest._hash_uniques(np.array([5, 5, 5, 7], np.uint64),
                                 np.array([1, 2, 1, 3], np.uint64), None)
        assert list(u[1]) == [1, 2, 1, 3]
        with pytest.raises(device_encode.HashCollisionError,
                           match="primary hash 5"):
            device_encode.merge_hash_uniques([u[0]], [u[1]])

    def test_collision_raises(self):
        h1 = [np.array([5], np.uint64), np.array([5], np.uint64)]
        h2 = [np.array([1], np.uint64), np.array([2], np.uint64)]
        with pytest.raises(device_encode.HashCollisionError,
                           match="primary hash 5"):
            device_encode.merge_hash_uniques(h1, h2)


# ---------------------------------------------------------------------------
# Stream-encode parity
# ---------------------------------------------------------------------------


@pytest.fixture(params=["lookup", "factorize"])
def code_path(request, monkeypatch):
    """Forces the hash route's in-jit code assignment. The CPU backend
    picks the lookup kernel by itself, so without this the tests would
    never run the accelerator's order: factorize dispatched before the
    host unique merge, its counts read after it."""
    monkeypatch.setattr(device_encode, "prefers_lookup_codes",
                        lambda: request.param == "lookup")
    return request.param


class TestStreamEncodeParity:

    def test_kernel_inputs_bit_identical(self, code_path):
        pids, pks, vals = _stream()
        host = ingest.stream_encode_columns(_chunks(pids, pks, vals))
        dev = ingest.stream_encode_columns(_chunks(pids, pks, vals),
                                           encode_mode="hash_device")
        _assert_kernel_input_parity(host, dev)
        assert list(dev.partition_vocab) == list(host.partition_vocab)

    @pytest.mark.parametrize("threads,depth", [(1, 1), (2, 8)])
    def test_pipelined_hash_encode_identical(self, threads, depth,
                                            code_path):
        pids, pks, vals = _stream()
        serial = ingest.stream_encode_columns(
            _chunks(pids, pks, vals), encode_mode="hash_device")
        piped = ingest.stream_encode_columns(
            _chunks(pids, pks, vals), encode_mode="hash_device",
            encode_threads=threads, pipeline_depth=depth)
        for a, b in zip(_padded(serial), _padded(piped)):
            assert np.array_equal(a, b)

    def test_nonfinite_drop_keeps_code_alignment(self, code_path):
        pids, pks, vals = _stream()
        vals = vals.copy()
        vals[2] = np.nan  # early drop: later codes must not shift
        vals[100] = np.inf
        host = ingest.stream_encode_columns(
            _chunks(pids, pks, vals), nonfinite="drop")
        dev = ingest.stream_encode_columns(
            _chunks(pids, pks, vals), nonfinite="drop",
            encode_mode="hash_device")
        _assert_kernel_input_parity(host, dev)

    def test_public_partitions(self, code_path):
        pids, pks, vals = _stream()
        public = [f"p{i}" for i in range(20)]
        host = ingest.stream_encode_columns(
            _chunks(pids, pks, vals), public_partitions=public)
        dev = ingest.stream_encode_columns(
            _chunks(pids, pks, vals), public_partitions=public,
            encode_mode="hash_device")
        _assert_kernel_input_parity(host, dev)
        assert dev.public_encoded and \
            list(dev.partition_vocab) == public

    def test_empty_stream(self, code_path):
        enc = ingest.stream_encode_columns([],
                                           encode_mode="hash_device")
        assert enc.n_rows == 0 and len(enc.partition_vocab) == 0
        assert enc.n_privacy_ids == 0

    def test_hash_vocab_decodes_lazily(self, code_path):
        pids, pks, vals = _stream()
        host = ingest.stream_encode_columns(_chunks(pids, pks, vals))
        dev = ingest.stream_encode_columns(_chunks(pids, pks, vals),
                                           encode_mode="hash_device")
        vocab = dev.partition_vocab
        ref = list(host.partition_vocab)
        vocab.prefetch([3, 7])
        assert vocab._cache and len(vocab._cache) == 2
        assert vocab[3] == ref[3] and vocab[7] == ref[7]
        # Unprefetched access degrades to one whole-table materialize.
        assert vocab[11] == ref[11]
        assert list(vocab) == ref
        with pytest.raises(IndexError):
            vocab[len(ref)]

    def test_mesh_encode_local_shard(self):
        pids, pks, vals = _stream(n=1600)
        mesh = make_mesh(n_devices=4)
        enc = ingest.encode_local_shard_to_mesh(
            _chunks(pids, pks, vals), mesh, encode_mode="hash_device")
        serial = ingest.stream_encode_columns(_chunks(pids, pks, vals))
        valid = np.asarray(enc.pk) >= 0
        assert valid.sum() == len(pids)
        assert np.array_equal(np.asarray(enc.pid)[valid],
                              np.asarray(serial.pid))
        assert np.array_equal(np.asarray(enc.pk)[valid],
                              np.asarray(serial.pk))
        assert list(enc.partition_vocab) == list(serial.partition_vocab)
        assert enc.n_privacy_ids == serial.n_privacy_ids

    def test_simulated_pod_hash_exchange(self):
        import pickle
        pids, pks, vals = _stream(n=1600)
        n = len(pids)
        half = n // 2
        payloads = {}
        for p, (lo, hi) in enumerate([(0, half), (half, n)]):
            shard = ingest._hash_encode_shard(
                iter(_chunks(pids[lo:hi], pks[lo:hi], vals[lo:hi])),
                None, "error")
            payloads[p] = pickle.dumps(shard.meta)
        mesh = make_mesh(n_devices=4)
        enc0 = ingest.encode_local_shard_to_mesh(
            _chunks(pids[:half], pks[:half], vals[:half]), mesh,
            exchange=lambda payload: [payloads[0], payloads[1]],
            encode_mode="hash_device")
        serial = ingest.stream_encode_columns(_chunks(pids, pks, vals))
        valid = np.asarray(enc0.pk) >= 0
        # Process 0 uploaded its half with GLOBAL codes and the GLOBAL
        # vocabulary (keys first seen on the simulated process 1
        # decode through the exchanged metas).
        assert np.array_equal(np.asarray(enc0.pid)[valid],
                              np.asarray(serial.pid)[:half])
        assert np.array_equal(np.asarray(enc0.pk)[valid],
                              np.asarray(serial.pk)[:half])
        assert list(enc0.partition_vocab) == \
            list(serial.partition_vocab)
        assert enc0.n_privacy_ids == serial.n_privacy_ids


# ---------------------------------------------------------------------------
# Collision safety (the crafted-collision satellite)
# ---------------------------------------------------------------------------


def _collide_keys(monkeypatch, victim="p1", target="p0"):
    """Monkeypatches the PRIMARY hash lane so `victim` collides with
    `target` while the secondary lane still tells them apart — the
    situation the two-lane detector exists for."""
    orig = ingest.hash_key_column_pair

    def colliding(raw):
        h0, h1 = orig(raw)
        arr = columnar._as_key_array(raw)
        h0 = h0.copy()
        h0[arr == victim] = orig(np.asarray([target], object))[0][0]
        return h0, h1

    monkeypatch.setattr(ingest, "hash_key_column_pair", colliding)


def _record_calls(monkeypatch):
    """Wraps the hash route's four steps of code assignment so a test
    reads the order the route called them in: "factorize" and "lookup"
    (the two in-jit kernels), "merge" (the host unique merge) and
    "fetch" (the device->host read of the distinct counts)."""
    from pipelinedp_tpu.parallel import mesh as mesh_lib
    calls = []

    def wrap(owner, attr, label):
        fn = getattr(owner, attr)

        def recorded(*args, **kwargs):
            calls.append(label)
            return fn(*args, **kwargs)

        monkeypatch.setattr(owner, attr, recorded)

    wrap(device_encode, "factorize_codes", "factorize")
    wrap(device_encode, "lookup_codes", "lookup")
    wrap(device_encode, "merge_hash_uniques", "merge")
    wrap(mesh_lib, "host_fetch", "fetch")
    return calls


class TestCollisionSafety:

    def test_detector_trips_counts_and_falls_back_bit_identically(
            self, monkeypatch, code_path):
        pids, pks, vals = _stream()
        host = ingest.stream_encode_columns(_chunks(pids, pks, vals))
        _collide_keys(monkeypatch)
        before = rt_telemetry.snapshot()
        enc = ingest.stream_encode_columns(_chunks(pids, pks, vals),
                                           encode_mode="hash_device")
        assert rt_telemetry.delta(before).get(
            "ingest_hash_collisions", 0) == 1
        # The fallback IS the exact host encoder: bit-identical columns
        # and the identical (eagerly decoded) vocabulary.
        assert np.array_equal(np.asarray(enc.pid), np.asarray(host.pid))
        assert np.array_equal(np.asarray(enc.pk), np.asarray(host.pk))
        assert list(enc.partition_vocab) == list(host.partition_vocab)

    def test_one_shot_iterator_raises_actionably(self, monkeypatch,
                                                code_path):
        pids, pks, vals = _stream()
        _collide_keys(monkeypatch)
        calls = _record_calls(monkeypatch)
        before = rt_telemetry.snapshot()
        with pytest.raises(device_encode.HashCollisionError,
                           match="one-shot iterator"):
            ingest.stream_encode_columns(
                iter(_chunks(pids, pks, vals)),
                encode_mode="hash_device")
        assert rt_telemetry.delta(before).get(
            "ingest_hash_collisions", 0) == 1
        # No device code was used: a factorize dispatched before the
        # merge is dropped with its counts never read.
        assert "merge" in calls
        assert "fetch" not in calls and "lookup" not in calls

    def test_privacy_id_collision_also_trips(self, monkeypatch,
                                             code_path):
        pids, pks, vals = _stream()
        _collide_keys(monkeypatch, victim="u1", target="u2")
        before = rt_telemetry.snapshot()
        enc = ingest.stream_encode_columns(_chunks(pids, pks, vals),
                                           encode_mode="hash_device")
        assert rt_telemetry.delta(before).get(
            "ingest_hash_collisions", 0) == 1
        host = ingest.stream_encode_columns(_chunks(pids, pks, vals))
        assert np.array_equal(np.asarray(enc.pid), np.asarray(host.pid))

    def test_pod_path_falls_back_too(self, monkeypatch):
        pids, pks, vals = _stream(n=1200)
        mesh = make_mesh(n_devices=4)
        host = ingest.encode_local_shard_to_mesh(
            _chunks(pids, pks, vals), mesh)
        _collide_keys(monkeypatch)
        before = rt_telemetry.snapshot()
        enc = ingest.encode_local_shard_to_mesh(
            _chunks(pids, pks, vals), mesh, encode_mode="hash_device")
        assert rt_telemetry.delta(before).get(
            "ingest_hash_collisions", 0) == 1
        assert np.array_equal(np.asarray(enc.pid), np.asarray(host.pid))
        assert np.array_equal(np.asarray(enc.pk), np.asarray(host.pk))
        assert list(enc.partition_vocab) == list(host.partition_vocab)


class TestFinalizeOrder:
    """The hash route's end on an accelerator: both factorizes are
    queued on the device before the host merges the chunk uniques, and
    the counts are read only after the merge. The lookup kernel needs
    the merged table, so there the merge comes first."""

    @pytest.mark.parametrize("public,expected", [
        (False, {"factorize": ["factorize", "factorize", "merge", "merge",
                               "fetch"],
                 "lookup": ["merge", "merge", "lookup", "lookup"]}),
        (True, {"factorize": ["factorize", "merge", "fetch"],
                "lookup": ["merge", "lookup"]}),
    ])
    def test_call_order(self, monkeypatch, code_path, public, expected):
        pids, pks, vals = _stream()
        calls = _record_calls(monkeypatch)
        ingest.stream_encode_columns(
            _chunks(pids, pks, vals), encode_mode="hash_device",
            public_partitions=([f"p{i}" for i in range(20)]
                               if public else None))
        assert calls == expected[code_path]

    def test_merge_and_codes_spans_are_siblings(self, code_path):
        from pipelinedp_tpu.runtime import trace as rt_trace
        pids, pks, vals = _stream()
        with rt_trace.scoped():
            seen = len(rt_trace.to_trace_events()["traceEvents"])
            ingest.stream_encode_columns(_chunks(pids, pks, vals),
                                         encode_mode="hash_device")
            events = rt_trace.to_trace_events()["traceEvents"][seen:]
        spans = [e for e in events if e["ph"] == "X"]
        merge = [e for e in spans if e["name"] == "ingest.unique_merge"]
        codes = [e for e in spans if e["name"] == "ingest.device_codes"]
        assert len(merge) == 1
        assert len(codes) == (2 if code_path == "factorize" else 1)
        ids = {e["args"]["id"] for e in merge + codes}
        assert all(e["args"].get("parent") not in ids
                   for e in merge + codes)
        # Both hang off the one ingest span, in the route's order.
        (ingest_span,) = [e for e in spans if e["name"] == "ingest"]
        assert {e["args"]["parent"] for e in merge + codes} == \
            {ingest_span["args"]["id"]}
        starts = sorted(merge + codes, key=lambda e: e["ts"])
        order = [e["name"] for e in starts]
        if code_path == "factorize":
            assert order == ["ingest.device_codes", "ingest.unique_merge",
                             "ingest.device_codes"]
        else:
            assert order == ["ingest.unique_merge", "ingest.device_codes"]


# ---------------------------------------------------------------------------
# Engine + all four meshed drivers
# ---------------------------------------------------------------------------


def _agg_params():
    return pdp.AggregateParams(metrics=[pdp.Metrics.COUNT,
                                        pdp.Metrics.SUM],
                               noise_kind=pdp.NoiseKind.LAPLACE,
                               max_partitions_contributed=4,
                               max_contributions_per_partition=8,
                               min_value=0.0,
                               max_value=9.0)


_EXTRACTORS = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                 partition_extractor=lambda r: r[1],
                                 value_extractor=lambda r: float(r[2]))


class TestEngineParity:
    """Hash-device == host releases, decoded and order-normalized, for
    the engine over every driver route, with equal ledger counts."""

    def _aggregate(self, mode, mesh=None, depth=None, **backend_kw):
        pids, pks, vals = _stream()
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1e6,
                                        total_delta=1e-5)
        backend = pdp.TPUBackend(noise_seed=29, mesh=mesh,
                                 encode_mode=mode, encode_threads=2,
                                 pipeline_depth=depth, **backend_kw)
        engine = pdp.DPEngine(acc, backend)
        result = engine.aggregate(
            pdp.ChunkSource(_chunks(pids, pks, vals)), _agg_params(),
            _EXTRACTORS)
        acc.compute_budgets()
        return dict(result), acc.mechanism_count

    def _select(self, mode, mesh=None, **backend_kw):
        pids, pks, vals = _stream()
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1e6,
                                        total_delta=1e-5)
        backend = pdp.TPUBackend(noise_seed=29, mesh=mesh,
                                 encode_mode=mode, **backend_kw)
        engine = pdp.DPEngine(acc, backend)
        result = engine.select_partitions(
            pdp.ChunkSource(_chunks(pids, pks, vals)),
            pdp.SelectPartitionsParams(max_partitions_contributed=4),
            _EXTRACTORS)
        acc.compute_budgets()
        return sorted(result), acc.mechanism_count

    @pytest.mark.parametrize("n_devices,depth", [(1, 1), (4, 8), (8, 8)])
    def test_dense_aggregate_parity(self, n_devices, depth):
        mesh = make_mesh(n_devices=n_devices)
        host, m_host = self._aggregate("host", mesh, depth)
        with reshard.forbid_row_fetches():
            dev, m_dev = self._aggregate("hash_device", mesh, depth)
        assert m_host == m_dev
        assert host and set(host) == set(dev)
        for k in host:
            assert host[k].count == dev[k].count
            assert host[k].sum == dev[k].sum

    @pytest.mark.parametrize("n_devices", [4])
    def test_blocked_aggregate_parity(self, n_devices):
        mesh = make_mesh(n_devices=n_devices)
        kw = dict(large_partition_threshold=16)
        host, m_host = self._aggregate("host", mesh, None, **kw)
        dev, m_dev = self._aggregate("hash_device", mesh, None, **kw)
        assert m_host == m_dev
        assert host and set(host) == set(dev)
        for k in host:
            assert host[k].count == dev[k].count
            assert host[k].sum == dev[k].sum

    @pytest.mark.parametrize("n_devices", [1, 4, 8])
    def test_dense_select_parity(self, n_devices):
        mesh = make_mesh(n_devices=n_devices)
        host, m_host = self._select("host", mesh)
        dev, m_dev = self._select("hash_device", mesh)
        assert m_host == m_dev and host and host == dev

    def test_blocked_select_parity(self):
        mesh = make_mesh(n_devices=4)
        kw = dict(large_partition_threshold=16)
        host, m_host = self._select("host", mesh, **kw)
        with reshard.forbid_row_fetches():
            dev, m_dev = self._select("hash_device", mesh, **kw)
        assert m_host == m_dev and host and host == dev

    def test_unsharded_engine_parity(self):
        host, m_host = self._aggregate("host")
        dev, m_dev = self._aggregate("hash_device")
        assert m_host == m_dev and host and set(host) == set(dev)
        for k in host:
            assert host[k].count == dev[k].count
            assert host[k].sum == dev[k].sum

    def test_chunk_source_overrides_backend_mode(self):
        pids, pks, vals = _stream(n=800)
        acc = pdp.NaiveBudgetAccountant(total_epsilon=1e6,
                                        total_delta=1e-5)
        engine = pdp.DPEngine(
            acc, pdp.TPUBackend(noise_seed=29, encode_mode="host"))
        before = rt_telemetry.snapshot()
        result = engine.aggregate(
            pdp.ChunkSource(_chunks(pids, pks, vals),
                            encode_mode="hash_device"),
            _agg_params(), _EXTRACTORS)
        acc.compute_budgets()
        assert dict(result)
        assert rt_telemetry.delta(before).get(
            "pipeline_device_encode_chunks", 0) > 0


# ---------------------------------------------------------------------------
# Knob validation + accumulator fills
# ---------------------------------------------------------------------------


class TestEncodeModeKnob:

    def test_validator(self):
        input_validators.validate_encode_mode("host", "t")
        input_validators.validate_encode_mode("hash_device", "t")
        for bad in ("device", "", None, 7, "HASH_DEVICE"):
            with pytest.raises(ValueError, match="encode_mode"):
                input_validators.validate_encode_mode(bad, "t")

    def test_backend_validates(self):
        with pytest.raises(ValueError, match="encode_mode"):
            pdp.TPUBackend(encode_mode="bogus")
        assert pdp.TPUBackend(encode_mode="hash_device").encode_mode == \
            "hash_device"

    def test_chunk_source_validates(self):
        with pytest.raises(ValueError, match="encode_mode"):
            pdp.ChunkSource([], encode_mode="bogus")
        assert pdp.ChunkSource([]).encode_mode is None

    def test_for_job_view_inherits_encode_mode(self):
        backend = pdp.TPUBackend(encode_mode="hash_device")
        assert backend.for_job("j").encode_mode == "hash_device"

    def test_stream_encode_rejects_bad_mode(self):
        with pytest.raises(ValueError, match="encode_mode"):
            ingest.stream_encode_columns([], encode_mode="bogus")


class TestAccumulatorFills:

    @pytest.mark.parametrize("donate", [False, True])
    def test_custom_fills_pad_the_tail(self, donate):
        sent = int(device_encode._U32_MAX)
        acc = rt_pipeline.DeviceRowAccumulator(
            donate=donate, fills=(sent, sent, 0))
        h = device_encode.pack_hash_rows(
            ingest.hash_key_column(np.arange(5)))
        k = device_encode.pack_hash_rows(
            ingest.hash_key_column(np.arange(5) % 2))
        v = np.arange(5.0)
        if donate:
            cap = executor.row_bucket(5)
            h, k, v = ingest._pad_chunk_rows(h, k, v, cap,
                                             (sent, sent, 0))
        acc.append(h, k, v, 5)
        bufs = acc.finalize()
        assert bufs[0].shape[0] == executor.row_bucket(5)
        tail = np.asarray(bufs[0])[5:]
        assert (tail == sent).all()

    def test_wide_rows_donated_equal_staged(self):
        """(n, 3) hash rows cross flat and take their shape on the device
        (the first chunk, and every append after it): the donated buffers
        equal the staged ones."""
        sent = int(device_encode._U32_MAX)
        fills = (sent, sent, 0)
        rng = np.random.default_rng(4)
        out = {}
        for donate in (False, True):
            acc = rt_pipeline.DeviceRowAccumulator(donate=donate,
                                                   fills=fills)
            for n in (5, 11, 3):
                h = device_encode.pack_hash_rows(ingest.hash_key_column(
                    rng.integers(0, 9, n)))
                k = device_encode.pack_hash_rows(ingest.hash_key_column(
                    rng.integers(0, 4, n)))
                v = rng.random(n)
                if donate:
                    h, k, v = ingest._pad_chunk_rows(
                        h, k, v, executor.row_bucket(n), fills)
                acc.append(h, k, v, n)
            out[donate] = [np.asarray(b) for b in acc.finalize()]
            rng = np.random.default_rng(4)
        for staged, donated in zip(out[False], out[True]):
            assert staged.shape == donated.shape
            assert np.array_equal(staged, donated)
