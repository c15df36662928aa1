"""Chunked, overlapped host->device ingest.

The reference delegates unbounded input to Beam/Spark IO
(pipeline_dp/pipeline_backend.py:223-374); the TPU build's equivalent is a
streaming host pipeline: parse -> factorize -> upload proceeds chunk by
chunk, and because device copies dispatch asynchronously, the upload of
chunk i overlaps the host parse/factorization of chunk i+1. On the 1-core
bench host that overlap — not host parallelism — is what moves end-to-end
time toward max(host encode, device transfer) instead of their sum.

With encode_threads >= 1 the same entry point routes through the
device-resident streaming executor (runtime/pipeline.py): the heavy,
order-independent half of vocabulary encoding (chunk_factorize) runs per
chunk on a host thread pool feeding a bounded staging queue, the
sequential half (ChunkedVocabEncoder.merge) stitches the global
vocabulary in stream order on the consumer, and rows accumulate into
persistent, buffer-donated device buffers (DeviceRowAccumulator) sized
to the executor.pad_rows power-of-two buckets — so the pipelined
encoding is bit-identical to the serial one, down to the padded kernel
input arrays. The merge looks non-negative integer keys of bounded range
up in one persistent code table indexed by the key, and any other key in
a pandas Index.

The result is a device-resident EncodedData whose columns are jax arrays;
the executor pads it on device (executor.pad_rows) and the engine accepts
it directly in place of a row collection (columnar.encode passthrough), so

    encoded = ingest.stream_encode_columns(chunk_iter)
    result = engine.aggregate(encoded, params, extractors)

is the bulk-file counterpart of handing the engine Python rows.

Contribution bounding is global per privacy id, so the fused kernel still
runs over the full device-resident dataset — streaming here bounds HOST
memory and overlaps transfer, not device memory (the blocked large-P path
owns that axis).
"""

import dataclasses
import logging
from collections import Counter as collections_counter
from typing import (Any, Callable, Iterable, List, Optional, Sequence,
                    Tuple)

import numpy as np

from pipelinedp_tpu import columnar

try:
    import pandas as _pd
except ImportError:  # pragma: no cover - pandas is in the standard image
    _pd = None

# Shared NaN canonicalization (columnar.factorize's dict fallback uses the
# same sentinel, so spilled state and chunk factorization agree).
_NAN_KEY = columnar._NAN_KEY
_dict_key = columnar._canonical_key


def _kind_group(dtype) -> str:
    """Coarse dtype family for the sorted-vocab compatibility check."""
    if dtype.kind in "biuf":
        return "num"
    if dtype.kind in "SU":
        return "str"
    return "obj"


def chunk_factorize(raw) -> Tuple[np.ndarray, np.ndarray]:
    """Chunk-local factorization: (int32 codes, uniques in
    first-occurrence order).

    The order-independent, C-speed half of ChunkedVocabEncoder.encode —
    pure and thread-safe, so the streaming executor
    (runtime/pipeline.py) can run it per chunk on the host thread pool
    while the sequential half (``ChunkedVocabEncoder.merge``: a lookup
    of the chunk's uniques in the integer code table, or in a pandas
    Index for other keys, and one gather of the chunk's codes) stitches
    the global vocabulary in stream order on the consumer.
    """
    raw = columnar._as_key_array(raw)
    if _pd is not None:
        codes, uniques = _pd.factorize(raw, use_na_sentinel=False)
        return codes.astype(np.int32), np.asarray(uniques)
    codes, uniques = columnar.factorize(raw)
    uniques = np.asarray(uniques)
    if columnar._pd is not None:
        # columnar.factorize took its pandas branch, which already
        # yields first-occurrence order — the normalization below would
        # redo a full np.unique + argsort per chunk for nothing.
        return codes.astype(np.int32), uniques
    # Normalize the chunk's uniques to first-occurrence order
    # (factorize's np.unique branch yields sorted order) so new global
    # codes are assigned exactly as one factorize over the concatenation
    # would.
    if len(uniques) > 1:
        _, first_idx = np.unique(codes, return_index=True)
        perm = np.argsort(first_idx)
        if not np.array_equal(perm, np.arange(len(perm))):
            inv = np.empty_like(perm)
            inv[perm] = np.arange(len(perm))
            codes = inv[codes].astype(np.int32)
            uniques = uniques[perm]
    return codes.astype(np.int32), uniques


# The code table of ChunkedVocabEncoder: integer keys in [0, limit) are
# looked up by address, where limit is _CODE_TABLE_ROWS_MULTIPLE times the
# rows merged so far (at least _CODE_TABLE_MIN_KEYS, at most
# _CODE_TABLE_MAX_KEYS). The int32 table doubles as it grows, so it holds
# fewer than 16 entries (64 bytes) a row merged, and at most 256 MiB.
_CODE_TABLE_ROWS_MULTIPLE = 8
_CODE_TABLE_MIN_KEYS = 1 << 16
_CODE_TABLE_MAX_KEYS = 1 << 26


class ChunkedVocabEncoder:
    """Incremental first-occurrence vocabulary encoding across chunks.

    Feeding chunks in order yields exactly the codes columnar.factorize
    would assign to the concatenation. With pandas, integer keys go to a
    direct-address code table and every other key to a pandas Index; the
    vectorized numpy fallback and the dict loop serve where pandas is
    missing.

    The code table: while every chunk's uniques are non-negative integers
    (dtype kind i or u) below the limit (_CODE_TABLE_ROWS_MULTIPLE x the
    rows merged so far, at least _CODE_TABLE_MIN_KEYS and at most
    _CODE_TABLE_MAX_KEYS), the encoder keeps an int32 array code_of[key],
    -1 where no code is assigned, and the keys in code order. A chunk's
    uniques look themselves up in it, the unknown ones take the next codes
    in the chunk's first-occurrence order and are written back: a gather
    per chunk, where an Index re-hashes each chunk's uniques and rebuilds
    its hash engine whenever it grows. The first chunk outside that rule
    (a negative or too large key, a float, string or object chunk, or an
    integer dtype whose promotion with the vocabulary's is not an integer)
    builds the Index once from the keys in code order, and the pandas path
    serves from that chunk on. Codes, ``vocabulary`` (values and dtype,
    which follows the Index's promotion) and ``len`` are those the pandas
    path alone gives.

    Without pandas: factorization (C speed) followed by a vectorized remap
    of the chunk's uniques against a sorted copy of the vocabulary
    (searchsorted + insert, O(V + new·log new)), including NaN
    unification (all NaN keys share one code, kept out of the sorted
    vocabulary where comparisons would mis-place it) and cross-chunk dtype
    promotion (a later chunk with a wider string / finer numeric dtype
    widens the stored vocabulary instead of truncating new keys). Only key
    types numpy cannot order fall back to a per-unique dict loop, which
    canonicalizes NaN through the same shared sentinel
    columnar.factorize's last-resort branch uses.
    """

    def __init__(self):
        self._rows = 0  # rows merged so far (sets the code table's limit)
        self._table = None  # int32 code_of[key], -1 where unassigned
        self._table_keys: List[np.ndarray] = []  # new keys, in code order
        self._table_dtype = None  # the dtype the pandas Index would hold
        self._index = None  # pandas Index (fast path)
        self._sorted_vocab = None  # numpy fallback: sorted non-NaN uniques
        self._sorted_codes = None  # global code of each sorted entry
        self._nan_code: Optional[int] = None  # shared code for NaN keys
        # Total codes assigned on the code table or the numpy fallback.
        self._next_code = 0
        self._dict: Optional[dict] = None  # unorderable-key last resort

    def encode(self, raw) -> np.ndarray:
        # _as_key_array inside chunk_factorize: np.asarray first would
        # explode composite (tuple) keys into a 2-D array instead of
        # object elements.
        return self.merge(*chunk_factorize(raw))

    def merge(self, codes: np.ndarray, uniques: np.ndarray) -> np.ndarray:
        """Sequential half of encode(): remaps one chunk's local codes
        (with uniques in first-occurrence order, from chunk_factorize)
        into the global vocabulary. Feeding chunks in stream order keeps
        the global codes identical to a single factorize over the
        concatenation — the pipelined encode calls this on the consumer
        while workers factorize chunks ahead."""
        if _pd is not None:
            self._rows += len(codes)
            if self._index is None:
                if self._fits_code_table(uniques):
                    return self._merge_code_table(codes, uniques)
                if self._table is not None:
                    self._index = _pd.Index(self.vocabulary)
                    self._table, self._table_keys = None, []
            uniques = _pd.Index(uniques)
            if self._index is None:
                self._index = uniques
                return codes.astype(np.int32)
            mapped = self._index.get_indexer(uniques)
            is_new = mapped == -1
            if is_new.any():
                mapped[is_new] = len(self._index) + np.arange(
                    int(is_new.sum()))
                self._index = self._index.append(uniques[is_new])
            return mapped.astype(np.int32)[codes]
        if self._dict is not None:
            return self._remap_dict(codes, uniques)
        try:
            return self._remap_sorted(codes, uniques)
        except TypeError:  # unorderable mixed-type keys
            self._spill_to_dict()
            return self._remap_dict(codes, uniques)

    def _fits_code_table(self, uniques: np.ndarray) -> bool:
        """Whether the code table takes this chunk: the class docstring's
        rule."""
        if uniques.dtype.kind not in "iu" or (
                self._table_dtype is not None and np.promote_types(
                    self._table_dtype, uniques.dtype).kind not in "iu"):
            return False
        if not len(uniques):
            return True
        limit = min(max(_CODE_TABLE_ROWS_MULTIPLE * self._rows,
                        _CODE_TABLE_MIN_KEYS), _CODE_TABLE_MAX_KEYS)
        return int(uniques.min()) >= 0 and int(uniques.max()) < limit

    def _merge_code_table(self, codes: np.ndarray,
                          uniques: np.ndarray) -> np.ndarray:
        from pipelinedp_tpu.runtime import telemetry as rt_telemetry

        if self._table_dtype is None:
            self._table_dtype = uniques.dtype
        top = int(uniques.max()) + 1 if len(uniques) else 0
        size = 0 if self._table is None else len(self._table)
        if self._table is None or top > size:
            grown = np.full(min(max(top, 2 * size), _CODE_TABLE_MAX_KEYS),
                            -1, np.int32)
            if size:
                grown[:size] = self._table
            self._table = grown
        look = self._table[uniques]
        new = np.flatnonzero(look < 0)
        if len(new):
            look[new] = np.arange(self._next_code,
                                  self._next_code + len(new), dtype=np.int32)
            keys = uniques[new]
            self._table[keys] = look[new]
            self._table_dtype = np.promote_types(self._table_dtype,
                                                 keys.dtype)
            self._table_keys.append(keys)
            self._next_code += len(new)
        rt_telemetry.record("vocab_table_merges")
        # take: about twice as fast as indexing by the int32 codes.
        return look.take(codes)

    def _remap_sorted(self, codes: np.ndarray,
                      uniques: np.ndarray) -> np.ndarray:
        """Vectorized remap of chunk uniques (first-occurrence order)
        against the sorted global vocabulary."""
        n_u = len(uniques)
        if self._sorted_vocab is None:
            self._sorted_vocab = np.empty(0, uniques.dtype)
            self._sorted_codes = np.empty(0, np.int64)
        elif len(self._sorted_vocab):
            # Mixed number/string chunks must spill to the dict path
            # (where 1.5 and '1.5' stay distinct keys, matching pandas):
            # numpy would otherwise silently STRINGIFY numbers via dtype
            # promotion instead of raising.
            a = _kind_group(self._sorted_vocab.dtype)
            b = _kind_group(uniques.dtype)
            if "obj" not in (a, b) and a != b:
                raise TypeError(
                    f"cannot mix {a} and {b} keys in the sorted vocab")
        # NaN never matches itself under searchsorted/==, so NaN keys are
        # tracked by a dedicated code and kept out of the sorted array
        # (where they would also corrupt later binary searches). Object
        # arrays get the per-element check: an all-float object chunk
        # compares without raising, so it would NOT spill to the dict path.
        if uniques.dtype.kind == "f":
            is_nan = np.isnan(uniques)
        elif uniques.dtype.kind == "O" and n_u:
            is_nan = np.fromiter(
                (_dict_key(k) is _NAN_KEY for k in uniques), bool, count=n_u)
        else:
            is_nan = np.zeros(n_u, bool)
        nan_idx = np.nonzero(is_nan)[0]
        remap = np.empty(n_u, np.int64)
        known = np.zeros(n_u, bool)
        if len(nan_idx) and self._nan_code is not None:
            known[nan_idx] = True
            remap[nan_idx] = self._nan_code
        reg_idx = np.nonzero(~is_nan)[0]
        u = uniques[reg_idx]
        n_vocab = len(self._sorted_vocab)
        if n_vocab and len(u):
            pos = np.searchsorted(self._sorted_vocab, u)  # may TypeError
            pos_c = np.minimum(pos, n_vocab - 1)
            found = (pos < n_vocab) & (self._sorted_vocab[pos_c] == u)
            known[reg_idx[found]] = True
            remap[reg_idx[found]] = self._sorted_codes[pos_c[found]]
        # New codes in first-occurrence order of the chunk (uniques are
        # already ordered that way) = the order a global factorize would
        # meet them. Duplicate NaN uniques (factorize now unifies NaN on
        # every branch, so this is defensive) alias to one representative.
        assign_new = ~known
        nan_is_new = bool(len(nan_idx)) and self._nan_code is None
        if nan_is_new:
            assign_new[nan_idx[1:]] = False
        new_idx = np.nonzero(assign_new)[0]
        remap[new_idx] = self._next_code + np.arange(len(new_idx))
        new_nan_code = None
        if nan_is_new:
            new_nan_code = int(remap[nan_idx[0]])
            remap[nan_idx] = new_nan_code
        new_reg = new_idx[~is_nan[new_idx]]
        if len(new_reg):
            new_u, new_c = uniques[new_reg], remap[new_reg]
            # Widen first: np.insert would silently cast new keys to the
            # stored dtype (truncating e.g. '<U5' into a '<U2' vocab).
            dt = np.promote_types(self._sorted_vocab.dtype,
                                  new_u.dtype)  # may TypeError
            if dt != new_u.dtype:
                new_u = new_u.astype(dt)
            no = np.argsort(new_u, kind="stable")  # may TypeError
            new_u, new_c = new_u[no], new_c[no]
            vocab = self._sorted_vocab
            if dt != vocab.dtype:
                vocab = vocab.astype(dt)
            ins = np.searchsorted(vocab, new_u)  # may TypeError
            # All TypeError-prone ops are done — commit state (a raise
            # above must leave the encoder untouched so the dict spill
            # rebuilds from a consistent vocabulary).
            self._sorted_vocab = np.insert(vocab, ins, new_u)
            self._sorted_codes = np.insert(self._sorted_codes, ins, new_c)
        self._next_code += len(new_idx)
        if nan_is_new:
            self._nan_code = new_nan_code
        return remap[codes].astype(np.int32)

    def _spill_to_dict(self) -> None:
        """Migrates the sorted-vocab state into the dict fallback when a
        chunk introduces keys numpy cannot order."""
        self._dict = {}
        if self._sorted_vocab is not None:
            for key, code in zip(self._sorted_vocab, self._sorted_codes):
                self._dict[key] = int(code)
            if self._nan_code is not None:
                self._dict[_NAN_KEY] = self._nan_code
            # Re-key by code order is unnecessary: dict lookups are by key.
            self._sorted_vocab = self._sorted_codes = None

    def _remap_dict(self, codes: np.ndarray,
                    uniques: np.ndarray) -> np.ndarray:
        remap = np.empty(len(uniques), np.int64)
        for j, key in enumerate(uniques):
            remap[j] = self._dict.setdefault(_dict_key(key),
                                             len(self._dict))
        return remap[codes].astype(np.int32)

    @property
    def vocabulary(self) -> Sequence[Any]:
        if self._index is not None:
            return np.asarray(self._index)
        if self._table is not None:
            if len(self._table_keys) != 1:
                self._table_keys = [np.concatenate(
                    self._table_keys or [np.empty(0, self._table_dtype)])]
            return self._table_keys[0].astype(self._table_dtype)
        if self._sorted_vocab is not None:
            dt = self._sorted_vocab.dtype
            if self._nan_code is not None:
                if dt.kind in "biu":
                    dt = np.promote_types(dt, np.float64)
                elif dt.kind != "f":
                    # A string/object vocab cannot hold a float NaN;
                    # promotion to '<U..' would store the STRING 'nan'.
                    dt = np.dtype(object)
            out = np.empty(self._next_code, dtype=dt)
            out[self._sorted_codes] = self._sorted_vocab
            if self._nan_code is not None:
                out[self._nan_code] = np.nan
            return out
        if self._dict:
            vocab = np.empty(len(self._dict), dtype=object)
            for key, code in self._dict.items():
                vocab[code] = np.nan if key is _NAN_KEY else key
            return vocab
        return np.empty(0, dtype=object)

    def __len__(self) -> int:
        if self._index is not None:
            return len(self._index)
        if self._table is not None or self._sorted_vocab is not None:
            return self._next_code
        return len(self._dict or ())


@dataclasses.dataclass
class _PreparedChunk:
    """One chunk's thread-pool encode output: chunk-local vocab codes +
    uniques (first-occurrence order) awaiting the sequential merge."""
    pid_codes: np.ndarray
    pid_uniques: np.ndarray
    pk_codes: np.ndarray  # vocab-final when publicly encoded
    pk_uniques: Optional[np.ndarray]  # None when pk was publicly encoded
    values: np.ndarray


def _prepare_chunk(chunk, partition_vocab, nonfinite,
                   value_dtype) -> _PreparedChunk:
    """Order-independent host encode of one chunk (runs on the encode
    thread pool): factorize keys, validate values. The sequential
    vocabulary merge happens on the consumer (ChunkedVocabEncoder.merge),
    so parallel workers can never reorder code assignment."""
    pid_raw, pk_raw, values = chunk
    pid_codes, pid_uniques = chunk_factorize(pid_raw)
    if partition_vocab is not None:
        pk_codes = columnar.encode_with_vocab(
            columnar._as_key_array(pk_raw), partition_vocab)
        pk_uniques = None
    else:
        pk_codes, pk_uniques = chunk_factorize(pk_raw)
    values = np.asarray(values, dtype=value_dtype)
    bad = columnar.nonfinite_value_rows(values, nonfinite)
    if bad is not None:
        pk_codes = np.where(bad, np.int32(-1), pk_codes).astype(np.int32)
        mask = bad if values.ndim == 1 else bad[:, None]
        values = np.where(mask, 0.0, values).astype(value_dtype)
    return _PreparedChunk(pid_codes, pid_uniques, pk_codes, pk_uniques,
                          values)


def _loaded(load, worker):
    """The encode worker of a source whose items are loaded where they are
    encoded (ChunkSource.from_netflix_file's byte ranges): `worker`
    applied to `load(item)`."""
    if load is None:
        return worker
    return lambda item: worker(load(item))


def _pad_chunk_rows(pid, pk, values, cap: int, fills=(0, -1, 0)):
    """Pads one chunk to `cap` rows with the accumulator's pad values
    (executor.pad_rows' pid 0 / pk -1 / values 0 on the host-encoded
    route; hash sentinels on the hash-device route) for the donating
    device accumulator."""
    n = len(pid)
    if cap == n:
        return pid, pk, values
    pad = cap - n
    pid = np.concatenate(
        [pid, np.full((pad,) + pid.shape[1:], fills[0], pid.dtype)])
    pk = np.concatenate(
        [pk, np.full((pad,) + pk.shape[1:], fills[1], pk.dtype)])
    values = np.concatenate(
        [values,
         np.full((pad,) + values.shape[1:], fills[2], values.dtype)])
    return pid, pk, values


# --- Hash-keyed encode (the host half of encode_mode="hash_device") --------
#
# The device-resident encode mode replaces the sequential vocabulary
# stitch with on-device hash factorization (device_encode.py): chunk
# workers only HASH raw keys to uint64 — vectorized, order-independent,
# perfectly parallel — and the dense integer codes are assigned inside
# jit from the hash columns. Everything below is that host half: two
# independent 64-bit hash lanes per key (lane 1 exists solely so the
# collision detector can tell "same key twice" from "two keys, one
# hash"), per-chunk unique triples feeding the deferred decode table,
# and NaN/dtype canonicalization that keeps hash identity aligned with
# the host encoder's key equality (all NaNs share one code; 3 and 3.0
# unify when both fit a float64 exactly).

# pandas hash_array keys must be exactly 16 bytes; one per hash lane.
_HASH_PD_KEYS = ("pdp_tpu_hash_ln0", "pdp_tpu_hash_ln1")
_HASH_SENTINEL64 = np.uint64((1 << 64) - 1)


def _splitmix64(x: np.ndarray, lane: int) -> np.ndarray:
    """Vectorized splitmix64 finalizer over uint64 bit patterns — a
    BIJECTION on 64 bits, so fixed-width numeric keys can never collide
    (only canonicalization-intended merges). Lane-salted by an input
    xor; used when pandas' C hash is unavailable."""
    x = x ^ np.uint64((0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F)[lane])
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _stable_hash_elements(raw: np.ndarray, lane: int) -> np.ndarray:
    """Per-element stable hash of keys no vectorized path can handle
    (mixed/composite object keys) — the hash counterpart of
    columnar.factorize's dict-loop last resort. Deterministic across
    processes (blake2b, never Python's salted hash); numbers
    canonicalize through float64 so 3, 3.0 and True==1 unify exactly as
    dict keys do."""
    import hashlib
    import pickle

    salt = _HASH_PD_KEYS[lane].encode()
    out = np.empty(len(raw), np.uint64)
    for i, key in enumerate(raw):
        canon = _dict_key(key)
        if canon is _NAN_KEY:
            payload = b"\x00nan"
        elif isinstance(canon, (bool, int, float, np.bool_, np.integer,
                                np.floating)) and \
                float(canon) == canon and abs(float(canon)) < 2.0**53:
            payload = b"\x01" + repr(float(canon)).encode()
        else:
            try:
                payload = pickle.dumps(canon, protocol=4)
            except Exception:  # noqa: BLE001 - unpicklable exotic keys hash by repr; any failure mode here must not kill ingest, only weaken hash quality for that key
                payload = repr(canon).encode()
        digest = hashlib.blake2b(payload, digest_size=8,
                                 key=salt).digest()
        out[i] = np.frombuffer(digest, np.uint64)[0]
    return out


def _canonical_numeric(raw: np.ndarray) -> np.ndarray:
    """Numeric keys canonicalized for hashing: float64 when every value
    is exactly representable (so int 3 and float 3.0 hash identically,
    matching host-encoder key equality), int64 bit patterns otherwise;
    NaNs collapse to the one canonical NaN, -0.0 to +0.0."""
    if raw.dtype.kind in "biu":
        as_f = raw.astype(np.float64)
        # Integers below 2^53 are exact in float64 — unify with floats.
        if bool((np.abs(as_f) < 2.0**53).all()):
            return as_f + 0.0
        return raw.astype(np.int64).view(np.float64)
    x = raw.astype(np.float64)
    x = np.where(np.isnan(x), np.float64("nan"), x)
    return x + 0.0  # -0.0 -> +0.0


_FNV_OFFSETS = (np.uint64(0xCBF29CE484222325),
                np.uint64(0x9AE16A3B2F90404F))
_FNV_PRIME = np.uint64(0x100000001B3)


def _fnv_ragged(units: np.ndarray, starts: np.ndarray,
                lengths: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Both hash lanes of n keys whose code units are
    ``units[starts[i] : starts[i] + lengths[i]]``: FNV-1a over the code
    units, code unit j of a key salted by its position j, finished with
    the splitmix64 bijection.

    Zero code units never touch the hash: the same key hashes
    identically whatever array width it arrived in (numpy itself strips
    trailing NULs, so skipping them mirrors its key equality); the
    position salt keeps interior characters order-sensitive. Vectorized
    over keys, one step per code-unit position: the keys, longest first,
    are a prefix of that order at every position, so position j steps
    only the keys longer than j and the work is the keys' characters,
    not keys x the widest key. The code units walked, in bytes, are the
    counter hash_key_bytes."""
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry

    n = len(lengths)
    rt_telemetry.record("hash_key_bytes",
                        int(lengths.sum()) * units.dtype.itemsize)
    order = np.argsort(lengths, kind="stable")[::-1]
    longest = lengths[order]
    # Keys longer than j, for every position j: a prefix of `order`.
    longer = np.searchsorted(-longest, -np.arange(longest[0] if n else 0),
                             side="left")
    at = starts[order].astype(np.int64)
    h0 = np.full(n, _FNV_OFFSETS[0])
    h1 = np.full(n, _FNV_OFFSETS[1])
    for j, k in enumerate(longer):
        col = units[at[:k] + j].astype(np.uint64)
        live = col != 0
        a0, a1 = h0[:k], h1[:k]
        step0 = (a0 ^ (col + np.uint64(0x9E3779B9 * (j + 1)))) * _FNV_PRIME
        step1 = (a1 ^ (col + np.uint64(0xC2B2AE35 * (j + 2)))) * _FNV_PRIME
        h0[:k] = np.where(live, step0, a0)
        h1[:k] = np.where(live, step1, a1)
    out0, out1 = np.empty(n, np.uint64), np.empty(n, np.uint64)
    out0[order], out1[order] = h0, h1
    return _splitmix64(out0, 0), _splitmix64(out1, 1)


def _vector_hash_fixed_width(raw: np.ndarray) -> Tuple[np.ndarray,
                                                       np.ndarray]:
    """Both hash lanes of a fixed-width 'U'/'S' key column (_fnv_ragged
    over its character matrix: each key to its last nonzero code unit).
    ~50x the throughput of a per-row C hash — this is what keeps the
    hash-device mode's host work to 'read the bytes once'."""
    n = len(raw)
    raw = np.ascontiguousarray(raw)
    unit = np.uint32 if raw.dtype.kind == "U" else np.uint8
    width = raw.dtype.itemsize // np.dtype(unit).itemsize
    units = raw.view(unit).reshape(n * width)
    return _fnv_ragged(units, np.arange(n, dtype=np.int64) * width,
                       np.strings.str_len(raw).astype(np.int64))


def _vector_hash_str_objects(raw: np.ndarray) -> Tuple[np.ndarray,
                                                       np.ndarray]:
    """Both hash lanes of an object column whose every element is a
    ``str``: _fnv_ragged over the code points of the keys laid end to
    end, the hash _vector_hash_fixed_width gives the same keys as a 'U'
    array, without the array: that has every key as wide as the chunk's
    widest (a 100-character key makes a 2^20-row chunk 400 MB)."""
    keys = raw.tolist()
    lengths = np.fromiter(map(len, keys), np.int64, count=len(keys))
    units = np.frombuffer(
        "".join(keys).encode("utf-32-le", "surrogatepass"), np.uint32)
    starts = np.cumsum(lengths) - lengths
    return _fnv_ragged(units, starts, lengths)


def hash_key_column_pair(raw) -> Tuple[np.ndarray, np.ndarray]:
    """Both deterministic uint64 hash lanes of a key column.

    THE key hash of encode_mode="hash_device": lane 0 is the partition /
    privacy-unit identity the device factorize groups by, lane 1 an
    independent family feeding only the collision detector (computing
    both in one content pass makes the detector ~free). Stable across
    processes and runs (vectorized FNV/splitmix or blake2b — never
    Python's salted hash()), with the uint64 maximum remapped away so
    the device pad sentinel is unreachable from data. Key identity
    follows the host encoder's equality: numeric keys canonicalize
    through float64 (3 == 3.0 == True-as-1), every NaN is one key.
    """
    raw = columnar._as_key_array(raw)
    if len(raw) == 0:
        return np.empty(0, np.uint64), np.empty(0, np.uint64)
    kind = raw.dtype.kind
    pair = None
    if kind in "biuf":
        bits = _canonical_numeric(raw).view(np.uint64)
        pair = (_splitmix64(bits, 0), _splitmix64(bits, 1))
    elif kind in "SU":
        pair = _vector_hash_fixed_width(raw)
    elif kind == "O" and _pd is not None:
        # Gate on a C-speed dtype inference: mixed object arrays (int 1
        # next to "1", tuples, ...) must go to the per-element stable
        # hash, never be silently stringified.
        inferred = _pd.api.types.infer_dtype(raw, skipna=False)
        if inferred == "string":
            pair = _vector_hash_str_objects(raw)
        elif inferred in ("integer", "boolean"):
            bits = _canonical_numeric(raw.astype(np.int64)
                                      if inferred == "integer" else
                                      raw.astype(bool)).view(np.uint64)
            pair = (_splitmix64(bits, 0), _splitmix64(bits, 1))
        elif inferred in ("floating", "mixed-integer-float"):
            bits = _canonical_numeric(
                raw.astype(np.float64)).view(np.uint64)
            pair = (_splitmix64(bits, 0), _splitmix64(bits, 1))
    if pair is None:
        pair = (_stable_hash_elements(raw, 0),
                _stable_hash_elements(raw, 1))
    top = _HASH_SENTINEL64 - np.uint64(1)
    return (np.where(pair[0] == _HASH_SENTINEL64, top, pair[0]),
            np.where(pair[1] == _HASH_SENTINEL64, top, pair[1]))


def hash_key_column(raw, lane: int = 0) -> np.ndarray:
    """One lane of hash_key_column_pair (see there)."""
    return hash_key_column_pair(raw)[lane]


def _hash_uniques(h1: np.ndarray, h2: np.ndarray, raw):
    """Chunk-local distinct (h1, h2) pairs + one representative raw key
    per pair (first occurrence) — the order-independent per-chunk
    contribution to collision detection and the deferred decode table.
    One stable sort of h1 over the chunk, no global state. Two keys that
    share an h1 stay apart by h2 (not deduped where their rows
    interleave), and the merge names that collision."""
    if len(h1) == 0:
        empty = np.empty(0, np.uint64)
        return empty, empty, (raw[:0] if raw is not None else None), \
            np.empty(0, np.int64)
    order = np.argsort(h1, kind="stable")
    s1, s2 = h1[order], h2[order]
    new = np.empty(len(s1), bool)
    new[0] = True
    new[1:] = (s1[1:] != s1[:-1]) | (s2[1:] != s2[:-1])
    # Representative row per pair: the first occurrence IN CHUNK ORDER
    # (the sort is stable, so within a run row indices ascend).
    first = order[new]
    return s1[new], s2[new], (raw[first] if raw is not None else None), \
        first.astype(np.int64)


@dataclasses.dataclass
class _HashChunk:
    """One chunk's hash-encode output: (n, 3) uint32 hash-row columns
    ([hash_hi, hash_lo, valid]) ready for the device accumulator, plus
    the chunk-local unique triples the consumer stashes (never merges)
    for collision detection and deferred decode."""
    pid_hash: np.ndarray  # (n, 3) uint32
    pid_u1: np.ndarray
    pid_u2: np.ndarray
    pid_pos: np.ndarray  # chunk-local first positions
    pk_col: np.ndarray  # (n, 3) uint32, or int32[n] when public-encoded
    pk_u1: Optional[np.ndarray]
    pk_u2: Optional[np.ndarray]
    pk_keys: Optional[np.ndarray]
    pk_pos: Optional[np.ndarray]  # chunk-local first positions
    values: np.ndarray

    @property
    def n_rows(self) -> int:
        return len(self.pid_hash)


def _prepare_hash_chunk(chunk, partition_vocab, nonfinite,
                        value_dtype) -> _HashChunk:
    """Hash-mode chunk worker (thread-pool safe, no shared state): hash
    both key columns on two lanes, record the chunk's unique pairs,
    validate values. The expensive vocabulary work this replaces
    (_prepare_chunk + the sequential merge) never happens."""
    from pipelinedp_tpu import device_encode

    pid_raw, pk_raw, values = chunk
    pid_raw = columnar._as_key_array(pid_raw)
    pid_h1, pid_h2 = hash_key_column_pair(pid_raw)
    pid_u1, pid_u2, _, pid_pos = _hash_uniques(pid_h1, pid_h2, None)
    if partition_vocab is not None:
        pk_col = columnar.encode_with_vocab(
            columnar._as_key_array(pk_raw), partition_vocab)
        pk_u1 = pk_u2 = pk_keys = pk_pos = None
    else:
        pk_raw = columnar._as_key_array(pk_raw)
        pk_h1, pk_h2 = hash_key_column_pair(pk_raw)
        pk_u1, pk_u2, pk_keys, pk_pos = _hash_uniques(pk_h1, pk_h2,
                                                      pk_raw)
    values = np.asarray(values, dtype=value_dtype)
    bad = columnar.nonfinite_value_rows(values, nonfinite)
    pk_valid = None
    if bad is not None:
        # Same invalid marks as the host route: the row drops out of its
        # partition (pk code -> -1) but BOTH key columns keep their real
        # hashes — the host encoder factorizes the raw columns before
        # rows are invalidated, so even a key seen only on dropped rows
        # claims its vocabulary slot and every later code stays
        # bit-aligned.
        if partition_vocab is not None:
            pk_col = np.where(bad, np.int32(-1), pk_col).astype(np.int32)
        else:
            pk_valid = ~bad
        mask = bad if values.ndim == 1 else bad[:, None]
        values = np.where(mask, 0.0, values).astype(value_dtype)
    if partition_vocab is None:
        pk_col = device_encode.pack_hash_rows(pk_h1, pk_valid)
    return _HashChunk(device_encode.pack_hash_rows(pid_h1), pid_u1,
                      pid_u2, pid_pos, pk_col, pk_u1, pk_u2, pk_keys,
                      pk_pos, values)


def stream_encode_columns(
        chunks: Iterable[Tuple[Sequence[Any], Sequence[Any],
                               Sequence[float]]],
        public_partitions: Optional[Sequence[Any]] = None,
        nonfinite: str = "error",
        encode_threads: int = 0,
        pipeline_depth: Optional[int] = None,
        encode_mode: str = "host",
        load: Optional[Callable[[Any], Any]] = None) -> columnar.EncodedData:
    """Encodes and uploads (pid_raw, pk_raw, values) column chunks,
    overlapping each chunk's device copy with the next chunk's parsing.

    load: None, or the function that turns one item of `chunks` into its
    columns (the byte-range reader of ChunkSource.from_netflix_file); it
    runs where the item is encoded, on the encode worker when
    encode_threads >= 1, not on the feeder thread.

    encode_threads=0 (the default) is the serial path: one loop,
    device copies overlapping the next chunk's parse only through jax's
    async dispatch. encode_threads >= 1 routes through the streaming
    executor (runtime/pipeline.py): chunk parse/factorize runs on a host
    thread pool feeding a bounded staging queue (window =
    ``pipeline_depth``, default the shared PIPELINE_DEPTH), the
    sequential vocabulary merge and device accumulation run on the
    consumer, and rows accumulate into persistent device buffers
    (power-of-two row buckets, donated across appends). Both paths
    yield bit-identical kernel inputs — the pipelined EncodedData
    arrives pre-padded to exactly the executor.pad_rows bucket.

    Non-finite VALUES are rejected per chunk (nonfinite="error", the
    default) or dropped with a warning (nonfinite="drop") — a NaN/Inf
    survives jnp.clip and would silently poison its partition's sums
    (columnar.nonfinite_value_rows).

    encode_mode="hash_device" replaces the host vocabulary work with
    on-device hash factorization (device_encode.py): chunk workers only
    hash raw keys to uint64, raw hash columns stream host->device once
    through the same accumulator, dense first-occurrence codes are
    assigned inside jit, and partition-key decode is deferred to the
    DP-selected indices (HashVocab). Result parity is bit-exact with
    encode_mode="host" under the same noise keys; a detected 64-bit
    hash collision falls back to this exact host encoder (re-iterable
    sources) or raises HashCollisionError (one-shot iterators).

    Returns a device-resident EncodedData (jax-array columns, values in
    the kernel compute dtype — float32 normally, at half the f64 upload
    volume; float64 when jax_enable_x64 is on, so streamed input loses no
    precision relative to the row-input path).
    """
    import jax.numpy as jnp

    from pipelinedp_tpu import executor
    from pipelinedp_tpu.runtime import trace as rt_trace
    if encode_mode not in ("host", "hash_device"):
        raise ValueError(f"encode_mode must be host|hash_device, "
                         f"got {encode_mode!r}")
    if encode_mode == "hash_device":
        return _stream_encode_hash_device(chunks, public_partitions,
                                          nonfinite, encode_threads,
                                          pipeline_depth, load)
    value_dtype = np.dtype(executor._ftype())

    pid_enc = ChunkedVocabEncoder()
    pk_enc = ChunkedVocabEncoder()
    partition_vocab = None
    if public_partitions is not None:
        partition_vocab = list(dict.fromkeys(public_partitions))

    def encoded_data(pid, pk, values):
        return columnar.EncodedData(
            pid=pid, pk=pk, values=values,
            partition_vocab=(partition_vocab
                             if partition_vocab is not None else
                             pk_enc.vocabulary),
            n_privacy_ids=len(pid_enc),
            public_encoded=public_partitions is not None)

    if encode_threads:
        return _stream_encode_pipelined(chunks, partition_vocab, nonfinite,
                                        value_dtype, pid_enc, pk_enc,
                                        encoded_data, encode_threads,
                                        pipeline_depth, load)

    dev_pid, dev_pk, dev_vals = [], [], []
    # The ingest span covers parse+factorize+upload for the whole stream;
    # its row count attribute lets trace summaries report ingest rate.
    with rt_trace.span("ingest") as ingest_span:
        n_rows = 0
        for pid_raw, pk_raw, values in (chunks if load is None else
                                        map(load, chunks)):
            pid = pid_enc.encode(pid_raw)
            if partition_vocab is not None:
                pk = columnar.encode_with_vocab(
                    columnar._as_key_array(pk_raw), partition_vocab)
            else:
                pk = pk_enc.encode(pk_raw)
            values = np.asarray(values, dtype=value_dtype)
            bad = columnar.nonfinite_value_rows(values, nonfinite)
            if bad is not None:
                pk = np.where(bad, np.int32(-1), pk).astype(np.int32)
                mask = bad if values.ndim == 1 else bad[:, None]
                values = np.where(mask, 0.0, values).astype(value_dtype)
            n_rows += len(pid)
            # jnp.asarray dispatches the host->device copy asynchronously;
            # the loop continues into the next chunk's parse while it
            # lands.
            dev_pid.append(jnp.asarray(pid))
            dev_pk.append(jnp.asarray(pk))
            dev_vals.append(jnp.asarray(values))
        if not dev_pid:
            empty = jnp.zeros(0, jnp.int32)
            dev_pid, dev_pk = [empty], [empty]
            dev_vals = [jnp.zeros(0, value_dtype)]
        ingest_span.set(rows=n_rows)
        return encoded_data(jnp.concatenate(dev_pid),
                            jnp.concatenate(dev_pk),
                            jnp.concatenate(dev_vals))


def _stream_encode_pipelined(chunks, partition_vocab, nonfinite,
                             value_dtype, pid_enc, pk_enc, encoded_data,
                             encode_threads: int,
                             pipeline_depth: Optional[int],
                             load=None) -> columnar.EncodedData:
    """The pipelined body of stream_encode_columns: thread-pool chunk
    factorization -> bounded staging queue -> sequential vocab merge ->
    device-resident bucket accumulation (runtime/pipeline.py)."""
    import functools

    import jax.numpy as jnp

    from pipelinedp_tpu import executor
    from pipelinedp_tpu.runtime import pipeline as rt_pipeline
    from pipelinedp_tpu.runtime import trace as rt_trace

    acc = rt_pipeline.DeviceRowAccumulator(
        batch_rows=rt_pipeline.APPEND_BATCH_ROWS)
    worker = _loaded(load, functools.partial(_prepare_chunk,
                                             partition_vocab=partition_vocab,
                                             nonfinite=nonfinite,
                                             value_dtype=value_dtype))
    with rt_trace.span("ingest", threads=encode_threads) as ingest_span:
        n_rows = 0
        for idx, prep in enumerate(
                rt_pipeline.map_overlapped(chunks, worker, encode_threads,
                                           pipeline_depth)):
            # Sequential merge in stream order: global codes are exactly
            # what the serial encode assigns.
            with rt_trace.span("ingest.merge", chunk=idx,
                               rows=len(prep.pid_codes)):
                pid = pid_enc.merge(prep.pid_codes, prep.pid_uniques)
                if partition_vocab is not None:
                    pk = prep.pk_codes
                else:
                    pk = pk_enc.merge(prep.pk_codes, prep.pk_uniques)
            n = len(pid)
            n_rows += n
            values = prep.values
            if n == 0:
                continue
            if acc.donating and not acc.batch_rows:
                pid, pk, values = _pad_chunk_rows(
                    pid, pk, values, executor.row_bucket(n))
            acc.append(pid, pk, values, n, chunk=idx)
        ingest_span.set(rows=n_rows)
        with rt_trace.span("ingest.finalize"):
            bufs = acc.finalize()
        if bufs is None:
            empty = jnp.zeros(0, jnp.int32)
            return encoded_data(empty, empty, jnp.zeros(0, value_dtype))
        return encoded_data(*bufs)


def _hash_empty_encoded(public: bool, value_dtype,
                        partition_vocab) -> columnar.EncodedData:
    """Empty-stream encoding of the hash route (mirrors the host one)."""
    import jax.numpy as jnp

    from pipelinedp_tpu import device_encode
    empty = jnp.zeros(0, jnp.int32)
    if public:
        vocab = partition_vocab
    else:
        nohash = np.empty(0, np.uint64)
        vocab = device_encode.HashVocab(
            0, nohash, np.empty(0, object),
            hash_by_code_host=nohash)
    return columnar.EncodedData(pid=empty, pk=empty,
                                values=jnp.zeros(0, value_dtype),
                                partition_vocab=vocab, n_privacy_ids=0,
                                public_encoded=public)


def _stream_encode_hash_device(chunks, public_partitions, nonfinite,
                               encode_threads: int,
                               pipeline_depth: Optional[int],
                               load=None) -> columnar.EncodedData:
    """The encode_mode="hash_device" body of stream_encode_columns.

    Chunk workers hash (thread pool when encode_threads >= 1, exactly
    like the host pipelined route), raw (n, 3) hash rows accumulate into
    the donated device buffers, the consumer stashes per-chunk uniques
    with NO sequential merge, and the dense codes come out of ONE device
    factorize per key column at finalize. Collision detection runs
    (vectorized, over uniques) before any device code is trusted; a trip
    increments ``ingest_hash_collisions`` and falls back to the exact
    host encoder when the source can be re-iterated.
    """
    import functools

    from pipelinedp_tpu import device_encode, executor
    from pipelinedp_tpu.runtime import pipeline as rt_pipeline
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry
    from pipelinedp_tpu.runtime import trace as rt_trace

    value_dtype = np.dtype(executor._ftype())
    public = public_partitions is not None
    partition_vocab = (list(dict.fromkeys(public_partitions))
                       if public else None)
    # Re-iterability decides the collision-fallback story up front,
    # before the stream is consumed.
    reiterable = iter(chunks) is not chunks
    sent32 = int(device_encode._U32_MAX)
    fills = (sent32, -1 if public else sent32, 0)
    acc = rt_pipeline.DeviceRowAccumulator(
        fills=fills, batch_rows=rt_pipeline.APPEND_BATCH_ROWS)
    pid_u1, pid_u2, pid_pos = [], [], []
    pk_u1, pk_u2, pk_keys, pk_pos = [], [], [], []
    worker = _loaded(load, functools.partial(_prepare_hash_chunk,
                                             partition_vocab=partition_vocab,
                                             nonfinite=nonfinite,
                                             value_dtype=value_dtype))
    with rt_trace.span("ingest", encode="hash_device",
                       threads=encode_threads) as ingest_span:
        n_rows = 0
        if encode_threads:
            prepared = rt_pipeline.map_overlapped(chunks, worker,
                                                  encode_threads,
                                                  pipeline_depth)
        else:
            prepared = map(worker, chunks)
        for idx, prep in enumerate(prepared):
            n = prep.n_rows
            pid_u1.append(prep.pid_u1)
            pid_u2.append(prep.pid_u2)
            # Chunk-local first positions -> stream positions (the
            # consumer sees chunks in stream order).
            pid_pos.append(prep.pid_pos + n_rows)
            if not public:
                pk_u1.append(prep.pk_u1)
                pk_u2.append(prep.pk_u2)
                pk_keys.append(prep.pk_keys)
                pk_pos.append(prep.pk_pos + n_rows)
            n_rows += n
            if n == 0:
                continue
            pid_col, pk_col, values = (prep.pid_hash, prep.pk_col,
                                       prep.values)
            if acc.donating and not acc.batch_rows:
                pid_col, pk_col, values = _pad_chunk_rows(
                    pid_col, pk_col, values, executor.row_bucket(n),
                    fills)
            acc.append(pid_col, pk_col, values, n, chunk=idx)
            rt_telemetry.record("pipeline_device_encode_chunks",
                                chunk=idx)
        ingest_span.set(rows=n_rows)
        with rt_trace.span("ingest.finalize"):
            bufs = acc.finalize()
        # On an accelerator the factorize needs only the hash rows, so
        # it is queued on the device BEFORE the host merge below and
        # runs under it; the lookup kernel needs the merged table first.
        factorized = None
        if bufs is not None and not device_encode.prefers_lookup_codes():
            factorized = _dispatch_hash_factorize(bufs[0], bufs[1], public)
        # Collision safety gate: nothing derived from the device codes
        # is released past this point unless every primary hash maps to
        # exactly one (secondary hash, key) identity — on a collision
        # the dispatched codes are dropped unread.
        try:
            with rt_trace.span("ingest.unique_merge"):
                pid_table = device_encode.merge_hash_uniques(
                    pid_u1, pid_u2, None, pid_pos, what="privacy-id")
                pk_table = None
                if not public:
                    pk_table = device_encode.merge_hash_uniques(
                        pk_u1, pk_u2, pk_keys, pk_pos, what="partition")
        except device_encode.HashCollisionError as err:
            rt_telemetry.record("ingest_hash_collisions")
            logging.warning(
                "hash-device encode detected a 64-bit key-hash "
                "collision (%s); %s", err,
                "falling back to the exact host encoder." if reiterable
                else "the chunk source is a one-shot iterator, so the "
                "exact-encoder fallback cannot re-read it.")
            if not reiterable:
                raise device_encode.HashCollisionError(
                    f"{err} — and the chunk source is a one-shot "
                    f"iterator, so the exact host-encoder fallback "
                    f"cannot re-read it. Pass a re-iterable source "
                    f"(list / factory) or encode_mode='host'.") from err
            return stream_encode_columns(
                chunks, public_partitions=public_partitions,
                nonfinite=nonfinite, encode_threads=encode_threads,
                pipeline_depth=pipeline_depth, encode_mode="host",
                load=load)
        if bufs is None:
            return _hash_empty_encoded(public, value_dtype,
                                       partition_vocab)
        return _finalize_hash_codes(*bufs, public, partition_vocab,
                                    pid_table, pk_table, factorized)


def _dispatch_hash_factorize(pid_hash, pk_col, public: bool):
    """Queues the device sort/unique factorize of both key columns
    (device_encode.factorize_codes; the public partition column is
    already coded) and returns (pid codes, pk codes, distinct counts)
    as device arrays, unread: jit dispatch does not block, so the host
    goes on to the unique merge while the device sorts."""
    import jax.numpy as jnp

    from pipelinedp_tpu import device_encode
    from pipelinedp_tpu.runtime import trace as rt_trace

    with rt_trace.span("ingest.device_codes"):
        pid_codes, n_pid = device_encode.factorize_codes(pid_hash)
        if public:
            return pid_codes, pk_col, jnp.stack([n_pid])
        pk, n_pk = device_encode.factorize_codes(pk_col)
        return pid_codes, pk, jnp.stack([n_pid, n_pk])


def _finalize_hash_codes(pid_hash, pk_col, values, public: bool,
                         partition_vocab, pid_table, pk_table,
                         factorized) -> columnar.EncodedData:
    """Device code assignment + deferred-decode vocabulary of the hash
    stream route (runs inside the ingest span, under its own sub-span
    so the e2e phase breakdown separates in-jit code assignment from
    the host hashing).

    Two interchangeable in-jit code-assignment kernels (identical
    codes): the self-contained sort/unique factorize on accelerators,
    already dispatched before the merge (``factorized``, from
    _dispatch_hash_factorize), and the host-table binary-search lookup
    on CPU (``factorized`` None), where XLA's comparator sort is the
    wrong tool — see device_encode.prefers_lookup_codes."""
    import jax.numpy as jnp

    from pipelinedp_tpu import device_encode
    from pipelinedp_tpu.parallel import mesh as mesh_lib
    from pipelinedp_tpu.runtime import trace as rt_trace

    with rt_trace.span("ingest.device_codes"):
        pk = pk_col
        if factorized is not None:
            pid_codes, pk, n_dev = factorized
        else:
            pid_codes = device_encode.lookup_codes(
                pid_hash,
                *device_encode.build_lookup_table(pid_table[0],
                                                  pid_table[3]))
            n_privacy_ids = pid_table[2]
            if not public:
                pk = device_encode.lookup_codes(
                    pk_col, *device_encode.build_lookup_table(
                        pk_table[0], pk_table[3]))
        if public:
            vocab = partition_vocab
        else:
            s1, keys, n_pk, pos = pk_table
            # Code order (global first occurrence) is host-derivable
            # from the chunk uniques' positions — decode then needs
            # zero device->host traffic.
            vocab = device_encode.HashVocab(
                n_pk, s1, keys,
                hash_by_code_host=s1[np.argsort(pos, kind="stable")])
        if factorized is not None:
            # The one wait on the device's factorize, after all host work.
            n_stats = mesh_lib.host_fetch(n_dev)
            n_privacy_ids = int(n_stats[0])
            if not public and int(n_stats[1]) != n_pk:
                raise RuntimeError(
                    f"device factorize found {int(n_stats[1])} "
                    f"distinct partition hashes but the host unique "
                    f"merge found {n_pk} (internal invariant)")
        # Pad rows factorize to -1; the pad_rows convention is pid 0.
        pid = jnp.maximum(pid_codes, 0)
        return columnar.EncodedData(pid=pid, pk=pk, values=values,
                                    partition_vocab=vocab,
                                    n_privacy_ids=n_privacy_ids,
                                    public_encoded=public)


# --- Multi-host ingest -----------------------------------------------------
#
# The reference scales unbounded IO by handing it to Beam/Spark workers
# (pipeline_dp/pipeline_backend.py:223-374). The TPU-native equivalent is
# host-sharded ingest: in a multi-host deployment each host process parses
# and vocab-encodes ITS contiguous shard of the input independently
# (encode_shard — pure numpy, no device), the per-host vocabularies are
# merged with one pass of the same incremental encoder
# (merge_host_vocabularies — the returned codes ARE each host's
# local->global remap), and each host remaps + uploads only its own rows
# to its local devices, so the only cross-host (DCN) traffic is the
# vocabularies and O(uniques) remap vectors — never row data. With hosts
# owning contiguous shards in stream order, the merged codes are exactly
# what a single-process factorize of the whole stream would assign.


@dataclasses.dataclass
class ShardEncoding:
    """One host's locally-encoded shard: int32 code columns + the local
    vocabularies they index. Picklable (pure numpy) so worker processes
    can ship it back to the coordinator."""
    pid: np.ndarray
    pk: np.ndarray
    values: np.ndarray
    pid_vocab: np.ndarray
    pk_vocab: Optional[np.ndarray]  # None when pk was publicly encoded


def encode_shard(
        chunks: Iterable[Tuple[Sequence[Any], Sequence[Any],
                               Sequence[float]]],
        public_partitions: Optional[Sequence[Any]] = None,
        nonfinite: str = "error") -> ShardEncoding:
    """Host-local chunked encoding of one input shard (no device work).

    The multi-host counterpart of stream_encode_columns' parse+factorize
    stage: runs in each ingest process over its own chunk iterator. The
    same per-chunk non-finite value policy applies (each ingest worker
    rejects/drops at its own boundary, so poisoned rows never travel).
    """
    pid_enc = ChunkedVocabEncoder()
    pk_enc = ChunkedVocabEncoder()
    partition_vocab = None
    if public_partitions is not None:
        partition_vocab = list(dict.fromkeys(public_partitions))
    pids, pks, vals = [], [], []
    for pid_raw, pk_raw, values in chunks:
        pids.append(pid_enc.encode(pid_raw))
        if partition_vocab is not None:
            pks.append(
                columnar.encode_with_vocab(columnar._as_key_array(pk_raw),
                                           partition_vocab))
        else:
            pks.append(pk_enc.encode(pk_raw))
        values = np.asarray(values, dtype=np.float64)
        bad = columnar.nonfinite_value_rows(values, nonfinite)
        if bad is not None:
            pks[-1] = np.where(bad, np.int32(-1), pks[-1]).astype(np.int32)
            mask = bad if values.ndim == 1 else bad[:, None]
            values = np.where(mask, 0.0, values)
        vals.append(values)
    empty = np.zeros(0, np.int32)
    return ShardEncoding(
        pid=np.concatenate(pids) if pids else empty,
        pk=np.concatenate(pks) if pks else empty,
        values=(np.concatenate(vals) if vals else np.zeros(0)),
        pid_vocab=np.asarray(pid_enc.vocabulary),
        pk_vocab=(None if partition_vocab is not None else np.asarray(
            pk_enc.vocabulary)))


def merge_host_vocabularies(
        vocabs: Sequence[Sequence[Any]]
) -> Tuple[np.ndarray, List[np.ndarray]]:
    """Merges per-host vocabularies into one global first-occurrence
    vocabulary (host order = stream order).

    The merge primitive is the incremental encoder itself: feeding host
    h's vocabulary (in local code order) as one "chunk" returns the
    global code of each local code — i.e. the remap vector
    ``global_code = remap[local_code]``.

    Returns (global_vocabulary, [remap_int32 per host]).
    """
    enc = ChunkedVocabEncoder()
    remaps = []
    for vocab in vocabs:
        vocab = columnar._as_key_array(vocab)
        remaps.append(
            enc.encode(vocab) if len(vocab) else np.zeros(0, np.int32))
    return np.asarray(enc.vocabulary), remaps


def merge_shards(shards: Sequence[ShardEncoding],
                 public_partitions: Optional[Sequence[Any]] = None
                 ) -> columnar.EncodedData:
    """Coordinator step: merge per-host shard encodings into one
    device-resident EncodedData.

    Row columns are remapped with each host's O(local uniques) remap
    vector and uploaded shard-by-shard (each shard's device copy overlaps
    the next shard's remap, as in stream_encode_columns). In a real
    multi-host deployment the remap vectors travel to the hosts instead
    of the rows travelling here — see the module docstring's DCN note;
    this single-process form is the semantics (and the dryrun target) of
    that deployment.
    """
    import jax.numpy as jnp

    from pipelinedp_tpu import executor

    value_dtype = np.dtype(executor._ftype())
    pid_vocab, pid_remaps = merge_host_vocabularies(
        [s.pid_vocab for s in shards])
    public = public_partitions is not None
    if public:
        for s in shards:
            if s.pk_vocab is not None:
                raise ValueError(
                    "shard was encoded without public partitions but "
                    "merge_shards was called with them — the shard's pk "
                    "codes index its private vocabulary, not the public "
                    "one")
        partition_vocab = list(dict.fromkeys(public_partitions))
        pk_remaps = None
    else:
        for s in shards:
            if s.pk_vocab is None:
                raise ValueError(
                    "shard was encoded with public partitions but "
                    "merge_shards was called without them")
        partition_vocab, pk_remaps = merge_host_vocabularies(
            [s.pk_vocab for s in shards])
    dev_pid, dev_pk, dev_vals = [], [], []
    for h, s in enumerate(shards):
        dev_pid.append(jnp.asarray(pid_remaps[h][s.pid]))
        dev_pk.append(
            jnp.asarray(s.pk if public else pk_remaps[h][s.pk]))
        dev_vals.append(jnp.asarray(s.values.astype(value_dtype)))
    if not dev_pid:
        empty = jnp.zeros(0, jnp.int32)
        dev_pid, dev_pk = [empty], [empty]
        dev_vals = [jnp.zeros(0, value_dtype)]
    return columnar.EncodedData(
        pid=jnp.concatenate(dev_pid),
        pk=jnp.concatenate(dev_pk),
        values=jnp.concatenate(dev_vals),
        partition_vocab=partition_vocab,
        n_privacy_ids=len(pid_vocab),
        public_encoded=public)


# --- Multi-controller (pod) ingest ----------------------------------------
#
# The live form of the design above: under jax.distributed, EACH process
# runs encode_shard over its own chunk iterator (host-local parse +
# factorize, no device work, no cross-host rows), the per-process
# vocabularies — O(uniques), not O(rows) — are exchanged once over the
# collective fabric, every process derives the identical global
# vocabulary + remap vectors (merge_host_vocabularies is deterministic in
# process order), and each process uploads ONLY its remapped shard to its
# local devices, assembled into one global mesh-sharded array
# (jax.make_array_from_process_local_data). The only DCN traffic before
# the driver's all_to_all is the vocabulary exchange.


@dataclasses.dataclass
class _ShardMeta:
    """The per-process facts the vocabulary exchange moves: local vocabs
    (pure numpy, picklable) + the process's row count."""
    n_rows: int
    pid_vocab: np.ndarray
    pk_vocab: Optional[np.ndarray]


def _collective_allgather_bytes(payload: bytes) -> List[bytes]:
    """All-gathers one bytes payload per process (process order), via two
    device collectives: a length gather fixes the pad, then the padded
    uint8 payloads gather. O(vocabulary) bytes — never rows."""
    import jax
    import numpy as np_  # local alias: keep module-level np for rows
    from jax.experimental import multihost_utils

    length = np_.asarray([len(payload)], np_.int64)
    lengths = np_.asarray(
        multihost_utils.process_allgather(length)).reshape(-1)
    cap = int(lengths.max()) if len(lengths) else 0
    padded = np_.zeros(max(cap, 1), np_.uint8)
    padded[:len(payload)] = np_.frombuffer(payload, np_.uint8)
    gathered = np_.asarray(multihost_utils.process_allgather(padded))
    gathered = gathered.reshape(int(jax.process_count()), -1)
    return [gathered[p, :int(lengths[p])].tobytes()
            for p in range(gathered.shape[0])]


def merge_shard_metas(metas: Sequence[_ShardMeta],
                      public: bool
                      ) -> Tuple[List[np.ndarray],
                                 Optional[List[np.ndarray]],
                                 np.ndarray, Sequence[Any]]:
    """Deterministic global merge every process runs identically:
    (pid remaps, pk remaps or None, global pid vocab, partition vocab)."""
    pid_vocab, pid_remaps = merge_host_vocabularies(
        [m.pid_vocab for m in metas])
    if public:
        return pid_remaps, None, pid_vocab, []
    pk_vocab, pk_remaps = merge_host_vocabularies(
        [m.pk_vocab for m in metas])
    return pid_remaps, pk_remaps, pid_vocab, pk_vocab


def _padded_local_rows(shard: ShardEncoding, pid_remap: np.ndarray,
                       pk_remap: Optional[np.ndarray], cap: int,
                       value_dtype) -> Tuple[np.ndarray, np.ndarray,
                                             np.ndarray]:
    """One process's remapped rows padded to its device capacity with the
    standard invalid marks (pid 0, pk -1 -> EncodedData.valid False)."""
    pid = (pid_remap[shard.pid] if len(shard.pid) else
           shard.pid).astype(np.int32)
    pk = shard.pk if pk_remap is None else (
        pk_remap[shard.pk] if len(shard.pk) else shard.pk)
    pk = np.asarray(pk, np.int32)
    values = np.asarray(shard.values, dtype=value_dtype)
    n = len(pid)
    pad = cap - n
    if pad:
        pid = np.concatenate([pid, np.zeros(pad, np.int32)])
        pk = np.concatenate([pk, np.full(pad, -1, np.int32)])
        values = np.concatenate(
            [values,
             np.zeros((pad,) + values.shape[1:], values.dtype)])
    return pid, pk, values


def _pod_row_capacity(n_rows_by_process, mesh) -> Tuple[int, bool]:
    """One shared per-device row capacity every pod process derives
    identically (from the exchanged row counts and the mesh alone): the
    largest per-device row load across processes, capacity-rounded so
    repeated pods of similar size reuse compiled shapes. Returns
    (per_device_capacity, simulated) — `simulated` marks the injected-
    exchange single-process simulation of a pod."""
    from pipelinedp_tpu.parallel import mesh as mesh_lib
    from pipelinedp_tpu.parallel.mesh import device_process, round_capacity

    n_dev = int(mesh.devices.size)
    devs_of = collections_counter(
        device_process(d) for d in mesh.devices.flat)
    simulated = (mesh_lib.process_count() == 1 and
                 len(n_rows_by_process) > 1)
    per_dev = 1
    for p, n_rows in enumerate(n_rows_by_process):
        if simulated:
            # Injected-exchange simulation of a pod inside one process:
            # pretend an even device split across the simulated hosts.
            n_p = max(n_dev // len(n_rows_by_process), 1)
        else:
            n_p = devs_of.get(p, 0)
        if n_rows and not n_p:
            raise ValueError(
                f"process {p} encoded {n_rows} rows but owns no device "
                f"of the mesh — every ingesting process must hold a mesh "
                f"slice to upload to")
        if n_p:
            per_dev = max(per_dev, -(-n_rows // n_p))
    return round_capacity(per_dev), simulated


def encode_local_shard_to_mesh(
        chunks: Iterable[Tuple[Sequence[Any], Sequence[Any],
                               Sequence[float]]],
        mesh,
        public_partitions: Optional[Sequence[Any]] = None,
        nonfinite: str = "error",
        exchange=None,
        encode_mode: str = "host") -> columnar.EncodedData:
    """Pod-scale ingest: this process encodes ONLY its own input shard.

    Runs encode_shard over `chunks` (host-local), exchanges the
    per-process vocabularies + row counts (`exchange(payload_bytes) ->
    [payload_bytes per process]`, default the collective all-gather —
    injectable so single-process tests can simulate a pod), merges them
    into the global vocabulary every process derives identically, remaps
    the local rows, and uploads them as this process's slice of one
    global mesh-sharded array set (jax.make_array_from_process_local_data
    over `mesh`'s row sharding). Per-process rows pad to a common
    per-device capacity (pk -1 -> EncodedData.valid False), so the global
    layout is an even leading-axis split the meshed drivers consume
    without any further eager cross-process reshaping.

    Rows never cross hosts here: the collective reshard inside the driver
    (hash(pid) mod D over the SAME global vocabulary codes) is what
    co-locates each privacy id, exactly as in the single-process path.
    Process order = stream order, so the merged codes equal a serial
    stream_encode_columns over the concatenated stream (proven in
    tests/test_multihost.py).

    encode_mode="hash_device" replaces the pickled host-vocabulary merge
    with the device collective factorize: each process only HASHES its
    shard, the compacted per-shard hash uniques cross the mesh in one
    ``lax.all_gather`` (device_encode.mesh_factorize_codes), and every
    process derives identical global first-occurrence codes on device.
    The byte exchange then carries only the O(uniques) collision /
    decode metadata — no vocabulary remap work rides it.
    """
    import pickle

    import jax
    import jax.numpy as jnp

    from pipelinedp_tpu import executor
    from pipelinedp_tpu.parallel import mesh as mesh_lib
    from pipelinedp_tpu.runtime import trace as rt_trace

    if encode_mode not in ("host", "hash_device"):
        raise ValueError(f"encode_mode must be host|hash_device, "
                         f"got {encode_mode!r}")
    if encode_mode == "hash_device":
        return _encode_local_shard_hash(chunks, mesh, public_partitions,
                                        nonfinite, exchange)
    value_dtype = np.dtype(executor._ftype())
    public = public_partitions is not None
    with rt_trace.span("ingest.local_shard") as sp:
        shard = encode_shard(chunks, public_partitions, nonfinite)
        sp.set(rows=int(len(shard.pid)))
    meta = _ShardMeta(n_rows=int(len(shard.pid)),
                      pid_vocab=np.asarray(shard.pid_vocab),
                      pk_vocab=(None if shard.pk_vocab is None else
                                np.asarray(shard.pk_vocab)))
    if exchange is None:
        if mesh_lib.process_count() == 1:
            exchange = lambda payload: [payload]  # noqa: E731 - trivial single-process identity
        else:
            exchange = _collective_allgather_bytes
    with rt_trace.span("ingest.vocab_exchange") as sp:
        payload = pickle.dumps(meta)
        sp.set(bytes=len(payload))
        metas = [pickle.loads(p) for p in exchange(payload)]
    my_p = mesh_lib.process_index()
    if not 0 <= my_p < len(metas):
        raise ValueError(
            f"vocabulary exchange returned {len(metas)} shard metas but "
            f"this is process {my_p} — every pod process must "
            f"participate exactly once")
    pid_remaps, pk_remaps, pid_vocab, pk_vocab = merge_shard_metas(
        metas, public)
    if public:
        partition_vocab = list(dict.fromkeys(public_partitions))
    else:
        partition_vocab = pk_vocab
    n_local_dev = max(len(mesh_lib.local_devices(mesh)), 1)
    n_dev = int(mesh.devices.size)
    # One shared per-device capacity (every process must agree on the
    # global shape, so it is derived purely from the exchanged metas and
    # the mesh).
    cap, _ = _pod_row_capacity([m.n_rows for m in metas], mesh)
    local_rows = cap * n_local_dev
    pid, pk, values = _padded_local_rows(
        shard, pid_remaps[my_p],
        None if pk_remaps is None else pk_remaps[my_p], local_rows,
        value_dtype)
    from jax.sharding import NamedSharding, PartitionSpec
    sharding = NamedSharding(mesh, PartitionSpec(mesh_lib.SHARD_AXIS))
    global_rows = cap * n_dev

    def to_global(col):
        if mesh_lib.process_count() == 1:
            return jax.device_put(jnp.asarray(col), sharding)
        return jax.make_array_from_process_local_data(
            sharding, col, (global_rows,) + col.shape[1:])

    return columnar.EncodedData(
        pid=to_global(pid),
        pk=to_global(pk),
        values=to_global(values),
        partition_vocab=partition_vocab,
        n_privacy_ids=len(pid_vocab),
        public_encoded=public)


# --- Multi-controller hash-device ingest -----------------------------------


@dataclasses.dataclass
class _HashShardMeta:
    """The per-process facts the hash-mode byte exchange moves: the row
    count (for the shared capacity) plus O(uniques) hash metadata —
    collision lanes for both key columns, and the partition uniques'
    first-occurrence positions + raw keys from which every process
    derives the identical decode table. NO vocabulary remap work rides
    this exchange; codes are assigned by the device collective."""
    n_rows: int
    pid_u1: np.ndarray
    pid_u2: np.ndarray
    pk_u1: Optional[np.ndarray]
    pk_u2: Optional[np.ndarray]
    pk_keys: Optional[np.ndarray]
    pk_pos: Optional[np.ndarray]  # shard-local first positions


@dataclasses.dataclass
class _HashShardEncoding:
    """One process's hash-encoded shard: (n, 3) uint32 hash-row columns
    (or int32 pk codes when publicly encoded) + its exchange meta."""
    pid_hash: np.ndarray
    pk_col: np.ndarray
    values: np.ndarray
    meta: _HashShardMeta


def _hash_encode_shard(chunks, public_partitions,
                       nonfinite: str) -> _HashShardEncoding:
    """Host-local hash encode of one input shard (no device work): the
    hash-mode counterpart of encode_shard — chunk hashing only, chunk
    uniques collected with shard-local first positions, no merge."""
    partition_vocab = None
    if public_partitions is not None:
        partition_vocab = list(dict.fromkeys(public_partitions))
    from pipelinedp_tpu import device_encode, executor
    value_dtype = np.dtype(executor._ftype())
    pid_cols, pk_cols, vals = [], [], []
    pid_u1, pid_u2 = [], []
    pk_u1, pk_u2, pk_keys, pk_pos = [], [], [], []
    offset = 0
    for chunk in chunks:
        pid_raw, pk_raw, values = chunk
        pid_raw = columnar._as_key_array(pid_raw)
        h1, h2 = hash_key_column_pair(pid_raw)
        u1, u2, _, _ = _hash_uniques(h1, h2, None)
        pid_u1.append(u1)
        pid_u2.append(u2)
        pk_valid = None
        if partition_vocab is not None:
            pk_col = columnar.encode_with_vocab(
                columnar._as_key_array(pk_raw), partition_vocab)
        else:
            pk_raw = columnar._as_key_array(pk_raw)
            k1, k2 = hash_key_column_pair(pk_raw)
            ku1, ku2, keys, first = _hash_uniques(k1, k2, pk_raw)
            pk_u1.append(ku1)
            pk_u2.append(ku2)
            pk_keys.append(keys)
            pk_pos.append(first + offset)
        values = np.asarray(values, dtype=value_dtype)
        bad = columnar.nonfinite_value_rows(values, nonfinite)
        if bad is not None:
            if partition_vocab is not None:
                pk_col = np.where(bad, np.int32(-1),
                                  pk_col).astype(np.int32)
            else:
                pk_valid = ~bad
            mask = bad if values.ndim == 1 else bad[:, None]
            values = np.where(mask, 0.0, values).astype(value_dtype)
        if partition_vocab is None:
            pk_col = device_encode.pack_hash_rows(k1, pk_valid)
        pid_cols.append(device_encode.pack_hash_rows(h1))
        pk_cols.append(pk_col)
        vals.append(values)
        offset += len(pid_raw)
    public = partition_vocab is not None
    empty_hash = np.empty((0, 3), np.uint32)
    pid_hash = np.concatenate(pid_cols) if pid_cols else empty_hash
    if pk_cols:
        pk_col = np.concatenate(pk_cols)
    else:
        pk_col = np.empty(0, np.int32) if public else empty_hash
    values = np.concatenate(vals) if vals else np.zeros(0, value_dtype)
    meta = _HashShardMeta(
        n_rows=int(len(pid_hash)),
        pid_u1=_concat_u64(pid_u1), pid_u2=_concat_u64(pid_u2),
        pk_u1=None if public else _concat_u64(pk_u1),
        pk_u2=None if public else _concat_u64(pk_u2),
        pk_keys=None if public else (np.concatenate(pk_keys)
                                     if pk_keys else np.empty(0, object)),
        pk_pos=None if public else (np.concatenate(pk_pos)
                                    if pk_pos else np.empty(0, np.int64)))
    return _HashShardEncoding(pid_hash, pk_col, values, meta)


def _concat_u64(arrays) -> np.ndarray:
    arrays = [a for a in arrays if len(a)]
    return np.concatenate(arrays) if arrays else np.empty(0, np.uint64)


def _pad_rows_to(col: np.ndarray, cap: int, fill, dtype) -> np.ndarray:
    out = np.full((cap,) + col.shape[1:], fill, dtype)
    out[:len(col)] = col
    return out


def _encode_local_shard_hash(chunks, mesh, public_partitions, nonfinite,
                             exchange) -> columnar.EncodedData:
    """The encode_mode="hash_device" body of encode_local_shard_to_mesh.

    This process hashes ONLY its own shard (no vocabulary work at all),
    the byte exchange moves O(uniques) collision/decode metadata, the
    padded (n, 3) hash rows upload as this process's slice of the global
    mesh-sharded array, and the dense first-occurrence codes come out of
    the device collective factorize (device_encode.mesh_factorize_codes:
    one all_gather of compacted per-shard uniques + a replicated merge
    every shard computes identically). A detected hash collision is
    derived identically by every process from the same exchanged metas,
    so all processes fall back to the host encoder together.
    """
    import pickle

    import jax
    import jax.numpy as jnp

    from pipelinedp_tpu import device_encode, executor
    from pipelinedp_tpu.parallel import mesh as mesh_lib
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry
    from pipelinedp_tpu.runtime import trace as rt_trace

    value_dtype = np.dtype(executor._ftype())
    public = public_partitions is not None
    reiterable = iter(chunks) is not chunks
    with rt_trace.span("ingest.local_shard", encode="hash_device") as sp:
        shard = _hash_encode_shard(chunks, public_partitions, nonfinite)
        sp.set(rows=shard.meta.n_rows)
        rt_telemetry.record("pipeline_device_encode_chunks")
    if exchange is None:
        if mesh_lib.process_count() == 1:
            exchange = lambda payload: [payload]  # noqa: E731 - trivial single-process identity
        else:
            exchange = _collective_allgather_bytes
    with rt_trace.span("ingest.vocab_exchange", encode="hash_device") as sp:
        payload = pickle.dumps(shard.meta)
        sp.set(bytes=len(payload))
        metas = [pickle.loads(p) for p in exchange(payload)]
    my_p = mesh_lib.process_index()
    if not 0 <= my_p < len(metas):
        raise ValueError(
            f"vocabulary exchange returned {len(metas)} shard metas but "
            f"this is process {my_p} — every pod process must "
            f"participate exactly once")
    # Global collision gate — identical on every process (same metas),
    # so the fallback decision can never diverge across the pod.
    try:
        _, _, n_pid_global, _ = device_encode.merge_hash_uniques(
            [m.pid_u1 for m in metas], [m.pid_u2 for m in metas],
            what="privacy-id")
        pk_table = None
        if not public:
            # Positions become global by offsetting each process's
            # shard-local first positions with its stream offset.
            offsets = np.cumsum([0] + [m.n_rows for m in metas[:-1]])
            pk_table = device_encode.merge_hash_uniques(
                [m.pk_u1 for m in metas], [m.pk_u2 for m in metas],
                [m.pk_keys for m in metas],
                [m.pk_pos + off for m, off in zip(metas, offsets)],
                what="partition")
    except device_encode.HashCollisionError as err:
        rt_telemetry.record("ingest_hash_collisions")
        logging.warning(
            "hash-device pod ingest detected a 64-bit key-hash "
            "collision (%s); every process falls back to the exact "
            "host encoder together.", err)
        if not reiterable:
            raise device_encode.HashCollisionError(
                f"{err} — and the chunk source is a one-shot iterator, "
                f"so the exact host-encoder fallback cannot re-read it. "
                f"Pass a re-iterable source or encode_mode='host'."
            ) from err
        return encode_local_shard_to_mesh(
            chunks, mesh, public_partitions=public_partitions,
            nonfinite=nonfinite, exchange=exchange, encode_mode="host")
    n_local_dev = max(len(mesh_lib.local_devices(mesh)), 1)
    n_dev = int(mesh.devices.size)
    cap, simulated = _pod_row_capacity([m.n_rows for m in metas], mesh)
    local_rows = cap * n_local_dev
    global_rows = cap * n_dev
    sent32 = int(device_encode._U32_MAX)
    pid_local = _pad_rows_to(shard.pid_hash, local_rows, sent32,
                             np.uint32)
    if public:
        pk_local = _pad_rows_to(shard.pk_col, local_rows, -1, np.int32)
    else:
        pk_local = _pad_rows_to(shard.pk_col, local_rows, sent32,
                                np.uint32)
    values_local = _pad_rows_to(shard.values, local_rows, 0, value_dtype)
    from jax.sharding import NamedSharding, PartitionSpec
    sharding = NamedSharding(mesh, PartitionSpec(mesh_lib.SHARD_AXIS))

    def to_global(col):
        if mesh_lib.process_count() == 1:
            return jax.device_put(jnp.asarray(col), sharding)
        return jax.make_array_from_process_local_data(
            sharding, col, (global_rows,) + col.shape[1:])

    pid_codes, n_pid_dev = device_encode.mesh_factorize_codes(
        mesh, to_global(pid_local))
    if public:
        pk = to_global(pk_local)
        vocab = list(dict.fromkeys(public_partitions))
    else:
        pk, n_pk_dev = device_encode.mesh_factorize_codes(
            mesh, to_global(pk_local))
        if not simulated and n_pk_dev != pk_table[2]:
            raise RuntimeError(
                f"device collective factorize found {n_pk_dev} distinct "
                f"partition hashes but the exchanged metas merge to "
                f"{pk_table[2]} (internal invariant)")
        # Code order (global first occurrence) is host-derivable from
        # the exchanged positions, so the decode table covers codes
        # whose rows live on other hosts too.
        s1, keys, n_pk, pos = pk_table
        code_hashes = s1[np.argsort(pos, kind="stable")]
        vocab = device_encode.HashVocab(n_pk, s1, keys,
                                        hash_by_code_host=code_hashes)
    pid = jnp.maximum(pid_codes, 0)
    return columnar.EncodedData(
        pid=pid,
        pk=pk,
        values=to_global(values_local),
        partition_vocab=vocab,
        n_privacy_ids=int(n_pid_global),
        public_encoded=public)
