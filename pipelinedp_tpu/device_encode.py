"""On-device hash factorization: the device half of hash-keyed ingest.

The host vocabulary stitch (ingest.ChunkedVocabEncoder) is the last
sequential O(rows-ish) stage of the ingest path: every chunk's uniques
are remapped against a growing host vocabulary before the rows may
upload. The hash-device encode mode removes it: chunk workers only
*hash* raw keys to uint64 (ingest.hash_key_column — vectorized, order
independent, parallel), the raw hash columns stream host->device once
through the standard accumulator, and *this* module assigns the dense
integer codes INSIDE jit:

  * ``factorize_codes`` — single-array sort/unique factorization. One
    stable three-key ``lax.sort`` over (hash_hi, hash_lo, row position)
    lands equal hashes adjacent with their occurrences in stream order;
    boundary masks + a cumsum yield hash-order unique ids; ranking the
    uniques by their first-occurrence row position converts them to
    FIRST-OCCURRENCE codes — exactly the codes ``pandas.factorize`` (and
    the chunked host encoder) assigns to the concatenated stream, so the
    hash-encoded kernel inputs are bit-identical to the host-encoded
    ones and release bit-identical noise (absent 128-bit hash
    collisions, which the host-side detector below catches).
  * ``mesh_factorize_codes`` — the pod form: each shard sort/uniques its
    local hash rows, the compacted per-shard uniques (with their global
    first-occurrence positions) cross the mesh in ONE ``lax.all_gather``
    — O(uniques), never rows — and every shard derives the identical
    global first-occurrence vocabulary and remaps its own rows in place.
    This replaces the pickled host vocabulary exchange of
    ``ingest.encode_local_shard_to_mesh`` with a device collective.

Hashes travel as (n, 2) uint32 lane pairs, not uint64 scalars: TPUs run
with x64 disabled, where a uint64 column would silently truncate to 32
bits and put collisions at the ~2^16-unique birthday bound.

Decode is DEFERRED: the host never materializes a code->key vocabulary.
``HashVocab`` carries the device ``hash_by_code`` columns plus a
hash-sorted (hash -> raw key) table assembled from the chunk workers'
per-chunk uniques, and looks keys up ONLY for the partition indices the
DP selection actually kept (executor._decode_rows prefetches exactly
those) — an O(kept) fetch through ``mesh.host_fetch``, matching the
release-taint discipline of the blocked drivers.

Collision safety: workers hash every key with TWO independent 64-bit
lanes; ``merge_hash_uniques`` verifies (vectorized, over uniques only)
that no primary hash maps to two secondary hashes. A detected collision
raises ``HashCollisionError`` and the ingest route falls back to the
exact host encoder (bit-identical by construction); an *undetected*
collision requires both independent 64-bit lanes to collide at once
(~2^-128 per pair).
"""

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from pipelinedp_tpu.parallel import mesh as mesh_lib
from pipelinedp_tpu.parallel.mesh import SHARD_AXIS, host_fetch, shard_map
from pipelinedp_tpu.runtime import telemetry as rt_telemetry
from pipelinedp_tpu.runtime import trace as rt_trace

# Invalid/pad marker: both uint32 lanes at their maximum. The host hash
# remaps a real key hashing to uint64-max down by one, so the sentinel
# is unreachable from data (ingest.hash_key_column).
HASH_SENTINEL = (1 << 64) - 1
_U32_MAX = np.uint32(0xFFFFFFFF)


class HashCollisionError(ValueError):
    """Two distinct raw keys collided on the primary 64-bit key hash.

    Raised by the hash-device ingest mode when its detector trips; the
    ingest route catches it and falls back to the exact host encoder
    when the chunk source is re-iterable.
    """


def pack_hash_rows(h: np.ndarray,
                   valid: Optional[np.ndarray] = None) -> np.ndarray:
    """uint64[n] -> (n, 3) uint32 device rows [hash_hi, hash_lo, valid].

    The explicit valid lane keeps the two invalidity notions apart: a
    pad/sentinel row (both hash lanes at max) never enters the
    vocabulary, while a REAL key on an invalid row (nonfinite-dropped)
    still claims its vocabulary slot — matching the host encoder, whose
    vocabulary order is first occurrence over ALL rows — but codes to -1
    like the host's pk mark.
    """
    out = np.empty((len(h), 3), np.uint32)
    out[:, 0] = (h >> np.uint64(32)).astype(np.uint32)
    out[:, 1] = (h & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    out[:, 2] = 1 if valid is None else valid.astype(np.uint32)
    return out


def join_hash64(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """(hi, lo) uint32 lanes -> uint64 hashes (host side)."""
    return ((hi.astype(np.uint64) << np.uint64(32)) |
            lo.astype(np.uint64))


# ---------------------------------------------------------------------------
# Host-side unique merge: collision detection + the deferred decode table
# ---------------------------------------------------------------------------


def _concat(arrays: Sequence[np.ndarray], dtype=None) -> np.ndarray:
    arrays = [a for a in arrays if len(a)]
    if not arrays:
        return np.empty(0, dtype or np.uint64)
    if len(arrays) == 1:
        return arrays[0]
    return np.concatenate(arrays)


def merge_hash_uniques(
        h1_chunks: Sequence[np.ndarray],
        h2_chunks: Sequence[np.ndarray],
        key_chunks: Optional[Sequence[np.ndarray]] = None,
        pos_chunks: Optional[Sequence[np.ndarray]] = None,
        what: str = "key",
) -> Tuple[np.ndarray, Optional[np.ndarray], int, Optional[np.ndarray]]:
    """Merges per-chunk unique (h1, h2[, key][, pos]) tuples.

    Fully vectorized (one sort over the total chunk-unique count —
    never rows): dedupes by (h1, h2) pair, verifies every primary hash
    maps to exactly one secondary hash (two secondaries = two distinct
    raw keys collided on h1 -> HashCollisionError), and returns
    ``(sorted_unique_h1, keys_or_None, n_unique, first_pos_or_None)`` —
    the hash-sorted decode table HashVocab searches at selection time,
    with each hash's FIRST-occurrence key and (when positions are
    given) minimum stream position, from which the pod path derives the
    code order on host. The entries it sorts are the counter
    hash_unique_entries.

    The sort is ONE stable argsort of h1. Each chunk holds a hash at
    most once and the chunks come in stream order (both callers
    concatenate them so), so a hash's entries lie in stream order and
    the first of them is the first occurrence.
    """
    h1 = _concat(h1_chunks)
    rt_telemetry.record("hash_unique_entries", len(h1))
    h2 = _concat(h2_chunks)
    keys = _concat(key_chunks, dtype=object) if key_chunks is not None \
        else None
    pos = _concat(pos_chunks, dtype=np.int64) if pos_chunks is not None \
        else None
    if len(h1) == 0:
        return (h1, (keys if keys is None else keys[:0]), 0,
                (pos if pos is None else pos[:0]))
    order = np.argsort(h1, kind="stable")
    s1, s2 = h1[order], h2[order]
    new1 = np.empty(len(s1), bool)
    new1[0] = True
    np.not_equal(s1[1:], s1[:-1], out=new1[1:])
    # An h1 run whose h2 changes: two distinct raw keys collided on h1.
    clash = ~new1[1:] & (s2[1:] != s2[:-1])
    if clash.any():
        bad = int(np.argmax(clash)) + 1
        raise HashCollisionError(
            f"uint64 hash collision among {what} keys: primary hash "
            f"{int(s1[bad])} maps to (at least) two distinct raw keys "
            f"(secondary lanes {int(s2[bad - 1])} != {int(s2[bad])}) — "
            f"{len(np.unique(s1[1:][clash]))} colliding primary hash(es) "
            f"total")
    first = order[new1]
    return (s1[new1], None if keys is None else keys[first],
            int(new1.sum()), None if pos is None else pos[first])


# ---------------------------------------------------------------------------
# Device factorization kernels
# ---------------------------------------------------------------------------


def _boundary(shi, slo):
    first = jnp.ones(1, bool)
    return jnp.concatenate(
        [first, (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1])])


@jax.jit
def _factorize_kernel(hashes):
    """(n, 3) uint32 [hash_hi, hash_lo, valid] rows -> first-occurrence
    dense codes.

    The vocabulary (and its first-occurrence order) is built over every
    non-sentinel row — valid or not — exactly as the host encoder
    factorizes the raw column before rows are invalidated; the CODE of
    an invalid row is -1 (sentinel rows too).

    Two payload-carrying sorts + one unique-indices scatter (the XLA
    diet the DP kernel itself is built on — no duplicate-index scatters,
    which serialize on every backend):

      1. sort by (hash, row) — equal hashes land adjacent with their
         occurrences in row order, so the run head IS the first
         occurrence, broadcast down the run by a cummax;
      2. sort by (first-occurrence position) — run boundaries now
         enumerate the uniques in first-occurrence order, so a cumsum
         IS the code; one permutation scatter routes codes back to row
         order.

    Returns (codes int32[n], n_unique int32). The code -> key-hash map
    is NOT materialized on device: it is host-derivable from the chunk
    workers' O(uniques) tables, which is where HashVocab gets it.
    """
    hi, lo, vflag = hashes[:, 0], hashes[:, 1], hashes[:, 2]
    n = hi.shape[0]
    i32 = jnp.int32
    big = jnp.iinfo(jnp.int32).max
    pos = jnp.arange(n, dtype=i32)
    shi, slo, spos, svalid = jax.lax.sort((hi, lo, pos, vflag),
                                          num_keys=3)
    sentinel_s = (shi == _U32_MAX) & (slo == _U32_MAX)
    new = _boundary(shi, slo) & ~sentinel_s
    n_unique = new.sum().astype(i32)
    # First-occurrence row of each sorted row's unique: spos at the run
    # start (spos ascends within a run), broadcast by cummax.
    start_at = jax.lax.cummax(jnp.where(new, pos, -1))
    first_pos = jnp.where(sentinel_s, big,
                          spos[jnp.maximum(start_at, 0)])
    dropped = (sentinel_s | (svalid != 1)).astype(i32)
    fp2, spos2, drop2 = jax.lax.sort((first_pos, spos, dropped),
                                     num_keys=1)
    new2 = jnp.concatenate(
        [jnp.ones(1, bool), fp2[1:] != fp2[:-1]])
    code2 = jnp.cumsum(new2.astype(i32)) - 1
    codes = jnp.zeros(n, i32).at[spos2].set(
        jnp.where(drop2 == 1, -1, code2), unique_indices=True)
    return codes, n_unique


factorize_codes = rt_trace.probe_jit("device_factorize", _factorize_kernel)


def prefers_lookup_codes() -> bool:
    """Which in-jit code-assignment kernel fits this backend.

    Accelerators keep the self-contained sort/unique factorize — sorts
    are the fast path on TPU (the DP kernel itself is built on them) and
    gathers are not. The CPU backend's comparator-based XLA sort loses
    badly to the O(n log V) vectorized binary search against the
    host-side unique table (which the collision detector and deferred
    decode already require), so CPU runs take the lookup kernel — same
    codes, proven by the parity tests. Mirrors the backend dispatch of
    runtime/pipeline._donation_supported.
    """
    return jax.default_backend() == "cpu"


def build_lookup_table(sorted_hashes: np.ndarray,
                       first_pos: np.ndarray):
    """Device operands of the lookup kernel from the merged unique
    table: (hash lanes (Vcap, 2) uint32, first-occurrence code of each
    hash-sorted entry (Vcap,) int32), sentinel-padded to a rounded
    capacity so repeated vocabulary sizes reuse one compiled program."""
    v = len(sorted_hashes)
    cap = mesh_lib.round_capacity(v)
    lanes = np.full((cap, 2), _U32_MAX, np.uint32)
    lanes[:v, 0] = (sorted_hashes >> np.uint64(32)).astype(np.uint32)
    lanes[:v, 1] = (sorted_hashes &
                    np.uint64(0xFFFFFFFF)).astype(np.uint32)
    codes = np.full(cap, -1, np.int32)
    order = np.argsort(first_pos, kind="stable")
    codes[order] = np.arange(v, dtype=np.int32)
    return jnp.asarray(lanes), jnp.asarray(codes)


@jax.jit
def _lookup_kernel(rows, table, table_codes):
    """In-jit code assignment by vectorized binary search of each row's
    hash in the host-merged unique table: log2(Vcap) gather rounds over
    the (Vcap, 2) table — no sort, no scatter. Identical codes to
    _factorize_kernel (the table's codes ARE first-occurrence ranks)."""
    rhi, rlo, vflag = rows[:, 0], rows[:, 1], rows[:, 2]
    thi, tlo = table[:, 0], table[:, 1]
    v_cap = thi.shape[0]
    n = rhi.shape[0]
    i32 = jnp.int32
    lo_i = jnp.zeros(n, i32)
    hi_i = jnp.full(n, v_cap, i32)
    # v_cap.bit_length() halvings drive the [lo, hi) interval from
    # v_cap to 0 — (v_cap - 1).bit_length() would leave a 1-wide
    # interval unresolved for half the keys.
    for _ in range(max(1, v_cap.bit_length())):
        mid = (lo_i + hi_i) >> 1
        mh, ml = thi[mid], tlo[mid]
        less = (mh < rhi) | ((mh == rhi) & (ml < rlo))
        lo_i = jnp.where(less, mid + 1, lo_i)
        hi_i = jnp.where(less, hi_i, mid)
    pos = jnp.minimum(lo_i, v_cap - 1)
    dropped = ((rhi == _U32_MAX) & (rlo == _U32_MAX)) | (vflag != 1)
    return jnp.where(dropped, -1, table_codes[pos])


lookup_codes = rt_trace.probe_jit("device_encode_lookup", _lookup_kernel)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _mesh_unique_cap_kernel(hashes, mesh: Mesh):
    """Replicated int32[] = max per-shard local unique count — the one
    control scalar the mesh factorize needs before compiling its
    all_gather capacity (same two-phase pattern as reshard's count
    stats)."""

    def per_shard(h_s):
        hi_s, lo_s = h_s[:, 0], h_s[:, 1]
        pos = jnp.arange(hi_s.shape[0], dtype=jnp.int32)
        shi, slo, _ = jax.lax.sort((hi_s, lo_s, pos), num_keys=3)
        sentinel_s = (shi == _U32_MAX) & (slo == _U32_MAX)
        n_new = (_boundary(shi, slo) & ~sentinel_s).sum().astype(jnp.int32)
        return jax.lax.pmax(n_new, SHARD_AXIS)

    fn = shard_map(per_shard, mesh=mesh, in_specs=(P(SHARD_AXIS),),
                   out_specs=P())
    return fn(hashes)


@functools.partial(jax.jit, static_argnames=("uniq_cap", "mesh"))
def _mesh_factorize_kernel(hashes, uniq_cap: int, mesh: Mesh):
    """Sharded first-occurrence factorize: local sort/unique, ONE
    all_gather of the compacted [D, uniq_cap] unique tables (hash lanes
    + global first-occurrence positions — O(uniques), never rows), a
    replicated global merge every shard computes identically, then each
    shard remaps its own rows in place. Returns (codes int32 sharded
    like the input rows, n_unique replicated int32)."""
    n_shards = mesh.devices.size
    U = n_shards * uniq_cap

    def per_shard(h_s):
        hi_s, lo_s, vflag = h_s[:, 0], h_s[:, 1], h_s[:, 2]
        local = hi_s.shape[0]
        i32 = jnp.int32
        big = jnp.iinfo(jnp.int32).max
        me = jax.lax.axis_index(SHARD_AXIS).astype(i32)
        pos = jnp.arange(local, dtype=i32)
        shi, slo, spos, svalid = jax.lax.sort((hi_s, lo_s, pos, vflag),
                                              num_keys=3)
        sentinel_s = (shi == _U32_MAX) & (slo == _U32_MAX)
        new = _boundary(shi, slo) & ~sentinel_s
        lseg = jnp.cumsum(new.astype(i32)) - 1  # local hash-order uid
        n_new = new.sum().astype(i32)
        # Compact the local uniques to the front IN HASH ORDER (so the
        # compacted slot of a unique == its lseg), carrying each
        # unique's global first-occurrence position. Shards own
        # contiguous stream slices in device order, so global position
        # order == stream order for real rows (pads are sentinels).
        sort_key = jnp.where(new, i32(0), i32(1))
        gpos = me * local + spos  # rows where new=True start their run
        _, chi, clo, cpos = jax.lax.sort((sort_key, shi, slo, gpos),
                                         num_keys=4)
        rank = jnp.arange(uniq_cap, dtype=i32)
        live = rank < n_new
        chi = jnp.where(live, chi[:uniq_cap], _U32_MAX)
        clo = jnp.where(live, clo[:uniq_cap], _U32_MAX)
        cpos = jnp.where(live, cpos[:uniq_cap], big)
        # O(uniques) collective: every shard receives every shard's
        # compacted unique table.
        g_hi = jax.lax.all_gather(chi, SHARD_AXIS).reshape(U)
        g_lo = jax.lax.all_gather(clo, SHARD_AXIS).reshape(U)
        g_pos = jax.lax.all_gather(cpos, SHARD_AXIS).reshape(U)
        # Replicated global merge (identical on every shard): dedupe by
        # hash, first occurrence = min global position, rank by it.
        gslot0 = jnp.arange(U, dtype=i32)
        ghi, glo, gp, gslot = jax.lax.sort((g_hi, g_lo, g_pos, gslot0),
                                           num_keys=3)
        ginvalid = (ghi == _U32_MAX) & (glo == _U32_MAX)
        gnew = _boundary(ghi, glo) & ~ginvalid
        gseg = jnp.cumsum(gnew.astype(i32)) - 1
        gstart = jax.lax.cummax(jnp.where(gnew, gslot0, -1))
        gfirst = gp[jnp.maximum(gstart, 0)]
        uslot = jnp.where(gnew, gseg, U)
        first_by_u = jnp.full(U + 1, big, i32).at[uslot].set(
            jnp.where(gnew, gfirst, big))[:U]
        perm = jnp.argsort(first_by_u)
        inv = jnp.zeros(U, i32).at[perm].set(gslot0)
        code_sorted = jnp.where(ginvalid, -1, inv[jnp.maximum(gseg, 0)])
        # Route codes back to the gathered slots, then slice this
        # shard's window: compacted local unique k (== lseg k) sits at
        # gathered slot me * uniq_cap + k.
        remap = jnp.full(U, -1, i32).at[gslot].set(code_sorted)
        my_remap = jax.lax.dynamic_slice(remap, (me * uniq_cap,),
                                         (uniq_cap,))
        dropped = sentinel_s | (svalid != 1)
        codes_s = jnp.where(dropped, -1,
                            my_remap[jnp.minimum(jnp.maximum(lseg, 0),
                                                 uniq_cap - 1)])
        codes = jnp.zeros(local, i32).at[spos].set(codes_s,
                                                   unique_indices=True)
        n_unique = jax.lax.pmax(gnew.sum().astype(i32), SHARD_AXIS)
        return codes, n_unique

    fn = shard_map(per_shard, mesh=mesh, in_specs=(P(SHARD_AXIS),),
                   out_specs=(P(SHARD_AXIS), P()))
    return fn(hashes)


_mesh_unique_cap_kernel = rt_trace.probe_jit("device_encode_unique_cap",
                                             _mesh_unique_cap_kernel)
_mesh_factorize_kernel = rt_trace.probe_jit("device_encode_mesh_factorize",
                                            _mesh_factorize_kernel)


def mesh_factorize_codes(mesh: Mesh, hashes) -> Tuple[jax.Array, int]:
    """Two-phase meshed factorize of row-sharded (n, 3) hash rows.

    Phase 1 fetches ONE replicated scalar (the max per-shard unique
    count) to fix the all_gather capacity — capacity-rounded so repeated
    pods of similar vocabulary size reuse the compiled program; phase 2
    is the collective factorize. Returns (codes sharded int32[n],
    n_unique host int).
    """
    cap_dev = _mesh_unique_cap_kernel(hashes, mesh)
    uniq_cap = mesh_lib.round_capacity(int(host_fetch(cap_dev)))
    codes, n_unique = _mesh_factorize_kernel(hashes, uniq_cap, mesh)
    return codes, int(host_fetch(n_unique))


# ---------------------------------------------------------------------------
# Deferred decode
# ---------------------------------------------------------------------------


class HashVocab:
    """Partition vocabulary of the hash-encoded path: decode deferred to
    DP-selected indices.

    Sequence-compatible (``len``, integer ``__getitem__``) so the
    executor's emit loops index it exactly like a host vocabulary — but
    a raw key is only looked up (hash-sorted table binary search) when
    its partition was actually selected: ``prefetch`` resolves exactly
    the kept codes in one O(kept) batch; an unprefetched ``__getitem__``
    (generic framework paths walking the whole vocabulary) degrades to
    one whole-table materialization.

    The code -> key-hash order is derived on HOST from the chunk
    workers' O(uniques) tables and their first-occurrence positions
    (``merge_hash_uniques``) — it covers codes whose rows live on other
    pod hosts, and it means decode performs zero device->host traffic.
    """

    def __init__(self, n_codes: int, table_hashes: np.ndarray,
                 table_keys: np.ndarray,
                 hash_by_code_host: np.ndarray = None):
        if hash_by_code_host is None or len(hash_by_code_host) != \
                int(n_codes):
            raise ValueError(
                f"HashVocab: hash_by_code_host must carry one hash per "
                f"code ({n_codes}), got "
                f"{None if hash_by_code_host is None else len(hash_by_code_host)}")
        self._n = int(n_codes)
        self._table_hashes = table_hashes  # uint64, ascending
        self._table_keys = table_keys
        self._host = hash_by_code_host  # uint64[n_codes]
        self._cache = {}  # code -> decoded raw key

    def __len__(self) -> int:
        return self._n

    def _keys_for_hashes(self, hashes: np.ndarray) -> np.ndarray:
        pos = np.searchsorted(self._table_hashes, hashes)
        in_range = pos < len(self._table_hashes)
        if not (in_range.all() and
                bool((self._table_hashes[np.minimum(
                    pos, len(self._table_hashes) - 1)] == hashes).all())):
            raise RuntimeError(
                "hash-device decode table is missing a selected "
                "partition's key hash — the device factorize and the "
                "host unique merge disagree (internal invariant)")
        return self._table_keys[pos]

    def prefetch(self, codes) -> None:
        """Resolves a batch of partition codes to raw keys in one
        O(kept) lookup — call with exactly the DP-selected indices."""
        need = sorted({
            int(c)
            for c in codes if 0 <= int(c) < self._n and
            int(c) not in self._cache
        })
        if not need:
            return
        idx = np.fromiter(need, np.int64, len(need))
        for code, key in zip(need,
                             self._keys_for_hashes(self._host[idx])):
            self._cache[code] = key

    def __getitem__(self, code):
        code = int(code)
        if not 0 <= code < self._n:
            raise IndexError(code)
        if code not in self._cache:
            # Unprefetched access: a generic path is walking the whole
            # vocabulary — materialize the code->key map once.
            self.prefetch(range(self._n))
        return self._cache[code]
