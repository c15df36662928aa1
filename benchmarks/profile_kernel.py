"""Phase timing of the main fused kernel: bounding sort vs reduce vs
finalize, sort key-count scaling, and payload-carry vs gather variants.

Not measured on today's code (PERF.md holds what has been).
"""
import functools
import os
import time

import _common

_common.path_setup()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from pipelinedp_tpu import executor  # noqa: E402

n = int(os.environ.get("BENCH_ROWS", 2**25))
P = int(os.environ.get("BENCH_P", 4096))

_, cfg, stds, (min_v, max_v, min_s, max_s, mid) = _common.build_spec(P)

key = jax.random.PRNGKey(0)


@jax.jit
def make(k):
    kp, ku, kv = jax.random.split(k, 3)
    u = jax.random.uniform(kp, (n,))
    pk = (jnp.power(u, 3.0) * P).astype(jnp.int32)
    pid = jax.random.randint(ku, (n,), 0, 1_000_000, dtype=jnp.int32)
    values = jax.random.uniform(kv, (n,), minval=0.0, maxval=5.0)
    return pid, pk, values, jnp.ones((n,), bool)


@jax.jit
def phase_bound(pid, pk, values, valid, k):
    spk, keep, pair, cols, _ = executor.bounded_row_columns(
        pid, pk, values, valid, min_v, max_v, min_s, max_s, mid, k, cfg)
    return spk, keep, pair, cols


@jax.jit
def phase_reduce(spk, keep, pair, cols):
    return executor.reduce_rows_to_partitions(spk, keep, pair, cols, P, 0)


@jax.jit
def phase_finalize(dense, k):
    return executor.finalize(dense, min_v, mid, jnp.asarray(stds), k, cfg)


@jax.jit
def sort_only(pid, pk, values, valid, k):
    # The 5-key bounding sort in isolation.
    key_total, key_linf, key_l0 = jax.random.split(k, 3)
    pk_sent = jnp.where(valid, pk, P).astype(jnp.int32)
    pid_sent = jnp.where(valid, pid, jnp.iinfo(jnp.int32).max)
    h0, h1 = executor._pair_hash(pid_sent, pk_sent, key_l0)
    rand = jax.random.uniform(key_linf, (n,))
    (spid, _, _, spk, _), pay = executor._sort_rows(
        [pid_sent, h0, h1, pk_sent, rand], [values, valid])
    return spid[0] + spk[-1]


_sync = jax.block_until_ready


def timed(fn, *args, reps=3):
    out = fn(*args)
    _sync(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        _sync(out)
        ts.append(time.perf_counter() - t0)
    return min(ts), out


@functools.partial(jax.jit, static_argnames=("nkeys",))
def sort_scaling(pid, pk, values, valid, nkeys):
    cols = [pid, pk.astype(jnp.uint32),
            (pid * 7919).astype(jnp.uint32), values,
            (pk * 31).astype(jnp.float32)][:nkeys]
    out = jax.lax.sort(tuple(cols) + (values, valid), num_keys=nkeys)
    return out[0][0]


@jax.jit
def sort_gather_variant(pid, pk, values, valid, k):
    # Same 5 keys, but carry a row index and gather the payloads after —
    # narrower sort records vs two extra gather passes.
    key_total, key_linf, key_l0 = jax.random.split(k, 3)
    pk_sent = jnp.where(valid, pk, P).astype(jnp.int32)
    pid_sent = jnp.where(valid, pid, jnp.iinfo(jnp.int32).max)
    h0, h1 = executor._pair_hash(pid_sent, pk_sent, key_l0)
    rand = jax.random.uniform(key_linf, (n,))
    iota = jnp.arange(n, dtype=jnp.int32)
    out = jax.lax.sort((pid_sent, h0, h1, pk_sent, rand, iota), num_keys=5)
    perm = out[5]
    return out[0][0] + values[perm][0] + valid[perm][0]


@jax.jit
def cumsum_cost(values):
    from pipelinedp_tpu.ops import segment_ops
    return segment_ops.chunked_cumsum(values)[-1]


@jax.jit
def scans_cost(values, pk):
    # The scan bundle the bounding phase runs besides the sort.
    from pipelinedp_tpu.ops import segment_ops
    new = segment_ops.boundary_mask(pk)
    seg, rank = segment_ops.segment_starts_and_ids(new)
    nxt = segment_ops.next_segment_start(new)
    c = segment_ops.chunked_cumsum(values)
    return seg[-1] + rank[-1] + nxt[-1] + c[-1]


data = make(key)
_sync(data)

# Null baseline: one dispatch + completion wait with no real compute
# (shared helper, min-of-3) — the floor under every number below.
print(f"null dispatch round trip: "
      f"{_common.null_roundtrip() * 1e3:.1f} ms", flush=True)

t_bound, bound = timed(phase_bound, *data, jax.random.fold_in(key, 1))
t_reduce, dense = timed(phase_reduce, *bound)
t_final, _ = timed(phase_finalize, dense, jax.random.fold_in(key, 2))
t_sort, _ = timed(sort_only, *data, jax.random.fold_in(key, 1))
print(f"rows={n}")
print(f"bound (sort5 + scans + clip): {t_bound*1e3:.0f} ms")
print(f"  of which bare 5-key sort:   {t_sort*1e3:.0f} ms")
print(f"reduce (1-key sort + cumsum): {t_reduce*1e3:.0f} ms")
print(f"finalize (select + noise):    {t_final*1e3:.0f} ms")
print(f"sum: {(t_bound+t_reduce+t_final)*1e3:.0f} ms "
      f"-> {n/(t_bound+t_reduce+t_final)/1e6:.1f}M rows/s", flush=True)

pid_, pk_, values_, valid_ = data
for nk in (1, 2, 3, 5):
    t_nk, _ = timed(sort_scaling, pid_, pk_, values_, valid_, nk)
    print(f"sort {nk} keys (+2 payload): {t_nk*1e3:.0f} ms", flush=True)
t_sg, _ = timed(sort_gather_variant, pid_, pk_, values_, valid_,
                jax.random.fold_in(key, 1))
print(f"sort 5 keys + iota, gather payloads after: {t_sg*1e3:.0f} ms",
      flush=True)
t_cs, _ = timed(cumsum_cost, values_)
print(f"chunked_cumsum: {t_cs*1e3:.1f} ms", flush=True)
t_sc, _ = timed(scans_cost, values_, pk_)
print(f"scan bundle (boundary+ranks+next+cumsum): {t_sc*1e3:.1f} ms",
      flush=True)


def time_packed_variants():
    """Key-packing experiment: (pid,h0)->i64 and (h1,pk)->i64 give a
    LOSSLESS 3-key sort with ordering identical to the 5-key original
    (all fields non-negative < 2^32, lexicographic order preserved by
    the shifts). TPU emulates int64 as register pairs, so comparator
    work per element is similar — the question the measurement answers
    is whether fewer lax.sort operands beat the packing overhead.

    Flips jax_enable_x64 globally (int64 is silently downcast without
    it); runs LAST in this script so earlier measurements keep the
    kernel's real f32/i32 dtypes."""
    jax.config.update("jax_enable_x64", True)
    try:

        @jax.jit
        def packed3(pid, pk, values, valid, k):
            _, key_linf, key_l0 = jax.random.split(k, 3)
            pk_sent = jnp.where(valid, pk, P).astype(jnp.int32)
            pid_sent = jnp.where(valid, pid, jnp.iinfo(jnp.int32).max)
            h0, h1 = executor._pair_hash(pid_sent, pk_sent, key_l0)
            rand = jax.random.uniform(key_linf, (n,), dtype=jnp.float32)
            # uint64, not int64: the high field spans the full uint32
            # range, and (h >= 2^31) << 32 would wrap a signed int64
            # negative — inverting the order vs the real sort's unsigned
            # uint32 comparisons.
            k1 = ((pid_sent.astype(jnp.uint32).astype(jnp.uint64) << 32)
                  | h0.astype(jnp.uint32).astype(jnp.uint64))
            k2 = ((h1.astype(jnp.uint32).astype(jnp.uint64) << 32)
                  | pk_sent.astype(jnp.uint32).astype(jnp.uint64))
            out = jax.lax.sort((k1, k2, rand, values, valid), num_keys=3)
            return out[0][0] + out[3][-1]

        @jax.jit
        def packed4(pid, pk, values, valid, k):
            # Half-packed: only (h0,h1) -> one i64 hash key.
            _, key_linf, key_l0 = jax.random.split(k, 3)
            pk_sent = jnp.where(valid, pk, P).astype(jnp.int32)
            pid_sent = jnp.where(valid, pid, jnp.iinfo(jnp.int32).max)
            h0, h1 = executor._pair_hash(pid_sent, pk_sent, key_l0)
            rand = jax.random.uniform(key_linf, (n,), dtype=jnp.float32)
            h64 = ((h0.astype(jnp.uint32).astype(jnp.uint64) << 32)
                   | h1.astype(jnp.uint32).astype(jnp.uint64))
            out = jax.lax.sort((pid_sent, h64, pk_sent, rand, values, valid),
                               num_keys=4)
            return out[0][0] + out[4][-1]

        for name, fn in (("3 keys (pid|h0, h1|pk, rand) i64-packed",
                          packed3),
                         ("4 keys (pid, h0|h1 i64, pk, rand)", packed4)):
            t, _ = timed(fn, pid_, pk_, values_, valid_,
                         jax.random.fold_in(key, 1))
            print(f"sort {name}: {t*1e3:.0f} ms", flush=True)
    finally:
        jax.config.update("jax_enable_x64", False)


time_packed_variants()
