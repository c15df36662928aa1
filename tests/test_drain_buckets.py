"""No program per kept count (PR 34): every drain of a kept-first
compacted release slices on the device to a BUCKET of the kept count
(runtime/pipeline.KeptPrefix, drain_bucket) and cuts to the count on the
host.

  * **The ladder** — drain_bucket over k = 0, 1, 8, 2^n, 2^n + 1, the
    column's length, and its caps.
  * **Pure indexing** — the stream every route emits is bitwise the form
    it had when the slice was `np.asarray(col)[:k]`: the dense drain on
    one device and on a 4-device mesh, a blocked job of several blocks,
    the journalled blocked path (a record holds exactly k rows) and
    select_partitions.
  * **Nothing is built for a new kept count** — after one job, jobs of
    the same shape under other noise seeds, whose kept counts differ but
    share a bucket, build no program (telemetry `backend_compiles`).
  * **What crosses is counted, and ends at the helper** — `d2h_bytes` is
    the prefixes' nbytes, and no row at or beyond k reaches
    executor._decode_rows.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pipelinedp_tpu as pdp
from pipelinedp_tpu import executor
from pipelinedp_tpu.parallel import large_p, make_mesh
from pipelinedp_tpu.runtime import journal as rt_journal
from pipelinedp_tpu.runtime import pipeline, telemetry
from tests.test_large_p import _spec

FLOOR = pipeline.DRAIN_MIN_ROWS  # 4096: the shortest bucket
DENSE_P = 6000  # a dense job's partitions = its columns' length
BLOCK = 1 << 13  # a block's capacity, likewise
BLOCKED_P = 4 * BLOCK


# --- (c) the ladder --------------------------------------------------------


@pytest.mark.parametrize("k,length,rows", [
    (0, 1 << 20, 0),  # nothing kept: nothing fetched
    (-1, 1 << 20, 0),
    (1, 1 << 20, FLOOR),  # the shortest prefix, whatever is kept under it
    (8, 1 << 20, FLOOR),
    (1540, 17_770, FLOOR),  # dense-netflix: every job of a window
    (1700, 17_770, FLOOR),  # meets one bucket
    (505, 1 << 20, FLOOR),  # keys-1e7's fullest block, either side of 512
    (525, 1 << 20, FLOOR),
    (FLOOR, 1 << 20, FLOOR),  # 2^n is its own bucket
    (FLOOR + 1, 1 << 20, 2 * FLOOR),  # 2^n + 1 the next
    (1 << 16, 1 << 20, 1 << 16),
    ((1 << 16) + 1, 1 << 20, 1 << 17),
    (1 << 20, 1 << 20, 1 << 20),  # k = the column's length: whole
    ((1 << 19) + 1, 1 << 20, 1 << 20),
    (5000, 6000, 6000),  # the bucket is capped at the column
    (4097, 10_000, 8192),
    (3, FLOOR, FLOOR),  # a column no longer than the floor goes whole
    (3, 100, 100),
    (100, 100, 100),
    (0, 100, 0),
])
def test_drain_bucket_ladder(k, length, rows):
    assert pipeline.drain_bucket(k, length) == rows


@pytest.mark.parametrize("k", [0, 1, 8, 1000, 4096, 4097, 5000, 6000])
def test_kept_prefix_is_the_first_k_rows(k):
    """KeptPrefix over a device id column, a 1-d and a 2-d column: host()
    is np.asarray(col)[:k] bitwise, owns its rows (no view of the fetched
    bucket, so no leftover row behind it), and the counters hold what
    crossed."""
    rng = np.random.default_rng(k)
    host = (rng.integers(0, 1 << 30, 6000).astype(np.int32),
            rng.normal(size=6000), rng.normal(size=(6000, 3)))
    device = tuple(jnp.asarray(a) for a in host)
    before = telemetry.snapshot()
    prefix = pipeline.KeptPrefix(device, k)
    rows = pipeline.drain_bucket(k, 6000)
    assert prefix.rows == rows
    assert prefix.nbytes == sum(
        rows * a[0].nbytes for a in host)  # bytes of `rows` rows of each
    got = prefix.host()
    moved = telemetry.delta(before)
    assert moved.get("drain_bucket_rows", 0) == rows
    assert moved.get("d2h_bytes", 0) == prefix.nbytes
    for a, g in zip(host, got):
        assert g.dtype == a.dtype and g.shape == a[:k].shape
        assert np.array_equal(g, a[:k])
        assert g.base is None or g.shape[0] == rows


def test_kept_prefix_cuts_a_host_array_without_a_fetch():
    """A batched lane hands host copies: cut, nothing dispatched or
    counted."""
    lane = np.arange(6000, dtype=np.int32)
    before = telemetry.snapshot()
    (got,) = pipeline.fetch_kept((lane,), 17)
    assert np.array_equal(got, lane[:17])
    moved = telemetry.delta(before)
    assert "d2h_bytes" not in moved and "drain_bucket_rows" not in moved


# --- the jobs --------------------------------------------------------------


def _rows(n=60_000, n_ids=9000, n_keys=DENSE_P, seed=5):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_ids, n),
            rng.permutation(np.arange(n) % n_keys),  # every key has rows
            rng.uniform(1, 5, n))


def _dense_job(noise_seed, mesh=None):
    """COUNT + SUM over 6,000 keys through the engine; the selection
    keeps a share of them that moves with the noise seed. Returns the
    release as a list, in emitted order."""
    pid, pk, values = _rows()
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=2,
        max_contributions_per_partition=1,
        min_value=1.0,
        max_value=5.0)
    ex = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                            partition_extractor=lambda r: r[1],
                            value_extractor=lambda r: r[2])
    acc = pdp.NaiveBudgetAccountant(total_epsilon=30.0, total_delta=1e-6)
    kw = {} if mesh is None else {"mesh": mesh}
    engine = pdp.DPEngine(acc, pdp.TPUBackend(noise_seed=noise_seed, **kw))
    step = len(pid) // 4
    chunks = [(pid[i:i + step], pk[i:i + step], values[i:i + step])
              for i in range(0, len(pid), step)]
    result = engine.aggregate(pdp.ChunkSource(chunks, encode_mode="host"),
                              params, ex)
    acc.compute_budgets()
    return list(result)


def _select_job(noise_seed):
    pid, pk, _ = _rows()
    acc = pdp.NaiveBudgetAccountant(total_epsilon=10.0, total_delta=1e-6)
    engine = pdp.DPEngine(acc, pdp.TPUBackend(noise_seed=noise_seed))
    result = engine.select_partitions(
        list(zip(pid.tolist(), pk.tolist())),
        pdp.SelectPartitionsParams(max_partitions_contributed=2),
        pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                           partition_extractor=lambda r: r[1]))
    acc.compute_budgets()
    return list(result)


def _blocked_args(key_seed, eps=60.0):
    cfg, stds, scalars = _spec(BLOCKED_P, l0=2, linf=2, eps=eps)
    rng = np.random.default_rng(7)
    n = 80_000
    pid = rng.integers(0, 12_000, n).astype(np.int32)
    pk = rng.integers(0, BLOCKED_P // 2, n).astype(np.int32) * 2
    values = rng.uniform(0, 5, n)
    return (pid, pk, values, np.ones(n, bool), *scalars, np.asarray(stds),
            jax.random.PRNGKey(key_seed), cfg)


def _blocked_job(key_seed, **kw):
    return large_p.aggregate_blocked(*_blocked_args(key_seed),
                                     block_partitions=BLOCK, **kw)


class _Fetches:
    """Records every KeptPrefix a job makes: (k, rows of the prefix)."""

    def __init__(self, monkeypatch):
        self.seen = []
        real = pipeline.KeptPrefix

        def recording(arrays, k):
            prefix = real(arrays, k)
            self.seen.append((prefix.k, prefix.rows))
            return prefix

        monkeypatch.setattr(pipeline, "KeptPrefix", recording)

    def engaged(self):
        """Fetches whose prefix is shorter than a whole block or dense
        column: the ladder sliced on the device."""
        return [(k, rows) for k, rows in self.seen
                if 0 < rows < min(DENSE_P, BLOCK)]


def _whole_columns(monkeypatch):
    """The slice's old host form: every column crosses whole and is cut
    with np.asarray(col)[:k]."""
    monkeypatch.setattr(pipeline, "DRAIN_MIN_ROWS", 1 << 40)


# --- (a) pure indexing -----------------------------------------------------


@pytest.mark.parametrize("n_devices", [None, 4])
def test_dense_stream_is_the_columns_first_k_rows(n_devices, monkeypatch):
    """The release the engine emits == _decode_rows over
    np.asarray(col)[:k] of the very arrays the launch returned, on one
    device and over a 4-device mesh (replicated arrays)."""
    fetches = _Fetches(monkeypatch)
    launched = []
    real = executor.decode_release_results

    def spy(n_kept, order, outputs, vocab, compound):
        launched.append((n_kept, order, outputs, vocab, compound))
        return real(n_kept, order, outputs, vocab, compound)

    monkeypatch.setattr(executor, "decode_release_results", spy)
    mesh = None if n_devices is None else make_mesh(n_devices=n_devices)
    released = _dense_job(31, mesh=mesh)
    ((n_kept, order, outputs, vocab, compound),) = launched
    k = int(n_kept)
    assert isinstance(order, jax.Array) and order.shape[0] == DENSE_P
    if n_devices:
        assert len(order.sharding.device_set) == n_devices
    assert fetches.engaged() == [(k, pipeline.drain_bucket(k, DENSE_P))]
    assert 0 < k < len(released) * 2 and k < DENSE_P // 2
    expected = list(executor._decode_rows(
        {name: np.asarray(col)[:k] for name, col in outputs.items()},
        enumerate(np.asarray(order)[:k]), vocab, compound))
    assert released == expected
    assert len(released) > 100


def test_select_partitions_stream_is_the_ids_first_k_rows(monkeypatch):
    fetches = _Fetches(monkeypatch)
    sliced = _select_job(13)
    assert fetches.engaged() and len(sliced) > 100
    _whole_columns(monkeypatch)
    fetches.seen.clear()
    assert _select_job(13) == sliced
    assert not fetches.engaged()


def test_blocked_stream_is_the_blocks_first_k_rows(monkeypatch):
    """Four blocks of 2^13 partitions, each drained through a bucket of
    its own kept count == the same job with every block's columns fetched
    whole and cut on the host."""
    fetches = _Fetches(monkeypatch)
    kept, outputs = _blocked_job(3)
    engaged = fetches.engaged()
    assert len(engaged) == 4 and sum(k for k, _ in engaged) == len(kept)
    assert len(set(kept)) == len(kept) > 400
    _whole_columns(monkeypatch)
    fetches.seen.clear()
    ref_kept, ref_outputs = _blocked_job(3)
    assert not fetches.engaged()
    assert kept.dtype == ref_kept.dtype and np.array_equal(kept, ref_kept)
    assert sorted(outputs) == sorted(ref_outputs) == ["count", "sum"]
    for name, col in outputs.items():
        assert col.dtype == ref_outputs[name].dtype
        assert np.array_equal(col, ref_outputs[name]), name


@pytest.mark.parametrize("route", ["aggregate", "select"])
def test_journal_record_holds_exactly_the_kept_rows(route, monkeypatch):
    """The journalled drains: each block's record holds its k kept rows
    and owns them (nothing of the fetched bucket behind the arrays), and
    the records are the unjournalled job's release."""
    fetches = _Fetches(monkeypatch)
    journal = rt_journal.BlockJournal()
    if route == "aggregate":
        kept, outputs = _blocked_job(3, journal=journal, job_id="j")
        ref_kept, ref_outputs = _blocked_job(3)
    else:
        args = _blocked_args(3)
        pid, pk, valid, key, cfg = args[0], args[1], args[3], args[-2], \
            args[-1]
        select = lambda **kw: large_p.select_partitions_blocked(
            pid, pk, valid, key, 2, BLOCKED_P, cfg.selection,
            block_partitions=BLOCK, **kw)
        kept, outputs = select(journal=journal, job_id="j"), {}
        ref_kept, ref_outputs = select(), {}
    assert np.array_equal(kept, ref_kept) and len(kept) > 400
    for name, col in outputs.items():
        assert np.array_equal(col, ref_outputs[name]), name
    ks = [k for k, rows in fetches.seen[:4]]  # the journalled job's blocks
    assert all(rows < BLOCK for _, rows in fetches.seen)
    records = [journal.get("j", rt_journal.block_key(b * BLOCK, BLOCK))
               for b in range(4)]
    assert [r.n_kept for r in records] == ks and sum(ks) == len(kept)
    assert np.array_equal(np.concatenate([r.ids for r in records]), kept)
    for record in records:
        assert sorted(record.outputs) == sorted(outputs)
        for col in record.outputs.values():
            assert col.shape[0] == record.n_kept and col.base is None


# --- (b) nothing is built for a new kept count -----------------------------


def _compiles(job):
    telemetry.install_compile_listener()
    before = telemetry.snapshot()
    job()
    return telemetry.delta(before).get("backend_compiles", 0)


@pytest.mark.parametrize("n_devices", [None, 4])
def test_dense_jobs_of_one_bucket_build_nothing(n_devices, monkeypatch):
    fetches = _Fetches(monkeypatch)
    mesh = None if n_devices is None else make_mesh(n_devices=n_devices)
    _dense_job(40, mesh=mesh)  # builds the release and its bucket's slices
    built = [_compiles(lambda s=s: _dense_job(s, mesh=mesh))
             for s in (41, 42, 43)]
    ks = [k for k, _ in fetches.engaged()]
    assert len(ks) == 4 and len(set(ks)) > 1, ks  # the kept counts moved
    assert len({rows for _, rows in fetches.engaged()}) == 1  # one bucket
    assert built == [0, 0, 0]


def test_blocked_jobs_of_the_same_buckets_build_nothing(monkeypatch):
    fetches = _Fetches(monkeypatch)
    _blocked_job(50)
    first = fetches.engaged()
    fetches.seen.clear()
    built = [_compiles(lambda s=s: _blocked_job(s)) for s in (51, 52)]
    later = fetches.engaged()
    assert {rows for _, rows in later} <= {rows for _, rows in first}
    assert {k for k, _ in later} - {k for k, _ in first}  # new kept counts
    assert built == [0, 0]


# --- (d) what crosses is counted, and ends at the helper -------------------


def test_only_kept_rows_reach_decode_and_the_counter_holds_what_crossed(
        monkeypatch):
    fetches = _Fetches(monkeypatch)
    seen = []
    real = executor._decode_rows

    def spy(outputs, row_idx_pairs, *a, **kw):
        pairs = list(row_idx_pairs)
        seen.append(({name: col for name, col in outputs.items()}, pairs))
        return real(outputs, pairs, *a, **kw)

    monkeypatch.setattr(executor, "_decode_rows", spy)
    _dense_job(60)  # warm: the encode's own fetches are not the drain's
    seen.clear(), fetches.seen.clear()
    before = telemetry.snapshot()
    released = _dense_job(61)
    moved = telemetry.delta(before)
    ((k, rows),) = fetches.engaged()
    assert rows == pipeline.drain_bucket(k, DENSE_P) > k
    ((outputs, pairs),) = seen
    assert len(pairs) == k >= len(released)
    for col in outputs.values():
        assert isinstance(col, np.ndarray)
        assert col.shape[0] == k and col.base is None
    f = np.dtype(executor._ftype()).itemsize
    assert moved["drain_bucket_rows"] == rows
    # The ids and COUNT + SUM, each `rows` long: the prefixes' nbytes.
    assert moved["d2h_bytes"] == rows * (4 + 2 * f)
