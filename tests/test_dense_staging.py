"""The dense route's staging of a host EncodedData (ISSUE 31).

``runtime/pipeline.stage_host_rows`` sends host columns up slab by slab
through reused host scratch and pads on the device. What must hold: the
kernel's inputs are bit-identical to ``jnp.asarray`` of ``pad_rows``' host
copies (so every release under a fixed noise seed is unchanged), the pad
rows never cross the link, a second job of the same shapes builds nothing
and allocates nothing, and two jobs staging at once do not see each
other's scratch.
"""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import columnar
from pipelinedp_tpu import executor
from pipelinedp_tpu.runtime import pipeline
from pipelinedp_tpu.runtime import telemetry
from pipelinedp_tpu.runtime import trace

SLAB_ROWS = 3000  # 10,001 rows: three full slabs and one of 1,001


def _row_bytes(values):
    """Bytes of a row as it crosses the link: pid, pk, the values in the
    device's float type, the valid flag."""
    ftype = jax.dtypes.canonicalize_dtype(np.float64)
    return 4 + 4 + ftype.itemsize * int(np.prod(values.shape[1:])) + 1


def _encoded(n, shape_tail=(), seed=0, partitions=6, ids=400):
    rng = np.random.default_rng(seed)
    return columnar.EncodedData(
        pid=rng.integers(0, ids, n).astype(np.int32),
        pk=rng.integers(-1, partitions, n).astype(np.int32),
        values=rng.uniform(0.0, 5.0, (n,) + shape_tail),
        partition_vocab=list(range(partitions)),
        n_privacy_ids=ids)


@pytest.fixture
def small_slabs(monkeypatch):
    """The slab constant patched down to SLAB_ROWS rows of these columns."""

    def patch(encoded):
        monkeypatch.setattr(pipeline, "DENSE_SLAB_BYTES",
                            SLAB_ROWS * _row_bytes(encoded.values))

    return patch


def _stage(encoded):
    return pipeline.stage_host_rows(encoded.pid, encoded.pk, encoded.values)


@pytest.mark.parametrize("shape_tail", [(), (5,)], ids=["n", "nx5"])
@pytest.mark.parametrize("n", [1, 8, 3000, 4096, 10001])
def test_staged_columns_are_pad_rows_copies(n, shape_tail, small_slabs):
    encoded = _encoded(n, shape_tail, seed=n)
    small_slabs(encoded)
    want = tuple(jnp.asarray(c) for c in executor.pad_rows(encoded))
    before = telemetry.snapshot()
    got = _stage(encoded)
    counted = telemetry.delta(before)
    assert len(got) == 4
    for g, w in zip(got, want):
        assert isinstance(g, jax.Array)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert counted["dense_stage_slabs"] == -(-n // SLAB_ROWS)
    # The real rows cross the link; the pad is written on the device.
    assert counted["h2d_bytes"] == n * _row_bytes(encoded.values)


def test_padded_host_copies_stage_as_they_are(small_slabs):
    """What a launch the interceptor declined hands on: pad_rows' host
    arrays, valid flags and all, already at their bucket's length."""
    encoded = _encoded(5000, (2,))
    small_slabs(encoded)
    padded = executor.pad_rows(encoded)
    got = pipeline.stage_host_rows(*padded)
    for g, w in zip(got, padded):
        assert g.shape == w.shape == (8192,) + w.shape[1:]
        np.testing.assert_array_equal(np.asarray(g), w)


def test_no_rows_stage_as_the_pad_alone():
    got = _stage(_encoded(0))
    assert [g.shape for g in got] == [(8,)] * 4
    assert not np.asarray(got[3]).any()
    assert (np.asarray(got[1]) == -1).all()


def _one_column_params():
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM,
                 pdp.Metrics.PRIVACY_ID_COUNT],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=2,
        max_contributions_per_partition=2,
        min_value=0.0,
        max_value=4.0)


def _two_column_params():
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT],
        max_partitions_contributed=3,
        max_contributions_per_partition=2,
        value_columns=[
            pdp.ValueColumn("a", 0.0, 4.0, [pdp.Metrics.SUM]),
            pdp.ValueColumn("b", 1.0, 5.0, [pdp.Metrics.MEAN])])


def _release(encoded, params, seed, public):
    acc = pdp.NaiveBudgetAccountant(total_epsilon=5.0, total_delta=1e-6)
    engine = pdp.DPEngine(acc, pdp.TPUBackend(noise_seed=seed))
    result = engine.aggregate(encoded, params, pdp.DataExtractors(),
                              public_partitions=public)
    acc.compute_budgets()
    return {k: tuple(np.asarray(f).tolist() for f in m) for k, m in result}


def _on_device(encoded):
    """The same rows already on the device and no power of two long: the
    jnp branch of executor._padded_copies pads them."""
    return dataclasses.replace(
        encoded, pid=jnp.asarray(encoded.pid), pk=jnp.asarray(encoded.pk),
        values=jnp.asarray(encoded.values))


@pytest.mark.parametrize("shape_tail,params,public", [
    ((), _one_column_params, None),
    ((), _one_column_params, list(range(6))),
    ((2,), _two_column_params, list(range(6))),
], ids=["private", "public", "two-columns"])
def test_release_equals_the_device_resident_copy(shape_tail, params, public,
                                                 small_slabs):
    encoded = _encoded(10001, shape_tail, seed=4)
    if public is not None:
        encoded = dataclasses.replace(encoded, public_encoded=True)
    small_slabs(encoded)
    got = _release(encoded, params(), 11, public)
    want = _release(_on_device(encoded), params(), 11, public)
    assert got and got == want


def test_second_job_builds_nothing_and_allocates_no_scratch(small_slabs):
    encoded = _encoded(10001, (2,), seed=2)
    small_slabs(encoded)
    telemetry.install_compile_listener()
    public = list(range(6))
    encoded = dataclasses.replace(encoded, public_encoded=True)
    first = _release(encoded, _two_column_params(), 3, public)
    with pipeline._scratch_lock:
        scratch = {key: tuple(map(id, pair))
                   for key, pair in pipeline._scratch.items()}
    before = telemetry.snapshot()
    # Other rows of the same shapes: every staging program and both
    # scratch buffers of each key are there already.
    other = dataclasses.replace(
        _encoded(10001, (2,), seed=3), public_encoded=True)
    _release(other, _two_column_params(), 3, public)
    second = _release(encoded, _two_column_params(), 3, public)
    counted = telemetry.delta(before)
    assert "backend_compiles" not in counted
    assert counted["dense_stage_slabs"] == 2 * 4
    with pipeline._scratch_lock:
        assert scratch == {key: tuple(map(id, pair))
                           for key, pair in pipeline._scratch.items()}
    # Nothing of the other job's rows was left for this one to find.
    assert second == first


def test_spans_pad_only_where_the_bucket_is_longer(small_slabs):
    small_slabs(_encoded(8))
    trace.enable()
    try:
        _stage(_encoded(4096))
        spans = trace.trace_summary()["spans"]
        assert "dense.pad" not in spans
        assert spans["dense.upload"]["count"] == 1
        _stage(_encoded(10001))
        spans = trace.trace_summary()["spans"]
        assert spans["dense.pad"]["count"] == 1
        assert spans["dense.upload"]["count"] == 2
        # Host passes and waits for the device, slab by slab, inside it.
        assert spans["dense.narrow"]["count"] == 2 + 4
        assert spans["dense.wait"]["count"] == 2 + 4
    finally:
        trace.disable()
        telemetry.reset()


def test_declined_offer_releases_what_the_solo_job_does(small_slabs):
    """A launch offered to the service's interceptor is decided before
    anything is staged and takes pad_rows' HOST arrays; declined, those go
    up and release what the job would have alone."""
    encoded = _encoded(3000, seed=6)
    small_slabs(encoded)
    solo = _release(encoded, _one_column_params(), 9, None)
    offered = []

    def decline(launch):
        offered.append(launch)
        return None

    with executor.launch_interceptor(decline):
        got = _release(encoded, _one_column_params(), 9, None)
    launch, = offered
    assert isinstance(launch.pid, np.ndarray) and launch.pid.shape == (4096,)
    assert launch.values.dtype == np.float64
    assert got == solo


def test_two_threads_staging_at_once(small_slabs):
    jobs = [(_encoded(10001, (2,), seed=20), 5),
            (_encoded(9000, (2,), seed=21), 6)]
    small_slabs(jobs[0][0])
    public = list(range(6))
    jobs = [(dataclasses.replace(e, public_encoded=True), s)
            for e, s in jobs]
    alone = [_release(e, _two_column_params(), s, public) for e, s in jobs]
    staged_alone = [tuple(np.asarray(c) for c in _stage(e)) for e, _ in jobs]
    rounds = 4
    barrier = threading.Barrier(2)
    got = [[] for _ in jobs]
    errors = []

    def run(i):
        try:
            encoded, seed = jobs[i]
            for _ in range(rounds):
                barrier.wait(timeout=60)
                staged = tuple(np.asarray(c) for c in _stage(encoded))
                released = _release(encoded, _two_column_params(), seed,
                                    public)
                got[i].append((staged, released))
        except BaseException as e:  # noqa: BLE001 - reported by the test below
            errors.append(e)
            barrier.abort()

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    for i in range(2):
        assert len(got[i]) == rounds
        for staged, released in got[i]:
            assert released == alone[i]
            for g, w in zip(staged, staged_alone[i]):
                np.testing.assert_array_equal(g, w)
