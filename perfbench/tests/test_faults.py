"""The rest of a run with the timed path broken underneath: the harness's
look for a chip is skipped (`--rehearse`, CPU, rehearsal sizes), the
program is patched where it produces its answers, and `correct` must come
out false. Once for each fault these cells can have:

  half_batch      half of the rows never reach the engine (every second
                  row of every chunk / of the columns left out), the
                  release made over the rest;
  answer_altered  one job's release has the answers of two partitions (its
                  largest and its smallest count) exchanged where the
                  program decodes them;
  psum_left_out   (a cell on several chips) the shards' partial columns
                  are not combined: the release is one shard's own;
  exchange_left_out  (a cell on several chips) the rows are laid over the
                  mesh but never exchanged, so a privacy id's rows stay on
                  several shards and each bounds them on its own.

(A step that returns its state unchanged is a fault of a training loop: no
cell has one.) The sound program, driven the same way, is correct.
"""

import argparse
import json
import os

import numpy as np
import pytest

from perfbench import run as perfbench_run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "workloads")))


def cell_file(workload):
    with open(os.path.join(HERE, "workloads", workload + ".json")) as f:
        return json.load(f)


MESHED = [c for c in CELLS if cell_file(c)["chips"] > 1]


def drive(workload, seed=99):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=12.0,
                              trace=0, rehearse=True, debug_dir=None)
    return perfbench_run.execute(args)


def over(result):
    return [k for k, (value, limit) in result["compared"].items()
            if limit is not None and not value <= limit]


@pytest.fixture
def fresh_programs():
    """A fault planted inside a jitted program only takes once the sound
    program's trace is dropped, and must not outlive its test."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("workload", CELLS)
def test_sound_program_is_correct(workload):
    result = drive(workload)
    assert result["correct"], over(result)
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["device"]["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", CELLS)
def test_half_batch_is_not_correct(workload, monkeypatch):
    """Where the cell's job is given its rows: every second row of every
    chunk, or of the encoded columns, never reaches the engine."""
    from pipelinedp_tpu import columnar
    from pipelinedp_tpu.runtime import pipeline

    if cell_file(workload)["traffic"]["input_form"].startswith("chunks"):
        chunk_init = pipeline.ChunkSource.__init__

        def half_chunks(self, chunks, *a, **kw):
            chunk_init(self, [tuple(c[::2] for c in chunk)
                              for chunk in chunks], *a, **kw)

        monkeypatch.setattr(pipeline.ChunkSource, "__init__", half_chunks)
    else:
        encoded_init = columnar.EncodedData.__init__

        def half_rows(self, pid, pk, values, *a, **kw):
            encoded_init(self, pid[::2], pk[::2], values[::2], *a, **kw)

        monkeypatch.setattr(columnar.EncodedData, "__init__", half_rows)
    result = drive(workload)
    assert not result["correct"]
    assert "count_bias_z" in over(result)


@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_not_correct(workload, monkeypatch):
    from pipelinedp_tpu import executor

    decode = executor._decode_rows
    calls = {"n": 0}

    def altered(outputs, row_idx_pairs, *a, **kw):
        """The second release (the window's first; the warm-up's is the
        first): the partitions with its largest and its smallest count
        exchange their partition ids, so each is released under the
        other's key."""
        calls["n"] += 1
        pairs = list(row_idx_pairs)
        if calls["n"] == 2 and len(pairs) > 2:
            counts = np.asarray(outputs["count"])[[r for r, _ in pairs]]
            i, j = int(counts.argmax()), int(counts.argmin())
            (ri, pi), (rj, pj) = pairs[i], pairs[j]
            pairs[i], pairs[j] = (ri, pj), (rj, pi)
        return decode(outputs, pairs, *a, **kw)

    monkeypatch.setattr(executor, "_decode_rows", altered)
    result = drive(workload)
    assert calls["n"] >= 2
    assert not result["correct"]
    assert "max_abs_z" in over(result)


@pytest.mark.parametrize("workload", MESHED)
def test_psum_left_out_is_not_correct(workload, monkeypatch, fresh_programs):
    from pipelinedp_tpu.parallel import sharded

    monkeypatch.setattr(sharded, "_combine_partials", lambda cols, cfg: cols)
    result = drive(workload)
    assert result["device"]["count"] > 1
    assert not result["correct"]
    assert "count_bias_z" in over(result)


@pytest.mark.parametrize("workload", MESHED)
def test_exchange_left_out_is_not_correct(workload, monkeypatch,
                                          fresh_programs):
    from pipelinedp_tpu.parallel import reshard

    def lay_out_only(mesh, pid, pk, values, valid, salt=0):
        per_shard = reshard.rows_per_shard(pid.shape[0], mesh.devices.size)
        return reshard._pad_and_shard(mesh, per_shard, pid, pk, values, valid)

    monkeypatch.setattr(reshard, "device_reshard_rows_by_pid", lay_out_only)
    result = drive(workload)
    assert result["device"]["count"] > 1
    assert not result["correct"]
    assert "ids_bias_z" in over(result)
