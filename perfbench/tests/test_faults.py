"""The rest of a run with the timed path broken underneath: the harness's
look for a chip is skipped (`--rehearse`, CPU, rehearsal sizes), the
program is patched where it produces its answers, and `correct` must come
out false. Once for each fault these cells can have:

  half_batch      half of the rows never reach the engine (every second
                  chunk / every second row left out), the release made
                  over the rest;
  answer_altered  one job's release has the answers of two partitions (its
                  largest and its smallest count) exchanged where the
                  program decodes them.

(A step that returns its state unchanged and an exchange between chips
left out are faults of a training loop and of a mesh: neither cell has
one.) The sound program, driven the same way, is correct.
"""

import argparse

import numpy as np
import pytest

from perfbench import run as perfbench_run

CELLS = ("netflix-sum-chunks", "keys1e7-sum-blocked")


def drive(workload, seed=99):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=12.0,
                              trace=0, rehearse=True, debug_dir=None)
    return perfbench_run.execute(args)


def over(result):
    return [k for k, (value, limit) in result["compared"].items()
            if limit is not None and not value <= limit]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_program_is_correct(workload):
    result = drive(workload)
    assert result["correct"], over(result)
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["device"]["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", CELLS)
def test_half_batch_is_not_correct(workload, monkeypatch):
    from pipelinedp_tpu import columnar
    from pipelinedp_tpu.runtime import pipeline

    chunk_init = pipeline.ChunkSource.__init__

    def half_chunks(self, chunks, *a, **kw):
        chunk_init(self, list(chunks)[::2], *a, **kw)

    encoded_init = columnar.EncodedData.__init__

    def half_rows(self, pid, pk, values, *a, **kw):
        encoded_init(self, pid[::2], pk[::2], values[::2], *a, **kw)

    monkeypatch.setattr(pipeline.ChunkSource, "__init__", half_chunks)
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
