"""One dense release body, one served path (PR 32).

  * **Release == reference + nonzero** — the served release kernels
    (kept-first compaction inside the program) equal the dense reference
    forms (`executor.aggregate_kernel` / `select_partitions_kernel`:
    the same traced body without the compaction) followed by
    `np.nonzero(keep)`: kept ids, their order and every column, bitwise,
    on one chip and through the 4-device mesh wrappers.
  * **No second path** — `TPUBackend(fused_release=...)` is a TypeError,
    and nothing under `pipelinedp_tpu/` calls the reference forms.
"""

import ast
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pipelinedp_tpu as pdp
from pipelinedp_tpu import executor
from pipelinedp_tpu.parallel import make_mesh, sharded
from tests.test_large_p import _spec as large_p_spec

P, L0, LINF = 12, 2, 3


def kept_release(release):
    """Host view of a compacted dense release (n_kept, ids, outputs,
    row_count): the kept partition ids, ascending, and each column's
    kept prefix."""
    n_kept, ids, outputs, _ = release
    k = int(n_kept)
    return np.asarray(ids)[:k], {name: np.asarray(col)[:k]
                                 for name, col in outputs.items()}


def _spec():
    cfg, stds, scalars = large_p_spec(P, l0=L0, linf=LINF)
    return cfg, cfg.selection, stds, scalars


def _exact_rows():
    """Integer-valued rows that meet the bounds exactly (every id in L0
    partitions with LINF rows each): bounding drops nothing and integer
    sums are exact in f64, so the release is a function of the row
    multiset and the key — the same on one chip and on any mesh. Ids
    per partition fall from 200 to 20, so selection keeps some partitions
    and drops others."""
    pid, pk, values = [], [], []
    u = 0
    for part in range(0, P, 2):
        for _ in range(200 - 36 * (part // 2)):
            for p in (part, part + 1):
                for r in range(LINF):
                    pid.append(u)
                    pk.append(p)
                    values.append(float((u + p + r) % 6))
            u += 1
    n = executor.row_bucket(len(pid))
    pad = n - len(pid)
    return (np.asarray(pid + [0] * pad, np.int32),
            np.asarray(pk + [0] * pad, np.int32),
            np.asarray(values + [0.0] * pad),
            np.arange(n) < len(pid))


@pytest.mark.parametrize("n_devices", [None, 4])
def test_aggregate_release_is_reference_plus_nonzero(n_devices):
    cfg, _, stds, scalars = _spec()
    pid, pk, values, valid = _exact_rows()
    key = jax.random.PRNGKey(3)
    ref_out, ref_keep, ref_rows = executor.aggregate_kernel(
        jnp.asarray(pid), jnp.asarray(pk), jnp.asarray(values),
        jnp.asarray(valid), *scalars, jnp.asarray(stds), key, cfg)
    ref_kept = np.nonzero(np.asarray(ref_keep))[0]
    assert 0 < len(ref_kept) < P  # both selection outcomes occur
    if n_devices is None:
        release = executor.aggregate_release_kernel(
            jnp.asarray(pid), jnp.asarray(pk), jnp.asarray(values),
            jnp.asarray(valid), *scalars, jnp.asarray(stds), key, cfg)
    else:
        release = sharded.sharded_aggregate_arrays(
            make_mesh(n_devices=n_devices), pid, pk, values, valid,
            *scalars, stds, key, cfg)
    n_kept, ids, outputs, n_rows = release
    # ids is the whole stable argsort of ~keep: kept first, then dropped,
    # each ascending.
    dropped = np.nonzero(~np.asarray(ref_keep))[0]
    np.testing.assert_array_equal(np.asarray(ids),
                                  np.concatenate([ref_kept, dropped]))
    assert int(n_kept) == len(ref_kept)
    np.testing.assert_array_equal(np.asarray(n_rows), np.asarray(ref_rows))
    kept, columns = kept_release(release)
    np.testing.assert_array_equal(kept, ref_kept)
    assert set(columns) == set(ref_out)
    for name, col in columns.items():
        np.testing.assert_array_equal(col, np.asarray(ref_out[name])[kept],
                                      err_msg=name)


@pytest.mark.parametrize("n_devices", [None, 4])
def test_select_release_is_reference_plus_nonzero(n_devices):
    _, selection, _, _ = _spec()
    pid, pk, _, valid = _exact_rows()
    key = jax.random.PRNGKey(5)
    ref_keep = np.asarray(executor.select_partitions_kernel(
        jnp.asarray(pid), jnp.asarray(pk), jnp.asarray(valid), key, L0, P,
        selection))
    ref_kept = np.nonzero(ref_keep)[0]
    assert 0 < len(ref_kept) < P
    if n_devices is None:
        n_kept, ids = executor.select_partitions_release_kernel(
            jnp.asarray(pid), jnp.asarray(pk), jnp.asarray(valid), key, L0,
            P, selection)
    else:
        n_kept, ids = sharded.sharded_select_partitions(
            make_mesh(n_devices=n_devices), pid, pk, valid, key, L0, P,
            selection)
    assert int(n_kept) == len(ref_kept)
    np.testing.assert_array_equal(
        np.asarray(ids), np.concatenate([ref_kept, np.nonzero(~ref_keep)[0]]))


def test_fused_release_argument_is_gone():
    with pytest.raises(TypeError, match="fused_release"):
        pdp.TPUBackend(fused_release=True)


def test_package_never_calls_the_reference_forms():
    """`aggregate_kernel` and `select_partitions_kernel` are the dense
    reference forms the tests compare against; the served paths call the
    release kernels. A call from package code would be a second path."""
    reference = {"aggregate_kernel", "select_partitions_kernel"}
    root = pathlib.Path(executor.__file__).parent
    callers = []
    for path in sorted(root.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = (fn.attr if isinstance(fn, ast.Attribute) else
                    fn.id if isinstance(fn, ast.Name) else None)
            if name in reference:
                callers.append(f"{path.relative_to(root)}:{node.lineno}")
    assert not callers, callers
