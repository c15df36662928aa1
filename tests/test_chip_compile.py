"""The served path's jitted programs, compiled for a described TPU v5e.

No chip is needed: the TPU compiler is installed with jaxlib and compiles
for a topology that is described, not attached (the on-chip-measurement
guide, section 2.3). These tests guard every later PR against a program
the chip's compiler refuses, at no chip time. Nothing runs, so they say
nothing about results or speed.

Shapes. Widths are the real ones — P = 17,770 (Netflix Prize movies),
C = 2^20 (the blocked drivers' default block), 2^24-row accumulator
buffers, a 4-device mesh. ROW counts of the sort-bearing programs are
REDUCED to 2^12: a sort-bearing TPU compile has a large fixed cost and
tier-1 cannot carry the full-size ones. Measured for PR 22 in this
sandbox (8 cores, one compile per core), full size:

    aggregate_release_kernel   2^24 rows, P=17,770   444 s   803 MB
    aggregate_release_kernel   2^20 rows             615 s*   31 MB
    blocked_bound_compact      2^22 rows, P=10^7     645 s*  153 MB
    blocked_block_kernel       C=2^20, cap 3.1M       24 s    82 MB
    select_kept_pair_stream    2^22 rows             420 s*   89 MB
    device_factorize           2^22 rows             302 s*   97 MB
    (* eight compiles side by side; alone they run ~1.5x faster)

against ~18 s for the reduced dense release below. Code that asks
jax.default_backend() takes its CPU branch under this rehearsal, so each
test compiles the jitted function itself.
"""

import dataclasses
import os
import warnings

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from benchmarks import _common  # the bench spec (build_spec)
from pipelinedp_tpu import device_encode, executor
from pipelinedp_tpu.parallel import large_p, reshard, sharded
from pipelinedp_tpu.parallel import mesh as mesh_lib
from pipelinedp_tpu.runtime import pipeline as rt_pipeline

HBM_BYTES = 16 * 2**30  # one v5e chip
MOVIES = 17_770
BLOCK = 1 << 20
ROWS = 1 << 12  # reduced; see the module docstring
F32, I32, U32 = np.float32, np.int32, np.uint32


@pytest.fixture(scope="module")
def topo():
    """The described (not attached) chip. Described here, inside a
    fixture of this one file: only one process at a time may load the
    TPU library, and xdist workers import every test module."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe the chip means "cannot rehearse here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip; keep these compiles
    out of it, and quiet."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo, no_persistent_cache):
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype, sharding=one):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    return shape


def _x32(spec_fn):
    """The chip runs with x64 off; this suite runs with it on. Specs
    and lowerings happen under x64 off so the programs are the chip's."""
    with jax.enable_x64(False):
        return spec_fn()


def _rows(shape, n, sharding=None):
    kw = {} if sharding is None else {"sharding": sharding}
    return (shape((n,), I32, **kw), shape((n,), I32, **kw),
            shape((n,), F32, **kw), shape((n,), np.bool_, **kw))


def _fits(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes +
             m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
    return m


@pytest.mark.parametrize("numeric_mode", ["fast", "safe"])
def test_dense_release_compiles(chip, numeric_mode):
    """The default fused route (executor.aggregate_release_kernel) at
    P = 17,770 in both accumulation disciplines."""

    def lower():
        _, cfg, stds, _ = _common.build_spec(MOVIES)
        cfg = dataclasses.replace(cfg, numeric_mode=numeric_mode)
        scalars = [chip((), F32)] * 5
        return executor.aggregate_release_kernel.lower(
            *_rows(chip, ROWS), *scalars, chip((len(stds),), F32),
            chip((2,), U32), cfg)

    compiled = _x32(lower).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert text.count(" sort(") >= 3  # bounding, reduce, compaction
    # An f64 ARRAY, not the bare letters: a cached trace's stack-frame
    # names (tests/test_numeric_armor.py's "..._f64_oracle_...") ride along
    # in the text when xdist hands both files to one worker.
    assert " f64[" not in text


def test_dense_release_compiles_with_five_value_columns(chip):
    """TPC-H Q1's job (perfbench/configs/q1-fewgroups.json): five value
    columns with their own clamps through ONE bounding sort and ONE reduce
    sort, P = 6 public partitions, rows reduced to 2^12. Full size, 2^26
    rows (SF10's 59,986,052), compiled for the described v5e in this
    sandbox (PR 30): see PERF.md section 4 for the seconds and the bytes."""
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import combiners

    M = pdp.Metrics
    params = pdp.AggregateParams(
        metrics=[M.COUNT], max_partitions_contributed=6,
        max_contributions_per_partition=16,
        value_columns=[
            pdp.ValueColumn("quantity", 1, 50, [M.SUM, M.MEAN]),
            pdp.ValueColumn("extendedprice", 0, 70000, [M.SUM, M.MEAN]),
            pdp.ValueColumn("disc_price", 0, 70000, [M.SUM]),
            pdp.ValueColumn("charge", 0, 70000, [M.SUM]),
            pdp.ValueColumn("discount", 0, 0.10, [M.MEAN])])
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=1.0,
                                           total_delta=0.0)
    with accountant.scope(weight=1):
        compound = combiners.create_compound_combiner(params, accountant)
    accountant.compute_budgets()
    cfg = executor.make_kernel_config(params, compound, 6, False, None)
    n_stds = len(executor.compute_noise_stds(compound, params))

    def lower():
        per_column, scalar = chip((5,), F32), chip((), F32)
        return executor.aggregate_release_kernel.lower(
            chip((ROWS,), I32), chip((ROWS,), I32), chip((ROWS, 5), F32),
            chip((ROWS,), np.bool_), per_column, per_column, scalar, scalar,
            per_column, chip((n_stds,), F32), chip((2,), U32), cfg)

    compiled = _x32(lower).compile()
    _fits(compiled)
    text = compiled.as_text()
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert sum("bound_sort" in line for line in sorts) == 1, sorts
    assert sum("segment_reduce" in line for line in sorts) == 1, sorts
    assert " f64[" not in text


def test_dense_release_compiles_with_percentiles(chip):
    """The upstream movie-ratings job with its PERCENTILEs
    (perfbench/configs/netflix-percentiles.json): 17,770 quantile trees
    on the lazy descent, rows reduced to 2^12. The trees' ops carry the
    `quantile_tree` scope a trace attributes them by: ONE sort of the rows
    by (partition, leaf), the tree's one pass over them, then a search
    loop per level and quantile whose gathers are [P, B - 1] wide — no
    scatter, and no gather a row wide. The two quantiles' root-level
    searches have identical inputs, and the compiler merges them: 7 loops
    for 2 quantiles x 4 levels. Full size, 2^24 rows, compiled for the
    described v5e in this sandbox (PR 38): PERF.md section 4."""
    import re

    import pipelinedp_tpu as pdp

    M = pdp.Metrics
    _, cfg, stds, _ = _common.build_spec(
        MOVIES, metrics=[M.COUNT, M.SUM, M.PRIVACY_ID_COUNT,
                         M.PERCENTILE(50), M.PERCENTILE(90)], l0=2, linf=1)
    assert executor.quantile_row_passes(cfg) == 1
    assert executor.quantile_node_searches(cfg) == 2 * 4 * MOVIES * 15

    def lower():
        scalars = [chip((), F32)] * 5
        return executor.aggregate_release_kernel.lower(
            *_rows(chip, ROWS), *scalars, chip((len(stds),), F32),
            chip((2,), U32), cfg)

    compiled = _x32(lower).compile()
    _fits(compiled)
    text = compiled.as_text()
    tree = [line for line in text.splitlines() if "quantile_tree" in line]
    assert sum(" sort(" in line for line in tree) == 1
    assert " scatter(" not in text
    row_wide = re.compile(r"= \w+\[%d[,\]]" % ROWS)
    gathers = [line for line in tree if " gather(" in line]
    assert gathers  # the searches
    assert not [line for line in gathers if row_wide.search(line)], gathers
    loops = [line for line in tree
             if " while(" in line and "searchsorted" not in line]
    assert len(loops) == 7, len(loops)
    assert " f64[" not in text


def test_blocked_block_kernel_compiles(chip):
    """One partition block at the real C = 2^20 (large_p), its row
    gather capacity reduced."""

    def lower():
        _, cfg, stds, _ = _common.build_spec(10_000_000)
        cfg_block = dataclasses.replace(cfg, n_partitions=BLOCK)
        scalar_i, scalar_f = chip((), I32), chip((), F32)
        return large_p._block_kernel_dev.lower(
            chip((ROWS,), I32), chip((ROWS,), np.bool_),
            {"sum": chip((ROWS,), F32)}, None, scalar_i, scalar_i,
            scalar_i, scalar_f, scalar_f, scalar_f,
            chip((len(stds),), F32), chip((2,), U32), cfg_block,
            mesh_lib.round_capacity(ROWS))

    compiled = _x32(lower).compile()
    _fits(compiled)
    assert " sort(" in compiled.as_text()


def _selection(l0=4):
    """The whole (1, 1e-6) on the selection, as keys-1e7-select's job."""
    from pipelinedp_tpu.aggregate_params import PartitionSelectionStrategy
    from pipelinedp_tpu.ops import selection_ops

    return selection_ops.selection_params_from_host(
        PartitionSelectionStrategy.TRUNCATED_GEOMETRIC, 1.0, 1e-6, l0, None)


SELECTION_SCOPES = ("select_pairs", "select_compact", "selection_block")


def test_selection_programs_compile_under_their_scopes(chip):
    """The blocked selection's two programs (perfbench/configs/
    keys-1e7-select.json: P = 10,154,742, l0 = 4, C = 2^20; rows and the
    block's gather capacity reduced): pass 1's two sorts carry
    `select_pairs` and `select_compact`, the block program's gather,
    scatter and sort `selection_block`, so a profiler trace attributes the
    device time to the three stages by `tf_op`."""
    keys = 10_154_742
    stream = _x32(lambda: executor.select_kept_pair_stream.lower(
        chip((ROWS,), I32), chip((ROWS,), I32), chip((ROWS,), np.bool_),
        chip((2,), U32), 4, keys)).compile()
    _fits(stream)
    sorts = [line for line in stream.as_text().splitlines()
             if " sort(" in line]
    assert len(sorts) == 2, len(sorts)
    assert "select_pairs" in sorts[0] and "select_compact" in sorts[1], sorts
    block = _x32(lambda: large_p._selection_block_kernel.lower(
        chip((ROWS,), I32), chip((), I32), chip((), I32), chip((), I32),
        BLOCK, chip((2,), U32), _selection(),
        mesh_lib.round_capacity(ROWS))).compile()
    _fits(block)
    text = block.as_text()
    for op in (" sort(", " scatter(", " gather("):
        lines = [line for line in text.splitlines() if op in line]
        assert lines and all("selection_block" in line for line in lines), op
    assert " f64[" not in text


def test_no_aggregation_program_carries_a_selection_scope(chip):
    """`_select_kept_pairs` is shared with the dense selection body and
    with nothing an aggregation runs: the dense release, blocked pass 1
    and the blocked block program lower without the three scopes (their
    lowered text with debug locations; nothing is compiled here), the
    dense selection with `select_pairs` alone."""

    def lowered():
        _, cfg, stds, _ = _common.build_spec(MOVIES)
        scalars = [chip((), F32)] * 5
        tail = (chip((len(stds),), F32), chip((2,), U32))
        dense = executor.aggregate_release_kernel.lower(
            *_rows(chip, ROWS), *scalars, *tail, cfg)
        pass1 = large_p._bounded_compact_kernel.lower(
            *_rows(chip, ROWS), *scalars, chip((2,), U32), cfg)
        scalar_i, scalar_f = chip((), I32), chip((), F32)
        block = large_p._block_kernel_dev.lower(
            chip((ROWS,), I32), chip((ROWS,), np.bool_),
            {"sum": chip((ROWS,), F32)}, None, scalar_i, scalar_i,
            scalar_i, scalar_f, scalar_f, scalar_f, *tail,
            dataclasses.replace(cfg, n_partitions=BLOCK),
            mesh_lib.round_capacity(ROWS))
        select = executor.select_partitions_release_kernel.lower(
            chip((ROWS,), I32), chip((ROWS,), I32), chip((ROWS,), np.bool_),
            chip((2,), U32), 4, MOVIES, _selection())
        return dense, pass1, block, select

    *aggregations, select = _x32(lowered)
    own = ("bound_sort", "p1_bound_compact", "block_finalize")
    for program, scope in zip(aggregations, own):
        text = program.as_text(debug_info=True)
        assert f")/{scope}" in text  # this text shows scopes: its own
        assert not any(scope in text for scope in SELECTION_SCOPES)
    text = select.as_text(debug_info=True)
    assert "select_pairs" in text
    assert "select_compact" not in text and "selection_block" not in text


def test_donated_append_and_grow_compile(chip):
    """The streaming accumulator's pair at real 2^24-row buffers. The
    append really donates on the chip (on CPU it is a warned no-op, so
    no test had ever seen the alias); the grow does not donate."""
    cap, chunk = 1 << 24, 1 << 20

    def cols(n):
        return (chip((n,), I32), chip((n,), I32), chip((n,), F32))

    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*onated buffers.*")
        append = _x32(lambda: rt_pipeline._append_fn().lower(
            cols(cap), cols(chunk), chip((), I32))).compile()
        grow = _x32(lambda: rt_pipeline._grow_fn().lower(
            cols(cap // 2), new_cap=cap)).compile()
    buffers = 3 * 4 * cap
    assert _fits(append).alias_size_in_bytes == buffers
    assert _fits(grow).alias_size_in_bytes == 0
    assert _fits(grow).output_size_in_bytes >= buffers


def test_device_factorize_compiles(chip):
    """The sort/unique factorize an accelerator takes in hash_device
    ingest (CPU runs take the lookup kernel instead)."""
    compiled = _x32(lambda: device_encode._factorize_kernel.lower(
        chip((ROWS, 3), U32))).compile()
    _fits(compiled)
    assert compiled.as_text().count(" sort(") >= 2


def test_meshed_release_compiles_with_collectives(topo, chip):
    """reshard="device" on a 4-device mesh: the all_to_all exchange,
    then the sharded fused release with its psum."""
    mesh = Mesh(np.asarray(topo.devices), (mesh_lib.SHARD_AXIS,))
    rows = NamedSharding(mesh, P(mesh_lib.SHARD_AXIS))
    repl = NamedSharding(mesh, P())
    per_shard = mesh_lib.rows_per_shard(ROWS, 4)

    exchange = _x32(lambda: reshard._exchange_kernel.lower(
        *_rows(chip, 4 * per_shard, rows),
        mesh_lib.round_capacity(per_shard // 2), per_shard, 4, 0,
        mesh)).compile()
    _fits(exchange)
    assert "all-to-all" in exchange.as_text()

    def lower():
        _, cfg, stds, _ = _common.build_spec(MOVIES)
        scalars = [chip((), F32, repl)] * 5
        return sharded._sharded_release_kernel.lower(
            *_rows(chip, 4 * per_shard, rows), *scalars,
            chip((len(stds),), F32, repl), chip((2,), U32, repl), cfg, mesh)

    release = _x32(lower).compile()
    _fits(release)
    assert "all-reduce" in release.as_text()
