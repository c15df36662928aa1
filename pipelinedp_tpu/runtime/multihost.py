"""Multi-controller pod harness: the 2-process CPU dryrun gate.

SNIPPETS.md's pjit/NamedSharding excerpts promise that the same sharded
code drives multi-process TPU pods; this module is where that promise is
made falsifiable on every CI box. It spawns a REAL jax.distributed job —
N separate python processes, each owning a slice of CPU devices, gloo
collectives across them — runs the four meshed drivers (aggregate/select
x dense/blocked) plus an engine-level aggregation over the pod-spanning
mesh, and proves the outputs BIT-IDENTICAL to a single-process run of
the same device count:

  * the workload recipe (run_pod_workload / run_pod_engine) is one
    function executed by the children (global multi-process mesh) and by
    the single-process reference (same D, one controller), so any
    divergence is the multi-controller runtime's fault, not the test's;
  * inputs are integer-valued with non-binding contribution bounds, so
    psums are exact and placement/sampling cannot perturb results — the
    same construction the elastic-mesh bit-identity tests use;
  * the identity scenario wraps the drivers in
    reshard.forbid_row_fetches: the only host traffic on the cross-host
    path is the replicated count-stats vector and O(kept) results;
  * the host-loss scenario injects a whole-host device loss
    (Fault(device_loss, process=...)): the surviving controller rebuilds
    the mesh over its own devices and completes bit-identically (block
    keys are geometry-independent), while the evacuated controller
    raises HostEvacuatedError and exits cleanly;
  * the grow scenario (fleet operations, PR 17) starts each controller
    on HALF its devices (one per process), announces the other half as
    join candidates at block 2, and proves the elastic scale-UP
    (retry.run_with_mesh_elasticity) completes bit-identically to the
    full-geometry reference — the mirror image of host loss;
  * the migrate_source scenario interrupts a journaled blocked run with
    an injected fatal at block 4 and persists each controller's
    odometer trail; the PARENT then adopts the journal records into its
    own scope (BlockJournal.adopt_job) and resumes at a DIFFERENT
    geometry, bit-identically — the drain-and-migrate path;
  * the drill:<gen>:<state_dir> scenarios are the pod half of the
    rolling-restart drill: each generation is a full controller respawn
    over a shared ledger directory (jax.distributed worlds are fixed at
    init, so a controller bounce IS a new generation), generation 1
    kills controller p1 inside its last ledger persist's fsync-to-
    rename window, and generation 2's restarted controllers reload
    their trails and re-charge the lost job under the SAME id —
    idempotent where the charge landed, an append where the kill ate it
    — with the final per-process trails reconciling bit-exactly.

The spawn helper enforces a HARD timeout — a wedged child (a collective
waiting on a dead peer) is killed and surfaced as a failure, so the
multihost tests can never hang tier-1.
"""

import json
import os
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

# Env vars the spawned children read (set by spawn_local_pod).
ENV_COORDINATOR = "PDP_MULTIHOST_COORDINATOR"
ENV_NUM_PROCESSES = "PDP_MULTIHOST_NUM_PROCESSES"
ENV_PROCESS_INDEX = "JAX_PROCESS_INDEX"

# The pod geometry every scenario runs: 2 controllers x 2 devices == the
# 4-device single-process reference.
POD_PROCESSES = 2
POD_DEVICES_PER_PROCESS = 2


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# Workload recipe (shared verbatim by children and the reference)
# ---------------------------------------------------------------------------


def _pod_spec(n_partitions: int, l0: int = 2, linf: int = 3):
    """(cfg, selection, stds, scalars) of a COUNT+SUM private-selection
    step with the noise stds zeroed — parity must be exact, and the
    selection decisions stay deterministic through the replicated key."""
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import combiners, executor
    from pipelinedp_tpu.aggregate_params import MechanismType
    from pipelinedp_tpu.ops import selection_ops

    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=l0,
        max_contributions_per_partition=linf,
        min_value=0.0,
        max_value=9.0)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=1.0, total_delta=1e-6)
    compound = combiners.create_compound_combiner(params, acc)
    budget = acc.request_budget(MechanismType.GENERIC)
    acc.compute_budgets()
    selection = selection_ops.selection_params_from_host(
        params.partition_selection_strategy, budget.eps, budget.delta,
        params.max_partitions_contributed, None)
    cfg = executor.make_kernel_config(params, compound, n_partitions,
                                      private_selection=True,
                                      selection_params=selection)
    stds = np.zeros_like(executor.compute_noise_stds(compound, params))
    return cfg, selection, stds, executor.kernel_scalars(params)


def _pod_rows(n_partitions: int, n_ids: int = 960,
              l0: int = 2, linf: int = 3):
    """Deterministic integer-valued rows whose contribution bounds are
    exactly met (never exceeded): bounding drops nothing, psums are
    exact, so outputs are a pure function of the multiset of rows —
    independent of mesh geometry, process topology and row order.
    Partitions are DENSE (~n_ids/6 privacy ids each) so private
    selection keeps them deterministically at eps=1."""
    u = np.arange(n_ids, dtype=np.int64)
    pid = np.repeat(u, l0 * linf)
    if n_partitions <= 64:
        p1 = (u * 7) % 12
        p2 = (u * 7 + 1) % 12
    else:
        # Large-P (blocked) recipe: 8 dense partitions spread across the
        # whole [0, P) range — several 512-partition blocks see some,
        # each partition holds ~n_ids/4 privacy ids (a thin spread over
        # P partitions would be dropped by selection and prove nothing).
        slots = 4
        p1 = (u % slots) * (n_partitions // slots) + 13
        p2 = ((u + 1) % slots) * (n_partitions // slots) + 200
    pk = np.repeat(
        np.stack([p1, p2], axis=1).ravel().astype(np.int32), linf)
    values = ((pid * 7 + pk) % 10).astype(np.float64)
    valid = np.ones(len(pid), dtype=bool)
    return pid, pk, values, valid


def _stage_global_rows(mesh, pid, pk, values, valid):
    """Lays the rows out as one global mesh-sharded array set.

    Single-controller: one upload. Multi-controller: each process uploads
    ONLY its contiguous row slice (padded to the shared per-device
    capacity, pk -1 / valid False marking the pad), assembled with
    jax.make_array_from_process_local_data — the driver-level counterpart
    of ingest.encode_local_shard_to_mesh's layout, so the reshard's
    _pad_and_shard passes it through without any eager cross-process
    copy.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from pipelinedp_tpu.parallel import mesh as mesh_lib

    sharding = NamedSharding(mesh, PartitionSpec(mesh_lib.SHARD_AXIS))
    n_proc = mesh_lib.process_count()
    if n_proc == 1:
        return (jnp.asarray(pid.astype(np.int32)), jnp.asarray(pk),
                jnp.asarray(values), jnp.asarray(valid))
    me = mesh_lib.process_index()
    n = len(pid)
    per_proc = -(-n // n_proc)
    lo, hi = me * per_proc, min((me + 1) * per_proc, n)
    n_local_dev = len(mesh_lib.local_devices(mesh))
    n_dev = int(mesh.devices.size)
    cap = mesh_lib.round_capacity(-(-per_proc // max(n_local_dev, 1)))
    local_rows = cap * n_local_dev
    global_rows = cap * n_dev

    def to_global(col, fill, dtype):
        local = np.full((local_rows,) + col.shape[1:], fill, dtype)
        local[:hi - lo] = col[lo:hi]
        return jax.make_array_from_process_local_data(
            sharding, local, (global_rows,) + col.shape[1:])

    return (to_global(pid.astype(np.int32), 0, np.int32),
            to_global(pk, -1, np.int32),
            to_global(values, 0.0, values.dtype),
            to_global(valid, False, bool))


def _kept_release(n_kept, ids, outputs) -> Dict[str, np.ndarray]:
    """The kept prefix of a compacted dense release (replicated on the
    mesh, so every controller reads its local replica), keyed for the
    cross-topology bit-compare."""
    from pipelinedp_tpu.parallel.mesh import host_fetch
    k = int(host_fetch(n_kept))
    return {
        "dense_ids": host_fetch(ids)[:k],
        "dense_count": host_fetch(outputs["count"])[:k],
        "dense_sum": host_fetch(outputs["sum"])[:k],
    }


def run_pod_workload(mesh, journal_dir: Optional[str] = None,  # staticcheck: disable=key-hygiene — fixed literal harness keys: the bit-identity proof REQUIRES every controller and the reference to derive from the same key; noise stds are zeroed, nothing here is a product release
                     elastic: bool = False) -> Dict[str, np.ndarray]:
    """The four meshed drivers over `mesh`, device-resident inputs,
    deterministic keys. Returns host-numpy outputs keyed for bitwise
    comparison across topologies."""
    import jax

    from pipelinedp_tpu.parallel import large_p, sharded
    from pipelinedp_tpu.parallel.mesh import host_fetch
    from pipelinedp_tpu.runtime import journal as rt_journal

    P_dense, P_big = 48, 4096
    cfg, selection, stds, (min_v, max_v, min_s, max_s, mid) = _pod_spec(
        P_dense)
    cfg_big, selection_big, stds_big, _ = _pod_spec(P_big)
    pid, pk, values, valid = _pod_rows(P_dense)
    pid_b, pk_b, values_b, valid_b = _pod_rows(P_big)
    key = jax.random.PRNGKey(3)
    journal = (rt_journal.BlockJournal(journal_dir)
               if journal_dir else None)
    runtime_kwargs = dict(elastic=elastic) if elastic else {}

    cols = _stage_global_rows(mesh, pid, pk, values, valid)
    n_kept, ids, outputs, _ = sharded.sharded_aggregate_arrays(
        mesh, *cols, min_v, max_v, min_s, max_s, mid, stds, key, cfg,
        **runtime_kwargs)
    sel_n, sel_ids = sharded.sharded_select_partitions(
        mesh, cols[0], cols[1], cols[3], jax.random.PRNGKey(5), 2,
        P_dense, selection, **runtime_kwargs)

    cols_b = _stage_global_rows(mesh, pid_b, pk_b, values_b, valid_b)
    blk_ids, blk_out = large_p.aggregate_blocked_sharded(
        mesh, *cols_b, min_v, max_v, min_s, max_s, mid, stds_big,
        jax.random.PRNGKey(7), cfg_big, block_partitions=512,
        journal=journal, **runtime_kwargs)
    blk_sel = large_p.select_partitions_blocked_sharded(
        mesh, cols_b[0], cols_b[1], cols_b[3], jax.random.PRNGKey(9), 2,
        P_big, selection_big, block_partitions=512, journal=journal,
        **runtime_kwargs)

    return {
        **_kept_release(n_kept, ids, outputs),
        "dense_sel": host_fetch(sel_ids)[:int(host_fetch(sel_n))],
        "blk_ids": np.asarray(blk_ids),
        "blk_count": np.asarray(blk_out["count"]),
        "blk_sum": np.asarray(blk_out["sum"]),
        "blk_sel": np.asarray(blk_sel),
    }


def _engine_chunks(lo: int, hi: int, chunk: int = 700):
    """String-keyed engine input chunks for rows [lo, hi) of the shared
    stream — string keys so the vocabulary exchange is exercised on real
    (object-dtype) vocabularies, integer values so sums stay exact."""
    rng = np.random.default_rng(17)
    n = 3000
    pids = np.char.add("u", (rng.integers(0, 250, n)).astype(str))
    pks = np.char.add("p", (rng.integers(0, 30, n)).astype(str))
    vals = rng.integers(0, 10, n).astype(np.float64)
    return [(pids[i:min(i + chunk, hi)], pks[i:min(i + chunk, hi)],
             vals[i:min(i + chunk, hi)])
            for i in range(lo, hi, chunk)], n


def run_pod_engine(mesh) -> Dict[str, np.ndarray]:
    """Engine-level pod aggregation over the multi-host ingest path:
    this process encodes only its shard (encode_local_shard_to_mesh),
    the engine aggregates over the pod mesh, and the budget ledger is
    returned for the zero-duplicate-registration check.

    Runs BOTH encode modes over the same shard and seed: the host
    vocabulary exchange and the hash-device collective factorize
    (device vocab all_gather + on-device unique,
    device_encode.mesh_factorize_codes) must release bit-identical
    results — asserted here on every controller AND compared bitwise
    across topologies through the returned hash_* keys, which is what
    gates the device vocab allgather in tier-1's 2-process pod."""
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import ingest
    from pipelinedp_tpu.parallel import mesh as mesh_lib

    n_proc = mesh_lib.process_count()
    me = mesh_lib.process_index()
    _, total = _engine_chunks(0, 0)
    per = -(-total // n_proc)
    lo, hi = me * per, min((me + 1) * per, total)
    chunks, _ = _engine_chunks(lo, hi)
    encoded = ingest.encode_local_shard_to_mesh(iter(chunks), mesh)

    params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT,
                                          pdp.Metrics.SUM],
                                 max_partitions_contributed=30,
                                 max_contributions_per_partition=60,
                                 min_value=0.0,
                                 max_value=9.0)
    ex = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                            partition_extractor=lambda r: r[1],
                            value_extractor=lambda r: float(r[2]))
    acc = pdp.NaiveBudgetAccountant(total_epsilon=1e7, total_delta=1e-6)
    engine = pdp.DPEngine(acc, pdp.TPUBackend(mesh=mesh, noise_seed=11))
    result = engine.aggregate(encoded, params, ex)
    acc.compute_budgets()
    result = dict(result)
    pks = sorted(result)

    # Hash-device ingest over the SAME shard and noise seed: the device
    # collective factorize must place every row on the same codes, so
    # the release is bit-identical to the host-exchanged one.
    hash_encoded = ingest.encode_local_shard_to_mesh(
        chunks, mesh, encode_mode="hash_device")
    acc_h = pdp.NaiveBudgetAccountant(total_epsilon=1e7,
                                      total_delta=1e-6)
    engine_h = pdp.DPEngine(acc_h,
                            pdp.TPUBackend(mesh=mesh, noise_seed=11))
    hash_lazy = engine_h.aggregate(hash_encoded, params, ex)
    acc_h.compute_budgets()
    hash_result = dict(hash_lazy)
    assert sorted(hash_result) == pks, (
        f"hash-device pod ingest kept a different partition set: "
        f"{len(hash_result)} vs {len(pks)}")
    for k in pks:
        assert (hash_result[k].count == result[k].count and
                hash_result[k].sum == result[k].sum), (
            f"hash-device pod ingest diverged from the host encode "
            f"at {k!r}")
    assert acc_h.mechanism_count == acc.mechanism_count
    # The budget odometer rides the bit-identity contract: every
    # controller (and the single-process reference) derives the SAME
    # audit trail for this ledger — record count == mechanism_count and
    # the per-mechanism eps shares sum EXACTLY to the ledger's spent
    # epsilon, asserted here and compared bitwise across topologies
    # through the outputs.
    from pipelinedp_tpu.runtime import observability
    odo = observability.odometer_report(accountant=acc)
    assert odo["reconciled"], odo
    assert odo["mechanisms"] == acc.mechanism_count, odo
    assert odo["spent_epsilon"] == acc.spent_epsilon(), odo
    return {
        "engine_pks": np.asarray([str(k) for k in pks]),
        "engine_counts": np.asarray([result[k].count for k in pks]),
        "engine_sums": np.asarray([result[k].sum for k in pks]),
        "hash_engine_counts": np.asarray(
            [hash_result[k].count for k in pks]),
        "hash_engine_sums": np.asarray(
            [hash_result[k].sum for k in pks]),
        "mechanism_count": np.asarray([acc.mechanism_count]),
        "odometer_mechanisms": np.asarray([odo["mechanisms"]]),
        "odometer_spent_eps": np.asarray([odo["spent_epsilon"]],
                                         dtype=np.float64),
    }


def run_host_loss_workload(mesh, lost_process: int,  # staticcheck: disable=key-hygiene — fixed literal harness key shared with the fault-free reference (bit-identity proof); noise-free, not a product release
                           journal_dir: str) -> Dict[str, np.ndarray]:
    """The blocked aggregate driver under an injected WHOLE-HOST loss:
    every device of `lost_process` drops at block 2 of the first
    dispatch. Host-numpy inputs (the multi-controller identical-input
    contract), elastic + journal, so the surviving controller rebuilds
    over its own devices, replays journaled blocks, re-derives the same
    fold_in keys and finishes bit-identically to a fault-free run —
    while the evacuated controller raises HostEvacuatedError (translated
    by the child main into an `evacuated` marker)."""
    import jax

    from pipelinedp_tpu.parallel import large_p
    from pipelinedp_tpu.runtime import faults as rt_faults
    from pipelinedp_tpu.runtime import journal as rt_journal

    P_big = 4096
    cfg_big, _, stds_big, (min_v, max_v, min_s, max_s, mid) = _pod_spec(
        P_big)
    pid_b, pk_b, values_b, valid_b = _pod_rows(P_big)
    journal = rt_journal.BlockJournal(journal_dir)
    schedule = rt_faults.FaultSchedule([
        rt_faults.Fault("device_loss", block=2, point="dispatch",
                        process=lost_process),
    ])
    with rt_faults.inject(schedule):
        blk_ids, blk_out = large_p.aggregate_blocked_sharded(
            mesh, pid_b, pk_b, values_b, valid_b, min_v, max_v, min_s,
            max_s, mid, stds_big, jax.random.PRNGKey(7), cfg_big,
            block_partitions=512, journal=journal, elastic=True)
    return {
        "blk_ids": np.asarray(blk_ids),
        "blk_count": np.asarray(blk_out["count"]),
        "blk_sum": np.asarray(blk_out["sum"]),
    }


def reference_host_loss_outputs() -> Dict[str, np.ndarray]:  # staticcheck: disable=key-hygiene — fixed literal harness key shared with the faulted run (bit-identity proof); noise-free, not a product release
    """Fault-free single-process reference of run_host_loss_workload
    (same recipe, same keys, no journal needed)."""
    import jax

    from pipelinedp_tpu.parallel import large_p
    from pipelinedp_tpu.parallel.mesh import make_mesh

    n_dev = POD_PROCESSES * POD_DEVICES_PER_PROCESS
    mesh = make_mesh(n_devices=n_dev)
    P_big = 4096
    cfg_big, _, stds_big, (min_v, max_v, min_s, max_s, mid) = _pod_spec(
        P_big)
    pid_b, pk_b, values_b, valid_b = _pod_rows(P_big)
    blk_ids, blk_out = large_p.aggregate_blocked_sharded(
        mesh, pid_b, pk_b, values_b, valid_b, min_v, max_v, min_s, max_s,
        mid, stds_big, jax.random.PRNGKey(7), cfg_big,
        block_partitions=512)
    return {
        "blk_ids": np.asarray(blk_ids),
        "blk_count": np.asarray(blk_out["count"]),
        "blk_sum": np.asarray(blk_out["sum"]),
    }


def run_grow_workload(journal_dir: str) -> Dict[str, np.ndarray]:  # staticcheck: disable=key-hygiene — fixed literal harness key shared with the full-geometry reference (bit-identity proof); noise stds are zeroed, not a product release
    """The blocked aggregate driver under an elastic SCALE-UP: each
    controller starts on HALF its devices (one per process — the pod's
    "before more hardware arrived" geometry), announces the remaining
    devices as join candidates at block 2, and runs with
    elastic_grow=True. Both controllers announce identically, so both
    unwind at the same block boundary, admit the same candidates (the
    jax.devices() enumeration order is pod-consistent) and rebuild the
    same full mesh — blocks 0-1 replay from each controller's scoped
    journal, the rest dispatch on the grown mesh with unchanged
    fold_in(final_key, b) keys. Host-numpy inputs, so every re-entry
    re-stages onto whatever mesh is current."""
    import jax

    from pipelinedp_tpu.parallel import large_p
    from pipelinedp_tpu.parallel import mesh as mesh_lib
    from pipelinedp_tpu.runtime import journal as rt_journal
    from pipelinedp_tpu.runtime import retry as rt_retry

    devices = sorted(jax.devices(),
                     key=lambda d: (d.process_index, d.id))
    by_proc: Dict[int, list] = {}
    for d in devices:
        by_proc.setdefault(int(d.process_index), []).append(d)
    small = [ds[0] for _, ds in sorted(by_proc.items())]
    mesh = mesh_lib.make_mesh(devices=small)

    P_big = 4096
    cfg_big, _, stds_big, (min_v, max_v, min_s, max_s, mid) = _pod_spec(
        P_big)
    pid_b, pk_b, values_b, valid_b = _pod_rows(P_big)
    journal = rt_journal.BlockJournal(journal_dir)
    rt_retry.announce_join(n_devices=len(devices), block=2)
    try:
        blk_ids, blk_out = large_p.aggregate_blocked_sharded(
            mesh, pid_b, pk_b, values_b, valid_b, min_v, max_v, min_s,
            max_s, mid, stds_big, jax.random.PRNGKey(7), cfg_big,
            block_partitions=512, journal=journal, elastic_grow=True)
    finally:
        rt_retry.clear_joins()
    return {
        "blk_ids": np.asarray(blk_ids),
        "blk_count": np.asarray(blk_out["count"]),
        "blk_sum": np.asarray(blk_out["sum"]),
    }


MIGRATE_JOB_ID = "migrate-job"


def run_migrate_source_workload(mesh,  # staticcheck: disable=key-hygiene — fixed literal harness key shared with the resumed run and the clean reference (bit-identity proof); noise-free, not a product release
                                journal_dir: str) -> None:
    """Pod A's half of drain-and-migrate: the journaled blocked
    aggregate is interrupted by an injected fatal at block 4 (the
    sharded driver numbers blocks by partition stride, so blocks 0 and
    2 are drained and journaled first), and the controller persists
    its odometer trail into its journal scope before exiting — the
    complete state a migration target needs. Raises InjectedFatalError
    (the caller marks the job interrupted)."""
    import jax

    from pipelinedp_tpu.parallel import large_p
    from pipelinedp_tpu.parallel import mesh as mesh_lib
    from pipelinedp_tpu.runtime import faults as rt_faults
    from pipelinedp_tpu.runtime import journal as rt_journal
    from pipelinedp_tpu.runtime import observability as rt_obs

    P_big = 4096
    cfg_big, _, stds_big, (min_v, max_v, min_s, max_s, mid) = _pod_spec(
        P_big)
    pid_b, pk_b, values_b, valid_b = _pod_rows(P_big)
    journal = rt_journal.BlockJournal(journal_dir)
    try:
        with rt_faults.inject(rt_faults.FaultSchedule(
                [rt_faults.Fault("fatal", block=4)])):
            large_p.aggregate_blocked_sharded(
                mesh, pid_b, pk_b, values_b, valid_b, min_v, max_v,
                min_s, max_s, mid, stds_big, jax.random.PRNGKey(7),
                cfg_big, block_partitions=512, journal=journal,
                job_id=MIGRATE_JOB_ID)
    finally:
        # The cancelled job's odometer trail rides along with its block
        # records (the entry wrapper only persists on success): the
        # migration target adopts BOTH, so the tenant ledger's
        # provenance survives the pod move.
        scoped = journal.scoped_to_process(mesh_lib.process_index())
        rt_obs.persist_odometer(scoped, MIGRATE_JOB_ID)


def run_migration_target(journal_dir: str,  # staticcheck: disable=key-hygiene — fixed literal harness key shared with the interrupted source and the clean reference (bit-identity proof); noise-free, not a product release
                         n_devices: int,
                         source_process_index: Optional[int] = None
                         ) -> Tuple[int, int, Dict[str, np.ndarray]]:
    """Pod B's half of drain-and-migrate: adopts the interrupted job's
    journal records into THIS process's scope (BlockJournal.adopt_job)
    and resumes the same driver call at a (possibly different) geometry.
    Adopted blocks replay, the rest re-derive the same geometry-
    independent keys — the resumed outputs are bit-identical to an
    uninterrupted run. Returns (records_adopted,
    adopted_odometer_records, outputs) — the odometer count is read
    BETWEEN adopt and resume, proving the tenant-ledger provenance
    crossed the pod boundary (the resume's own teardown persist
    supersedes it afterwards)."""
    import jax

    from pipelinedp_tpu.parallel import large_p
    from pipelinedp_tpu.parallel.mesh import make_mesh
    from pipelinedp_tpu.runtime import journal as rt_journal
    from pipelinedp_tpu.runtime import observability as rt_obs

    journal = rt_journal.BlockJournal(journal_dir)
    adopted = journal.adopt_job(MIGRATE_JOB_ID,
                                source_process_index=source_process_index)
    adopted_odometer = len(rt_obs.load_odometer(journal, MIGRATE_JOB_ID))
    P_big = 4096
    cfg_big, _, stds_big, (min_v, max_v, min_s, max_s, mid) = _pod_spec(
        P_big)
    pid_b, pk_b, values_b, valid_b = _pod_rows(P_big)
    blk_ids, blk_out = large_p.aggregate_blocked_sharded(
        make_mesh(n_devices=n_devices), pid_b, pk_b, values_b, valid_b,
        min_v, max_v, min_s, max_s, mid, stds_big, jax.random.PRNGKey(7),
        cfg_big, block_partitions=512, journal=journal,
        job_id=MIGRATE_JOB_ID)
    return adopted, adopted_odometer, {
        "blk_ids": np.asarray(blk_ids),
        "blk_count": np.asarray(blk_out["count"]),
        "blk_sum": np.asarray(blk_out["sum"]),
    }


# ---------------------------------------------------------------------------
# Rolling-restart drill generations (the pod half of the drill)
# ---------------------------------------------------------------------------

# The drill's tenant and planned job ids (service format, so
# TenantLedger.max_job_seq parses them).
DRILL_TENANT = "acme"


def _drill_planned_jobs(gen: int) -> List[str]:
    """Generation g's planned job ids. Every generation after the first
    FIRST re-charges the previous generation's last job under the SAME
    id: where the charge landed the replay is idempotent (no second
    spend), where the mid-persist kill ate it the replay is the append
    that makes the trail whole — the no-loss/no-double-spend pincer."""
    own = [f"{DRILL_TENANT}--j{gen:03d}1", f"{DRILL_TENANT}--j{gen:03d}2"]
    if gen <= 1:
        return own
    return [f"{DRILL_TENANT}--j{gen - 1:03d}2"] + own


def _drill_records() -> List[dict]:
    """A real accountant's mechanism trail (COUNT+SUM registration, eps
    shares resolved by compute_budgets), deterministic across processes
    and generations — the charge payload every drill job records."""
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import combiners
    from pipelinedp_tpu.runtime import observability as rt_obs

    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        max_partitions_contributed=2,
        max_contributions_per_partition=3,
        min_value=0.0,
        max_value=9.0)
    acc = pdp.NaiveBudgetAccountant(total_epsilon=1.0, total_delta=1e-6)
    combiners.create_compound_combiner(params, acc)
    acc.compute_budgets()
    records = rt_obs.odometer_report(accountant=acc)["records"]
    rt_obs.prune_odometer(accountant=acc)
    return records


def _drill_dense_outputs(mesh) -> Dict[str, np.ndarray]:  # staticcheck: disable=key-hygiene — fixed literal harness key: every drill generation and the reference must draw identical outputs for the cross-controller bit-compare; not a product release
    """The drill generations' sustained traffic: the dense meshed
    aggregate over the shared recipe — cheap, and bit-comparable to the
    single-process reference across every generation."""
    import jax

    from pipelinedp_tpu.parallel import sharded

    P_dense = 48
    cfg, _, stds, (min_v, max_v, min_s, max_s, mid) = _pod_spec(P_dense)
    pid, pk, values, valid = _pod_rows(P_dense)
    cols = _stage_global_rows(mesh, pid, pk, values, valid)
    n_kept, ids, outputs, _ = sharded.sharded_aggregate_arrays(
        mesh, *cols, min_v, max_v, min_s, max_s, mid, stds,
        jax.random.PRNGKey(3), cfg)
    return _kept_release(n_kept, ids, outputs)


def reference_drill_outputs() -> Dict[str, np.ndarray]:
    """Single-process reference of the drill generations' traffic."""
    from pipelinedp_tpu.parallel.mesh import make_mesh

    n_dev = POD_PROCESSES * POD_DEVICES_PER_PROCESS
    return _drill_dense_outputs(make_mesh(n_devices=n_dev))


def _drill_generation(gen: int, state_dir: str, mesh,
                      info: Dict[str, object]) -> Dict[str, np.ndarray]:
    """One controller's life in drill generation `gen` (see the module
    docstring): reload the per-process ledger trail from the shared
    state_dir, run the sustained traffic, charge the generation's
    planned jobs — and in generation 1, controller p1 dies inside its
    LAST charge's ledger persist (fsync done, rename never happens),
    modelling the kill -9 the rolling restart must absorb."""
    from pipelinedp_tpu.parallel import mesh as mesh_lib
    from pipelinedp_tpu.runtime import faults as rt_faults
    from pipelinedp_tpu.runtime import journal as rt_journal
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry
    from pipelinedp_tpu.service.ledger import TenantLedger

    me = mesh_lib.process_index()
    ledger_journal = rt_journal.BlockJournal(
        state_dir).scoped_to_process(me)
    ledger = TenantLedger(DRILL_TENANT, 100.0, ledger_journal)
    info["ledger_jobs_at_start"] = sorted(
        {r.get("job_id") for r in ledger.records()})
    if gen > 1:
        # A later generation IS this controller's rolling restart:
        # fresh process, ledger reloaded from the durable trail.
        rt_telemetry.record("rolling_restarts", generation=gen)
    outputs = _drill_dense_outputs(mesh)
    planned = _drill_planned_jobs(gen)
    info["planned_jobs"] = planned
    info["died_during_persist"] = False
    for job_id in planned:
        records = _drill_records()
        if gen == 1 and me == 1 and job_id == planned[-1]:
            try:
                with rt_faults.inject(rt_faults.FaultSchedule(
                        [rt_faults.Fault("restart_during_persist",
                                         point="odometer")])):
                    ledger.charge(job_id, records)
            except rt_faults.InjectedRestartError:
                # A real kill -9 ends the process here: the in-memory
                # trail dies with it, the disk keeps only what renamed.
                # (The drill child exits cleanly so the spawner does
                # not mistake the SCRIPTED kill for a harness failure.)
                info["died_during_persist"] = True
                break
        else:
            ledger.charge(job_id, records)
    info["ledger_spent"] = ledger.spent_epsilon()
    info["ledger_jobs_at_end"] = sorted(
        {r.get("job_id") for r in ledger.records()})
    return outputs


def reference_identity_outputs(tmp_journal_dir: Optional[str] = None
                               ) -> Dict[str, np.ndarray]:
    """Single-process reference of the identity scenario: same recipe,
    same keys, one controller owning all POD devices."""
    from pipelinedp_tpu.parallel.mesh import make_mesh

    n_dev = POD_PROCESSES * POD_DEVICES_PER_PROCESS
    mesh = make_mesh(n_devices=n_dev)
    out = run_pod_workload(mesh, journal_dir=tmp_journal_dir)
    out.update(run_pod_engine(mesh))
    return out


# ---------------------------------------------------------------------------
# Child process main
# ---------------------------------------------------------------------------


def _child_main(scenario: str, out_path: str) -> int:
    """Entry point of one spawned controller (see spawn_local_pod).

    Every child runs fully OBSERVED: tracing + per-span memory sampling
    on, a portless file metrics exporter live for the whole run (read
    back MID-RUN into info["scrape"] — the scrapeable-while-in-flight
    proof), and a full observability export (counters, gauges, health,
    odometer, trace buffer under this controller's process index as its
    Perfetto pid) written at teardown. Process 0 then performs the
    collective-free host-side gather: it waits for its siblings' export
    files and writes the merged pod rollup (one trace, both tracks).
    """
    import jax

    from pipelinedp_tpu.parallel import mesh as mesh_lib
    from pipelinedp_tpu.runtime import observability as rt_obs
    from pipelinedp_tpu.runtime import retry as rt_retry
    from pipelinedp_tpu.runtime import telemetry as rt_telemetry
    from pipelinedp_tpu.runtime import trace as rt_trace
    from pipelinedp_tpu.runtime import health as rt_health

    coordinator = os.environ[ENV_COORDINATOR]
    num_processes = int(os.environ[ENV_NUM_PROCESSES])
    process_id = int(os.environ[ENV_PROCESS_INDEX])
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    mesh_lib.initialize_distributed(coordinator, num_processes,
                                    process_id)
    assert jax.process_count() == num_processes
    mesh = mesh_lib.make_mesh()
    out_dir = os.path.dirname(out_path)
    journal_dir = os.path.join(out_dir, "journal")
    rt_trace.enable()
    rt_obs.enable_memory_sampling()
    me = mesh_lib.process_index()
    exporter = rt_obs.start_exporter(
        path=os.path.join(out_dir, f"metrics_p{me}.prom"),
        interval_s=0.2)
    info: Dict[str, object] = {
        "process_index": me,
        "n_devices": int(mesh.devices.size),
        "n_local_devices": len(mesh_lib.local_devices(mesh)),
        "fully_addressable": mesh_lib.is_fully_addressable(mesh),
        "evacuated": False,
    }
    outputs: Dict[str, np.ndarray] = {}
    if scenario == "identity":
        from pipelinedp_tpu.parallel import reshard
        # The transfer guard rides the whole driver pass: the only host
        # traffic on the cross-host reshard path is the replicated
        # count-stats vector, block offsets and O(kept) results.
        with reshard.forbid_row_fetches():
            outputs.update(run_pod_workload(mesh,
                                            journal_dir=journal_dir))
        # MID-RUN scrape: the drivers above are drained but the engine
        # half of this controller's job is still ahead — the exporter
        # file at this instant is what an external scraper would see
        # while the pod is in flight.
        with open(exporter.path) as f:
            info["scrape"] = f.read()
        outputs.update(run_pod_engine(mesh))
    elif scenario == "host_loss":
        lost = num_processes - 1
        try:
            outputs.update(
                run_host_loss_workload(mesh, lost, journal_dir))
        except rt_retry.HostEvacuatedError as e:
            info["evacuated"] = True
            info["evacuation_error"] = str(e)[:500]
        with open(exporter.path) as f:
            info["scrape"] = f.read()
    elif scenario == "grow":
        outputs.update(run_grow_workload(journal_dir))
        with open(exporter.path) as f:
            info["scrape"] = f.read()
    elif scenario == "migrate_source":
        from pipelinedp_tpu.runtime import faults as rt_faults
        try:
            run_migrate_source_workload(mesh, journal_dir)
            raise SystemExit(
                "migrate_source: the injected fatal never fired")
        except rt_faults.InjectedFatalError as e:
            info["interrupted"] = True
            info["interruption_error"] = str(e)[:500]
        with open(exporter.path) as f:
            info["scrape"] = f.read()
    elif scenario.startswith("drill:"):
        _, gen_s, state_dir = scenario.split(":", 2)
        outputs.update(
            _drill_generation(int(gen_s), state_dir, mesh, info))
        with open(exporter.path) as f:
            info["scrape"] = f.read()
    else:
        raise SystemExit(f"unknown scenario {scenario!r}")
    info["counters"] = dict(rt_telemetry.snapshot())
    info["health"] = {
        job: snap["state"]
        for job, snap in rt_health.snapshot_all().items()
    }
    np.savez(out_path + ".npz", **outputs)
    with open(out_path + ".json", "w") as f:
        json.dump(info, f)
    # Teardown observability gather: every controller exports its own
    # state atomically; process 0 merges whatever its siblings managed
    # to write into the pod rollup (a dead sibling costs coverage, not
    # the rollup).
    exporter.stop()
    rt_obs.export_process_state(out_dir, process_index=me)
    if me == 0:
        rt_obs.write_pod_rollup(out_dir, num_processes, timeout_s=60.0)
    return 0


# ---------------------------------------------------------------------------
# Spawner (hard-timeout enforced)
# ---------------------------------------------------------------------------


def spawn_local_pod(scenario: str, out_dir: str,
                    n_processes: int = POD_PROCESSES,
                    devices_per_process: int = POD_DEVICES_PER_PROCESS,
                    timeout_s: float = 240.0) -> List[Tuple[dict, dict]]:
    """Spawns an n-process jax.distributed CPU pod running `scenario`.

    Returns one (info_json, outputs_npz_dict) pair per process, in
    process order. Enforces a HARD timeout: children still alive at the
    deadline are killed (a collective waiting on a dead peer would
    otherwise wedge forever) and a TimeoutError carries their last
    output, so a wedged pod can never hang the calling test suite.
    """
    import pipelinedp_tpu

    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(pipelinedp_tpu.__file__)))
    port = _free_port()
    procs = []
    for p in range(n_processes):
        env = os.environ.copy()
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS":
                f"--xla_force_host_platform_device_count"
                f"={devices_per_process}",
            "JAX_ENABLE_X64": "1",
            ENV_PROCESS_INDEX: str(p),
            ENV_COORDINATOR: f"127.0.0.1:{port}",
            ENV_NUM_PROCESSES: str(n_processes),
            "PYTHONPATH": repo_root + os.pathsep + env.get("PYTHONPATH",
                                                           ""),
        })
        out = os.path.join(out_dir, f"proc{p}")
        proc = subprocess.Popen(
            [sys.executable, "-m", "pipelinedp_tpu.runtime.multihost",
             scenario, out],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=repo_root)
        procs.append((p, proc, out))
    deadline = time.monotonic() + timeout_s
    logs = {}
    try:
        for p, proc, _ in procs:
            left = max(deadline - time.monotonic(), 0.001)
            try:
                logs[p], _ = proc.communicate(timeout=left)
            except subprocess.TimeoutExpired:
                raise TimeoutError(
                    f"multihost pod scenario {scenario!r}: process {p} "
                    f"still running after {timeout_s:.0f}s — killed. "
                    f"A wedged collective (dead peer) is the usual "
                    f"cause.")
    finally:
        for _, proc, _ in procs:
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
    results = []
    for p, proc, out in procs:
        if proc.returncode != 0:
            tail = "\n".join((logs.get(p) or "").splitlines()[-30:])
            raise RuntimeError(
                f"multihost pod scenario {scenario!r}: process {p} "
                f"exited rc={proc.returncode}\n--- tail of its output "
                f"---\n{tail}")
        with open(out + ".json") as f:
            info = json.load(f)
        with np.load(out + ".npz", allow_pickle=False) as data:
            outputs = {name: data[name] for name in data.files}
        results.append((info, outputs))
    return results


# ---------------------------------------------------------------------------
# Checks (shared by tests/test_multihost.py and the __graft_entry__ dryrun)
# ---------------------------------------------------------------------------


def _assert_outputs_equal(got: Dict[str, np.ndarray],
                          want: Dict[str, np.ndarray],
                          what: str) -> None:
    assert set(got) == set(want), (
        f"{what}: output key mismatch {set(got) ^ set(want)}")
    for name in sorted(want):
        assert np.array_equal(np.asarray(got[name]),
                              np.asarray(want[name])), (
            f"{what}: {name!r} differs\n got={got[name]!r}\n "
            f"want={want[name]!r}")


def check_identity_results(results: List[Tuple[dict, dict]],
                           reference: Dict[str, np.ndarray]) -> str:
    """Asserts the identity scenario: every controller produced the same
    outputs, bit-identical to the single-process reference, with equal
    budget-ledger counts and no journal cross-talk."""
    assert len(results) == POD_PROCESSES
    for p, (info, outputs) in enumerate(results):
        assert info["process_index"] == p
        assert info["n_devices"] == POD_PROCESSES * POD_DEVICES_PER_PROCESS
        assert info["n_local_devices"] == POD_DEVICES_PER_PROCESS
        assert not info["fully_addressable"], (
            "the pod mesh must span processes")
        _assert_outputs_equal(outputs, reference,
                              f"process {p} vs single-process reference")
    mech = {int(outputs["mechanism_count"][0])
            for _, outputs in results}
    mech.add(int(reference["mechanism_count"][0]))
    assert len(mech) == 1, (
        f"budget-ledger mechanism counts diverged across topologies: "
        f"{mech}")
    kept = len(reference["dense_ids"])
    return (f"{POD_PROCESSES} processes x {POD_DEVICES_PER_PROCESS} "
            f"devices == 1 process x "
            f"{POD_PROCESSES * POD_DEVICES_PER_PROCESS} devices "
            f"bit-identical on all four drivers + engine "
            f"({kept} dense partitions kept, "
            f"{len(reference['blk_ids'])} blocked partitions, ledger "
            f"{int(reference['mechanism_count'][0])} mechanisms)")


def check_host_loss_results(results: List[Tuple[dict, dict]],
                            reference: Dict[str, np.ndarray]) -> str:
    """Asserts the host-loss scenario: the surviving controller finished
    bit-identically to the fault-free reference with DEGRADED health and
    the loss counters incremented; the lost controller evacuated."""
    assert len(results) == POD_PROCESSES
    survivor_info, survivor_out = results[0]
    evacuated_info, _ = results[-1]
    assert not survivor_info["evacuated"], (
        "the surviving controller must complete, not evacuate")
    assert evacuated_info["evacuated"], (
        "the lost controller must raise HostEvacuatedError")
    _assert_outputs_equal(survivor_out, reference,
                          "surviving process vs fault-free reference")
    counters = survivor_info["counters"]
    assert counters.get("host_losses", 0) >= 1, counters
    assert counters.get("mesh_degradations", 0) >= 1, counters
    assert counters.get("journal_replays", 0) >= 1, counters
    states = set(survivor_info["health"].values())
    assert "DEGRADED" in states, survivor_info["health"]
    return (f"whole-host loss: survivor completed bit-identically "
            f"(mesh_degradations="
            f"{counters.get('mesh_degradations')}, host_losses="
            f"{counters.get('host_losses')}, journal_replays="
            f"{counters.get('journal_replays')}), lost controller "
            f"evacuated cleanly")


def check_grow_results(results: List[Tuple[dict, dict]],
                       reference: Dict[str, np.ndarray]) -> str:
    """Asserts the grow scenario: every controller scaled UP mid-run
    (mesh_expansions fired, journaled blocks replayed) and finished
    bit-identically to the full-geometry reference."""
    assert len(results) == POD_PROCESSES
    for p, (info, outputs) in enumerate(results):
        _assert_outputs_equal(outputs, reference,
                              f"process {p} grown run vs full-geometry "
                              f"reference")
        counters = info["counters"]
        assert counters.get("mesh_expansions", 0) >= 1, counters
        assert counters.get("journal_replays", 0) >= 1, counters
        assert counters.get("mesh_degradations", 0) == 0, counters
    return (f"elastic scale-UP: {POD_PROCESSES} controllers grew "
            f"{POD_PROCESSES} -> "
            f"{POD_PROCESSES * POD_DEVICES_PER_PROCESS} devices at "
            f"block 2 and finished bit-identically "
            f"({len(reference['blk_ids'])} blocked partitions)")


def check_migration_results(results: List[Tuple[dict, dict]],
                            adopted: int,
                            adopted_odometer: int,
                            resumed: Dict[str, np.ndarray],
                            reference: Dict[str, np.ndarray]) -> str:
    """Asserts drain-and-migrate: every source controller was
    interrupted AFTER journaling its progress, the target adopted a
    complete scope (blocks + odometer trail), and the resumed run —
    different process, different geometry — is bit-identical to an
    uninterrupted one."""
    assert len(results) == POD_PROCESSES
    for p, (info, _) in enumerate(results):
        assert info.get("interrupted"), (
            f"process {p} was never interrupted — the migration source "
            f"finished instead of draining")
    assert adopted >= 1, (
        "the migration target adopted no records — nothing migrated")
    assert adopted_odometer >= 1, (
        "the adopted scope carried no odometer trail — the tenant "
        "ledger's provenance was lost in the move")
    _assert_outputs_equal(resumed, reference,
                          "migrated resume vs uninterrupted reference")
    return (f"drain-and-migrate: adopted {adopted} journal record(s) "
            f"(odometer trail included) from the interrupted pod and "
            f"resumed bit-identically at a different geometry "
            f"({len(reference['blk_ids'])} blocked partitions)")


def run_pod_drill(state_dir: str, out_root: str,
                  generations: int = 2,
                  timeout_s: float = 240.0
                  ) -> List[List[Tuple[dict, dict]]]:
    """Runs `generations` pod generations of the rolling-restart drill
    over one shared ledger state_dir. Each generation is a full
    controller respawn (jax.distributed worlds are fixed at init — a
    bounced controller IS a new process in a new world); generation 1
    takes the scripted mid-persist kill on controller p1."""
    all_results = []
    for gen in range(1, generations + 1):
        out_dir = os.path.join(out_root, f"gen{gen}")
        os.makedirs(out_dir, exist_ok=True)
        all_results.append(spawn_local_pod(
            f"drill:{gen}:{state_dir}", out_dir, timeout_s=timeout_s))
    return all_results


def check_pod_drill_results(all_results: List[List[Tuple[dict, dict]]],
                            state_dir: str,
                            reference: Dict[str, np.ndarray]) -> str:
    """Asserts the pod drill's zero-loss gates across generations:

      * generation 1's controller p1 died inside its last ledger
        persist (the scripted kill), p0 did not;
      * every generation's traffic on every controller is bit-identical
        to the single-process reference (restarts never perturbed
        results);
      * the final per-process disk trails charge every planned job
        EXACTLY once, with per-job eps sums bit-equal across the two
        controllers (same seq layout, same spend — the trail the kill
        interrupted was made whole by the same-id re-charge, without
        double-charging the controller where the original landed);
      * restarted controllers counted their rolling_restarts.
    """
    from pipelinedp_tpu.runtime import journal as rt_journal
    from pipelinedp_tpu.runtime import observability as rt_obs

    generations = len(all_results)
    assert generations >= 2, "the drill needs >= 2 generations"
    gen1 = all_results[0]
    assert gen1[1][0].get("died_during_persist"), (
        "generation 1 controller p1 never took the scripted "
        "mid-persist kill")
    assert not gen1[0][0].get("died_during_persist")
    for gen, results in enumerate(all_results, start=1):
        for p, (info, outputs) in enumerate(results):
            _assert_outputs_equal(
                outputs, reference,
                f"drill generation {gen} process {p} vs reference")
            if gen > 1:
                assert info["counters"].get("rolling_restarts", 0) >= 1, (
                    f"generation {gen} process {p} never counted its "
                    f"rolling restart")
    # The planned universe: every generation's jobs, deduplicated (the
    # re-charged job appears in two generations by design).
    planned = set()
    for gen in range(1, generations + 1):
        planned.update(_drill_planned_jobs(gen))
    journal = rt_journal.BlockJournal(state_dir)
    per_proc = []
    for p in range(POD_PROCESSES):
        trail = rt_obs.load_odometer(journal.scoped_to_process(p),
                                     DRILL_TENANT)
        sums: Dict[str, float] = {}
        counts: Dict[str, int] = {}
        for r in trail:
            jid = r.get("job_id") or ""
            if r.get("eps") is not None:
                sums[jid] = sums.get(jid, 0.0) + \
                    r["eps"] * r.get("count", 1)
            counts[jid] = counts.get(jid, 0) + 1
        seqs = [r.get("seq") for r in trail]
        assert seqs == sorted(set(seqs)), (
            f"process {p} trail seq numbers are not unique/ordered — a "
            f"record was double-charged: {seqs}")
        assert set(sums) == planned, (
            f"process {p} trail charges {sorted(sums)} but the drill "
            f"planned {sorted(planned)} — a lost or phantom job")
        per_proc.append((sums, counts))
    sums0, counts0 = per_proc[0]
    for p, (sums, counts) in enumerate(per_proc[1:], start=1):
        assert sums == sums0, (
            f"per-job spends diverged between controller trails (p0 vs "
            f"p{p}): {sums0} vs {sums} — must be bit-equal")
        assert counts == counts0, (
            f"per-job record counts diverged (p0 vs p{p}): {counts0} "
            f"vs {counts}")
    final_spent = {info["ledger_spent"]
                   for info, _ in all_results[-1]}
    assert len(final_spent) == 1, (
        f"final-generation ledgers disagree on total spend: "
        f"{final_spent}")
    return (f"pod rolling-restart drill: {generations} generations, "
            f"{len(planned)} planned jobs each charged exactly once on "
            f"both controller trails (total spend "
            f"{final_spent.pop():.6f} eps, bit-equal across "
            f"controllers); generation-1 mid-persist kill absorbed")


def check_pod_observability(out_dir: str,
                            results: List[Tuple[dict, dict]],
                            scenario: str) -> str:
    """Asserts the pod's merged observability plane (both scenarios):

      * process 0 wrote the merged rollup (the collective-free teardown
        gather), and the merged Perfetto trace carries span events from
        BOTH controllers on distinct pid tracks with named
        process_name metadata rows;
      * each controller's mid-run metrics scrape parses under the
        strict Prometheus line grammar and exposes counters;
      * every incident appears in the merge EXACTLY ONCE per process
        that recorded it: for each controller, the count of
        ``host_losses`` (and ``injected_faults``) instants on its pid
        track equals that controller's own counter — a merge that
        double-ingested a per-process buffer would double it.
    """
    from pipelinedp_tpu.runtime import observability as rt_obs

    rollup_path = os.path.join(out_dir, rt_obs.POD_ROLLUP_NAME)
    assert os.path.exists(rollup_path), (
        f"process 0 never wrote the pod rollup {rollup_path!r}")
    with open(rollup_path) as f:
        rollup = json.load(f)
    expected_pids = list(range(len(results)))
    assert rollup["processes"] == expected_pids, rollup["processes"]

    events = rollup["trace"]["traceEvents"]
    span_pids = {ev["pid"] for ev in events if ev.get("ph") == "X"}
    assert span_pids == set(expected_pids), (
        f"merged trace must carry spans from every controller on its "
        f"own pid track: got pids {sorted(span_pids)}")
    names = {
        ev["pid"]: ev["args"]["name"]
        for ev in events
        if ev.get("ph") == "M" and ev.get("name") == "process_name"
    }
    for pid in expected_pids:
        assert names.get(pid) == f"pipelinedp-tpu p{pid}", names

    scraped_counters = 0
    for info, _ in results:
        parsed = rt_obs.parse_prometheus(info["scrape"])
        counters = [n for n, entry in parsed.items()
                    if entry["type"] == "counter"]
        assert counters, "mid-run scrape exposed no counters"
        scraped_counters = max(scraped_counters, len(counters))

    # Exactly-once incident accounting across the merge.
    once_checked = []
    for incident in ("host_losses", "injected_faults",
                     "mesh_degradations"):
        for info, _ in results:
            pid = info["process_index"]
            on_track = sum(
                1 for ev in events
                if ev.get("ph") == "i" and ev["name"] == incident and
                ev["pid"] == pid)
            want = int(info["counters"].get(incident, 0))
            assert on_track == want, (
                f"{incident} appears {on_track}x on pid {pid}'s merged "
                f"track but the controller counted {want} — the merge "
                f"double- or under-ingested a per-process buffer")
            if want:
                once_checked.append(f"{incident}@p{pid}={want}")
    assert not rollup.get("truncated"), (
        "pod trace buffers overflowed — the merge under-reports")
    return (f"pod rollup merged {len(expected_pids)} controllers "
            f"(spans on pid tracks {sorted(span_pids)}, "
            f"{scraped_counters} counters in the mid-run scrape"
            + (f", incidents exactly-once: {', '.join(once_checked)}"
               if once_checked else ", no incidents") + ")")


# ---------------------------------------------------------------------------
# Topology report
# ---------------------------------------------------------------------------


def multihost_receipt(mesh=None) -> Dict[str, object]:
    """The multihost_* topology keys (read by tests/test_multihost.py
    only since bench.py went — ROADMAP D10): process topology, per-process
    ingest overlap (each controller parses/encodes only its shard — the
    overlap factor is the process count on an evenly-sharded stream),
    the cross-host share of the collective-reshard exchange volume
    (geometry fraction x the traced exchange bytes), and
    ``multihost_trace_merged`` — this run's trace pushed through the
    export→aggregate→merge path (the machinery the 2-process dryrun
    proves end to end; a single controller truthfully reports one
    track)."""
    import tempfile

    import jax

    from pipelinedp_tpu.parallel import mesh as mesh_lib
    from pipelinedp_tpu.runtime import observability as rt_obs
    from pipelinedp_tpu.runtime import trace as rt_trace

    if mesh is None:
        mesh = mesh_lib.make_mesh()
    frac = mesh_lib.cross_process_fraction(mesh)
    exchanged = 0
    for ev in rt_trace.to_trace_events().get("traceEvents", []):
        if ev.get("name") == "reshard.collective":
            exchanged += int(ev.get("args", {}).get("bytes", 0) or 0)
    with tempfile.TemporaryDirectory() as tmp:
        rt_obs.export_process_state(tmp)
        pod = rt_obs.aggregate_directory(tmp)
    merged_events = pod["trace"]["traceEvents"]
    return {
        "multihost_processes": int(jax.process_count()),
        "multihost_local_devices": len(mesh_lib.local_devices(mesh)),
        "multihost_mesh_devices": int(mesh.devices.size),
        "multihost_per_process_ingest_overlap": int(jax.process_count()),
        "multihost_cross_host_fraction": round(frac, 4),
        "multihost_cross_host_exchange_bytes": int(exchanged * frac),
        "multihost_trace_merged": {
            "processes": pod["processes"],
            "span_tracks": sorted({
                ev["pid"] for ev in merged_events
                if ev.get("ph") == "X"
            }),
            "n_events": len(merged_events),
            "truncated": pod["truncated"],
        },
    }


if __name__ == "__main__":
    if len(sys.argv) != 3:
        raise SystemExit(
            "usage: python -m pipelinedp_tpu.runtime.multihost "
            "<scenario> <out_path>")
    raise SystemExit(_child_main(sys.argv[1], sys.argv[2]))
