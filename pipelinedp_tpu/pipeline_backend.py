"""Pipeline execution backends.

The engine is a backend-generic dataflow builder: every step is a call to one
of the ~20 `PipelineBackend` primitives with a stage-name string (reference:
pipeline_dp/pipeline_backend.py:38-195). Backends provided here:

  * LocalBackend        — lazy Python generators; the ground-truth semantics.
  * TPUBackend          — columnar JAX/XLA execution. It is a *marker + device
                          config* object: DPEngine recognizes it and lowers
                          the whole aggregation to one fused XLA program
                          (executor.py) instead of interpreting the op graph.
                          The generic op vocabulary is still implemented
                          (host-side, numpy) so non-fused utilities
                          (histograms, analysis glue) run anywhere.
  * MultiProcLocalBackend — multiprocessing Pool over materialized stages.
  * BeamBackend / SparkRDDBackend — thin adapters over Apache Beam / PySpark,
                          available when those packages are importable
                          (they are optional, exactly as in the reference).

An Annotator hook mirrors reference :826-852.
"""

import abc
import collections
import functools
import itertools
import operator
import random
from typing import Any, Callable, Iterable, List, Optional

import numpy as np

from pipelinedp_tpu import combiners as dp_combiners
from pipelinedp_tpu import input_validators
from pipelinedp_tpu import sampling_utils

try:
    import apache_beam as beam
except ImportError:
    beam = None

try:
    import pyspark
except ImportError:
    pyspark = None


class PipelineBackend(abc.ABC):
    """Interface implemented by all execution backends."""

    def to_collection(self, collection_or_iterable, col, stage_name: str):
        """Converts an iterable to the backend-native collection."""
        del col, stage_name
        return collection_or_iterable

    def to_multi_transformable_collection(self, col):
        """Returns a collection that can be iterated multiple times."""
        return col

    @abc.abstractmethod
    def map(self, col, fn, stage_name: str):
        pass

    @abc.abstractmethod
    def map_with_side_inputs(self, col, fn, side_input_cols, stage_name: str):
        """fn(row, *side_inputs) where each side input collection is
        materialized and passed as one object."""

    @abc.abstractmethod
    def flat_map(self, col, fn, stage_name: str):
        pass

    def flat_map_with_side_inputs(self, col, fn, side_input_cols,
                                  stage_name: str):
        raise NotImplementedError(
            f"flat_map_with_side_inputs is not supported in "
            f"{type(self).__name__}")

    @abc.abstractmethod
    def map_tuple(self, col, fn, stage_name: str):
        pass

    @abc.abstractmethod
    def map_values(self, col, fn, stage_name: str):
        pass

    @abc.abstractmethod
    def group_by_key(self, col, stage_name: str):
        """(key, value) -> (key, iterable-of-values)."""

    @abc.abstractmethod
    def filter(self, col, fn, stage_name: str):
        pass

    @abc.abstractmethod
    def filter_by_key(self, col, keys_to_keep, stage_name: str):
        """Keeps only (key, data) whose key is in keys_to_keep (local list/set
        or distributed collection)."""

    @abc.abstractmethod
    def keys(self, col, stage_name: str):
        pass

    @abc.abstractmethod
    def values(self, col, stage_name: str):
        pass

    @abc.abstractmethod
    def sample_fixed_per_key(self, col, n: int, stage_name: str):
        """(key, value) -> (key, [<=n uniformly sampled values])."""

    @abc.abstractmethod
    def count_per_element(self, col, stage_name: str):
        """element -> (element, count)."""

    @abc.abstractmethod
    def sum_per_key(self, col, stage_name: str):
        pass

    @abc.abstractmethod
    def combine_accumulators_per_key(self, col,
                                     combiner: 'dp_combiners.Combiner',
                                     stage_name: str):
        """Merges all accumulators per key with combiner.merge_accumulators."""

    @abc.abstractmethod
    def reduce_per_key(self, col, fn: Callable, stage_name: str):
        """Reduces values per key with an associative commutative fn."""

    @abc.abstractmethod
    def flatten(self, cols: Iterable, stage_name: str):
        """Union of several collections."""

    @abc.abstractmethod
    def distinct(self, col, stage_name: str):
        pass

    @abc.abstractmethod
    def to_list(self, col, stage_name: str):
        """1-element collection holding the list of all elements."""

    def annotate(self, col, stage_name: str, **kwargs):
        """Applies all registered annotators (no-op by default)."""
        return col


class UniqueLabelsGenerator:
    """Generates unique stage labels (needed by Beam transform naming)."""

    def __init__(self, suffix):
        self._labels = set()
        self._suffix = ("_" + suffix) if suffix else ""

    def _add_if_unique(self, label):
        if label in self._labels:
            return False
        self._labels.add(label)
        return True

    def unique(self, label):
        if not label:
            label = "UNDEFINED_STAGE_NAME"
        suffix_label = label + self._suffix
        if self._add_if_unique(suffix_label):
            return suffix_label
        for i in itertools.count(1):
            label_candidate = f"{label}_{i}{self._suffix}"
            if self._add_if_unique(label_candidate):
                return label_candidate


class LocalBackend(PipelineBackend):
    """Lazy single-machine backend over Python generators.

    Ground-truth semantics for every other backend (reference :477-583).
    """

    def __init__(self, seed: Optional[int] = None):
        self._rng = random.Random(seed)

    def to_multi_transformable_collection(self, col):
        return list(col)

    def map(self, col, fn, stage_name: str = None):
        return (fn(x) for x in col)

    def map_with_side_inputs(self, col, fn, side_input_cols, stage_name=None):
        side_inputs = [list(s) for s in side_input_cols]

        def gen():
            for x in col:
                yield fn(x, *side_inputs)

        return gen()

    def flat_map(self, col, fn, stage_name: str = None):
        return (x for el in col for x in fn(el))

    def flat_map_with_side_inputs(self, col, fn, side_input_cols,
                                  stage_name=None):
        side_inputs = [list(s) for s in side_input_cols]

        def gen():
            for el in col:
                yield from fn(el, *side_inputs)

        return gen()

    def map_tuple(self, col, fn, stage_name: str = None):
        return (fn(*x) for x in col)

    def map_values(self, col, fn, stage_name: str = None):
        return ((k, fn(v)) for k, v in col)

    def group_by_key(self, col, stage_name: str = None):

        def gen():
            d = collections.defaultdict(list)
            for key, value in col:
                d[key].append(value)
            yield from d.items()

        return gen()

    def filter(self, col, fn, stage_name: str = None):
        return (x for x in col if fn(x))

    def filter_by_key(self, col, keys_to_keep, stage_name: str = None):

        def gen():
            keys = keys_to_keep if isinstance(keys_to_keep,
                                              (set, frozenset, dict)) else set(
                                                  keys_to_keep)
            for key, value in col:
                if key in keys:
                    yield key, value

        return gen()

    def keys(self, col, stage_name: str = None):
        return (k for k, _ in col)

    def values(self, col, stage_name: str = None):
        return (v for _, v in col)

    def sample_fixed_per_key(self, col, n: int, stage_name: str = None):

        def gen():
            for key, values in self.group_by_key(col):
                if len(values) > n:
                    values = self._rng.sample(values, n)
                yield key, values

        return gen()

    def count_per_element(self, col, stage_name: str = None):

        def gen():
            yield from collections.Counter(col).items()

        return gen()

    def sum_per_key(self, col, stage_name: str = None):
        return self.reduce_per_key(col, operator.add, stage_name)

    def combine_accumulators_per_key(self, col,
                                     combiner: 'dp_combiners.Combiner',
                                     stage_name: str = None):
        return self.reduce_per_key(col, combiner.merge_accumulators, stage_name)

    def reduce_per_key(self, col, fn: Callable, stage_name: str = None):

        def gen():
            d = {}
            for key, value in col:
                d[key] = fn(d[key], value) if key in d else value
            yield from d.items()

        return gen()

    def flatten(self, cols, stage_name: str = None):
        return itertools.chain(*cols)

    def distinct(self, col, stage_name: str = None):

        def gen():
            yield from set(col)

        return gen()

    def to_list(self, col, stage_name: str = None):
        return iter([list(col)])

    def annotate(self, col, stage_name: str, **kwargs):
        for annotator in _annotators:
            col = annotator.annotate(col, self, stage_name, **kwargs)
        return col


class TPUBackend(LocalBackend):
    """Columnar JAX/XLA backend.

    DPEngine detects this backend and lowers aggregate() to the fused
    columnar executor (executor.py / parallel/sharded.py): one jit-compiled
    program doing contribution bounding + per-partition combine + partition
    selection + noise on device. Standalone select_partitions() lowers to
    its own single-program device kernel
    (executor.select_partitions_release_kernel): pair dedupe + L0
    sampling via one payload-carrying sort, privacy-id counts via
    segment ops, vectorized
    selection — O(rows) memory, no dense per-partition columns.

    The generic op vocabulary is inherited from LocalBackend so that
    non-fused framework utilities (dataset histograms, analysis glue,
    explain-report plumbing) keep working with this backend too.

    Args:
        mesh: optional jax.sharding.Mesh (1-D, axis "shards", see
            parallel/mesh.make_mesh). When set, rows are sharded by privacy
            id across the mesh and partials combined with lax.psum
            (parallel/sharded.py). When None, single-device jit.
        reshard: how meshed paths co-locate each privacy id's rows on one
            shard (parallel/reshard.stage_rows_to_mesh). "auto" (default):
            device-resident columns (streamed ingest) reshard on device —
            pid-hash bucketize + one padded jax.lax.all_to_all over ICI,
            rows never touching the host — while host-numpy inputs take
            the exact load-balanced host permutation they'd pay an upload
            for anyway. "host"/"device" force one path (escape hatches:
            exact row balance, or a platform without all_to_all).
        max_partitions: optional static result width. When set, the kernel
            compiles for this many partitions regardless of how many appear
            in the data — reuse it across datasets to avoid recompiles.
        noise_seed: base seed for the on-device counter-based RNG. None ->
            fresh nondeterministic seed per aggregation.
        secure_noise: release values snapped to a discrete grid with
            table-sampled discrete Laplace/Gaussian noise
            (ops/secure_noise.py) instead of continuous f32 draws — the
            device counterpart of the reference's PyDP snapped secure
            mechanisms (dp_computations.py:131-152). Costs one O(log K)
            table search per released value.
        large_partition_threshold: partition counts above this route
            aggregation AND standalone partition selection through the
            blocked partition-axis path (parallel/large_p.py), which
            never materializes dense [0, P) state and transfers only
            kept partitions — the reference's unbounded-key regime. With
            a mesh the blocked path runs sharded (pid-sharded pass 1,
            one [C]-sized psum per partition block over ICI). None
            disables the routing.
        retry: optional pipelinedp_tpu.runtime.RetryPolicy for transient
            block-dispatch failures (None = the runtime default: 3
            retries, bounded exponential backoff). A retried block
            re-derives the same fold_in key and redraws bit-identical
            noise — no second DP release, no budget re-spend. OOM on a
            block kernel instead halves the partition block capacity and
            re-plans; see README "Failure semantics".
        journal: optional pipelinedp_tpu.runtime.BlockJournal. When set,
            the blocked drivers record each consumed block's drained
            O(kept) results keyed by (job_id, block); an interrupted run
            re-invoked with the same journal + job_id resumes from the
            last consumed block instead of restarting. Pair with
            noise_seed for a deterministic resume (a journal without a
            seed warns: only journaled blocks keep their original noise).
        job_id: journal key namespace for this pipeline's aggregations.
            None derives a digest of the static kernel config + seed —
            pass explicit distinct ids when one pipeline runs several
            identically-configured aggregations.
        block_partitions: partition block capacity C of the blocked path
            (None = the drivers' default, 2^20). The failure-domain knob:
            smaller blocks mean finer-grained retry/journal/OOM-degrade
            units at more dispatch overhead.
        timeout_s: per-operation deadline (seconds) for the blocked
            drivers' watchdog: every block dispatch, drain sync and the
            device-reshard collective must finish inside it or the
            watchdog cancels at the next cooperative point. A timed-out
            block retries under the SAME fold_in key (bit-identical
            noise); repeated timeouts degrade the block capacity like
            OOM; a timed-out reshard collective falls back to the host
            permutation. None (default) enforces no deadline unless
            `watchdog` is given.
        watchdog: optional pipelinedp_tpu.runtime.Watchdog instance to
            share/configure directly (auto-derived deadlines from the
            pass-1 profile, custom multiplier). timeout_s is shorthand
            for watchdog=Watchdog(timeout_s=...).
        elastic: device-loss tolerance for the meshed paths. When True,
            a device-fatal runtime failure (a chip dropping off the
            slice) no longer kills the run: the runtime probes the mesh
            for surviving devices, rebuilds a smaller mesh, re-derives
            shardings and the reshard permutation for the new geometry
            and re-enters the driver — journaled blocks replay, the
            rest re-derive the same fold_in(final_key, b) keys, so the
            degraded run is bit-compatible with the un-faulted one
            (zero duplicate ledger registrations). At the one-device
            floor the unsharded driver runs instead. Meaningless
            without a mesh.
        elastic_grow: full fleet elasticity for the meshed paths. When
            True, the meshed drivers run under
            runtime/retry.run_with_mesh_elasticity: everything elastic
            does (shrink tolerance is included — elastic_grow implies
            elastic), PLUS scale-UP — join candidates announced via
            runtime/retry.announce_join (new hosts/devices probed
            healthy) are admitted at the next block boundary and the
            mesh rebuilds over the larger device set. Block keys are
            geometry-independent, so the grown run's releases are
            bit-identical to the fixed-geometry run's. Meaningless
            without a mesh.
        min_devices: elastic degradation floor (default 1). Losses that
            leave fewer live devices raise
            runtime.MeshDegradationError naming the job_id and journal
            path a resume needs, and health() reports FAILED.
        pipeline_depth: staging window of the streaming executor
            (runtime/pipeline.py): at most this many encoded chunks in
            flight between the host encode pool and the device
            accumulator when aggregating a ChunkSource. None (default)
            takes the shared PIPELINE_DEPTH (8) — the same depth that
            bounds the blocked drivers' in-flight block kernels.
            Backpressure: a full window stops the producer from pulling
            new chunks, so host memory holds O(depth) chunks.
        encode_threads: host thread pool size for chunk
            parse/factorization on the streamed (ChunkSource) entry.
            None (default) auto-sizes (min(4, cpu_count)); 0 forces the
            serial chunk encode; >= 1 pipelines: chunk k+1 factorizes on
            the pool while chunk k's columns land in the device-resident
            accumulator. Pipelined and serial execution are
            bit-identical — the accumulator reproduces executor.pad_rows
            exactly, so the same compiled kernel sees the same arrays
            and releases the same noise.
        encode_mode: how streamed (ChunkSource) input is vocabulary-
            encoded. "host" (default): the exact chunked host encoder —
            per-chunk factorize on the encode pool, sequential
            vocabulary stitch on the consumer. "hash_device": chunk
            workers only HASH raw keys (vectorized, order-independent),
            raw hash columns stream host->device once, dense
            first-occurrence codes are assigned inside jit
            (device_encode.py), and partition keys are decoded only at
            the DP-selected indices. Bit-identical outputs to "host"
            under the same noise keys; a detected 64-bit hash collision
            (counted in ingest_hash_collisions) falls back to the exact
            host encoder when the chunk source is re-iterable. A
            ChunkSource(encode_mode=...) overrides this per source.
        coordinator_address: jax.distributed coordinator endpoint
            ("host:port"). With num_processes, brings up the
            multi-controller runtime at backend construction
            (parallel/mesh.initialize_distributed — idempotent, selects
            the gloo CPU collectives the 2-process dryrun uses) so
            jax.devices() spans the pod before any mesh is built. The
            process id comes from JAX_PROCESS_INDEX or cluster
            auto-detection. Both knobs None (the default) skips
            distributed bring-up entirely.
        num_processes: total controller count of the jax.distributed
            job; must be identical on every process. See
            coordinator_address.
        aot: ahead-of-time executable routing (runtime/aot.py). When
            True, the warm-path jit entry points (the fused kernels,
            the sharded kernels, the blocked block bodies) execute
            cached ``.lower().compile()`` executables keyed by (spec
            fingerprint, row bucket, mesh geometry, dtype/sharding
            set) instead of re-entering jax.jit's Python dispatch —
            the first call per key compiles (aot_cache_misses), every
            later call across every job and tenant of the process hits
            (aot_cache_hits), with zero Python retraces. Results are
            bit-identical; any entry that cannot lower falls back to
            the traced jit path with one warning. Off by default.
        overlap_drain: compute/drain overlap on the blocked drivers
            (opt-in, default False): block b's drain sync, journal
            fsync and staged transfers run on a dedicated drainer
            thread while block b+1 dispatches. Blocks are consumed
            strictly FIFO under the same watchdog/health/fault scopes,
            so journal records, replay keys and results are
            bit-identical to the serial consume loop. Opt-in because
            drain deadlines then measure wall time that includes
            dispatch-side compile contention — on a shared-core host a
            tight timeout_s can expire on drains that are merely
            queued behind a compile; pair with a generous deadline.
        trace: span-based pipeline tracing (runtime/trace.py). When
            True, every run records nested, job-scoped spans (stage
            phases, per-block dispatch/drain, reshard collectives with
            byte counts, jit compile attribution) and instant events for
            every runtime incident the counters record. Export with
            dump_trace(path) (Chrome/Perfetto trace-event JSON) or read
            trace_summary(). Off (the default) costs one bool check per
            call site — the blocked-driver hot path is unaffected.
        metrics_port: live Prometheus scrape endpoint
            (runtime/observability.py). When set, a background thread
            serves every declared counter and gauge (queue depth, live
            devices, health states, budget remaining, memory
            watermarks) per job_id at
            http://127.0.0.1:<port>/metrics WHILE runs are in flight —
            0 binds an ephemeral port, read back via
            backend.metrics_endpoint(). None (default) serves nothing.
        metrics_path: the portless scrape mode for CI sandboxes that
            cannot open sockets: the same Prometheus text re-written
            atomically (write-then-rename, never torn) to this file
            every ~250ms. Combinable with metrics_port; None (default)
            writes nothing.
        numeric_mode: accumulation arithmetic discipline for the fused
            release kernels (pipelinedp_tpu/numeric.py). "fast" (the
            default) keeps the historical f32 segment reduction —
            bit-identical programs, the release sentinel only refuses
            NaN/Inf. "safe" switches segment sums to a compensated
            (TwoSum hi/lo) associative scan — exact for integer-valued
            workloads to ~2**48 — and arms the sentinel's overflow
            classification: saturation raises a typed
            NumericOverflowError, the release fails closed (nothing
            decoded, nothing journaled, budget settled conservatively).
        snap_grid_bits: floor exponent for the power-of-two snapping
            grid used by the discrete/snapped mechanisms and the
            secure-noise tables: releases land on multiples of
            max(mechanism grid, 2**snap_grid_bits). None (default)
            leaves the mechanism-chosen grid alone; coarser grids cost
            sensitivity (the snap widens Δ by one grid unit).
    """

    def __init__(self,
                 mesh=None,
                 max_partitions: Optional[int] = None,
                 noise_seed: Optional[int] = None,
                 secure_noise: bool = False,
                 large_partition_threshold: Optional[int] = 1 << 21,
                 reshard: str = "auto",
                 retry=None,
                 journal=None,
                 job_id: Optional[str] = None,
                 block_partitions: Optional[int] = None,
                 timeout_s: Optional[float] = None,
                 watchdog=None,
                 elastic: bool = False,
                 elastic_grow: bool = False,
                 min_devices: int = 1,
                 trace: bool = False,
                 aot: bool = False,
                 overlap_drain: bool = False,
                 pipeline_depth: Optional[int] = None,
                 encode_threads: Optional[int] = None,
                 encode_mode: str = "host",
                 coordinator_address: Optional[str] = None,
                 num_processes: Optional[int] = None,
                 metrics_port: Optional[int] = None,
                 metrics_path: Optional[str] = None,
                 numeric_mode: str = "fast",
                 snap_grid_bits: Optional[int] = None):
        super().__init__(seed=noise_seed)
        if reshard not in ("auto", "host", "device"):
            raise ValueError(
                f"reshard must be auto|host|device, got {reshard!r}")
        # Runtime knobs are validated here, at the API boundary, so a bad
        # timeout/job_id/retry budget fails with an actionable message
        # instead of deep inside the journal or the watchdog monitor.
        if timeout_s is not None:
            input_validators.validate_timeout_s(timeout_s, "TPUBackend")
        if job_id is not None:
            input_validators.validate_job_id(job_id, "TPUBackend")
        if retry is not None:
            input_validators.validate_retry_policy(retry, "TPUBackend")
        if journal is not None:
            input_validators.validate_journal(journal, "TPUBackend")
        if watchdog is not None:
            input_validators.validate_watchdog(watchdog, "TPUBackend")
        input_validators.validate_elastic(elastic, "TPUBackend")
        input_validators.validate_elastic_grow(elastic_grow, "TPUBackend")
        input_validators.validate_min_devices(min_devices, "TPUBackend")
        input_validators.validate_trace(trace, "TPUBackend")
        input_validators.validate_aot(aot, "TPUBackend")
        input_validators.validate_overlap_drain(overlap_drain, "TPUBackend")
        if pipeline_depth is not None:
            input_validators.validate_pipeline_depth(
                pipeline_depth, "TPUBackend")
        if encode_threads is not None:
            input_validators.validate_encode_threads(
                encode_threads, "TPUBackend")
        input_validators.validate_encode_mode(encode_mode, "TPUBackend")
        if num_processes is not None:
            input_validators.validate_num_processes(
                num_processes, "TPUBackend")
        if coordinator_address is not None:
            input_validators.validate_coordinator_address(
                coordinator_address, "TPUBackend")
        if metrics_port is not None:
            input_validators.validate_metrics_port(
                metrics_port, "TPUBackend")
        if metrics_path is not None:
            input_validators.validate_metrics_path(
                metrics_path, "TPUBackend")
        input_validators.validate_numeric_mode(numeric_mode, "TPUBackend")
        if snap_grid_bits is not None:
            input_validators.validate_snap_grid_bits(
                snap_grid_bits, "TPUBackend")
        if (coordinator_address is None) != (num_processes is None):
            raise ValueError(
                "TPUBackend: coordinator_address and num_processes must "
                "be set together — they are the two halves of the "
                "jax.distributed bring-up (process_id comes from "
                "JAX_PROCESS_INDEX or cluster auto-detection).")
        if coordinator_address is not None and num_processes > 1:
            # Multi-controller bring-up BEFORE any mesh is touched:
            # jax.devices() must already span the pod when the caller
            # builds (or defaults) the mesh. Idempotent across backends.
            from pipelinedp_tpu.parallel import mesh as mesh_lib
            mesh_lib.initialize_distributed(coordinator_address,
                                            num_processes)
        self.mesh = mesh
        self.max_partitions = max_partitions
        self.noise_seed = noise_seed
        self.secure_noise = secure_noise
        self.large_partition_threshold = large_partition_threshold
        self.reshard = reshard
        self.retry = retry
        self.journal = journal
        self.job_id = job_id
        self.block_partitions = block_partitions
        self.timeout_s = timeout_s
        self.watchdog = watchdog
        self.elastic = elastic
        self.elastic_grow = elastic_grow
        self.min_devices = min_devices
        self.trace = trace
        self.aot = aot
        self.overlap_drain = overlap_drain
        self.pipeline_depth = pipeline_depth
        self.encode_threads = encode_threads
        self.encode_mode = encode_mode
        self.coordinator_address = coordinator_address
        self.num_processes = num_processes
        self.metrics_port = metrics_port
        self.metrics_path = metrics_path
        self.numeric_mode = numeric_mode
        self.snap_grid_bits = snap_grid_bits
        # The compile count that works with tracing off
        # (backend_compiles): one process-wide listener, idempotent.
        from pipelinedp_tpu.runtime import telemetry as rt_telemetry
        rt_telemetry.install_compile_listener()
        if trace:
            from pipelinedp_tpu.runtime import trace as rt_trace
            rt_trace.enable()
        # Live metrics exporters (HTTP endpoint and/or atomic file):
        # started here so counters and gauges are scrapeable from the
        # first aggregation, stopped via stop_metrics().
        self._metrics_exporters = []
        if metrics_port is not None or metrics_path is not None:
            from pipelinedp_tpu.runtime import observability as rt_obs
            if metrics_port is not None:
                self._metrics_exporters.append(
                    rt_obs.start_exporter(port=metrics_port))
            if metrics_path is not None:
                self._metrics_exporters.append(
                    rt_obs.start_exporter(path=metrics_path))
        # Job ids whose health this backend's aggregations fed (the
        # executor records them as it resolves/derives them).
        self._health_jobs = set()

    @property
    def is_tpu(self) -> bool:
        return True

    def for_job(self,
                job_id: Optional[str] = None,
                noise_seed: Optional[int] = None,
                journal=None) -> 'TPUBackend':
        """A job-scoped view of this backend for concurrent multiplexing.

        The multi-tenant service (pipelinedp_tpu/service/) holds ONE
        backend/mesh for its lifetime but runs many jobs on it at once;
        each job needs its own noise seed and job id without mutating
        the shared backend under a concurrent sibling. The derived
        backend shares the mesh and every data-plane/runtime knob —
        jit-compiled entry points are cached per function + shapes +
        static config, so identical specs submitted through different
        for_job views hit the SAME compiled programs (the compile-cache
        reuse the service asserts) — while job_id/noise_seed/journal
        override per job. Metrics exporters and distributed bring-up
        stay owned by the parent: a view never starts or stops either.
        """
        return TPUBackend(
            mesh=self.mesh,
            max_partitions=self.max_partitions,
            noise_seed=(self.noise_seed if noise_seed is None
                        else noise_seed),
            secure_noise=self.secure_noise,
            large_partition_threshold=self.large_partition_threshold,
            reshard=self.reshard,
            retry=self.retry,
            journal=(self.journal if journal is None else journal),
            job_id=(self.job_id if job_id is None else job_id),
            block_partitions=self.block_partitions,
            timeout_s=self.timeout_s,
            watchdog=self.watchdog,
            elastic=self.elastic,
            elastic_grow=self.elastic_grow,
            min_devices=self.min_devices,
            aot=self.aot,
            overlap_drain=self.overlap_drain,
            pipeline_depth=self.pipeline_depth,
            encode_threads=self.encode_threads,
            encode_mode=self.encode_mode,
            numeric_mode=self.numeric_mode,
            snap_grid_bits=self.snap_grid_bits)

    def dump_trace(self, path: str, job_id: Optional[str] = None) -> str:
        """Writes the recorded trace as Chrome/Perfetto trace-event JSON
        (load in ui.perfetto.dev or chrome://tracing). With a job_id,
        only that job's events. Returns the path. Requires
        TPUBackend(trace=True) (or runtime.trace.enable()) to have been
        on while the runs of interest executed."""
        from pipelinedp_tpu.runtime import trace as rt_trace
        return rt_trace.dump(path, job_id=job_id)

    def trace_summary(self, job_id: Optional[str] = None) -> dict:
        """In-memory trace rollup: top spans by inclusive/exclusive wall
        time, instant-event counts, transferred bytes and per-entry-point
        jit compile stats — see runtime/trace.trace_summary."""
        from pipelinedp_tpu.runtime import trace as rt_trace
        return rt_trace.trace_summary(job_id=job_id)

    def health(self) -> dict:
        """Health snapshots of the jobs this backend has run (or, before
        any blocked run attributed a job to this backend, every job the
        process tracked): {job_id: {state, counters, phase_seconds,
        journal_quarantined, ...}} — see runtime/health.py for the
        HEALTHY/DEGRADED/STALLED/FAILED semantics."""
        from pipelinedp_tpu.runtime import health as rt_health
        snaps = rt_health.snapshot_all()
        jobs = set(self._health_jobs)
        if self.job_id is not None:
            jobs.add(self.job_id)
        if jobs:
            return {j: s for j, s in snaps.items() if j in jobs}
        return snaps

    def odometer(self, job_id: Optional[str] = None,
                 accountant=None) -> dict:
        """The privacy-budget odometer: spent-vs-remaining over the
        ordered per-mechanism audit trail (one record per
        BudgetAccountant registration — job, metric, mechanism kind,
        eps/delta share, process provenance). Filter by job_id and/or
        a specific accountant; with an accountant the report includes
        total/remaining epsilon and `reconciled` (record count ==
        mechanism_count AND eps shares sum exactly to the ledger's
        spent epsilon). See runtime/observability.odometer_report."""
        from pipelinedp_tpu.runtime import observability as rt_obs
        return rt_obs.odometer_report(accountant=accountant,
                                      job_id=job_id)

    def scrape_metrics(self) -> str:
        """The current Prometheus exposition text (counters + gauges,
        gauge sources refreshed) — the same bytes the metrics_port
        endpoint and metrics_path file serve. Works without either
        knob."""
        from pipelinedp_tpu.runtime import observability as rt_obs
        return rt_obs.render_prometheus()

    def metrics_endpoint(self) -> Optional[str]:
        """The live scrape address: the HTTP URL when metrics_port is
        configured (resolved ephemeral port included), else the
        metrics_path file, else None."""
        for exporter in self._metrics_exporters:
            if exporter.port is not None:
                return exporter.endpoint
        for exporter in self._metrics_exporters:
            return exporter.endpoint
        return None

    def stop_metrics(self) -> None:
        """Stops this backend's metrics exporters (the HTTP server
        thread and/or the file re-writer)."""
        for exporter in self._metrics_exporters:
            exporter.stop()
        self._metrics_exporters = []


# Lambdas cannot be pickled for Pool.map; with the fork start method the
# function is instead inherited by workers through a module-global set by the
# pool initializer (the reference uses the same workaround,
# pipeline_backend.py:586-598).
_pool_current_func = None


def _pool_worker_init(func):
    global _pool_current_func
    _pool_current_func = func


def _pool_worker(row):
    return _pool_current_func(row)


def _pool_worker_flat(row):
    # flat_map fns may return generators, which can't be pickled back to the
    # driver — materialize in the worker.
    return list(_pool_current_func(row))


class MultiProcLocalBackend(PipelineBackend):
    """Multiprocessing backend: elementwise stages fan out over a Pool.

    Stages materialize their input (no laziness); keyed ops run on the driver.
    Experimental — mirrors the reference's experimental status
    (pipeline_backend.py:586-823).
    """

    def __init__(self, n_jobs: Optional[int] = None):
        import multiprocessing as mp
        self._mp = mp
        self._n_jobs = n_jobs or mp.cpu_count()
        self._local = LocalBackend()

    def to_multi_transformable_collection(self, col):
        # Generators from this backend's lazy stages are single-iteration;
        # the contract requires re-iterability.
        return list(col)

    def _pool_map(self, fn, data):
        with self._mp.Pool(self._n_jobs,
                           initializer=_pool_worker_init,
                           initargs=(fn,)) as pool:
            return pool.map(_pool_worker, data)

    def map(self, col, fn, stage_name: str = None):
        # Lazy: the pool fan-out happens on first iteration, preserving the
        # two-phase budget protocol (results materialized only after
        # compute_budgets()).
        def gen():
            yield from self._pool_map(fn, list(col))

        return gen()

    def map_with_side_inputs(self, col, fn, side_input_cols, stage_name=None):
        return self._local.map_with_side_inputs(col, fn, side_input_cols)

    def flat_map(self, col, fn, stage_name: str = None):

        def gen():
            with self._mp.Pool(self._n_jobs,
                               initializer=_pool_worker_init,
                               initargs=(fn,)) as pool:
                batches = pool.map(_pool_worker_flat, list(col))
            for batch in batches:
                yield from batch

        return gen()

    def map_tuple(self, col, fn, stage_name: str = None):
        return (fn(*x) for x in col)

    def map_values(self, col, fn, stage_name: str = None):
        return self._local.map_values(col, fn)

    def group_by_key(self, col, stage_name: str = None):
        return self._local.group_by_key(col)

    def filter(self, col, fn, stage_name: str = None):
        return self._local.filter(col, fn)

    def filter_by_key(self, col, keys_to_keep, stage_name: str = None):
        return self._local.filter_by_key(col, keys_to_keep)

    def keys(self, col, stage_name: str = None):
        return self._local.keys(col)

    def values(self, col, stage_name: str = None):
        return self._local.values(col)

    def sample_fixed_per_key(self, col, n: int, stage_name: str = None):
        return self._local.sample_fixed_per_key(col, n)

    def count_per_element(self, col, stage_name: str = None):
        return self._local.count_per_element(col)

    def sum_per_key(self, col, stage_name: str = None):
        return self._local.sum_per_key(col)

    def combine_accumulators_per_key(self, col, combiner, stage_name=None):
        return self._local.combine_accumulators_per_key(col, combiner)

    def reduce_per_key(self, col, fn, stage_name: str = None):
        return self._local.reduce_per_key(col, fn)

    def flatten(self, cols, stage_name: str = None):
        return itertools.chain(*cols)

    def distinct(self, col, stage_name: str = None):
        return self._local.distinct(col)

    def to_list(self, col, stage_name: str = None):
        return iter([list(col)])

    def annotate(self, col, stage_name: str, **kwargs):
        return self._local.annotate(col, stage_name, **kwargs)


if beam is not None:

    class BeamBackend(PipelineBackend):
        """Apache Beam adapter (optional dependency, reference :223-374)."""

        def __init__(self, suffix: str = ""):
            self._ulg = UniqueLabelsGenerator(suffix)

        @property
        def unique_lable_generator(self):  # reference-compatible name
            return self._ulg

        def to_collection(self, collection_or_iterable, col, stage_name):
            if isinstance(collection_or_iterable, beam.PCollection):
                return collection_or_iterable
            return col.pipeline | self._ulg.unique(stage_name) >> beam.Create(
                collection_or_iterable)

        def map(self, col, fn, stage_name):
            return col | self._ulg.unique(stage_name) >> beam.Map(fn)

        def map_with_side_inputs(self, col, fn, side_input_cols, stage_name):
            side_inputs = [
                beam.pvalue.AsList(side) for side in side_input_cols
            ]
            return col | self._ulg.unique(stage_name) >> beam.Map(
                fn, *side_inputs)

        def flat_map(self, col, fn, stage_name):
            return col | self._ulg.unique(stage_name) >> beam.FlatMap(fn)

        def flat_map_with_side_inputs(self, col, fn, side_input_cols,
                                      stage_name):
            side_inputs = [
                beam.pvalue.AsList(side) for side in side_input_cols
            ]
            return col | self._ulg.unique(stage_name) >> beam.FlatMap(
                fn, *side_inputs)

        def map_tuple(self, col, fn, stage_name):
            return col | self._ulg.unique(stage_name) >> beam.Map(
                lambda x: fn(*x))

        def map_values(self, col, fn, stage_name):
            return col | self._ulg.unique(stage_name) >> beam.MapTuple(
                lambda k, v: (k, fn(v)))

        def group_by_key(self, col, stage_name):
            return col | self._ulg.unique(stage_name) >> beam.GroupByKey()

        def filter(self, col, fn, stage_name):
            return col | self._ulg.unique(stage_name) >> beam.Filter(fn)

        def filter_by_key(self, col, keys_to_keep, stage_name):

            class PartitionsFilterJoin(beam.DoFn):

                def process(self, joined_data):
                    key, rest = joined_data
                    values, to_keep = rest.get(VALUES), rest.get(TO_KEEP)
                    if not values:
                        return
                    if to_keep:
                        for value in values:
                            yield key, value

            VALUES, TO_KEEP = 0, 1
            if isinstance(keys_to_keep, (list, set)):
                keys_to_keep_pcol = col.pipeline | self._ulg.unique(
                    "keys_to_keep") >> beam.Create(keys_to_keep)
            else:
                keys_to_keep_pcol = keys_to_keep
            keys_to_keep_kv = keys_to_keep_pcol | self._ulg.unique(
                "key_by") >> beam.Map(lambda k: (k, True))
            return ({
                VALUES: col,
                TO_KEEP: keys_to_keep_kv
            } | self._ulg.unique(stage_name) >> beam.CoGroupByKey() |
                    self._ulg.unique("Filter join") >> beam.ParDo(
                        PartitionsFilterJoin()))

        def keys(self, col, stage_name):
            return col | self._ulg.unique(stage_name) >> beam.Keys()

        def values(self, col, stage_name):
            return col | self._ulg.unique(stage_name) >> beam.Values()

        def sample_fixed_per_key(self, col, n, stage_name):
            return col | self._ulg.unique(
                stage_name) >> beam.combiners.Sample.FixedSizePerKey(n)

        def count_per_element(self, col, stage_name):
            return col | self._ulg.unique(
                stage_name) >> beam.combiners.Count.PerElement()

        def sum_per_key(self, col, stage_name):
            return col | self._ulg.unique(stage_name) >> beam.CombinePerKey(sum)

        def combine_accumulators_per_key(self, col, combiner, stage_name):

            def merge_accumulators(accumulators):
                return functools.reduce(combiner.merge_accumulators,
                                        accumulators)

            return col | self._ulg.unique(stage_name) >> beam.CombinePerKey(
                merge_accumulators)

        def reduce_per_key(self, col, fn, stage_name):
            return col | self._ulg.unique(stage_name) >> beam.CombinePerKey(
                lambda values: functools.reduce(fn, values))

        def flatten(self, cols, stage_name):
            return tuple(cols) | self._ulg.unique(stage_name) >> beam.Flatten()

        def distinct(self, col, stage_name):
            return col | self._ulg.unique(stage_name) >> beam.Distinct()

        def to_list(self, col, stage_name):
            return col | self._ulg.unique(stage_name) >> beam.combiners.ToList()

        def annotate(self, col, stage_name, **kwargs):
            for annotator in _annotators:
                col = annotator.annotate(col, self,
                                         self._ulg.unique(stage_name), **kwargs)
            return col


if pyspark is not None:

    class SparkRDDBackend(PipelineBackend):
        """PySpark RDD adapter (optional dependency, reference :377-474)."""

        def __init__(self, sc: 'pyspark.SparkContext'):
            self._sc = sc

        def to_collection(self, collection_or_iterable, col, stage_name):
            if isinstance(collection_or_iterable, pyspark.RDD):
                return collection_or_iterable
            return self._sc.parallelize(collection_or_iterable)

        def map(self, col, fn, stage_name=None):
            return col.map(fn)

        def map_with_side_inputs(self, col, fn, side_input_cols, stage_name):
            raise NotImplementedError(
                "map_with_side_inputs is not implemented for SparkRDDBackend.")

        def flat_map(self, col, fn, stage_name=None):
            return col.flatMap(fn)

        def map_tuple(self, col, fn, stage_name=None):
            return col.map(lambda x: fn(*x))

        def map_values(self, col, fn, stage_name=None):
            return col.mapValues(fn)

        def group_by_key(self, col, stage_name=None):
            return col.groupByKey()

        def filter(self, col, fn, stage_name=None):
            return col.filter(fn)

        def filter_by_key(self, col, keys_to_keep, stage_name=None):
            if isinstance(keys_to_keep, pyspark.RDD):
                filtering_rdd = keys_to_keep.map(lambda x: (x, None))
                return col.join(filtering_rdd).map(lambda x: (x[0], x[1][0]))
            keys = set(keys_to_keep)
            return col.filter(lambda x: x[0] in keys)

        def keys(self, col, stage_name=None):
            return col.keys()

        def values(self, col, stage_name=None):
            return col.values()

        def sample_fixed_per_key(self, col, n, stage_name=None):
            # Uniformity caveat matches the reference (:446-449).
            return col.groupByKey().mapValues(
                lambda vals: sampling_utils.
                choose_from_list_without_replacement(list(vals), n))

        def count_per_element(self, col, stage_name=None):
            return col.map(lambda x: (x, 1)).reduceByKey(operator.add)

        def sum_per_key(self, col, stage_name=None):
            return col.reduceByKey(operator.add)

        def combine_accumulators_per_key(self, col, combiner, stage_name=None):
            return col.reduceByKey(combiner.merge_accumulators)

        def reduce_per_key(self, col, fn, stage_name=None):
            return col.reduceByKey(fn)

        def flatten(self, cols, stage_name=None):
            return self._sc.union(list(cols))

        def distinct(self, col, stage_name=None):
            return col.distinct()

        def to_list(self, col, stage_name=None):
            raise NotImplementedError(
                "to_list is not implemented for SparkRDDBackend.")


class Annotator(abc.ABC):
    """User hook attaching metadata (budget, params) to collections."""

    @abc.abstractmethod
    def annotate(self, col, backend: PipelineBackend, stage_name: str,
                 **kwargs):
        """Returns `col` annotated with metadata from kwargs."""


_annotators: List[Annotator] = []


def register_annotator(annotator: Annotator):
    _annotators.append(annotator)
