"""DPEngine: the DP aggregation dataflow builder.

Reference parity: pipeline_dp/dp_engine.py:30-543. The engine builds:
extract -> (public-partition filter | contribution bounding) -> per-key
combine -> (private partition selection) -> noise/metrics, narrated by a
ReportGenerator, over the generic PipelineBackend op vocabulary.

TPU fast path: when the backend is a TPUBackend (and standard combiners are
used), aggregate() lowers the whole graph to the fused columnar executor
(pipelinedp_tpu/executor.py) — one jit-compiled XLA program
(executor.aggregate_release_kernel; select_partitions():
executor.select_partitions_release_kernel). Laziness is
preserved: the device program runs when the returned collection is first
iterated, which must happen after BudgetAccountant.compute_budgets() (noise
scales enter the compiled program as traced inputs).

Routing within the TPU path is owned by the backend's knobs, not this
module: TPUBackend(mesh=...) sends the program through the meshed kernels
(parallel/sharded.py's shard_map wrappers of the same traced release
body, or parallel/large_p.py above large_partition_threshold), and
TPUBackend(reshard=...) picks how each privacy id's rows are
co-located on one shard — device-resident
streamed-ingest columns take the on-device all_to_all reshard
(parallel/reshard.py) and never revisit the host between ingest and
dispatch; host rows take the exact load-balanced host permutation.

Streamed input: passing a runtime.pipeline.ChunkSource (an iterable of
(pid_raw, pk_raw, values) column chunks) as `col` routes encoding
through the device-resident streaming executor — host thread-pool
factorization feeding a bounded staging queue, rows accumulating into
donated device buffers — under TPUBackend(encode_threads=,
pipeline_depth=). Pipelined and serial execution are bit-identical
(README "End-to-end pipeline").
"""

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

from pipelinedp_tpu import aggregate_params as agg_params
from pipelinedp_tpu import budget_accounting
from pipelinedp_tpu import combiners
from pipelinedp_tpu import contribution_bounders
from pipelinedp_tpu import partition_selection
from pipelinedp_tpu import pipeline_backend
from pipelinedp_tpu import pipeline_functions
from pipelinedp_tpu import report_generator
from pipelinedp_tpu import sampling_utils
from pipelinedp_tpu.aggregate_params import AggregateParams, Metrics
from pipelinedp_tpu.data_extractors import DataExtractors


class DPEngine:
    """Performs DP aggregations."""

    def __init__(self, budget_accountant: budget_accounting.BudgetAccountant,
                 backend: pipeline_backend.PipelineBackend):
        self._budget_accountant = budget_accountant
        self._backend = backend
        self._report_generators = []

    @property
    def _current_report_generator(self):
        return self._report_generators[-1]

    def _add_report_stage(self, stage_description):
        self._current_report_generator.add_stage(stage_description)

    def _add_report_stages(self, stages_description):
        for stage_description in stages_description:
            self._add_report_stage(stage_description)

    def explain_computations_report(self):
        return [generator.report() for generator in self._report_generators]

    def aggregate(self,
                  col,
                  params: AggregateParams,
                  data_extractors: DataExtractors,
                  public_partitions=None,
                  out_explain_computation_report: Optional[
                      report_generator.ExplainComputationReport] = None):
        """Computes DP aggregate metrics.

        Args:
          col: collection of same-typed elements — or, on a TPUBackend,
            a pre-encoded columnar.EncodedData or a
            runtime.pipeline.ChunkSource of raw column chunks (streamed
            through the device-resident pipeline; extractors are not
            consulted for either).
          params: metrics to compute and computation parameters. With
            `params.value_columns` (several scalar value columns, each
            with its own clamp and its own SUM / MEAN, bounded in ONE
            pass; see AggregateParams) a row's value is the sequence of
            its d column values, or EncodedData.values is [n, d]. TPC-H
            Q1 with the customer as privacy unit: COUNT plus
            ValueColumn("quantity", 1, 50, [SUM, MEAN]),
            ValueColumn("extendedprice", 0, 70000, [SUM, MEAN]),
            ValueColumn("disc_price", 0, 70000, [SUM]),
            ValueColumn("charge", 0, 70000, [SUM]),
            ValueColumn("discount", 0, 0.1, [MEAN]) releases its eight
            aggregates from one contribution bounding (README "Several
            value columns in one pass").
          data_extractors: how to obtain (privacy_id, partition_key, value)
            from an element.
          public_partitions: optional collection of partition keys that appear
            in the result; if absent, partitions are selected DP-ly.
          out_explain_computation_report: out-param capturing this
            aggregation's Explain Computation report.

        Returns:
          Collection of (partition_key, MetricsTuple).
        """
        self._check_aggregate_params(col, params, data_extractors)
        self._check_budget_accountant_compatibility(
            public_partitions is not None, params.metrics,
            params.custom_combiners is not None)

        from pipelinedp_tpu.runtime import trace as rt_trace
        with self._budget_accountant.scope(weight=params.budget_weight), \
                rt_trace.span("graph_build"):
            self._report_generators.append(
                report_generator.ReportGenerator(params, "aggregate",
                                                 public_partitions is not None))
            if out_explain_computation_report is not None:
                out_explain_computation_report._set_report_generator(
                    self._current_report_generator)
            if self._use_tpu_path(params):
                col = self._aggregate_columnar(col, params, data_extractors,
                                               public_partitions)
            else:
                col = self._aggregate(col, params, data_extractors,
                                      public_partitions)
            budget = self._budget_accountant._compute_budget_for_aggregation(
                params.budget_weight)
            return self._annotate(col, params=params, budget=budget)

    def _use_tpu_path(self, params: AggregateParams) -> bool:
        if not isinstance(self._backend, pipeline_backend.TPUBackend):
            return False
        from pipelinedp_tpu import executor as tpu_executor
        return tpu_executor.supports(params)

    def _aggregate_columnar(self, col, params: AggregateParams,
                            data_extractors: DataExtractors,
                            public_partitions):
        """Lowers the aggregation to the fused columnar executor."""
        from pipelinedp_tpu import executor as tpu_executor
        return tpu_executor.lazy_aggregate(
            backend=self._backend,
            col=col,
            params=params,
            data_extractors=data_extractors,
            public_partitions=public_partitions,
            budget_accountant=self._budget_accountant,
            report_generator=self._current_report_generator)

    def _aggregate(self, col, params: AggregateParams,
                   data_extractors: DataExtractors, public_partitions):
        if params.custom_combiners:
            combiner = combiners.create_compound_combiner_with_custom_combiners(
                params, self._budget_accountant, params.custom_combiners)
        else:
            combiner = self._create_compound_combiner(params)

        col = self._extract_columns(col, data_extractors)
        # col : (privacy_id, partition_key, value)
        if (public_partitions is not None and
                not params.public_partitions_already_filtered):
            col = self._drop_partitions(col,
                                        public_partitions,
                                        partition_extractor=lambda row: row[1])
            self._add_report_stage(
                "Public partition selection: dropped non public partitions")
        if not params.contribution_bounds_already_enforced:
            contribution_bounder = self._create_contribution_bounder(
                params, combiner.expects_per_partition_sampling())
            col = contribution_bounder.bound_contributions(
                col, params, self._backend, self._current_report_generator,
                combiner.create_accumulator)
            # col : ((privacy_id, partition_key), accumulator)
            col = self._backend.map_tuple(col, lambda pid_pk, v:
                                          (pid_pk[1], v), "Drop privacy id")
            # col : (partition_key, accumulator)
        else:
            col = self._backend.map(col, lambda row: row[1:],
                                    "Remove privacy_id")
            # col : (partition_key, value)
            col = self._backend.map_values(
                col, lambda value: combiner.create_accumulator([value]),
                "Wrap values into accumulators")
            # col : (partition_key, accumulator)

        if public_partitions:
            col = self._add_empty_public_partitions(col, public_partitions,
                                                    combiner.create_accumulator)
        # col : (partition_key, accumulator)
        col = self._backend.combine_accumulators_per_key(
            col, combiner, "Reduce accumulators per partition key")
        # col : (partition_key, accumulator)

        if public_partitions is None:
            max_rows_per_privacy_id = 1
            if params.contribution_bounds_already_enforced:
                # Without privacy IDs we cannot guarantee one row per id;
                # conservatively assume each id contributed the max possible
                # rows.
                max_rows_per_privacy_id = (
                    params.max_contributions or
                    params.max_contributions_per_partition)

            col = self._select_private_partitions_internal(
                col, params.max_partitions_contributed, max_rows_per_privacy_id,
                params.partition_selection_strategy, params.pre_threshold)
        # col : (partition_key, accumulator)

        # Compute DP metrics.
        self._add_report_stages(combiner.explain_computation())
        col = self._backend.map_values(col, combiner.compute_metrics,
                                       "Compute DP metrics")
        return col

    def select_partitions(self, col, params: agg_params.SelectPartitionsParams,
                          data_extractors: DataExtractors):
        """Returns a collection of DP-selected partition keys."""
        self._check_select_private_partitions(col, params, data_extractors)
        self._check_budget_accountant_compatibility(False, [], False)

        from pipelinedp_tpu.runtime import trace as rt_trace
        with self._budget_accountant.scope(weight=params.budget_weight), \
                rt_trace.span("graph_build"):
            self._report_generators.append(
                report_generator.ReportGenerator(params, "select_partitions"))
            if isinstance(self._backend, pipeline_backend.TPUBackend):
                col = self._select_partitions_columnar(col, params,
                                                       data_extractors)
            else:
                col = self._select_partitions(col, params, data_extractors)
            budget = self._budget_accountant._compute_budget_for_aggregation(
                params.budget_weight)
            return self._annotate(col, params=params, budget=budget)

    def _select_partitions_columnar(self, col,
                                    params: agg_params.SelectPartitionsParams,
                                    data_extractors: DataExtractors):
        """Lowers standalone partition selection to one device program
        (executor.select_partitions_release_kernel): pair dedupe + L0
        sampling, per-partition privacy-id counts via segment ops, and the
        vectorized selection strategies — the TPU counterpart of the
        reference's shuffle pipeline (dp_engine.py:224-278)."""
        from pipelinedp_tpu import executor as tpu_executor
        return tpu_executor.lazy_select_partitions(
            backend=self._backend,
            col=col,
            params=params,
            data_extractors=data_extractors,
            budget_accountant=self._budget_accountant,
            report_generator=self._current_report_generator)

    def _select_partitions(self, col,
                           params: agg_params.SelectPartitionsParams,
                           data_extractors: DataExtractors):
        max_partitions_contributed = params.max_partitions_contributed
        col = self._backend.map(
            col, lambda row: (data_extractors.privacy_id_extractor(row),
                              data_extractors.partition_extractor(row)),
            "Extract (privacy_id, partition_key)")
        # col : (privacy_id, partition_key)
        col = self._backend.group_by_key(col, "Group by privacy_id")

        # col : (privacy_id, [partition_key])
        def sample_unique_elements_fn(pid_and_pks):
            pid, pks = pid_and_pks
            unique_pks = list(set(pks))
            sampled = sampling_utils.choose_from_list_without_replacement(
                unique_pks, max_partitions_contributed)
            return ((pid, pk) for pk in sampled)

        col = self._backend.flat_map(col, sample_unique_elements_fn,
                                     "Sample cross-partition contributions")
        # col : (privacy_id, partition_key)
        # An empty compound accumulator tracks the raw privacy-id count.
        compound_combiner = combiners.CompoundCombiner([],
                                                       return_named_tuple=False)
        col = self._backend.map_tuple(
            col, lambda pid, pk: (pk, compound_combiner.create_accumulator([])),
            "Drop privacy id and add accumulator")
        col = self._backend.combine_accumulators_per_key(
            col, compound_combiner, "Combine accumulators per partition key")
        col = self._select_private_partitions_internal(
            col,
            max_partitions_contributed,
            max_rows_per_privacy_id=1,
            strategy=params.partition_selection_strategy,
            pre_threshold=params.pre_threshold)
        return self._backend.keys(
            col, "Drop accumulators, keep only partition keys")

    def _drop_partitions(self, col, partitions, partition_extractor: Callable):
        """Keeps only rows whose partition is in `partitions`."""
        col = pipeline_functions.key_by(self._backend, col, partition_extractor,
                                        "Key by partition")
        col = self._backend.filter_by_key(col, partitions,
                                          "Filtering out partitions")
        return self._backend.values(col, "Drop key")

    def _add_empty_public_partitions(self, col, public_partitions,
                                     aggregator_fn):
        """Unions empty accumulators for every public partition."""
        self._add_report_stage(
            "Adding empty partitions for public partitions that are missing in "
            "data")
        public_partitions = self._backend.to_collection(
            public_partitions, col, "Public partitions to collection")
        empty_accumulators = self._backend.map(
            public_partitions, lambda pk: (pk, aggregator_fn([])),
            "Build empty accumulators")
        return self._backend.flatten(
            (col, empty_accumulators),
            "Join public partitions with partitions from data")

    def _select_private_partitions_internal(
            self, col, max_partitions_contributed: int,
            max_rows_per_privacy_id: int,
            strategy: agg_params.PartitionSelectionStrategy,
            pre_threshold: Optional[int]):
        """Filters partitions by the DP selection strategy, reading the
        privacy-id count from the compound accumulator's row count."""
        from pipelinedp_tpu.runtime import observability as rt_observability
        with rt_observability.mechanism_label("partition_selection"):
            budget = self._budget_accountant.request_budget(
                mechanism_type=agg_params.MechanismType.GENERIC)

        def filter_fn(budget, max_partitions, max_rows_per_privacy_id,
                      strategy, pre_threshold, row) -> bool:
            row_count, _ = row[1]
            # Conservative lower bound of contributing privacy IDs.
            privacy_id_count = (row_count + max_rows_per_privacy_id -
                                1) // max_rows_per_privacy_id
            selector = partition_selection.create_partition_selection_strategy(
                strategy, budget.eps, budget.delta, max_partitions,
                pre_threshold)
            return selector.should_keep(privacy_id_count)

        filter_fn = functools.partial(filter_fn, budget,
                                      max_partitions_contributed,
                                      max_rows_per_privacy_id, strategy,
                                      pre_threshold)
        pre_threshold_str = (f", pre_threshold={pre_threshold}"
                             if pre_threshold else "")
        self._add_report_stage(
            lambda: f"Private Partition selection: using {strategy.value} "
            f"method with (eps={budget.eps}, delta={budget.delta}"
            f"{pre_threshold_str})")
        return self._backend.filter(col, filter_fn,
                                    "Filter private partitions")

    def _create_compound_combiner(
            self, params: AggregateParams) -> combiners.CompoundCombiner:
        return combiners.create_compound_combiner(params,
                                                  self._budget_accountant)

    def _create_contribution_bounder(
            self, params: AggregateParams, expects_per_partition_sampling: bool
    ) -> contribution_bounders.ContributionBounder:
        if params.max_contributions:
            return (contribution_bounders.
                    SamplingPerPrivacyIdContributionBounder())
        if expects_per_partition_sampling:
            return (contribution_bounders.
                    SamplingCrossAndPerPartitionContributionBounder())
        return contribution_bounders.SamplingCrossPartitionContributionBounder(
        )

    def _extract_columns(self, col, data_extractors: DataExtractors):
        if data_extractors.privacy_id_extractor is None:
            # contribution_bounds_already_enforced: no privacy ids needed.
            privacy_id_extractor = lambda row: None
        else:
            privacy_id_extractor = data_extractors.privacy_id_extractor
        return self._backend.map(
            col, lambda row: (privacy_id_extractor(row),
                              data_extractors.partition_extractor(row),
                              data_extractors.value_extractor(row)),
            "Extract (privacy_id, partition_key, value)")

    def _check_aggregate_params(self,
                                col,
                                params: AggregateParams,
                                data_extractors: DataExtractors,
                                check_data_extractors: bool = True):
        _check_col(col)
        if params is None:
            raise ValueError("params must be set to a valid AggregateParams")
        if not isinstance(params, AggregateParams):
            raise TypeError("params must be set to a valid AggregateParams")
        if params.max_contributions is not None:
            supported = [
                Metrics.PRIVACY_ID_COUNT, Metrics.COUNT, Metrics.SUM,
                Metrics.MEAN
            ]
            not_supported = set(params.metrics).difference(supported)
            if not_supported:
                raise NotImplementedError(
                    f"max_contributions is not supported for {not_supported}")
        if check_data_extractors:
            _check_data_extractors(data_extractors)
        if params.contribution_bounds_already_enforced:
            if data_extractors.privacy_id_extractor:
                raise ValueError("privacy_id_extractor should be set iff "
                                 "contribution_bounds_already_enforced is "
                                 "False")
            if Metrics.PRIVACY_ID_COUNT in params.metrics:
                raise ValueError(
                    "PRIVACY_ID_COUNT cannot be computed when "
                    "contribution_bounds_already_enforced is True.")

    def _check_select_private_partitions(
            self, col, params: agg_params.SelectPartitionsParams,
            data_extractors: DataExtractors):
        if col is None or not col:
            raise ValueError("col must be non-empty")
        if params is None:
            raise ValueError(
                "params must be set to a valid SelectPartitionsParams")
        if not isinstance(params, agg_params.SelectPartitionsParams):
            raise TypeError(
                "params must be set to a valid SelectPartitionsParams")
        if (not isinstance(params.max_partitions_contributed, int) or
                params.max_partitions_contributed <= 0):
            raise ValueError("params.max_partitions_contributed must be set "
                             "(to a positive integer)")
        if data_extractors is None:
            raise ValueError("data_extractors must be set to a DataExtractors")
        if not isinstance(data_extractors, DataExtractors):
            raise TypeError("data_extractors must be set to a DataExtractors")

    def calculate_private_contribution_bounds(
            self,
            col,
            params: agg_params.CalculatePrivateContributionBoundsParams,
            data_extractors: DataExtractors,
            partitions: Any,
            partitions_already_filtered: bool = False):
        """DP computation of contribution bounds for COUNT/PRIVACY_ID_COUNT.

        Returns a 1-element collection of PrivateContributionBounds.
        """
        self._check_calculate_private_contribution_bounds_params(
            col, params, data_extractors)
        if not partitions_already_filtered:
            col = self._drop_partitions(col, partitions,
                                        data_extractors.partition_extractor)
        from pipelinedp_tpu.dataset_histograms import computing_histograms
        from pipelinedp_tpu.private_contribution_bounds import (
            PrivateL0Calculator)
        histograms = computing_histograms.compute_dataset_histograms(
            col, data_extractors, self._backend)
        l0_calculator = PrivateL0Calculator(params, partitions, histograms,
                                            self._backend)
        return pipeline_functions.collect_to_container(
            self._backend,
            {"max_partitions_contributed": l0_calculator.calculate()},
            agg_params.PrivateContributionBounds,
            "Collect calculated private contribution bounds into "
            "PrivateContributionBounds dataclass")

    def _check_calculate_private_contribution_bounds_params(
            self,
            col,
            params: agg_params.CalculatePrivateContributionBoundsParams,
            data_extractors: DataExtractors,
            check_data_extractors: bool = True):
        _check_col(col)
        if params is None:
            raise ValueError(
                "params must be set to a valid "
                "CalculatePrivateContributionBoundsParams")
        if not isinstance(params,
                          agg_params.CalculatePrivateContributionBoundsParams):
            raise TypeError("params must be set to a valid "
                            "CalculatePrivateContributionBoundsParams")
        if check_data_extractors:
            _check_data_extractors(data_extractors)

    def _check_budget_accountant_compatibility(
            self, is_public_partition: bool,
            metrics: Sequence[agg_params.Metric], custom_combiner: bool):
        if isinstance(self._budget_accountant,
                      budget_accounting.NaiveBudgetAccountant):
            return  # all aggregations supported
        # Private partition selection IS supported under PLD here (the GENERIC
        # mechanism composes through the loss distribution,
        # budget_accounting.py PLDBudgetAccountant._compose_distributions) —
        # the reference disallows it (/root/reference/pipeline_dp/
        # dp_engine.py:511-521); this framework lifts that restriction.
        del is_public_partition
        supported = [
            Metrics.COUNT, Metrics.PRIVACY_ID_COUNT, Metrics.SUM, Metrics.MEAN
        ]
        non_supported = set(metrics) - set(supported)
        if non_supported:
            raise NotImplementedError(f"Metrics {non_supported} do not "
                                      f"support PLD budget accounting")
        if custom_combiner:
            raise ValueError("PLD budget accounting does not support custom "
                             "combiners")

    def _annotate(self, col, params, budget: budget_accounting.Budget):
        col = self._backend.annotate(col,
                                     "annotation",
                                     params=params,
                                     budget=budget)
        return self._guard_lazy_execution(col)

    def _guard_lazy_execution(self, col):
        """Wraps a lazily-executed result so that iterating it cannot grow
        the budget ledger.

        Every mechanism must register at graph-build time (inside
        aggregate()/select_partitions()); the deferred execution — which
        under the fault-tolerant runtime includes block retries, journal
        resume and OOM re-planning — must never call request_budget, or
        the composition accounting double-spends epsilon for a release
        that already happened. Local-family backends return lazy Python
        generators, so the check brackets the actual execution; Beam/Spark
        collections execute out of process and are returned untouched.
        """
        if not isinstance(self._backend, pipeline_backend.LocalBackend):
            return col
        accountant = self._budget_accountant

        def guarded():
            before = accountant.mechanism_count
            yield from col
            grew = accountant.mechanism_count - before
            if grew:
                raise AssertionError(
                    f"{grew} mechanism(s) registered with the "
                    f"BudgetAccountant while iterating an aggregation "
                    f"result: mechanisms must register at graph-build "
                    f"time, never during (possibly retried) execution — "
                    f"this would double-spend the privacy budget.")

        return guarded()


def _check_col(col):
    if col is None or _is_falsey_local(col):
        raise ValueError("col must be non-empty")


def _is_falsey_local(col) -> bool:
    # Distributed collections (e.g. RDDs) may not implement truthiness; only
    # local list/tuple emptiness is checked.
    try:
        return not col
    except Exception:  # noqa: BLE001 - truthiness probe: distributed collections may raise anything from __bool__; non-local input is simply not length-checkable
        return False


def _check_data_extractors(data_extractors: DataExtractors):
    if data_extractors is None:
        raise ValueError("data_extractors must be set to a DataExtractors")
    if not isinstance(data_extractors, DataExtractors):
        raise TypeError("data_extractors must be set to a DataExtractors")
