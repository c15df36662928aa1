"""Approximate DP aggregation directly on peeker sketches.

Counterpart of reference utility_analysis/peeker_engine.py:24-180. Consumes
(partition_key, per_user_aggregated_value, partition_count) sketches from
DataPeeker.sketch and runs a shortcut DP pipeline on them: probabilistic
cross-partition bounding, per-partition clipping, compound combining,
truncated-geometric partition selection, then noise. Intended for fast
interactive utility analysis — NOT a releasable DP aggregation (the
cross-partition bound is only approximated).
"""

import functools
from typing import Any, Sequence, Tuple

import numpy as np

from pipelinedp_tpu import aggregate_params as agg
from pipelinedp_tpu import budget_accounting
from pipelinedp_tpu import combiners as dp_combiners
from pipelinedp_tpu import partition_selection
from pipelinedp_tpu import pipeline_backend
from pipelinedp_tpu import sampling_utils


def aggregate_sketch_true(backend: pipeline_backend.PipelineBackend, col,
                          metric: agg.Metric):
    """Raw (no-noise) aggregation of sketches; COUNT or SUM only
    (reference peeker_engine.py:25-66)."""
    if metric == agg.Metrics.SUM:
        aggregator_fn = sum
    elif metric == agg.Metrics.COUNT:
        aggregator_fn = len
    else:
        raise ValueError('Aggregate sketch only supports sum or count')
    col = backend.map_tuple(col, lambda pk, pval, _: (pk, pval),
                            'Drop partition count')
    col = backend.group_by_key(col, "Group by partition key")
    return backend.map_values(col, lambda vals: aggregator_fn(list(vals)),
                              "Aggregate by partition key")


class PeekerEngine:
    """Sketch-based approximate DP aggregation
    (reference peeker_engine.py:68-150)."""

    def __init__(self,
                 budget_accountant: budget_accounting.BudgetAccountant,
                 backend: pipeline_backend.PipelineBackend):
        self._budget_accountant = budget_accountant
        self._be = backend

    def aggregate_sketches(self, col, params: agg.AggregateParams):
        """Approximate DP aggregation over sketches; one COUNT or SUM metric.

        col: (partition_key, per_user_aggregated_value, partition_count).
        Returns (partition_key, MetricsTuple).
        """
        if params.value_columns:
            raise NotImplementedError(
                "the sketch route (utility_analysis/) aggregates one value "
                "column: AggregateParams.value_columns is not supported")
        if len(params.metrics) != 1 or params.metrics[0] not in (
                agg.Metrics.SUM, agg.Metrics.COUNT):
            raise ValueError("Sketch only supports a single aggregation and "
                             "it must be COUNT or SUM.")
        combiner = dp_combiners.create_compound_combiner(
            params, self._budget_accountant)

        col = self._be.filter(
            col,
            functools.partial(_cross_partition_filter_fn,
                              params.max_partitions_contributed),
            "Cross partition bounding")
        col = self._be.map_tuple(
            col,
            functools.partial(_per_partition_bounding,
                              params.max_contributions_per_partition),
            "Per partition bounding")
        # (pk, bounded_value) → compound accumulator (1 privacy id, (value,))
        col = self._be.map_values(col, lambda x: (1, (x,)),
                                  "Convert to compound accumulator")
        col = self._be.combine_accumulators_per_key(
            col, combiner, "Aggregate by partition key")

        budget = self._budget_accountant.request_budget(
            mechanism_type=agg.MechanismType.GENERIC)
        keep_fn = functools.partial(_partition_selection_filter_fn, budget,
                                    params.max_partitions_contributed)
        col = self._be.filter(col, keep_fn, "Filter private partitions")
        return self._be.map_values(col, combiner.compute_metrics,
                                   "Compute DP metrics")


def _cross_partition_filter_fn(max_partitions: int,
                               row: Tuple[Any, float, int]) -> bool:
    """Approximate L0 bounding: keep a sketch row with probability
    max_partitions / partition_count (reference peeker_engine.py:153-159)."""
    _, _, partition_count = row
    if partition_count <= max_partitions:
        return True
    return sampling_utils.keep_with_probability(
        max_partitions / partition_count)


def _per_partition_bounding(max_contributions_per_partition: int, pk: Any,
                            pval: float, pcount: int) -> Tuple[Any, float]:
    del pcount  # consumed by the cross-partition filter
    return pk, min(pval, max_contributions_per_partition)


def _partition_selection_filter_fn(
        budget: budget_accounting.MechanismSpec, max_partitions: int,
        row) -> bool:
    """Truncated-geometric keep decision on the sketch's privacy-id count
    (reference peeker_engine.py:162-180); lazily builds the native selector
    once the budget is finalized."""
    privacy_id_count, _ = row[1]
    selector = partition_selection.create_partition_selection_strategy(
        agg.PartitionSelectionStrategy.TRUNCATED_GEOMETRIC, budget.eps,
        budget.delta, max_partitions)
    return selector.should_keep(privacy_id_count)
