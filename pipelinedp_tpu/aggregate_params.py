"""Declarative DP aggregation parameters, metric registry and enums.

Mirrors the semantic surface of the reference parameter layer
(/root/reference/pipeline_dp/aggregate_params.py:29-625): the same metrics,
noise kinds, mechanism types, partition-selection strategies, parameter
dataclasses and `__post_init__` validation rules — re-written for this
TPU-native framework (parameters here additionally feed static shapes /
traced scalars of the XLA aggregation kernels).
"""

import dataclasses
import logging
import math
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Callable, List, Optional, Sequence

import numpy as np

from pipelinedp_tpu import input_validators


@dataclass
class Metric:
    """A DP metric, optionally parameterized (e.g. PERCENTILE(90)).

    Reference parity: pipeline_dp/aggregate_params.py:29-58.
    """
    name: str
    parameter: Optional[float] = None

    def __eq__(self, other: 'Metric') -> bool:
        if not isinstance(other, Metric):
            return False
        return self.name == other.name and self.parameter == other.parameter

    def __str__(self):
        if self.parameter is None:
            return self.name
        return f'{self.name}({self.parameter})'

    def __repr__(self):
        return self.__str__()

    def __hash__(self):
        return hash(str(self))

    @property
    def is_percentile(self):
        return self.name == 'PERCENTILE'


class Metrics:
    """Registry of the supported DP metrics (reference :61-72)."""
    COUNT = Metric('COUNT')
    PRIVACY_ID_COUNT = Metric('PRIVACY_ID_COUNT')
    SUM = Metric('SUM')
    MEAN = Metric('MEAN')
    VARIANCE = Metric('VARIANCE')
    VECTOR_SUM = Metric('VECTOR_SUM')

    @classmethod
    def PERCENTILE(cls, percentile_to_compute: float):
        return Metric('PERCENTILE', percentile_to_compute)


class NoiseKind(Enum):
    LAPLACE = 'laplace'
    GAUSSIAN = 'gaussian'

    def convert_to_mechanism_type(self) -> 'MechanismType':
        if self == NoiseKind.LAPLACE:
            return MechanismType.LAPLACE
        return MechanismType.GAUSSIAN


class MechanismType(Enum):
    LAPLACE = 'Laplace'
    GAUSSIAN = 'Gaussian'
    GENERIC = 'Generic'

    def to_noise_kind(self) -> NoiseKind:
        if self == MechanismType.LAPLACE:
            return NoiseKind.LAPLACE
        if self == MechanismType.GAUSSIAN:
            return NoiseKind.GAUSSIAN
        raise ValueError(f"MechanismType {self.value} can not be converted to "
                         f"NoiseKind")


class NormKind(Enum):
    Linf = "linf"
    L0 = "l0"
    L1 = "l1"
    L2 = "l2"


class PartitionSelectionStrategy(Enum):
    TRUNCATED_GEOMETRIC = 'Truncated Geometric'
    LAPLACE_THRESHOLDING = 'Laplace Thresholding'
    GAUSSIAN_THRESHOLDING = 'Gaussian Thresholding'


@dataclass
class CalculatePrivateContributionBoundsParams:
    """Parameters for DPEngine.calculate_private_contribution_bounds().

    Only COUNT / PRIVACY_ID_COUNT aggregations are supported downstream.
    Reference parity: pipeline_dp/aggregate_params.py:113-150.
    """
    aggregation_noise_kind: NoiseKind
    aggregation_eps: float
    aggregation_delta: float
    calculation_eps: float
    max_partitions_contributed_upper_bound: int

    def __post_init__(self):
        input_validators.validate_epsilon_delta(
            self.aggregation_eps, self.aggregation_delta,
            "CalculatePrivateContributionBoundsParams")
        if self.aggregation_noise_kind is None:
            raise ValueError("aggregation_noise_kind must be set.")
        if (self.aggregation_noise_kind == NoiseKind.GAUSSIAN and
                self.aggregation_delta == 0):
            raise ValueError(
                "The Gaussian noise requires that the aggregation_delta is "
                "greater than 0.")
        input_validators.validate_epsilon_delta(
            self.calculation_eps, 0, "CalculatePrivateContributionBoundsParams")
        _check_is_positive_int(self.max_partitions_contributed_upper_bound,
                               "max_partitions_contributed_upper_bound")


@dataclass
class PrivateContributionBounds:
    """DP-computed contribution bounds (reference :153-163)."""
    max_partitions_contributed: int


@dataclass
class ValueColumn:
    """One scalar value column of a several-columns aggregation
    (AggregateParams.value_columns): its own clamp and its own metrics.

    name: prefix of the released fields (`<name>_sum`, `<name>_mean`).
    metrics: a non-empty subset of {SUM, MEAN}.
    """
    name: str
    min_value: float
    max_value: float
    metrics: List[Metric]

    def __post_init__(self):
        if not isinstance(self.name, str) or not self.name.isidentifier():
            raise ValueError(f"ValueColumn: name must be an identifier, "
                             f"got {self.name!r}")
        for bound in (self.min_value, self.max_value):
            if bound is None or _not_a_proper_number(bound):
                raise ValueError(f"ValueColumn {self.name}: min_value and "
                                 f"max_value must be finite numbers")
        if self.min_value > self.max_value:
            raise ValueError(f"ValueColumn {self.name}: max_value must be "
                             f"equal to or greater than min_value")
        self.metrics = list(self.metrics or [])
        not_allowed = set(self.metrics) - {Metrics.SUM, Metrics.MEAN}
        if not self.metrics or not_allowed:
            raise ValueError(f"ValueColumn {self.name}: metrics must be a "
                             f"non-empty subset of SUM, MEAN; got "
                             f"{self.metrics}")


@dataclass
class AggregateParams:
    """Parameters of DPEngine.aggregate().

    Several value columns in ONE pass (`value_columns`): each row's value
    is then a sequence of d scalars (the value extractor yields d values,
    or EncodedData.values is [n, d]) and column j has its own
    [min_value, max_value] and its own subset of {SUM, MEAN}; `metrics`
    may add COUNT and PRIVACY_ID_COUNT, released once. Semantics: per
    (privacy id, partition) a uniform sample of at most
    max_contributions_per_partition rows, per privacy id at most
    max_partitions_contributed partitions - ONE sample shared by all
    columns; each sampled value clamped to its column's range; per column
    the single-column rule of combiners.create_compound_combiner (a column
    with MEAN is one mean combiner that also yields its SUM as mean x
    count, a column with SUM alone is a sum combiner), every mechanism one
    equal share of the budget under the naive accountant (a mean combiner
    holds two: its count and its normalised sum), sensitivities per column
    as for one column. COUNT is released by the first MEAN column's count
    mechanism (as for one column), else by a count combiner of its own.
    Released fields: `count`, `privacy_id_count`, `<name>_sum`,
    `<name>_mean`. TPC-H Q1 is the example (README "Several value columns").

    Validation rules replicate the reference semantics
    (pipeline_dp/aggregate_params.py:166-365):
      - min_value/max_value and min_sum_per_partition/max_sum_per_partition
        must each be both-set-or-both-unset, and are mutually exclusive;
      - metrics requiring value bounds are rejected without them;
      - VECTOR_SUM is incompatible with scalar value metrics;
      - either max_contributions XOR both (max_partitions_contributed,
        max_contributions_per_partition) must be set.
    """
    metrics: List[Metric]
    noise_kind: NoiseKind = NoiseKind.LAPLACE
    max_partitions_contributed: Optional[int] = None
    max_contributions_per_partition: Optional[int] = None
    max_contributions: Optional[int] = None
    budget_weight: float = 1
    min_value: Optional[float] = None
    max_value: Optional[float] = None
    min_sum_per_partition: Optional[float] = None
    max_sum_per_partition: Optional[float] = None
    custom_combiners: Sequence['CustomCombiner'] = None
    vector_norm_kind: Optional[NormKind] = None
    vector_max_norm: Optional[float] = None
    vector_size: Optional[int] = None
    contribution_bounds_already_enforced: bool = False
    public_partitions_already_filtered: bool = False
    partition_selection_strategy: PartitionSelectionStrategy = (
        PartitionSelectionStrategy.TRUNCATED_GEOMETRIC)
    pre_threshold: Optional[int] = None
    value_columns: Optional[Sequence[ValueColumn]] = None

    @property
    def metrics_str(self) -> str:
        if self.custom_combiners:
            return (f"custom combiners="
                    f"{[c.metrics_names() for c in self.custom_combiners]}")
        columns = "".join(
            f" {c.name}[{c.min_value}, {c.max_value}]="
            f"{[str(m) for m in c.metrics]}"
            for c in self.value_columns or ())
        if self.metrics:
            return f"metrics={[str(m) for m in self.metrics]}" + columns
        return "metrics=[]" + columns

    def column_params(self, column: ValueColumn) -> 'AggregateParams':
        """The one-column AggregateParams a value column stands for: its
        clamp and its metrics (plus this job's COUNT / PRIVACY_ID_COUNT),
        every other parameter this job's."""
        return dataclasses.replace(
            self, metrics=list(self.metrics or []) + list(column.metrics),
            min_value=column.min_value, max_value=column.max_value,
            value_columns=None)

    @property
    def bounds_per_contribution_are_set(self) -> bool:
        return self.min_value is not None and self.max_value is not None

    @property
    def bounds_per_partition_are_set(self) -> bool:
        return (self.min_sum_per_partition is not None and
                self.max_sum_per_partition is not None)

    def __post_init__(self):
        self._check_both_set_or_unset("min_value", "max_value")
        self._check_both_set_or_unset("min_sum_per_partition",
                                      "max_sum_per_partition")

        value_bound = self.min_value is not None
        partition_bound = self.min_sum_per_partition is not None

        if value_bound and partition_bound:
            raise ValueError(
                "min_value and min_sum_per_partition can not be both set.")
        if self.value_columns is not None:
            self._check_value_columns(value_bound or partition_bound)

        if value_bound:
            self._check_range("min_value", "max_value")
        if partition_bound:
            self._check_range("min_sum_per_partition", "max_sum_per_partition")

        if self.metrics:
            if Metrics.VECTOR_SUM in self.metrics:
                if (Metrics.SUM in self.metrics or
                        Metrics.MEAN in self.metrics or
                        Metrics.VARIANCE in self.metrics):
                    raise ValueError(
                        "AggregateParams: vector sum can not be computed "
                        "together with scalar metrics such as sum, mean etc")
            elif partition_bound:
                allowed = {Metrics.SUM, Metrics.PRIVACY_ID_COUNT,
                           Metrics.COUNT}
                not_allowed = set(self.metrics).difference(allowed)
                if not_allowed:
                    raise ValueError(
                        f"AggregateParams: min_sum_per_partition is not "
                        f"compatible with metrics {not_allowed}. Please"
                        f"use min_value/max_value.")
            elif not partition_bound and not value_bound:
                allowed = {Metrics.PRIVACY_ID_COUNT, Metrics.COUNT}
                not_allowed = set(self.metrics).difference(allowed)
                if not_allowed:
                    raise ValueError(
                        f"AggregateParams: for metrics {not_allowed} "
                        f"bounds per partition are required (e.g. min_value,"
                        f"max_value).")

            if (self.contribution_bounds_already_enforced and
                    Metrics.PRIVACY_ID_COUNT in self.metrics):
                raise ValueError(
                    "AggregateParams: Cannot calculate PRIVACY_ID_COUNT when "
                    "contribution_bounds_already_enforced is set to True.")
        if self.custom_combiners:
            logging.warning("Warning: custom combiners are used. This is an "
                            "experimental feature. It might not work properly "
                            "and it might be changed or removed without any "
                            "notifications.")
        if self.metrics and self.custom_combiners:
            raise ValueError(
                "Custom combiners can not be used with standard metrics")
        if self.max_contributions is not None:
            _check_is_positive_int(self.max_contributions, "max_contributions")
            if ((self.max_partitions_contributed is not None) or
                    (self.max_contributions_per_partition is not None)):
                raise ValueError(
                    "AggregateParams: only one in max_contributions or "
                    "both max_partitions_contributed and "
                    "max_contributions_per_partition must be set")
        else:
            n_set = _count_not_none(self.max_partitions_contributed,
                                    self.max_contributions_per_partition)
            if n_set == 0:
                raise ValueError(
                    "AggregateParams: either max_contributions must be set or "
                    "both max_partitions_contributed and "
                    "max_contributions_per_partition must be set.")
            elif n_set == 1:
                raise ValueError("AggregateParams: either none or both "
                                 "max_partitions_contributed and "
                                 "max_contributions_per_partition must be set.")
            _check_is_positive_int(self.max_partitions_contributed,
                                   "max_partitions_contributed")
            _check_is_positive_int(self.max_contributions_per_partition,
                                   "max_contributions_per_partition")
        if self.pre_threshold is not None:
            _check_is_positive_int(self.pre_threshold, "pre_threshold")

    def _check_value_columns(self, scalar_bound: bool):
        self.value_columns = tuple(self.value_columns)
        if not self.value_columns or not all(
                isinstance(c, ValueColumn) for c in self.value_columns):
            raise ValueError("AggregateParams: value_columns must be a "
                             "non-empty sequence of ValueColumn")
        names = [c.name for c in self.value_columns]
        if len(set(names)) != len(names):
            raise ValueError(f"AggregateParams: value_columns names must "
                             f"differ, got {names}")
        if scalar_bound:
            raise ValueError(
                "AggregateParams: with value_columns every column has its "
                "own min_value/max_value; min_value, max_value, "
                "min_sum_per_partition and max_sum_per_partition must be "
                "None")
        if self.custom_combiners:
            raise ValueError("AggregateParams: value_columns can not be "
                             "used with custom combiners")
        not_allowed = set(self.metrics or []) - {Metrics.COUNT,
                                                 Metrics.PRIVACY_ID_COUNT}
        if not_allowed:
            raise ValueError(
                f"AggregateParams: with value_columns, metrics may hold "
                f"COUNT and PRIVACY_ID_COUNT only (SUM and MEAN belong to a "
                f"column); got {not_allowed}")

    def _check_both_set_or_unset(self, name1: str, name2: str):
        v1, v2 = getattr(self, name1), getattr(self, name2)
        if (v1 is None) != (v2 is None):
            raise ValueError(
                f"AggregateParams: {name1} and {name2} should"
                f" be both set or both None.")

    def _check_range(self, min_name: str, max_name: str):
        for name in (min_name, max_name):
            value = getattr(self, name)
            if _not_a_proper_number(value):
                raise ValueError(
                    f"AggregateParams: {name} must be a finite number")
        if getattr(self, min_name) > getattr(self, max_name):
            raise ValueError(
                f"AggregateParams: {max_name} must be equal to or "
                f"greater than {min_name}")

    def __str__(self):
        return parameters_to_readable_string(self)


@dataclass
class SelectPartitionsParams:
    """Parameters of DPEngine.select_partitions() (reference :368-395)."""
    max_partitions_contributed: int
    budget_weight: float = 1
    partition_selection_strategy: PartitionSelectionStrategy = (
        PartitionSelectionStrategy.TRUNCATED_GEOMETRIC)
    pre_threshold: Optional[int] = None

    def __post_init__(self):
        if self.pre_threshold is not None:
            _check_is_positive_int(self.pre_threshold, "pre_threshold")

    def __str__(self):
        return "Private Partitions"


@dataclass
class SumParams:
    """Convenience params for DP sum (reference :398-430)."""
    max_partitions_contributed: int
    max_contributions_per_partition: int
    min_value: float
    max_value: float
    partition_extractor: Callable
    value_extractor: Callable
    budget_weight: float = 1
    noise_kind: NoiseKind = NoiseKind.LAPLACE
    contribution_bounds_already_enforced: bool = False


@dataclass
class VarianceParams:
    """Convenience params for DP variance (reference :433-468)."""
    max_partitions_contributed: int
    max_contributions_per_partition: int
    min_value: float
    max_value: float
    partition_extractor: Callable
    value_extractor: Callable
    budget_weight: float = 1
    noise_kind: NoiseKind = NoiseKind.LAPLACE
    contribution_bounds_already_enforced: bool = False


@dataclass
class MeanParams:
    """Convenience params for DP mean (reference :471-504)."""
    max_partitions_contributed: int
    max_contributions_per_partition: int
    min_value: float
    max_value: float
    partition_extractor: Callable
    value_extractor: Callable
    budget_weight: float = 1
    noise_kind: NoiseKind = NoiseKind.LAPLACE
    contribution_bounds_already_enforced: bool = False


@dataclass
class CountParams:
    """Convenience params for DP count (reference :507-533)."""
    noise_kind: NoiseKind
    max_partitions_contributed: int
    max_contributions_per_partition: int
    partition_extractor: Callable
    budget_weight: float = 1
    contribution_bounds_already_enforced: bool = False


@dataclass
class PrivacyIdCountParams:
    """Convenience params for DP privacy-id count (reference :536-562)."""
    noise_kind: NoiseKind
    max_partitions_contributed: int
    partition_extractor: Callable
    budget_weight: float = 1
    contribution_bounds_already_enforced: bool = False


def _not_a_proper_number(num: Any) -> bool:
    return math.isnan(num) or math.isinf(num)


def _check_is_positive_int(num: Any, field_name: str) -> None:
    if not (_is_int(num) and num > 0):
        raise ValueError(
            f"{field_name} has to be positive integer, but {num} given.")


def _count_not_none(*args):
    return sum(1 for arg in args if arg is not None)


def _is_int(value: Any) -> bool:
    return isinstance(value, (int, np.integer))


def _append_if_present(obj: Any, property_name: str, n_spaces: int,
                       res: List[str]):
    if not hasattr(obj, property_name):
        return
    value = getattr(obj, property_name)
    if value is None:
        return
    res.append(" " * n_spaces + f"{property_name}={value}")


def parameters_to_readable_string(params,
                                  is_public_partition: Optional[bool] = None
                                 ) -> str:
    """Human-readable rendering used in Explain Computation reports
    (reference :594-625)."""
    result = [f"{type(params).__name__}:"]
    if hasattr(params, "metrics_str"):
        result.append(f" {params.metrics_str}")
    if hasattr(params, "noise_kind"):
        result.append(f" noise_kind={params.noise_kind.value}")
    if hasattr(params, "budget_weight"):
        result.append(f" budget_weight={params.budget_weight}")
    result.append(" Contribution bounding:")
    for name in ("max_partitions_contributed",
                 "max_contributions_per_partition", "max_contributions",
                 "min_value", "max_value", "min_sum_per_partition",
                 "max_sum_per_partition"):
        _append_if_present(params, name, 2, result)
    if getattr(params, "contribution_bounds_already_enforced", False):
        result.append("  contribution_bounds_already_enforced=True")
    for name in ("vector_max_norm", "vector_size", "vector_norm_kind"):
        _append_if_present(params, name, 2, result)

    if is_public_partition is not None:
        type_str = ("public"
                    if is_public_partition else "private") + " partitions"
        result.append(f" Partition selection: {type_str}")

    return "\n".join(result)
