"""Combiners: per-partition aggregation kernels for DP metrics.

Reference parity: pipeline_dp/combiners.py:32-871. Combiners follow the
Beam-CombineFn-style triad — create_accumulator / merge_accumulators /
compute_metrics — with merge associative, so the same logic runs:

  * element-wise on the generic backends (Local/Beam/Spark), and
  * as dense array columns on the TPU path: executor.build_plan lowers each
    scalar-accumulator combiner to a static MetricPlanEntry evaluated as
    (n_partitions,) dense columns with segment-sums and vectorized noise.

Mechanisms are built lazily from MechanismSpec (dropped from serialized
state), so budget finalization can happen after graph construction.
"""

import abc
import collections
import copy
import threading
from typing import Callable, Iterable, List, Optional, Sized, Tuple, Union

import numpy as np

from pipelinedp_tpu import aggregate_params
from pipelinedp_tpu import budget_accounting
from pipelinedp_tpu import dp_computations
from pipelinedp_tpu.aggregate_params import Metrics, NoiseKind
from pipelinedp_tpu.ops import quantile_tree as quantile_tree_ops
from pipelinedp_tpu.runtime.concurrency import guarded_by

ArrayLike = Union[np.ndarray, List[float]]
ExplainComputationReport = Union[Callable, str, List[Union[Callable, str]]]


class Combiner(abc.ABC):
    """Base class for all combiners.

    Combiners hold logic; accumulators hold data. The framework:
      1. calls create_accumulator() per (privacy_id, partition) group,
      2. merges accumulators pairwise per partition (associative),
      3. calls compute_metrics() once per surviving partition.
    """

    @abc.abstractmethod
    def create_accumulator(self, values):
        """Creates an accumulator from `values`."""

    @abc.abstractmethod
    def merge_accumulators(self, accumulator1, accumulator2):
        """Merges two accumulators (associative)."""

    @abc.abstractmethod
    def compute_metrics(self, accumulator):
        """Computes the DP result from the final accumulator."""

    @abc.abstractmethod
    def metrics_names(self) -> List[str]:
        pass

    @abc.abstractmethod
    def explain_computation(self) -> ExplainComputationReport:
        pass

    def expects_per_partition_sampling(self) -> bool:
        """Whether the framework must sample values per partition down to
        max_contributions_per_partition before create_accumulator()."""
        return True


class CustomCombiner(Combiner, abc.ABC):
    """User-provided combiner for custom DP aggregations (experimental).

    The custom combiner implements its own DP mechanism in compute_metrics()
    and, if needed, contribution bounding in create_accumulator().
    """

    @abc.abstractmethod
    def request_budget(self,
                       budget_accountant: budget_accounting.BudgetAccountant):
        """Called during graph construction. Store the returned MechanismSpec
        in self; never store the budget_accountant itself (driver-only)."""

    def set_aggregate_params(self,
                             params: aggregate_params.AggregateParams):
        self._aggregate_params = params

    def metrics_names(self) -> List[str]:
        return [self.__class__.__name__]


class CombinerParams:
    """Budget spec + aggregation params bundled for a combiner."""

    def __init__(self, spec: budget_accounting.MechanismSpec,
                 params: aggregate_params.AggregateParams):
        self._mechanism_spec = spec
        self.aggregate_params = copy.copy(params)

    @property
    def eps(self):
        return self._mechanism_spec.eps

    @property
    def delta(self):
        return self._mechanism_spec.delta

    @property
    def mechanism_spec(self) -> budget_accounting.MechanismSpec:
        return self._mechanism_spec

    @property
    def scalar_noise_params(self):
        p = self.aggregate_params
        return dp_computations.ScalarNoiseParams(
            self.eps, self.delta, p.min_value, p.max_value,
            p.min_sum_per_partition, p.max_sum_per_partition,
            p.max_partitions_contributed, p.max_contributions_per_partition,
            p.noise_kind)

    @property
    def additive_vector_noise_params(
            self) -> dp_computations.AdditiveVectorNoiseParams:
        p = self.aggregate_params
        return dp_computations.AdditiveVectorNoiseParams(
            eps_per_coordinate=self.eps / p.vector_size,
            delta_per_coordinate=self.delta / p.vector_size,
            max_norm=p.vector_max_norm,
            l0_sensitivity=p.max_partitions_contributed,
            linf_sensitivity=p.max_contributions_per_partition,
            norm_kind=p.vector_norm_kind,
            noise_kind=p.noise_kind)


class MechanismContainerMixin(abc.ABC):
    """Lazily creates and caches a DP mechanism; drops it on serialization
    (mechanisms are rebuilt from the budget-finalized spec on the worker)."""

    @abc.abstractmethod
    def create_mechanism(
        self
    ) -> Union[dp_computations.AdditiveMechanism,
               dp_computations.MeanMechanism]:
        pass

    def __getstate__(self):
        state = self.__dict__.copy()
        state.pop("_mechanism", None)
        return state

    def get_mechanism(self):
        if not hasattr(self, "_mechanism"):
            self._mechanism = self.create_mechanism()
        return self._mechanism


class AdditiveMechanismMixin(MechanismContainerMixin):
    """MechanismContainerMixin for additive (Laplace/Gaussian) mechanisms."""

    def create_mechanism(self) -> dp_computations.AdditiveMechanism:
        return dp_computations.create_additive_mechanism(
            self.mechanism_spec(), self.sensitivities())

    @abc.abstractmethod
    def sensitivities(self) -> dp_computations.Sensitivities:
        pass

    @abc.abstractmethod
    def mechanism_spec(self) -> budget_accounting.MechanismSpec:
        pass

    def noise_std(self) -> float:
        """Noise stddev of the finalized mechanism (TPU path: traced input)."""
        return self.get_mechanism().std


class CountCombiner(Combiner, AdditiveMechanismMixin):
    """DP count. Accumulator: int count of contributions."""
    AccumulatorType = int

    def __init__(self, mechanism_spec: budget_accounting.MechanismSpec,
                 params: aggregate_params.AggregateParams):
        self._mechanism_spec = mechanism_spec
        self._sensitivities = dp_computations.compute_sensitivities_for_count(
            params)

    def create_accumulator(self, values: Sized) -> AccumulatorType:
        return len(values)

    def merge_accumulators(self, count1, count2):
        return count1 + count2

    def compute_metrics(self, count: AccumulatorType) -> dict:
        return {'count': self.get_mechanism().add_noise(count)}

    def metrics_names(self) -> List[str]:
        return ['count']

    def explain_computation(self) -> ExplainComputationReport:
        return lambda: (f"Computed DP count with\n"
                        f"     {self.get_mechanism().describe()}")

    def mechanism_spec(self) -> budget_accounting.MechanismSpec:
        return self._mechanism_spec

    def sensitivities(self) -> dp_computations.Sensitivities:
        return self._sensitivities



class PrivacyIdCountCombiner(Combiner, AdditiveMechanismMixin):
    """DP privacy-id count. Accumulator: int (1 per contributing id)."""
    AccumulatorType = int

    def __init__(self, mechanism_spec: budget_accounting.MechanismSpec,
                 params: aggregate_params.AggregateParams):
        self._mechanism_spec = mechanism_spec
        self._sensitivities = (
            dp_computations.compute_sensitivities_for_privacy_id_count(params))

    def create_accumulator(self, values: Sized) -> AccumulatorType:
        return 1 if values else 0

    def merge_accumulators(self, count1, count2):
        return count1 + count2

    def compute_metrics(self, count: AccumulatorType) -> dict:
        return {"privacy_id_count": self.get_mechanism().add_noise(count)}

    def metrics_names(self) -> List[str]:
        return ['privacy_id_count']

    def explain_computation(self) -> ExplainComputationReport:
        return lambda: (f"Computed DP privacy_id_count with\n"
                        f"     {self.get_mechanism().describe()}")

    def mechanism_spec(self) -> budget_accounting.MechanismSpec:
        return self._mechanism_spec

    def sensitivities(self) -> dp_computations.Sensitivities:
        return self._sensitivities

    def expects_per_partition_sampling(self) -> bool:
        return False



class SumCombiner(Combiner, AdditiveMechanismMixin):
    """DP sum with two clipping regimes (reference :327-379):

      * per-contribution bounds (min_value/max_value): clip each value, sum;
      * per-partition bounds (min_sum_per_partition/...): sum, then clip the
        per-(privacy_id, partition) sum.
    """
    AccumulatorType = float

    def __init__(self, mechanism_spec: budget_accounting.MechanismSpec,
                 params: aggregate_params.AggregateParams):
        self._mechanism_spec = mechanism_spec
        self._sensitivities = dp_computations.compute_sensitivities_for_sum(
            params)
        self._bounding_per_partition = params.bounds_per_partition_are_set
        if self._bounding_per_partition:
            self._min_bound = params.min_sum_per_partition
            self._max_bound = params.max_sum_per_partition
        else:
            self._min_bound = params.min_value
            self._max_bound = params.max_value

    def create_accumulator(self, values: Iterable[float]) -> AccumulatorType:
        if self._bounding_per_partition:
            return float(np.clip(sum(values), self._min_bound,
                                 self._max_bound))
        return float(
            np.clip(np.asarray(list(values), dtype=np.float64),
                    self._min_bound, self._max_bound).sum())

    def merge_accumulators(self, sum1, sum2):
        return sum1 + sum2

    def compute_metrics(self, sum_: AccumulatorType) -> dict:
        return {"sum": self.get_mechanism().add_noise(sum_)}

    def metrics_names(self) -> List[str]:
        return ['sum']

    def expects_per_partition_sampling(self) -> bool:
        return not self._bounding_per_partition

    def explain_computation(self) -> ExplainComputationReport:
        return lambda: (f"Computed DP sum with\n"
                        f"     {self.get_mechanism().describe()}")

    def mechanism_spec(self) -> budget_accounting.MechanismSpec:
        return self._mechanism_spec

    def sensitivities(self) -> dp_computations.Sensitivities:
        return self._sensitivities


    @property
    def bounding_per_partition(self) -> bool:
        return self._bounding_per_partition

    @property
    def bounds(self) -> Tuple[float, float]:
        return self._min_bound, self._max_bound


class MeanCombiner(Combiner, MechanismContainerMixin):
    """DP mean via the normalized-sum trick; optionally also count and sum.

    Accumulator: (count, normalized_sum) with values normalized to the range
    middle so the sum's sensitivity is (max-min)/2 per contribution.
    """
    AccumulatorType = Tuple[int, float]

    def __init__(self, count_spec: budget_accounting.MechanismSpec,
                 sum_spec: budget_accounting.MechanismSpec,
                 params: aggregate_params.AggregateParams,
                 metrics_to_compute: Iterable[str]):
        metrics_to_compute = list(metrics_to_compute)
        if len(metrics_to_compute) != len(set(metrics_to_compute)):
            raise ValueError(f"{metrics_to_compute} cannot contain duplicates")
        for metric in metrics_to_compute:
            if metric not in ('count', 'sum', 'mean'):
                raise ValueError(
                    f"{metric} should be one of ['count', 'sum', 'mean']")
        if 'mean' not in metrics_to_compute:
            raise ValueError(
                f"one of the {metrics_to_compute} should be 'mean'")
        self._count_spec = count_spec
        self._sum_spec = sum_spec
        self._metrics_to_compute = metrics_to_compute
        self._min_value = params.min_value
        self._max_value = params.max_value
        self._count_sensitivities = (
            dp_computations.compute_sensitivities_for_count(params))
        self._sum_sensitivities = (
            dp_computations.compute_sensitivities_for_normalized_sum(params))

    def create_accumulator(self, values: Iterable[float]) -> AccumulatorType:
        values = np.asarray(list(values), dtype=np.float64)
        middle = dp_computations.compute_middle(self._min_value,
                                                self._max_value)
        normalized = np.clip(values, self._min_value, self._max_value) - middle
        return len(values), float(normalized.sum())

    def merge_accumulators(self, accum1, accum2):
        return accum1[0] + accum2[0], accum1[1] + accum2[1]

    def compute_metrics(self, accum: AccumulatorType) -> dict:
        total_count, total_normalized_sum = accum
        noisy_count, noisy_sum, noisy_mean = self.get_mechanism().compute_mean(
            total_count, total_normalized_sum)
        result = {'mean': noisy_mean}
        if 'count' in self._metrics_to_compute:
            result['count'] = noisy_count
        if 'sum' in self._metrics_to_compute:
            result['sum'] = noisy_sum
        return result

    def metrics_names(self) -> List[str]:
        return self._metrics_to_compute

    def explain_computation(self) -> ExplainComputationReport:
        return lambda: "DP mean computation:\n" + self.get_mechanism().describe(
        )

    def create_mechanism(self) -> dp_computations.MeanMechanism:
        middle = dp_computations.compute_middle(self._min_value,
                                                self._max_value)
        return dp_computations.create_mean_mechanism(middle, self._count_spec,
                                                     self._count_sensitivities,
                                                     self._sum_spec,
                                                     self._sum_sensitivities)

    def mechanism_spec(self):
        return (self._count_spec, self._sum_spec)


    @property
    def value_bounds(self) -> Tuple[float, float]:
        return self._min_value, self._max_value


class VarianceCombiner(Combiner):
    """DP variance (+ optionally mean/sum/count).

    Accumulator: (count, normalized_sum, normalized_sum_of_squares).
    """
    AccumulatorType = Tuple[int, float, float]

    def __init__(self, params: CombinerParams,
                 metrics_to_compute: Iterable[str]):
        self._params = params
        metrics_to_compute = list(metrics_to_compute)
        if len(metrics_to_compute) != len(set(metrics_to_compute)):
            raise ValueError(f"{metrics_to_compute} cannot contain duplicates")
        for metric in metrics_to_compute:
            if metric not in ('count', 'sum', 'mean', 'variance'):
                raise ValueError(f"{metric} should be one of "
                                 f"['count', 'sum', 'mean', 'variance']")
        if 'variance' not in metrics_to_compute:
            raise ValueError(
                f"one of the {metrics_to_compute} should be 'variance'")
        self._metrics_to_compute = metrics_to_compute

    def create_accumulator(self, values: Iterable[float]) -> AccumulatorType:
        p = self._params.aggregate_params
        middle = dp_computations.compute_middle(p.min_value, p.max_value)
        values = np.asarray(list(values), dtype=np.float64)
        normalized = np.clip(values, p.min_value, p.max_value) - middle
        return len(values), float(normalized.sum()), float(
            (normalized**2).sum())

    def merge_accumulators(self, accum1, accum2):
        return (accum1[0] + accum2[0], accum1[1] + accum2[1],
                accum1[2] + accum2[2])

    def compute_metrics(self, accum: AccumulatorType) -> dict:
        count, nsum, nsum2 = accum
        noisy_count, noisy_sum, noisy_mean, noisy_variance = (
            dp_computations.compute_dp_var(count, nsum, nsum2,
                                           self._params.scalar_noise_params))
        result = {'variance': noisy_variance}
        if 'count' in self._metrics_to_compute:
            result['count'] = noisy_count
        if 'sum' in self._metrics_to_compute:
            result['sum'] = noisy_sum
        if 'mean' in self._metrics_to_compute:
            result['mean'] = noisy_mean
        return result

    def metrics_names(self) -> List[str]:
        return self._metrics_to_compute

    def explain_computation(self) -> ExplainComputationReport:
        return lambda: (f"Computed variance with (eps={self._params.eps} "
                        f"delta={self._params.delta})")

    def mechanism_spec(self) -> budget_accounting.MechanismSpec:
        return self._params.mechanism_spec



class QuantileCombiner(Combiner):
    """DP percentiles via the dense-array quantile tree (ops/quantile_tree).

    Accumulator: serialized tree bytes (mergeable across workers); on the TPU
    path the tree is a dense per-partition matrix and merge is vector add.
    """
    AccumulatorType = bytes

    def __init__(self,
                 params: CombinerParams,
                 percentiles_to_compute: List[float],
                 tree_height: int = quantile_tree_ops.DEFAULT_TREE_HEIGHT,
                 branching_factor: int = (
                     quantile_tree_ops.DEFAULT_BRANCHING_FACTOR)):
        self._params = params
        self._percentiles = percentiles_to_compute
        self._quantiles_to_compute = [p / 100 for p in percentiles_to_compute]
        self._tree_height = tree_height
        self._branching_factor = branching_factor

    def _empty_tree(self) -> quantile_tree_ops.DenseQuantileTree:
        p = self._params.aggregate_params
        return quantile_tree_ops.DenseQuantileTree(p.min_value, p.max_value,
                                                   self._tree_height,
                                                   self._branching_factor)

    def create_accumulator(self, values) -> AccumulatorType:
        tree = self._empty_tree()
        tree.add_entries(list(values))
        return tree.serialize()

    def merge_accumulators(self, acc1, acc2):
        tree = quantile_tree_ops.DenseQuantileTree.deserialize(acc1)
        tree.merge(quantile_tree_ops.DenseQuantileTree.deserialize(acc2))
        return tree.serialize()

    def compute_metrics(self, accumulator: AccumulatorType) -> dict:
        tree = quantile_tree_ops.DenseQuantileTree.deserialize(accumulator)
        p = self._params.aggregate_params
        quantiles = tree.compute_quantiles(
            self._params.eps, self._params.delta,
            p.max_partitions_contributed, p.max_contributions_per_partition,
            self._quantiles_to_compute, p.noise_kind)
        return dict(zip(self.metrics_names(), quantiles))

    def metrics_names(self) -> List[str]:

        def format_metric_name(p: float):
            int_p = int(round(p))
            p_str = str(int_p) if int_p == p else str(p).replace('.', '_')
            return f"percentile_{p_str}"

        return list(map(format_metric_name, self._percentiles))

    def explain_computation(self) -> ExplainComputationReport:
        return lambda: (f"Computed percentiles {self._percentiles} with "
                        f"(eps={self._params.eps} delta={self._params.delta})")

    def mechanism_spec(self) -> budget_accounting.MechanismSpec:
        return self._params.mechanism_spec


class VectorSumCombiner(Combiner):
    """DP elementwise sum of fixed-size vectors."""
    AccumulatorType = np.ndarray

    def __init__(self, params: CombinerParams):
        self._params = params

    def create_accumulator(self,
                           values: Iterable[ArrayLike]) -> AccumulatorType:
        expected_shape = (self._params.aggregate_params.vector_size,)
        array_sum = None
        for val in values:
            val = np.asarray(val)
            if val.shape != expected_shape:
                raise TypeError(
                    f"Shape mismatch: {val.shape} != {expected_shape}")
            array_sum = val.copy() if array_sum is None else array_sum + val
        if array_sum is None:
            array_sum = np.zeros(expected_shape)
        return array_sum

    def merge_accumulators(self, array_sum1, array_sum2):
        return array_sum1 + array_sum2

    def compute_metrics(self, array_sum: AccumulatorType) -> dict:
        return {
            'vector_sum':
                dp_computations.add_noise_vector(
                    array_sum, self._params.additive_vector_noise_params)
        }

    def metrics_names(self) -> List[str]:
        return ['vector_sum']

    def explain_computation(self) -> ExplainComputationReport:
        return lambda: (f"Computed vector sum with (eps={self._params.eps} "
                        f"delta={self._params.delta})")

    def mechanism_spec(self) -> budget_accounting.MechanismSpec:
        return self._params.mechanism_spec


def column_field_name(label: str, metric: str) -> str:
    """The released field of a value column's metric: `sum` / `mean` carry
    the column's name, the per-job counts keep theirs. THE naming rule of
    AggregateParams.value_columns (the combiners and the columnar plan
    both read it)."""
    if metric in ('count', 'privacy_id_count'):
        return metric
    return f"{label}_{metric}"


class ColumnCombiner(Combiner):
    """One value column of a several-columns aggregation
    (AggregateParams.value_columns): the existing sum or mean combiner
    `inner`, built from the column's own one-column params, fed column
    `column` of every sampled row's values and releasing `sum` / `mean`
    as `<label>_sum` / `<label>_mean` (`count` keeps its name). Every
    child of the compound sees the SAME sampled rows, so one contribution
    bounding serves all columns."""

    def __init__(self, inner: Combiner, column: int, label: str):
        self.inner = inner
        self.column = column
        self.label = label

    def create_accumulator(self, values):
        return self.inner.create_accumulator(
            [row[self.column] for row in values])

    def merge_accumulators(self, accumulator1, accumulator2):
        return self.inner.merge_accumulators(accumulator1, accumulator2)

    def compute_metrics(self, accumulator) -> dict:
        return {
            column_field_name(self.label, metric): value for metric, value in
            self.inner.compute_metrics(accumulator).items()
        }

    def metrics_names(self) -> List[str]:
        return [column_field_name(self.label, m)
                for m in self.inner.metrics_names()]

    def explain_computation(self) -> ExplainComputationReport:
        return self.inner.explain_computation()

    def expects_per_partition_sampling(self) -> bool:
        return self.inner.expects_per_partition_sampling()


def unwrap_column(child: Combiner):
    """(combiner, column, label) of a compound's child: a value column's
    inner combiner with its column index and label, any other child as it
    is with column -1 (the one scalar column)."""
    if isinstance(child, ColumnCombiner):
        return child.inner, child.column, child.label
    return child, -1, ''


# Cache for namedtuple result types (Beam-style serialization support).
# Guarded: the service's worker pool builds CompoundCombiners on
# concurrent threads, and an unlocked get-or-create can install TWO
# distinct classes for one key — isinstance and pickle identity then
# differ between jobs that should share the type (thread-escape's
# first-run catch).
_named_tuple_cache_lock = threading.Lock()
_named_tuple_cache = {}
_GUARDED_BY = guarded_by("_named_tuple_cache_lock", "_named_tuple_cache")


def _get_or_create_named_tuple(type_name: str,
                               field_names: tuple) -> 'MetricsTuple':
    cache_key = (type_name, field_names)
    with _named_tuple_cache_lock:
        named_tuple = _named_tuple_cache.get(cache_key)
        if named_tuple is None:
            named_tuple = collections.namedtuple(type_name, field_names)
            named_tuple.__reduce__ = lambda self: (
                _create_named_tuple_instance,
                (type_name, field_names, tuple(self)))
            _named_tuple_cache[cache_key] = named_tuple
    return named_tuple


def _create_named_tuple_instance(type_name: str, field_names: tuple, values):
    return _get_or_create_named_tuple(type_name, field_names)(*values)


class CompoundCombiner(Combiner):
    """Combiner of combiners: computes several metrics in one pass.

    Accumulator: (row_count, (child accumulators...)). row_count equals the
    privacy-id count when rows are grouped per privacy id — private partition
    selection reads it.

    compute_metrics returns a MetricsTuple namedtuple (return_named_tuple) or
    the plain tuple of child results.
    """

    AccumulatorType = Tuple[int, Tuple]

    def __init__(self, combiners: Iterable['Combiner'],
                 return_named_tuple: bool):
        self._combiners = list(combiners)
        self._metrics_to_compute = []
        self._return_named_tuple = return_named_tuple
        if not self._return_named_tuple:
            return
        for combiner in self._combiners:
            self._metrics_to_compute.extend(combiner.metrics_names())
        if len(self._metrics_to_compute) != len(set(self._metrics_to_compute)):
            raise ValueError(
                f"two combiners in {combiners} cannot compute the same metrics")
        self._metrics_to_compute = tuple(self._metrics_to_compute)
        self._MetricsTuple = _get_or_create_named_tuple(
            "MetricsTuple", self._metrics_to_compute)

    @property
    def combiners(self) -> List[Combiner]:
        return self._combiners

    def create_accumulator(self, values) -> AccumulatorType:
        return (1,
                tuple(
                    combiner.create_accumulator(values)
                    for combiner in self._combiners))

    def merge_accumulators(self, acc1: AccumulatorType,
                           acc2: AccumulatorType) -> AccumulatorType:
        row_count1, children1 = acc1
        row_count2, children2 = acc2
        merged = tuple(
            combiner.merge_accumulators(a1, a2)
            for combiner, a1, a2 in zip(self._combiners, children1, children2))
        return (row_count1 + row_count2, merged)

    def compute_metrics(self, compound_accumulator: AccumulatorType):
        _, children = compound_accumulator
        if not self._return_named_tuple:
            return tuple(
                combiner.compute_metrics(acc)
                for combiner, acc in zip(self._combiners, children))

        combined_metrics = {}
        for combiner, acc in zip(self._combiners, children):
            for metric, value in combiner.compute_metrics(acc).items():
                if metric in combined_metrics:
                    raise Exception(
                        f"{metric} computed by {combiner} was already computed "
                        f"by another combiner")
                combined_metrics[metric] = value
        return _create_named_tuple_instance("MetricsTuple",
                                            tuple(combined_metrics.keys()),
                                            tuple(combined_metrics.values()))

    def metrics_names(self) -> List[str]:
        return list(self._metrics_to_compute)

    def explain_computation(self) -> ExplainComputationReport:
        return [combiner.explain_computation() for combiner in self._combiners]

    def expects_per_partition_sampling(self) -> bool:
        return any(c.expects_per_partition_sampling() for c in self._combiners)


def create_compound_combiner(
        params: aggregate_params.AggregateParams,
        budget_accountant: budget_accounting.BudgetAccountant
) -> CompoundCombiner:
    """Builds the CompoundCombiner for the requested metrics, requesting one
    budget per mechanism (reference :791-858).

    Each request is wrapped in observability.mechanism_label so the
    privacy-budget odometer's audit records carry the DP metric the
    mechanism serves (count/sum/...), not just its noise kind.
    """
    # Lazy import: combiners must stay importable without the runtime
    # package (the generic backends use them standalone).
    from pipelinedp_tpu.runtime import observability
    combiners = []
    mechanism_type = params.noise_kind.convert_to_mechanism_type()

    def request(metric_label: str):
        with observability.mechanism_label(metric_label):
            return budget_accountant.request_budget(
                mechanism_type, weight=params.budget_weight)

    if params.value_columns:
        return CompoundCombiner(_column_combiners(params, request),
                                return_named_tuple=True)
    if Metrics.VARIANCE in params.metrics:
        budget_variance = request('variance')
        metrics_to_compute = ['variance']
        if Metrics.MEAN in params.metrics:
            metrics_to_compute.append('mean')
        if Metrics.COUNT in params.metrics:
            metrics_to_compute.append('count')
        if Metrics.SUM in params.metrics:
            metrics_to_compute.append('sum')
        combiners.append(
            VarianceCombiner(CombinerParams(budget_variance, params),
                             metrics_to_compute))
    elif Metrics.MEAN in params.metrics:
        budget_count = request('count')
        budget_sum = request('sum')
        metrics_to_compute = ['mean']
        if Metrics.COUNT in params.metrics:
            metrics_to_compute.append('count')
        if Metrics.SUM in params.metrics:
            metrics_to_compute.append('sum')
        combiners.append(
            MeanCombiner(budget_count, budget_sum, params, metrics_to_compute))
    else:
        if Metrics.COUNT in params.metrics:
            combiners.append(CountCombiner(request('count'), params))
        if Metrics.SUM in params.metrics:
            combiners.append(SumCombiner(request('sum'), params))
    if Metrics.PRIVACY_ID_COUNT in params.metrics:
        combiners.append(
            PrivacyIdCountCombiner(request('privacy_id_count'), params))
    if Metrics.VECTOR_SUM in params.metrics:
        combiners.append(
            VectorSumCombiner(
                CombinerParams(request('vector_sum'), params)))

    percentiles_to_compute = [
        metric.parameter for metric in params.metrics if metric.is_percentile
    ]
    if percentiles_to_compute:
        combiners.append(
            QuantileCombiner(
                CombinerParams(request('percentile'), params),
                percentiles_to_compute))

    return CompoundCombiner(combiners, return_named_tuple=True)


def _column_combiners(params: aggregate_params.AggregateParams,
                      request: Callable) -> List[Combiner]:
    """The children of a several-columns aggregation, by the single-column
    rule above applied to each column: MEAN -> one mean combiner (also
    yielding the column's SUM where asked), SUM alone -> a sum combiner.
    COUNT is released once: by the first mean combiner's count mechanism,
    else by a count combiner of its own."""
    combiners = []
    count_wanted = Metrics.COUNT in params.metrics
    for column, spec in enumerate(params.value_columns):
        column_params = params.column_params(spec)
        if Metrics.MEAN in spec.metrics:
            metrics_to_compute = ['mean']
            if count_wanted:
                metrics_to_compute.append('count')
                count_wanted = False
            if Metrics.SUM in spec.metrics:
                metrics_to_compute.append('sum')
            inner = MeanCombiner(request(f'{spec.name}_count'),
                                 request(f'{spec.name}_sum'), column_params,
                                 metrics_to_compute)
        else:
            inner = SumCombiner(request(f'{spec.name}_sum'), column_params)
        combiners.append(ColumnCombiner(inner, column, spec.name))
    if count_wanted:
        combiners.insert(0, CountCombiner(request('count'), params))
    if Metrics.PRIVACY_ID_COUNT in params.metrics:
        combiners.append(
            PrivacyIdCountCombiner(request('privacy_id_count'), params))
    return combiners


def create_compound_combiner_with_custom_combiners(
        params: aggregate_params.AggregateParams,
        budget_accountant: budget_accounting.BudgetAccountant,
        custom_combiners: Iterable[CustomCombiner]) -> CompoundCombiner:
    for combiner in custom_combiners:
        params_copy = copy.copy(params)
        params_copy.custom_combiners = None
        combiner.set_aggregate_params(params_copy)
        combiner.request_budget(budget_accountant)
    return CompoundCombiner(custom_combiners, return_named_tuple=False)
