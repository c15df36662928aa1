"""pipelinedp-tpu: a TPU-native framework for differentially-private
aggregation over large keyed datasets.

Same capability surface as PipelineDP (reference: pipeline_dp/__init__.py),
re-designed TPU-first: the aggregation hot path (contribution bounding,
per-partition combining, partition selection, noise) runs as one fused
JAX/XLA program over columnar sharded arrays; budget accounting and report
generation stay host-side.
"""

from pipelinedp_tpu.aggregate_params import (
    AggregateParams,
    CalculatePrivateContributionBoundsParams,
    CountParams,
    MeanParams,
    Metric,
    Metrics,
    MechanismType,
    NoiseKind,
    NormKind,
    PartitionSelectionStrategy,
    PrivacyIdCountParams,
    PrivateContributionBounds,
    SelectPartitionsParams,
    SumParams,
    ValueColumn,
    VarianceParams,
)
from pipelinedp_tpu.budget_accounting import (
    Budget,
    BudgetAccountant,
    MechanismSpec,
    NaiveBudgetAccountant,
    PLDBudgetAccountant,
)
from pipelinedp_tpu.data_extractors import (
    DataExtractors,
    MultiValueDataExtractors,
    PreAggregateExtractors,
)
from pipelinedp_tpu.report_generator import ExplainComputationReport
from pipelinedp_tpu.combiners import Combiner, CustomCombiner
from pipelinedp_tpu.dp_engine import DPEngine
from pipelinedp_tpu.private_collection import (
    CombinePerKeyParams,
    PrivateCollection,
    PrivateCombineFn,
    make_private,
)
from pipelinedp_tpu.pipeline_backend import (
    LocalBackend,
    MultiProcLocalBackend,
    PipelineBackend,
    TPUBackend,
    register_annotator,
    Annotator,
)
# The chunked streaming entry for DPEngine.aggregate/select_partitions:
# wrap an iterable of (pid_raw, pk_raw, values) column chunks and the
# executor streams it through the device-resident pipeline
# (runtime/pipeline.py) under the backend's encode_threads /
# pipeline_depth knobs.
from pipelinedp_tpu.runtime.pipeline import ChunkSource
# Raised (instead of silently merging two partitions) when the
# hash-device encode mode detects a 64-bit key-hash collision and the
# chunk source cannot be re-iterated for the exact-encoder fallback.
from pipelinedp_tpu.device_encode import HashCollisionError

# Beam/Spark backends exist only when the corresponding framework is
# importable (reference exports them unconditionally from
# pipeline_dp/__init__.py:36-39 because it hard-depends on both).
from pipelinedp_tpu import pipeline_backend as _pb

if hasattr(_pb, 'BeamBackend'):
    from pipelinedp_tpu.pipeline_backend import BeamBackend
if hasattr(_pb, 'SparkRDDBackend'):
    from pipelinedp_tpu.pipeline_backend import SparkRDDBackend
del _pb

__version__ = '0.1.0'
