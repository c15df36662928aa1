"""Vectorized (device) partition selection.

Evaluates the same closed forms as partition_selection.py, but in jnp over
the whole partition axis at once, inside the fused aggregation program. The
host precomputes a handful of strategy scalars (SelectionParams); the device
computes keep probabilities for every partition and draws the Bernoulli keep
decisions — replacing the reference's per-partition C++ `should_keep` calls
(dp_engine.py:345-348).
"""

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from pipelinedp_tpu import partition_selection as host_ps
from pipelinedp_tpu.aggregate_params import PartitionSelectionStrategy


@dataclass(frozen=True)
class SelectionParams:
    """Host-precomputed scalars driving the device selection kernel.

    kind: 0 = truncated geometric, 1 = laplace thresholding,
          2 = gaussian thresholding.
    """
    kind: int
    pre_shift: int  # pre_threshold - 1 (0 if unset)
    # Truncated geometric:
    eps1: float = 0.0
    delta1: float = 0.0
    n_cross: int = 0
    pi_cross: float = 0.0
    # Thresholding:
    threshold: float = 0.0
    scale: float = 1.0  # Laplace b or Gaussian sigma


def selection_params_from_host(
        strategy: PartitionSelectionStrategy, eps: float, delta: float,
        max_partitions_contributed: int,
        pre_threshold: Optional[int]) -> SelectionParams:
    """Builds SelectionParams from the host strategy object."""
    selector = host_ps.create_partition_selection_strategy(
        strategy, eps, delta, max_partitions_contributed, pre_threshold)
    pre_shift = (pre_threshold - 1) if pre_threshold else 0
    if isinstance(selector, host_ps.TruncatedGeometricPartitionSelector):
        return SelectionParams(kind=0,
                               pre_shift=pre_shift,
                               eps1=selector._eps1,
                               delta1=selector._delta1,
                               n_cross=selector._n_cross,
                               pi_cross=selector._pi_cross)
    if isinstance(selector, host_ps.LaplaceThresholdingPartitionSelector):
        return SelectionParams(kind=1,
                               pre_shift=pre_shift,
                               threshold=selector.threshold,
                               scale=selector._b)
    if isinstance(selector, host_ps.GaussianThresholdingPartitionSelector):
        return SelectionParams(kind=2,
                               pre_shift=pre_shift,
                               threshold=selector.threshold,
                               scale=selector.sigma)
    raise ValueError(f"Unknown selector {type(selector)}")


def keep_probabilities(counts: jnp.ndarray,
                       params: SelectionParams) -> jnp.ndarray:
    """probability_of_keep for an integer array of privacy-id counts.

    Mirrors partition_selection.PartitionSelector.probability_of_keep_vec.
    `params` fields are static Python floats (hashable dataclass), so each
    strategy configuration compiles once.
    """
    n = counts.astype(jnp.float64 if jax.config.jax_enable_x64 else
                      jnp.float32) - params.pre_shift
    if params.kind == 0:
        eps1, delta1 = params.eps1, params.delta1
        n_cross, pi_cross = params.n_cross, params.pi_cross
        n_eff = jnp.maximum(n, 1.0)
        # Phase 1 in log space (overflow-safe for huge eps):
        n1 = jnp.minimum(n_eff, n_cross)
        log_pi1 = (math.log(delta1) + (n1 - 1.0) * eps1 +
                   jnp.log1p(-jnp.exp(-n1 * eps1)) -
                   math.log1p(-math.exp(-eps1)))
        pi1 = jnp.exp(jnp.minimum(log_pi1, 0.0))
        k = jnp.maximum(n_eff - n_cross, 0.0)
        decay = jnp.exp(-k * eps1)
        geo = math.exp(-eps1) * (1.0 - decay) / (1.0 - math.exp(-eps1)) \
            if eps1 < 700 else 0.0
        q = decay * (1.0 - pi_cross) - delta1 * geo
        pi2 = 1.0 - jnp.maximum(q, 0.0)
        probs = jnp.clip(jnp.where(n_eff <= n_cross, pi1, pi2), 0.0, 1.0)
    elif params.kind == 1:
        z = (n - params.threshold) / params.scale
        probs = jnp.where(z >= 0, 1.0 - 0.5 * jnp.exp(-jnp.abs(z)),
                          0.5 * jnp.exp(-jnp.abs(z)))
    elif params.kind == 2:
        z = (params.threshold - n) / params.scale
        probs = 0.5 * jax.scipy.special.erfc(z / math.sqrt(2))
    else:
        raise ValueError(f"Unknown selection kind {params.kind}")
    return jnp.where(n <= 0, 0.0, probs)


# A float32 uniform draw is k * 2^-23 with k uniform over [0, 2^23).
_F32_UNIFORM_CELLS = float(1 << 23)


def keep_from_uniforms(u: jnp.ndarray, tie: jnp.ndarray,
                       probs: jnp.ndarray) -> jnp.ndarray:
    """Bernoulli(probs) from two float32 uniform draws.

    A float32 uniform takes only the 2^23 values k * 2^-23, so `u < p`
    keeps with probability ceil(p * 2^23) / 2^23: a probability in the
    delta tail is rounded UP to the next multiple of 1.19e-7 (2.5e-7, what
    truncated-geometric selection gives a one-user partition at delta =
    1e-6 and l0 = 4, becomes 3.58e-7: measured on the chip over the AOL
    log's ten million near-singleton queries, PERF.md section 6, PR 37) —
    a delta the budget does not hold. So the one cell of width 2^-23 that
    contains p is split by `tie`: below the cell keep, above it drop,
    inside it keep with probability frac(p * 2^23). Every decision `u < p`
    made stays as it was but a share 2^-23 of them, and P(keep) is p to
    within 2^-46.
    """
    scaled = probs * _F32_UNIFORM_CELLS  # exact: a power of two
    cell = jnp.floor(scaled)
    k = u * _F32_UNIFORM_CELLS  # the integer k, exactly
    return (k < cell) | ((k == cell) & (tie < scaled - cell))


def sample_keep_decisions(key: jax.Array, counts: jnp.ndarray,
                          params: SelectionParams) -> jnp.ndarray:
    """Bernoulli keep decision per partition. A float64 draw (x64 on)
    resolves any keep probability a budget gives; a float32 one (the
    chip) takes a second draw for the cell that holds it
    (keep_from_uniforms)."""
    probs = keep_probabilities(counts, params)
    u = jax.random.uniform(key, counts.shape)
    if u.dtype != jnp.float32:
        return u < probs
    tie = jax.random.uniform(jax.random.fold_in(key, 1), counts.shape)
    return keep_from_uniforms(u, tie, probs)
