"""What chip_smoke.py and tests/test_chip_compile.py share: the
compile-cache rule, the standard aggregation spec and skewed test data.
The benchmark is perfbench/ (python3 -m perfbench.run); nothing here
measures anything.
"""
import os

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache():
    """THE persistent compile-cache rule of every launcher in the repo
    (chip_smoke.py, perfbench/run.py): where the environment
    sets JAX_COMPILATION_CACHE_DIR, JAX reads it itself and no
    directory is set in code; otherwise the cache lives at one fixed
    path inside the checkout, <repo>/.jax_cache (git-ignored). The
    path is part of the cache key, so it never moves. Sort-bearing
    programs cost minutes of TPU compile each (PERF.md), which is why
    a launcher that forgets this pays them on every run. Returns the
    directory in effect."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_REPO, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def build_spec(n_partitions, metrics=None, l0=4, linf=8, eps=1.0,
               noise_kind=None, private=True):
    """The standard aggregation spec — defaults to COUNT+SUM,
    Laplace, eps=1, private truncated-geometric selection (BASELINE
    configs 1/3 shape); `metrics`/`noise_kind`/`private` cover the other
    BASELINE config shapes (Gaussian + public partitions, compound).

    Returns (params, cfg, stds ndarray, (min_v, max_v, min_s, max_s, mid)).
    """
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import combiners, executor
    from pipelinedp_tpu.aggregate_params import MechanismType
    from pipelinedp_tpu.ops import selection_ops

    params = pdp.AggregateParams(
        metrics=metrics or [pdp.Metrics.COUNT, pdp.Metrics.SUM],
        noise_kind=noise_kind or pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=l0,
        max_contributions_per_partition=linf,
        min_value=0.0,
        max_value=5.0)
    accountant = pdp.NaiveBudgetAccountant(total_epsilon=eps,
                                           total_delta=1e-6)
    compound = combiners.create_compound_combiner(params, accountant)
    selection = None
    if private:
        budget = accountant.request_budget(MechanismType.GENERIC)
    accountant.compute_budgets()
    if private:
        selection = selection_ops.selection_params_from_host(
            params.partition_selection_strategy, budget.eps, budget.delta,
            params.max_partitions_contributed, None)
    cfg = executor.make_kernel_config(params, compound, n_partitions,
                                      private_selection=private,
                                      selection_params=selection)
    stds = np.asarray(executor.compute_noise_stds(compound, params))
    return params, cfg, stds, executor.kernel_scalars(params)


def zipfish_data(n, n_partitions, n_users=1_000_000, power=6.0, seed=5):
    """Host columnar data with exponentially-tilted partition popularity.

    power=6.0 concentrates rows in a heavy head with a long sparse tail
    across the full partition space (the large-P regime).
    """
    rng = np.random.default_rng(seed)
    pid = rng.integers(0, n_users, n).astype(np.int32)
    pk = (np.power(rng.random(n), power) * n_partitions).astype(np.int32)
    values = rng.uniform(0, 5, n)
    return pid, pk, values, np.ones(n, dtype=bool)
