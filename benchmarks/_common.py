"""Shared harness for the benchmark scripts: spec construction + data.

Call path_setup() before importing pipelinedp_tpu. `JAX_PLATFORMS=cpu`
in the environment runs a script CPU-only; unset, JAX uses the attached
accelerator.
"""
import os
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def path_setup():
    sys.path.insert(0, _REPO)
    enable_compile_cache()


def enable_compile_cache():
    """THE persistent compile-cache rule of every launcher in the repo
    (chip_smoke.py, bench.py, benchmarks/*.py): where the environment
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


def device_stamp():
    """platform / device_kind / device count, as JAX reports them — the
    keys every printed result carries so a CPU run can never be read as
    a chip run."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def null_roundtrip(reps=3):
    """Min-of-`reps` timing of one dispatch + completion wait with no
    real compute — the per-dispatch floor to read sub-100 ms phase
    timings against."""
    import time

    import jax
    import jax.numpy as jnp
    null = jax.jit(lambda x: x + 1.0)
    x = jnp.float32(0.0)
    jax.block_until_ready(null(x))  # compile outside the timed samples
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(null(x))
        best = min(best, time.perf_counter() - t0)
    return best


def build_spec(n_partitions, metrics=None, l0=4, linf=8, eps=1.0,
               noise_kind=None, private=True):
    """The standard bench aggregation spec — defaults to COUNT+SUM,
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


def build_selection(params, eps=1.0, delta=1e-6):
    """Standalone-selection spec (whole budget on selection) shared by
    bench.py and bench_large_p.py so their kept counts stay comparable."""
    from pipelinedp_tpu.ops import selection_ops
    return selection_ops.selection_params_from_host(
        params.partition_selection_strategy, eps, delta,
        params.max_partitions_contributed, None)


def zipfish_data(n, n_partitions, n_users=1_000_000, power=6.0, seed=5):
    """Host columnar data with exponentially-tilted partition popularity.

    power=6.0 concentrates rows in a heavy head with a long sparse tail
    across the full partition space (the large-P regime); the dense-kernel
    profile uses power=3.0 over its small P.
    """
    rng = np.random.default_rng(seed)
    pid = rng.integers(0, n_users, n).astype(np.int32)
    pk = (np.power(rng.random(n), power) * n_partitions).astype(np.int32)
    values = rng.uniform(0, 5, n)
    return pid, pk, values, np.ones(n, dtype=bool)
