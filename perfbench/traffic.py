"""The one traffic driver: a closed-loop batch-job loop.

An analyst's batch job, again and again: raw rows in -> the release
materialised as a Python dict. One job in flight; the next starts when the
last has returned. The window opens at the first job's start and closes
when the job in flight at `seconds` returns: no job is cut and none is
dropped from the count.

What a job IS comes from a file found by name: the cell's
`traffic.input_form` names perfbench/forms/<input_form>.py, whose

    build_job(cell, config, columns) -> job(noise_seed) -> the release,
        a dict {partition key: tuple of released values}

builds the job from the cell's `traffic` parameters and the
configuration's `guarantees`. A form builds the job; it never times it.
What stays here, out of a form's hands: the closed loop, the window's start
and end, each job's noise seed, the `pb:job` annotation around the whole
job, the failed-job rules and the count of programs built
(run.ProgramsBuilt).

Every job gets a noise seed of its own, derived from --seed and the job's
index, so the window's releases are independent draws over the same rows.
Each job, and (in `engine_job`) each call it makes, is wrapped in a
`jax.profiler.TraceAnnotation` (`pb:job`, `pb:source`, `pb:aggregate`,
`pb:budgets`, `pb:materialise`): free when no trace is being taken, and
what the traced run attributes the device's idle gaps to.
"""

import math
import time

import perfbench


def noise_seed(seed, index):
    """A 63-bit seed per (run seed, job index); job −1 is the warm-up."""
    return (int(seed) * 1_000_003 + int(index) + 1) % (1 << 63)


def chunked(columns, chunk_rows):
    n = len(columns[0])
    return [tuple(c[i:i + chunk_rows] for c in columns)
            for i in range(0, n, chunk_rows)]


def build_job(cell, config, columns):
    """The cell's job, built by the form its `traffic.input_form` names,
    inside the `pb:job` annotation the traced run's window is made of."""
    import jax

    form = perfbench.find("forms", cell["traffic"]["input_form"])
    inner = form.build_job(cell, config, columns)

    def job(seed):
        with jax.profiler.TraceAnnotation("pb:job"):
            return inner(seed)

    return job


def engine_job(g, source, backend=None):
    """A helper for forms whose job is ONE `DPEngine.aggregate` over rows of
    (privacy id, partition key, value) under guarantees of the law
    `bounded_laplace_geometric`: job(noise_seed) -> {partition key: one
    value per metric of g["metrics"], in that order}.

    `source()` gives the job's input anew for every job; `backend(seed)`
    the backend it runs on (default: `TPUBackend(noise_seed=seed,
    numeric_mode=g["numeric_mode"])`, one chip)."""
    import jax
    import pipelinedp_tpu as pdp

    if g["noise"] != "laplace" or g["selection"] != "truncated_geometric":
        raise ValueError("engine_job knows Laplace noise and "
                         "truncated-geometric selection")
    metrics = {"count": pdp.Metrics.COUNT, "sum": pdp.Metrics.SUM,
               "privacy_id_count": pdp.Metrics.PRIVACY_ID_COUNT}
    released = tuple(g["metrics"])  # also the released row's attributes
    params = pdp.AggregateParams(
        metrics=[metrics[m] for m in released],
        noise_kind=pdp.NoiseKind.LAPLACE,
        partition_selection_strategy=(
            pdp.PartitionSelectionStrategy.TRUNCATED_GEOMETRIC),
        max_partitions_contributed=g["l0"],
        max_contributions_per_partition=g["linf"],
        min_value=g["min_value"], max_value=g["max_value"])
    extractors = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                    partition_extractor=lambda r: r[1],
                                    value_extractor=lambda r: r[2])
    annotate = jax.profiler.TraceAnnotation
    if backend is None:
        def backend(seed):
            return pdp.TPUBackend(noise_seed=seed,
                                  numeric_mode=g["numeric_mode"])

    def job(seed):
        accountant = pdp.NaiveBudgetAccountant(
            total_epsilon=g["epsilon"], total_delta=g["delta"])
        engine = pdp.DPEngine(accountant, backend(seed))
        with annotate("pb:source"):
            rows = source()
        with annotate("pb:aggregate"):
            result = engine.aggregate(rows, params, extractors)
        with annotate("pb:budgets"):
            accountant.compute_budgets()
        with annotate("pb:materialise"):
            return {key: tuple(float(getattr(m, name)) for name in released)
                    for key, m in result}

    return job


def closed_loop(job, seed, seconds, rows_per_job, built, small_allowed,
                on_job_start=None):
    """Runs jobs back to back until the one in flight at `seconds` returns.
    `built` is the benchmark's running count of programs built in the
    process (run.ProgramsBuilt: `.large` and `.small`); a job that raised,
    released nothing or a non-finite value, or during which a large
    program was built or loaded, or more than `small_allowed` small ones
    were built, is `failed`. Returns the jobs' records and the window's
    (start, end) on time.perf_counter."""
    records = []
    window_start = time.perf_counter()
    while True:
        index = len(records)
        if on_job_start is not None:
            on_job_start(index)
        large_before, small_before = built.large, built.small
        start = time.perf_counter()
        release, error = None, None
        try:
            release = job(noise_seed(seed, index))
        except Exception as e:  # noqa: BLE001 - a job that raises is counted as failed and shown; the window goes on
            error = repr(e)
        end = time.perf_counter()
        large = built.large - large_before
        small = built.small - small_before
        finite = bool(release) and all(
            math.isfinite(v) for row in release.values() for v in row)
        records.append({"index": index, "start": start, "end": end,
                        "rows": rows_per_job, "release": release,
                        "error": error, "programs_built": large,
                        "small_programs_built": small,
                        "failed": error is not None or not finite
                        or large > 0 or small > small_allowed})
        if end - window_start >= seconds:
            return records, (window_start, end)
