"""The one traffic driver: a closed-loop batch-job loop.

An analyst's batch job, again and again: raw rows in -> `DPEngine` ->
`compute_budgets` -> the release materialised as a Python dict. One job in
flight; the next starts when the last has returned. The window opens at the
first job's start and closes when the job in flight at `seconds` returns:
no job is cut and none is dropped from the count.

What a job is comes from the cell's file (`traffic` there):
  input_form  chunks_host      host chunks -> ChunkSource(encode_mode="host")
              encoded_columns  pre-encoded integer columns (EncodedData)
  chunk_rows  rows per host chunk (chunks_host)
and the configuration's `guarantees` give the engine's parameters,
`metrics` among them. A new cell of these forms is a new file, not new
code; a new form or entry point comes with the cell that runs it.

Every job gets a noise seed of its own, derived from --seed and the job's
index, so the window's releases are independent draws over the same rows.
Each job and each call it makes is wrapped in a `jax.profiler.
TraceAnnotation` (`pb:job`, `pb:source`, `pb:aggregate`, `pb:budgets`,
`pb:materialise`): free when no trace is being taken, and what the traced
run attributes the device's idle gaps to.
"""

import math
import time

INPUT_FORMS = ("chunks_host", "encoded_columns")


def noise_seed(seed, index):
    """A 63-bit seed per (run seed, job index); job −1 is the warm-up."""
    return (int(seed) * 1_000_003 + int(index) + 1) % (1 << 63)


def chunked(columns, chunk_rows):
    n = len(columns[0])
    return [tuple(c[i:i + chunk_rows] for c in columns)
            for i in range(0, n, chunk_rows)]


def build_job(cell, config, columns):
    """job(noise_seed) -> the release, a dict {partition key: one value
    per metric of the configuration's `metrics`, in that order}."""
    import jax
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import columnar

    traffic, g = cell["traffic"], config["guarantees"]
    form = traffic["input_form"]
    if form not in INPUT_FORMS:
        raise ValueError(f"cell {cell['name']}: input form {form!r} not in "
                         f"{INPUT_FORMS}")
    if g["noise"] != "laplace" or g["selection"] != "truncated_geometric":
        raise ValueError("guarantees: this driver knows Laplace noise and "
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

    if form == "encoded_columns":
        encoded = config["encoded"]  # the id spaces the columns index
        pid, pk, values = columns

        def source():
            return columnar.EncodedData(
                pid=pid, pk=pk, values=values,
                partition_vocab=range(encoded["partitions"]),
                n_privacy_ids=encoded["privacy_ids"])
    else:
        chunks = chunked(columns, int(traffic["chunk_rows"]))

        def source():
            return pdp.ChunkSource(chunks, encode_mode="host")

    def job(seed):
        with annotate("pb:job"):
            accountant = pdp.NaiveBudgetAccountant(
                total_epsilon=g["epsilon"], total_delta=g["delta"])
            engine = pdp.DPEngine(
                accountant, pdp.TPUBackend(noise_seed=seed,
                                           numeric_mode=g["numeric_mode"]))
            with annotate("pb:source"):
                rows = source()
            with annotate("pb:aggregate"):
                result = engine.aggregate(rows, params, extractors)
            with annotate("pb:budgets"):
                accountant.compute_budgets()
            with annotate("pb:materialise"):
                return {key: tuple(float(getattr(m, name))
                                   for name in released)
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
