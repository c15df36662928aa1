"""Input form `encoded_once_public`: rows of (privacy id, partition id, d
values) encoded ONCE, in set-up, against the configuration's PUBLIC
partitions (`columnar.encode_columns(..., public_partitions)`: rows outside
them carry pk = -1), and every job given that same host
`columnar.EncodedData` -> ONE `DPEngine.aggregate(public_partitions)` with
`AggregateParams.value_columns` (a clamp and a subset of SUM / MEAN per
column, COUNT once) on one chip -> {partition id: the released fields in
the order of the guarantees' `released`}. The guarantees are those of the
law `columns_laplace_public`."""


def build_job(cell, config, columns):
    import jax
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import columnar

    g = config["guarantees"]
    if g["noise"] != "laplace":
        raise ValueError("encoded_once_public knows Laplace noise")
    publics = list(range(int(g["public_partitions"])))
    encoded = columnar.encode_columns(*columns, public_partitions=publics)
    metrics = {"sum": pdp.Metrics.SUM, "mean": pdp.Metrics.MEAN}
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT],
        noise_kind=pdp.NoiseKind.LAPLACE,
        max_partitions_contributed=g["l0"],
        max_contributions_per_partition=g["linf"],
        value_columns=[
            pdp.ValueColumn(c["name"], c["min_value"], c["max_value"],
                            [metrics[m] for m in c["metrics"]])
            for c in g["columns"]])
    extractors = pdp.DataExtractors()  # pre-encoded: never consulted
    released = tuple(g["released"])
    annotate = jax.profiler.TraceAnnotation

    def job(seed):
        accountant = pdp.NaiveBudgetAccountant(
            total_epsilon=g["epsilon"], total_delta=g["delta"])
        engine = pdp.DPEngine(
            accountant, pdp.TPUBackend(noise_seed=seed,
                                       numeric_mode=g["numeric_mode"]))
        with annotate("pb:aggregate"):
            result = engine.aggregate(encoded, params, extractors,
                                      public_partitions=publics)
        with annotate("pb:budgets"):
            accountant.compute_budgets()
        with annotate("pb:materialise"):
            return {key: tuple(float(getattr(m, name)) for name in released)
                    for key, m in result}

    return job
