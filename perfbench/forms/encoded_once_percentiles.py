"""Input form `encoded_once_percentiles`: `encoded_once`'s job with the
upstream script's whole metric list. The raw ids are encoded ONCE, in
set-up (`columnar.encode_columns`), and every job is given that same host
`columnar.EncodedData` -> ONE `DPEngine.aggregate` on one chip with
COUNT / SUM / PRIVACY_ID_COUNT and the PERCENTILEs the guarantees name
(`percentile_<p>`: one quantile tree per partition, one budget) ->
{partition key: the released values in the order of the guarantees'
`metrics`}. The guarantees are those of the law
`bounded_laplace_geometric_quantiles`; the tree's height and branching
are the program's defaults, which the guarantees' `tree` states and this
form checks."""


def build_job(cell, config, columns):
    import jax
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import columnar
    from pipelinedp_tpu.ops import quantile_tree

    g = config["guarantees"]
    if g["noise"] != "laplace" or g["selection"] != "truncated_geometric":
        raise ValueError("encoded_once_percentiles knows Laplace noise and "
                         "truncated-geometric selection")
    if (g["tree"]["height"], g["tree"]["branching"]) != (
            quantile_tree.DEFAULT_TREE_HEIGHT,
            quantile_tree.DEFAULT_BRANCHING_FACTOR):
        raise ValueError("the guarantees' tree is not the program's")
    sums = {"count": pdp.Metrics.COUNT, "sum": pdp.Metrics.SUM,
            "privacy_id_count": pdp.Metrics.PRIVACY_ID_COUNT}
    released = tuple(g["metrics"])  # also the released row's attributes

    def metric(name):
        if name in sums:
            return sums[name]
        return pdp.Metrics.PERCENTILE(
            float(name[len("percentile_"):].replace("_", ".")))

    params = pdp.AggregateParams(
        metrics=[metric(name) for name in released],
        noise_kind=pdp.NoiseKind.LAPLACE,
        partition_selection_strategy=(
            pdp.PartitionSelectionStrategy.TRUNCATED_GEOMETRIC),
        max_partitions_contributed=g["l0"],
        max_contributions_per_partition=g["linf"],
        min_value=g["min_value"], max_value=g["max_value"])
    extractors = pdp.DataExtractors()  # pre-encoded: never consulted
    encoded = columnar.encode_columns(*columns)
    annotate = jax.profiler.TraceAnnotation

    def job(seed):
        accountant = pdp.NaiveBudgetAccountant(
            total_epsilon=g["epsilon"], total_delta=g["delta"])
        engine = pdp.DPEngine(
            accountant, pdp.TPUBackend(noise_seed=seed,
                                       numeric_mode=g["numeric_mode"]))
        with annotate("pb:aggregate"):
            result = engine.aggregate(encoded, params, extractors)
        with annotate("pb:budgets"):
            accountant.compute_budgets()
        with annotate("pb:materialise"):
            return {key: tuple(float(getattr(m, name)) for name in released)
                    for key, m in result}

    return job
