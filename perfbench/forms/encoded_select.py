"""Input form `encoded_select`: the rows as ONE host `columnar.EncodedData`
of pre-encoded integer columns over the id spaces the configuration's
`encoded` states (as `encoded_columns` builds it; the value column rides
along unread) -> ONE `DPEngine.select_partitions` on one chip ->
{released partition key: ()}: a key-only release, no value.

The job is written here (`traffic.engine_job` is `DPEngine.aggregate`'s):
`NaiveBudgetAccountant(epsilon, delta)` — a job's one mechanism, the
selection, gets the whole of both — `TPUBackend(noise_seed=the job's)`,
`SelectPartitionsParams(max_partitions_contributed=l0)` with the default
strategy, truncated geometric. The guarantees are those of the law
`selection_geometric`."""


def build_job(cell, config, columns):
    import jax
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import columnar

    g = config["guarantees"]
    if g["selection"] != "truncated_geometric":
        raise ValueError("encoded_select knows truncated-geometric selection")
    spaces = config["encoded"]  # the id spaces the columns index
    pid, pk, values = columns
    encoded = columnar.EncodedData(
        pid=pid, pk=pk, values=values,
        partition_vocab=range(spaces["partitions"]),
        n_privacy_ids=spaces["privacy_ids"])
    params = pdp.SelectPartitionsParams(
        max_partitions_contributed=g["l0"],
        partition_selection_strategy=(
            pdp.PartitionSelectionStrategy.TRUNCATED_GEOMETRIC))
    extractors = pdp.DataExtractors()  # pre-encoded: never consulted
    annotate = jax.profiler.TraceAnnotation

    def job(seed):
        accountant = pdp.NaiveBudgetAccountant(
            total_epsilon=g["epsilon"], total_delta=g["delta"])
        engine = pdp.DPEngine(accountant, pdp.TPUBackend(noise_seed=seed))
        with annotate("pb:aggregate"):
            keys = engine.select_partitions(encoded, params, extractors)
        with annotate("pb:budgets"):
            accountant.compute_budgets()
        with annotate("pb:materialise"):
            return dict.fromkeys(keys, ())

    return job
