"""Input form `encoded_columns`: the rows as ONE `columnar.EncodedData` of
pre-encoded integer columns over the id spaces the configuration's
`encoded` states -> `DPEngine.aggregate` on one chip (no ingest encode)."""

from perfbench import traffic


def build_job(cell, config, columns):
    from pipelinedp_tpu import columnar

    encoded = config["encoded"]  # the id spaces the columns index
    pid, pk, values = columns

    def source():
        return columnar.EncodedData(
            pid=pid, pk=pk, values=values,
            partition_vocab=range(encoded["partitions"]),
            n_privacy_ids=encoded["privacy_ids"])

    return traffic.engine_job(config["guarantees"], source)
