"""Input form `encoded_once`: the raw ids are encoded ONCE, in set-up
(`columnar.encode_columns`: one hash factorisation of each key column), and
every job is given that same host `columnar.EncodedData` ->
`DPEngine.aggregate` on one chip. No ingest inside a job: the dense route's
staging of host columns (pad to the row bucket, narrow, upload), the
release kernel and the decode are the whole job."""

from perfbench import traffic


def build_job(cell, config, columns):
    from pipelinedp_tpu import columnar

    encoded = columnar.encode_columns(*columns)
    return traffic.engine_job(config["guarantees"], source=lambda: encoded)
