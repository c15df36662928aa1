"""Input form `chunks_host`: the rows as host chunks of `traffic.chunk_rows`
raw rows -> `ChunkSource(encode_mode="host")` -> `DPEngine.aggregate` on one
chip (ingest encodes the raw ids on the host and streams them up)."""

from perfbench import traffic


def build_job(cell, config, columns):
    import pipelinedp_tpu as pdp

    chunks = traffic.chunked(columns, int(cell["traffic"]["chunk_rows"]))
    return traffic.engine_job(
        config["guarantees"],
        source=lambda: pdp.ChunkSource(chunks, encode_mode="host"))
