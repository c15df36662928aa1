"""Input form `chunks_hash_device`: `chunks_host`'s job with the keys
encoded on the device — host chunks of `traffic.chunk_rows` raw rows ->
`ChunkSource(encode_mode="hash_device")` -> `DPEngine.aggregate` on one
chip (workers hash the raw keys, the device gives them their codes, the
partition keys are decoded at the kept indices only).

Where the configuration has a `text` block (`keys-1e7-strings`), the
generator's integer key ranks become query strings in set-up
(perfbench/query_text.py: one str per distinct rank, the rows' column an
object array of them), the job is handed the text, and each released
string is mapped back to its rank by the bijection's exact inverse before
the comparison reads it: a string no rank gives lands outside [0, keys),
where `unknown_keys` counts it."""

from perfbench import query_text, traffic


def build_job(cell, config, columns):
    import numpy as np
    import pipelinedp_tpu as pdp

    spec = config.get("text")
    text = None
    if spec is not None:
        pid, pk, values = columns
        text = query_text.QueryText(spec, config["generator"]["args"]["keys"])
        columns = (pid, text.strings()[pk], values)
    chunks = traffic.chunked(columns, int(cell["traffic"]["chunk_rows"]))
    job = traffic.engine_job(
        config["guarantees"],
        source=lambda: pdp.ChunkSource(chunks, encode_mode="hash_device"))
    if text is None:
        return job

    def ranked(seed):
        release = job(seed)
        ranks = text.ranks(release)
        # Each string no rank gives keeps a key of its own past the ranks.
        lost = ranks < 0
        ranks[lost] = text.keys + np.arange(int(lost.sum()))
        return dict(zip(ranks.tolist(), release.values()))

    return ranked
