"""Input form `chunks_host_mesh`: `chunks_host`'s job on a mesh over the
cell's chips — host chunks of `traffic.chunk_rows` raw rows ->
`ChunkSource(encode_mode="host")` -> `DPEngine.aggregate` on
`TPUBackend(mesh=make_mesh(<the cell's chips>), reshard=traffic.reshard)`:
the ingested rows are co-located by privacy id over the mesh
(parallel/reshard.py), bounded and reduced per shard, combined by one psum
(parallel/sharded.py). The mesh is built once, in set-up; a rehearsal takes
as many of the cell's chips as the CPU shows devices."""

from perfbench import traffic


def build_job(cell, config, columns):
    import jax
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu.parallel import make_mesh

    g, spec = config["guarantees"], cell["traffic"]
    mesh = make_mesh(devices=jax.devices()[:int(cell["chips"])])
    chunks = traffic.chunked(columns, int(spec["chunk_rows"]))

    def backend(seed):
        return pdp.TPUBackend(mesh=mesh, reshard=spec["reshard"],
                              noise_seed=seed,
                              numeric_mode=g["numeric_mode"])

    return traffic.engine_job(
        g, source=lambda: pdp.ChunkSource(chunks, encode_mode="host"),
        backend=backend)
