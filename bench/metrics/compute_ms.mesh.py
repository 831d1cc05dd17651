"""Time per query (ms) in ``jax.compute`` on the mesh: the jitted program
over every chip's shard, combines included, up to its
``block_until_ready`` (which a traced session adds)."""
from bench.layer_read import span_ms_per_query


def read(ctx):
    return span_ms_per_query(ctx, ("jax.compute",))
