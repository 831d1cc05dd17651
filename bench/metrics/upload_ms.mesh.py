"""Time per query (ms) the monolithic executor spends in ``jax.upload`` on
the mesh: placing the table's columns on the chips as row shards, each
chip's rows over its own host link."""
from bench.layer_read import span_ms_per_query


def read(ctx):
    return span_ms_per_query(ctx, ("jax.upload",))
