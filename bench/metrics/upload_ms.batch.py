"""Time per query (ms) the monolithic executor spends in ``jax.upload``:
building the jitted program's input columns on the device."""
from bench.layer_read import span_ms_per_query


def read(ctx):
    return span_ms_per_query(ctx, ("jax.upload",))
