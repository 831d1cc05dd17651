"""Time a query's chunks waited in ``jax.block_until_ready`` for their
device results, summed over its chunks, per query (ms): the
``worker.ready_ms`` counter."""
from bench.layer_read import counter_per_query


def read(ctx):
    return counter_per_query(ctx, "worker.ready_ms")
