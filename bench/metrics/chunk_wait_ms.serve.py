"""Time chunks waited in the shared chunk queue (``SharedChunkPool``), summed
over a query's chunks, per query (ms): the ``queue.wait_ms`` counter."""
from bench.layer_read import counter_per_query


def read(ctx):
    return counter_per_query(ctx, "queue.wait_ms")
