"""Host time of a query's chunks in the partitioned executor, summed over
its chunks, per query (ms): the ``worker.host_ms`` counter, from a chunk's
start until its work returns (gather, pad, upload enqueue and launch)."""
from bench.layer_read import counter_per_query


def read(ctx):
    return counter_per_query(ctx, "worker.host_ms")
