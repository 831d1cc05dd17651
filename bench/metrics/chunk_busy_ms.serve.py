"""Worker time the partitioned executor spent on a query's chunks, summed
over its chunks, per query (ms): the ``worker.busy_ms`` counter."""
from bench.layer_read import counter_per_query


def read(ctx):
    return counter_per_query(ctx, "worker.busy_ms")
