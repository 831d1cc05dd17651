"""Time per query (ms) in ``densify``: device results to host rows, then
ORDER BY and LIMIT."""
from bench.layer_read import span_ms_per_query


def read(ctx):
    return span_ms_per_query(ctx, ("densify",))
