"""Frontend and planner time per query (ms): the ``sql.parse`` or
``mr.translate``, ``canonicalize``, ``dispatch.lookup`` and ``optimize``
spans.  On the warm path only ``dispatch.lookup`` runs."""
from bench.layer_read import span_ms_per_query


def read(ctx):
    return span_ms_per_query(ctx, ("sql.parse", "mr.translate", "canonicalize",
                                   "dispatch.lookup", "optimize"))
