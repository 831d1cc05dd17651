"""Host-to-device rate (GB/s) of ``jax.upload``: the bytes the window's
queries placed (``upload.bytes``, every placement) over the span's time.
On the mesh, the rate the chips' host links give together."""
from bench.layer_read import span_ms_per_query


def read(ctx):
    placed = sum(v for k, v in ctx.counters.items() if k.split("{")[0] == "upload.bytes")
    ms = span_ms_per_query(ctx, ("jax.upload",))
    if not placed or not ms:
        return None
    return placed / (1e-3 * ms * ctx.n_queries) / 1e9
