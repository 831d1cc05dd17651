"""Mean wait (ms) for an admission slot in ``QueryServer``: the sum of
``serve.block_ms`` over the window divided by ``serve.admitted``."""


def read(ctx):
    admitted = ctx.counters.get("serve.admitted", 0.0)
    if not admitted:
        return None
    return ctx.hists.get("serve.block_ms", (0.0, 0.0))[1] / admitted
