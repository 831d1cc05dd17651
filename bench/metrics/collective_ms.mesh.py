"""Device time per query (ms) of the collectives that combine or move data
between chips: the self time of every operation named for one (psum, pmax,
pmin, all-reduce, all-gather, all-to-all, collective-permute,
reduce-scatter), mean over the chips."""
import re

COLLECTIVE = re.compile(r"psum|pmax|pmin|all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter")


def read(ctx):
    dev = ctx.device
    if dev is None or not ctx.n_queries:
        return None
    return 1e3 * sum(s for op, s in dev.op_s.items() if COLLECTIVE.search(op)) / ctx.n_queries
