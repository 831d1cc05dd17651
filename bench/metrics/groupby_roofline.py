"""Share (%) of the GROUP BY's roofline: the least time its needed work
takes at the chip's peaks, over the device time of the window's compute
operations (transfers between host and device left out).  Needed work is
defined on the aggregation itself (``bench/work.py``), so the share reads
the same whatever implements the GROUP BY."""
from bench.work import groupby_seconds


def read(ctx):
    dev = ctx.device
    if dev is None or dev.compute_s <= 0 or not ctx.peaks:
        return None
    need = 0.0
    memo = {}
    for req in ctx.executed:
        name = req.template["name"]
        if name not in memo:
            memo[name] = groupby_seconds(req.template["query"], ctx.tables, ctx.peaks)
        need += memo[name] or 0.0
    if need <= 0:
        return None
    return 100.0 * need / dev.compute_s
