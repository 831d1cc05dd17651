"""Share of the traced window (%) in which no operation ran on a chip, the
mean over the chips."""
from bench.layer_read import idle_percent


def read(ctx):
    return idle_percent(ctx)
