"""Share (%) of the GROUP BY's roofline on all the chips traced: the
``groupby_roofline`` share (one chip's peaks over the mean chip's compute
time) over the number of chips, since they share the work.  It reads the
same whatever implements the GROUP BY."""
from bench import spec


def read(ctx):
    one_chip = spec.metric_reader("groupby_roofline")(ctx)
    if one_chip is None:
        return None
    return one_chip / ctx.device.n_devices
