"""Share of the traced window in which no operation ran on the device, in
%, averaged over the chips: 100 * (1 - busy / window).  Serves every
split of the metric (``device_idle.analyze``, ``device_idle.newton``)."""


def read(ctx):
    r = ctx.reduction
    return 100.0 * (1.0 - r.busy_mean_s / r.window_s)
