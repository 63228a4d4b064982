"""Percent of the traced window in which no operation ran on the
device, averaged over the chips, the check's fetches left out."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
