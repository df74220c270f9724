"""device_idle_share — share of the traced window in which no operation
ran on the device: 1 - (union of op intervals / window), averaged over
the chips used."""


def read(ctx):
    if ctx.trace is None or ctx.busy_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
