"""Share of the traced window in which no operation runs on the device,
averaged over the cell's chips: 100 * (1 - busy / window)."""


def read(ctx):
    if ctx.trace.window_s <= 0 or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s(ctx.chips) / ctx.trace.window_s)
