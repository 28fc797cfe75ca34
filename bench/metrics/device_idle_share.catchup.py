"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips: 1 - (union of the device's operation
intervals) / window. Layer: device."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
