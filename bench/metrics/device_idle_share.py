"""device_idle_share: 1 - (union of the device's operation intervals
over the traced window), in %."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0 or ctx.trace.device_events == 0:
        return None
    return (1.0 - ctx.trace.busy_s / ctx.trace.window_s) * 100.0
