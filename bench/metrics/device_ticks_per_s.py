"""device_ticks_per_s: devices x ticks completed in the window, over the
time of those ticks (host clock). The window is whole merge cycles."""


def read(ctx):
    ticks = ctx.log.in_window()
    if not ticks:
        return None
    span = ticks[-1].end - ticks[0].start
    return ctx.cell.config["n_devices"] * len(ticks) / span
