"""tick_ms: mean time of runtime.tick over the window's ticks, from the
benchmark's span around the call (host clock)."""


def read(ctx):
    ticks = ctx.log.in_window()
    if not ticks:
        return None
    return sum(r.end - r.start for r in ticks) / len(ticks) * 1e3
