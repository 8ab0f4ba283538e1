"""window_put_ms: mean ``tick.put`` span over the window's ticks, in ms:
the resident tick's window and operands copied to the device, fenced
(program span, host clock)."""
from bench import spans


def read(ctx):
    got = spans.window(ctx, "tick.put")
    if not got:
        return None
    spans.note(ctx, "window_put_ms", got)
    return sum(s.seconds for s in got) / len(got) * 1e3
