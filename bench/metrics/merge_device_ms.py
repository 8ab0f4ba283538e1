"""merge_device_ms: device-busy time inside each merge phase of the
window (from the host-clock merge interval mapped onto the trace),
averaged over the window's merge rounds."""


def read(ctx):
    if ctx.trace is None:
        return None
    rounds = [r for r in ctx.log.in_window() if r.merge and r.merge_s]
    if not rounds:
        return None
    busy = sum(ctx.trace.busy_between(r.end - r.merge_s, r.end) for r in rounds)
    return busy / len(rounds) * 1e3 if busy > 0 else None
