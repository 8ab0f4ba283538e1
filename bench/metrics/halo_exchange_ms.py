"""halo_exchange_ms: device time of the collective operations
(``collective-permute*``: the ring's halo rows; ``all-reduce*``: a
star's partials) inside each merge phase of the window (the host-clock
merge interval mapped onto the trace), averaged over the cell's chips,
per round, in ms. A cell on one chip, or a program that ships nothing
between chips, has none: the reader returns None."""
import re

COLLECTIVE = re.compile(r"^(collective-permute|all-reduce)")


def read(ctx):
    if ctx.trace is None or len(ctx.trace.devices) < 2:
        return None
    rounds = [r for r in ctx.log.in_window() if r.merge and r.merge_s]
    if not rounds:
        return None
    total_ns = 0.0
    for r in rounds:
        a, b = ctx.trace.to_ns(r.end - r.merge_s), ctx.trace.to_ns(r.end)
        total_ns += sum(min(e, b) - max(s, a) for evs in ctx.trace.devices
                        for n, s, e in evs if COLLECTIVE.match(n) and e > a and s < b)
    if total_ns <= 0:
        return None
    return total_ns / len(ctx.trace.devices) / len(rounds) / 1e6
