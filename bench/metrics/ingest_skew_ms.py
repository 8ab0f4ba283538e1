"""ingest_skew_ms: per window tick, the latest chip's last
``fleet_ingest_kernel`` end minus the earliest chip's (the straggler
chip the tick waits on), as the mean over the ticks in which every chip
of the cell ran the kernel, in ms (device trace, ticks' host-clock
intervals mapped onto it). One chip has no skew: the reader returns
None."""
KERNEL = "fleet_ingest_kernel"


def read(ctx):
    if ctx.trace is None or len(ctx.trace.devices) < 2:
        return None
    skews = []
    for r in ctx.log.in_window():
        a, b = ctx.trace.to_ns(r.start), ctx.trace.to_ns(r.end)
        last = [max((e for n, s, e in evs if n == KERNEL and a <= e <= b), default=None)
                for evs in ctx.trace.devices]
        if None not in last:
            skews.append(max(last) - min(last))
    if not skews:
        return None
    ctx.notes.append(f"ingest_skew_ms over {len(skews)} ticks, largest "
                     f"{max(skews) / 1e6:.3f} ms")
    return sum(skews) / len(skews) / 1e6
