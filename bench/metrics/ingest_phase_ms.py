"""ingest_phase_ms: mean TickReport.ingest_seconds over the window's
ticks (the program's fenced host-clock time of ingest and detect; on the
paged runtime the whole paging loop)."""


def read(ctx):
    xs = [r.ingest_s for r in ctx.log.in_window() if r.ingest_s is not None]
    if not xs:
        return None
    return sum(xs) / len(xs) * 1e3
