"""page_copy_ms: host paging time of a paged tick, in ms: per tick, the
summed ``page.stage`` (window slice and page puts) and ``page.store``
(page back to the host arena) spans of its pages, averaged over the
window's ticks (program spans, host clock). The wait for each page's
ingest (``page.wait``) is left out."""
from bench import spans


def read(ctx):
    got = spans.window(ctx, "page.stage", "page.store", "page.wait")
    copies = spans.by_seq(s for s in got if s.name != "page.wait")
    if not copies:
        return None
    spans.note(ctx, "page_copy_ms", got)
    per_tick = [sum(s.seconds for s in ss) for ss in copies.values()]
    return sum(per_tick) / len(per_tick) * 1e3
