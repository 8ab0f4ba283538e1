"""The program's own spans (``repro.obs.trace``) inside the measured
window, for the per-layer readers that time a layer where its work
happens rather than from the benchmark's calls around it.

A program that records no spans (one older than its span ring) gives
none, and the readers return None. With a device trace, each reader
also notes the device-busy seconds under its spans, which joins the
program's spans to the device's time.
"""
from __future__ import annotations


def window(ctx, *names) -> list:
    """The window's closed spans named ``names``, oldest first; empty
    where the program records none."""
    try:
        from repro.obs.trace import spans_between
    except ImportError:
        return []
    w0, w1 = ctx.log.window
    return [s for s in spans_between(w0, w1) if s.name in names]


def by_seq(spans) -> dict:
    """Spans grouped by their tick or window number."""
    out = {}
    for s in spans:
        out.setdefault(s.seq, []).append(s)
    return out


def note(ctx, metric: str, spans) -> None:
    """Append, per span name, its count, host seconds and the device-busy
    seconds under it (traced runs only)."""
    if ctx.trace is None:
        return
    for name in sorted({s.name for s in spans}):
        mine = [s for s in spans if s.name == name]
        host = sum(s.seconds for s in mine)
        busy = sum(ctx.trace.busy_between(s.start, s.end) for s in mine)
        ctx.notes.append(f"{metric}: {name} x{len(mine)}, host {host:.6f} s, "
                         f"device busy {busy:.6f} s")
