"""ingress_host_ms: the front-end's host time per window tick on the
event loop, in ms: the window's summed admission time (``admit_s`` on
its ``ingress.close``), cutting the window (``ingress.close``) and
resolving its acks (``ingress.complete``), averaged over the windows
whose close and completion both lie in the measured window (program
spans, host clock)."""
from bench import spans


def read(ctx):
    got = spans.window(ctx, "ingress.close", "ingress.queued", "ingress.complete")
    per_window = []
    for ss in spans.by_seq(got).values():
        closes = [s for s in ss if s.name == "ingress.close"]
        completes = [s for s in ss if s.name == "ingress.complete"]
        if not completes or not any("admit_s" in s.attrs for s in closes):
            continue
        per_window.append(sum(s.attrs.get("admit_s", 0.0) + s.seconds for s in closes)
                          + sum(s.seconds for s in completes))
    if not per_window:
        return None
    spans.note(ctx, "ingress_host_ms", got)
    return sum(per_window) / len(per_window) * 1e3
