"""window_fill: samples served per tick over the tick's capacity
(devices x batch), averaged over the window's ticks, in %. Counted from
the acks' tick numbers."""
import numpy as np


def read(ctx):
    req = ctx.log.requests
    ticks = ctx.log.in_window()
    if req is None or not ticks:
        return None
    ok = req["status"] == "ok"
    served = np.bincount(req["tick"][ok], minlength=ticks[-1].tick + 1)
    cap = ctx.cell.config["n_devices"] * ctx.cell.traffic["batch"]
    return float(np.mean([served[r.tick] for r in ticks])) / cap * 100.0
