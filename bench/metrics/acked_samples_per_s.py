"""acked_samples_per_s: samples acked ok inside the window over the time
they took (host clock): from the window's start to the last ack inside
it. The closed loop acks a tick's 8,192 requests in one burst, so the
window is whole ticks: it ends with the acks of the last tick of the
first merge cycle that ends after --seconds, every client resubmitting
up to then (``Served._window_done``). One request carries one sample."""
import numpy as np


def read(ctx):
    req = ctx.log.requests
    if req is None:
        return None
    w0, w1 = ctx.log.window
    ok = ((req["phase"] == 1) & (req["status"] == "ok")
          & (req["ack"] >= w0) & (req["ack"] <= w1))
    if not ok.any():
        return None
    return float(np.count_nonzero(ok)) / (float(req["ack"][ok].max()) - w0)
