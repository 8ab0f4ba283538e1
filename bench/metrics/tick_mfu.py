"""tick_mfu: model operations of the window's work over the window's
seconds times the bf16 peak of the cell's chips (chips x one chip's),
in %: the k=1 chain of every served row's samples plus Eq. 8 for each
merge round, from unpadded widths (costs.py). The chain is float32 vector work, so the bf16 peak is more
than it can reach; the share is an upper bound on the room left."""
from bench import costs


def read(ctx):
    if ctx.peaks is None:
        return None
    ticks = ctx.log.in_window()
    if not ticks:
        return None
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    n, h, act = cfg["n_features"], cfg["n_hidden"], cfg["activation"]
    steps = costs.steps_per_row(tr)
    flops = 0.0
    for r in ticks:
        flops += r.served_rows * steps * costs.sample_flops(n, h, act)
        if r.merge:
            flops += costs.merge_flops(n, h, cfg["n_devices"], int(r.mask.sum()),
                                       cfg["topology"], cfg.get("hops", 0))
    w0, w1 = ctx.log.window
    peak = ctx.cell.chips * ctx.peaks["bf16_flops_per_s"]
    return flops / ((w1 - w0) * peak) * 100.0
