"""merge_kernel_roofline: the least time of the window's fused ring
merges (``banded_merge_solve``) over that kernel's device time in the
trace, summed over the cell's chips, in %.

The kernel's own work, per device of the fleet and round, from unpadded
widths in costs.py's terms (Ñ hidden, m = n outputs): 2·hops neighbour
sums of a Ñ·(Ñ+m) payload and one solve (an inverse and 2 Ñ² m for β);
bytes: the 2·hops + 1 payloads it reads and the (P, β) it writes, each
Ñ·(Ñ+m) float32. The least time is the larger of operations over the
bf16 peak and bytes over HBM bandwidth. A cell whose merges do not run
the kernel (a star) has none: the reader returns None.
"""
from bench import costs

KERNEL = r"^banded_merge_solve$"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    kernel_s = ctx.trace.op_seconds(KERNEL)
    if kernel_s <= 0:
        return None
    cfg = ctx.cell.config
    d, h, m, hops = cfg["n_devices"], cfg["n_hidden"], cfg["n_features"], cfg["hops"]
    payload = h * (h + m)
    flops = d * (2 * hops * payload + costs._inverse_flops(h) + 2 * h * h * m)
    nbytes = d * (2 * hops + 2) * costs.F32 * payload
    rounds = sum(1 for r in ctx.log.in_window() if r.merge)
    least, bound = costs.least_seconds(flops, nbytes, ctx.peaks)
    ctx.notes.append(f"merge_kernel_roofline bound by {bound}, kernel {kernel_s:.6f} s, "
                     f"least {rounds * least:.6f} s over {rounds} rounds")
    return rounds * least / kernel_s * 100.0
