"""ingest_kernel_roofline: the least time the window's ingest work could
take on one chip over the device time of the ingest kernel's events in
the trace, summed over the cell's chips, in %.

The work is what the served rows need, from unpadded widths (costs.py):
each served device's k=1 chain over its window, its state read and
written once and its window read once, plus the shared basis once per
kernel call: one call a tick on each chip of a resident fleet, one per
cohort of a paged one. The least time is the larger of operations over the bf16
peak and bytes over HBM bandwidth; at every cell so far the bytes set it.
"""
from bench import costs

KERNEL = r"^fleet_ingest_kernel$"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    kernel_s = ctx.trace.op_seconds(KERNEL)
    if kernel_s <= 0:
        return None
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    n, h, act = cfg["n_features"], cfg["n_hidden"], cfg["activation"]
    steps = costs.steps_per_row(tr)
    if "cohort_size" in cfg:
        calls = cfg["n_devices"] // cfg["cohort_size"]
    else:
        calls = ctx.cell.chips
    least, bounds = 0.0, set()
    for r in ctx.log.in_window():
        flops = r.served_rows * steps * costs.sample_flops(n, h, act)
        nbytes = (r.served_rows * costs.tick_bytes(n, h, steps)
                  + calls * costs.basis_bytes(n, h))
        t, bound = costs.least_seconds(flops, nbytes, ctx.peaks)
        least += t
        bounds.add(bound)
    ctx.notes.append(f"ingest_kernel_roofline bound by {'/'.join(sorted(bounds))}, "
                     f"kernel {kernel_s:.6f} s, least {least:.6f} s")
    return least / kernel_s * 100.0
