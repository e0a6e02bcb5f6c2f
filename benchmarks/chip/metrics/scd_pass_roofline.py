"""Share of the HBM roofline that one SCD map + reduce pass reaches.

The least time of a pass at the cell's (n, K, E), from the work counted
by ``chipbench.work.scd_pass`` at the chip's peak, over the measured
device time of the ``scd_fused_hist`` kernel per pass (kernel time over
the iterations of the traced solves). Silent when the kernel did not run.
"""
from chipbench import work

KERNEL = "scd_fused_hist"


def read(ctx):
    passes = sum(u["iters"] for u in ctx.units)
    ns = calls = 0
    for iv in ctx.trace["ops"]:
        t, c = ctx.devtrace.kernel_ns(iv, KERNEL)
        ns, calls = ns + t, calls + c
    if not calls or not passes:
        return None
    c = ctx.config
    edges = 2 * c["solver"]["bucket_half"] + 1
    ops, nbytes = work.scd_pass(c["n_users"], c["k"], edges)
    least, _ = work.least_seconds(ops, nbytes, ctx.peak)
    per_pass = ns * 1e-9 / len(ctx.trace["ops"]) / passes
    return 100.0 * least / per_pass
