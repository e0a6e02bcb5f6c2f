"""Device time per solve outside the SCD passes, in milliseconds.

Device busy time of the traced solves less the ``scd_fused_hist``
kernel's time, per solve: the relayout copies, the final primal, the
post-processing and the small per-iteration threshold programs.
"""
KERNEL = "scd_fused_hist"


def read(ctx):
    if not ctx.units:
        return None
    kernel = sum(ctx.devtrace.kernel_ns(iv, KERNEL)[0]
                 for iv in ctx.trace["ops"]) / len(ctx.trace["ops"]) * 1e-9
    return (ctx.trace["busy_s"] - kernel) / len(ctx.units) * 1e3
