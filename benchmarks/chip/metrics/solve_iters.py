"""SCD iterations per traced solve (``SolveResult.iters``)."""


def read(ctx):
    if not ctx.units:
        return None
    return sum(u["iters"] for u in ctx.units) / len(ctx.units)
