"""Seconds of one cold solve: the time of all solves in the window over
their count, each timed from the call to the completion of its result."""


def read(ctx):
    if not ctx.units:
        return None
    return sum(u["seconds"] for u in ctx.units) / len(ctx.units)
