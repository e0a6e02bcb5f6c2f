"""Seconds of one warm daily refresh: the time of all refreshes in the
window over their count, each timed from ``RefreshEngine.refresh`` until
it returns published (record and LIVE.json fsynced)."""


def read(ctx):
    if not ctx.units:
        return None
    return sum(u["seconds"] for u in ctx.units) / len(ctx.units)
