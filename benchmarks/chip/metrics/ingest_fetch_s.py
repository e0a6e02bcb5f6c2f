"""Seconds of the program's ``ingest.fetch`` spans per traced refresh: host time producing chunks (the source's fetch)."""

SPAN = "ingest.fetch"


def read(ctx):
    if not ctx.units:
        return None
    total = sum(ctx.spans.seconds(SPAN, *u["spans"]) for u in ctx.units)
    return total / len(ctx.units)
