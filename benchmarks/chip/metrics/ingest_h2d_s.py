"""Seconds of the program's ``ingest.h2d`` spans per traced refresh: host time uploading chunks to the device."""

SPAN = "ingest.h2d"


def read(ctx):
    if not ctx.units:
        return None
    total = sum(ctx.spans.seconds(SPAN, *u["spans"]) for u in ctx.units)
    return total / len(ctx.units)
