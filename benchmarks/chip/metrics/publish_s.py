"""Seconds of the program's ``refresh.publish`` spans per traced refresh: time saving the generation record and flipping LIVE.json."""

SPAN = "refresh.publish"


def read(ctx):
    if not ctx.units:
        return None
    total = sum(ctx.spans.seconds(SPAN, *u["spans"]) for u in ctx.units)
    return total / len(ctx.units)
