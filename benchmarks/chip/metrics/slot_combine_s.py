"""Seconds of the program's ``slots.combine`` spans per traced refresh:
the sharded runtime's per-epoch read-back of the (S, ...) slot partials
and their fold in fixed slot order. Silent where the program opens no
such span."""

SPAN = "slots.combine"


def read(ctx):
    units = [ctx.spans.spans[a:b] for a, b in (u["spans"] for u in ctx.units)]
    seconds = [d for unit in units for p, d in unit if p == SPAN]
    if not seconds:
        return None
    return sum(seconds) / len(units)
