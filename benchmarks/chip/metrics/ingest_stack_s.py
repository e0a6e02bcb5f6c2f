"""Seconds of the program's ``ingest.stack`` spans per traced refresh:
the sharded runtime's host copy of each column's slot chunks into one
(S, chunk, K) array before its sharded upload. Silent where the program
opens no such span."""

SPAN = "ingest.stack"


def read(ctx):
    units = [ctx.spans.spans[a:b] for a, b in (u["spans"] for u in ctx.units)]
    seconds = [d for unit in units for p, d in unit if p == SPAN]
    if not seconds:
        return None
    return sum(seconds) / len(units)
