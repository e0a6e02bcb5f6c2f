"""Seconds of the program's ``ckpt.save`` spans per traced refresh: the
resume-state saves (host reads of the state, then the files, their
fsyncs and the prune). Silent where the program opens no such span."""

SPAN = "ckpt.save"


def read(ctx):
    units = [ctx.spans.spans[a:b] for a, b in (u["spans"] for u in ctx.units)]
    seconds = [d for unit in units for p, d in unit if p == SPAN]
    if not seconds:
        return None
    return sum(seconds) / len(units)
