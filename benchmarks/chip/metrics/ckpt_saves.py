"""Resume-state saves per traced refresh: the count of the program's
``ckpt.save`` spans. Silent where the program opens no such span."""

SPAN = "ckpt.save"


def read(ctx):
    units = [ctx.spans.spans[a:b] for a, b in (u["spans"] for u in ctx.units)]
    saves = sum(p == SPAN for unit in units for p, _ in unit)
    if not saves:
        return None
    return saves / len(units)
