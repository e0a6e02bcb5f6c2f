"""Seconds of a traced refresh that no top-level program span covers.

A refresh's time less the summed seconds of the spans that tile it
(``TOP``), per traced refresh: how much of a refresh the program leaves
unexplained. Silent unless every traced refresh opened each span that
every refresh has (``ALWAYS``): a program without them explains nothing.
"""

TOP = ("refresh.prepare", "solve.fingerprint", "solve.iterate",
       "solve.finalize", "refresh.stamp", "refresh.publish",
       "refresh.readback")
ALWAYS = ("refresh.prepare", "solve.fingerprint", "solve.finalize",
          "refresh.stamp", "refresh.publish", "refresh.readback")


def read(ctx):
    if not ctx.units:
        return None
    total = 0.0
    for u in ctx.units:
        a, b = u["spans"]
        spans = [(p, d) for p, d in ctx.spans.spans[a:b] if p in TOP]
        if not set(ALWAYS) <= {p for p, _ in spans}:
            return None
        total += u["seconds"] - sum(d for _, d in spans)
    return total / len(ctx.units)
