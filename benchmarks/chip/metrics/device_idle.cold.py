"""Device idle share over the traced operations, mean over the chips.

100 * (1 - busy / window): busy is the union of the intervals in which
an XLA operation ran on a chip, the window runs from the start of the
first traced operation to the end of the last.
"""


def read(ctx):
    window = ctx.trace["window_s"]
    if window <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / window)
