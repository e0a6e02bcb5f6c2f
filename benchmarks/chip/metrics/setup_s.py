"""Seconds from process start to the window: JAX start, data made from
the seed, compiles or cache reads, the mix's set-up operations."""


def read(ctx):
    return ctx.setup_s
