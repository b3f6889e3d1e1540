"""Seconds from process start to the window's opening: JAX start-up,
weights, compilation or cache loads, warm-up and filling the batch."""


def read(ctx):
    return ctx.setup_s
