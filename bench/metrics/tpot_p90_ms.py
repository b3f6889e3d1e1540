"""90th percentile, over requests with two or more tokens in the window,
of the mean gap between their tokens in the window."""
import endtoend


def read(ctx):
    return endtoend.p90(endtoend.tpot_ms(ctx.window))
