"""Percentiles of the scheduler's per-request ``request.<phase>`` spans."""
import endtoend


def p90_ms(ctx, name):
    """90th percentile, in ms, of the ``name`` spans that end in the
    window; None where there are none."""
    return endtoend.p90([1e3 * (e - s) for n, (s, e) in ctx.spans
                         if n == name])
