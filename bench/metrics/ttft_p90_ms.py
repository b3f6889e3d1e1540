"""90th percentile, over every request due in the window, of the time
from its due time to the return of the step that emitted its first
token."""
import endtoend


def read(ctx):
    return endtoend.p90(endtoend.ttft_ms(ctx.window))
