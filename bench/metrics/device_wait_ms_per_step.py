"""Host ms per step blocked on a device value: the scheduler's
``device_wait`` spans (the decode argmax's copy to the host, the first
token's and non-greedy rows' samples)."""
from metrics._spans import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ("device_wait",))
