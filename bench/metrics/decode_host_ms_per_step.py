"""Host ms per step in the scheduler's ``decode`` span; it ends with the
argmax's copy to the host, so it includes waiting for the device."""
from metrics._spans import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ("decode",))
