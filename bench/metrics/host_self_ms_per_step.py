"""Host ms per step of the host's own work: the scheduler's ``step``
spans less the ``device_wait`` spans inside them, over the window's
steps."""
from metrics._spans import ms_per_step


def read(ctx):
    step = ms_per_step(ctx, ("step",))
    wait = ms_per_step(ctx, ("device_wait",))
    return None if step is None or wait is None else step - wait
