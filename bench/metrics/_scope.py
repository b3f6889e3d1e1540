"""Device seconds of the ops that ran under one ``jax.named_scope`` of the
program, or in one Pallas kernel, in the traced window: the union of
those ops' intervals on each device, mean over the devices
(``trace_reduce.Reduced.scope_s``). A kernel's reader divides its own
operations or bytes by this time."""


def device_s(ctx, scope):
    """Seconds the device spent under ``scope``; None without a trace or
    where no op of that scope ran."""
    if ctx.trace is None:
        return None
    t = ctx.trace.scope_s.get(scope, 0.0)
    return t if t > 0 else None
