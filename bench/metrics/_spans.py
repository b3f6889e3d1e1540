"""Host milliseconds per scheduler step spent in ``obs`` spans."""


def ms_per_step(ctx, names):
    steps = len(ctx.steps)
    if not steps:
        return None
    total = sum(e - s for n, (s, e) in ctx.spans if n in names)
    found = any(n in names for n, _ in ctx.spans)
    return 1e3 * total / steps if found else None
