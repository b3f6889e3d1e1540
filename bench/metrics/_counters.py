"""The program's own counters over the window: the change of a numeric
leaf of ``session.stats()`` between the reads before the window opens
and after it closes (``Reading.counters``), by its dotted path, as
``sched.steps`` or ``sched.decoded_tokens``."""


def delta(ctx, name):
    """The change of counter ``name`` over the window; None where the
    program has no such counter."""
    return ctx.counters.get(name)
