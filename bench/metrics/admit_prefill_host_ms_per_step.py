"""Host ms per step in the scheduler's ``admit_prefill`` span: admission
and the step's prefill chunks, up to the first token's sampling."""
from metrics._spans import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ("admit_prefill",))
