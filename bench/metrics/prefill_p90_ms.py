"""90th percentile of the scheduler's ``request.prefill`` spans ending in
the window: from a request taking its slot to its first token sampled,
every step its prompt's chunks wait for the step's prefill budget
included."""
from metrics._request import p90_ms


def read(ctx):
    return p90_ms(ctx, "request.prefill")
