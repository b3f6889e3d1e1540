"""90th percentile of the scheduler's ``request.queue`` spans ending in
the window: a request's wait from submit to a slot, and a preempted
request's time off its slot."""
from metrics._request import p90_ms


def read(ctx):
    return p90_ms(ctx, "request.queue")
