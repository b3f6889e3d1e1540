"""Host ms per step in the scheduler's ``collect`` and ``park_issue``
spans: waiting on and scattering back the fetched pages, then parking
every live row and issuing the next fetches."""
from metrics._spans import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ("collect", "park_issue"))
