"""Ring slots the decode read fetched over the slots of every row's whole
ring, in percent, over the window's decode steps: the program's counters
``sched.decode_kv_slots_read`` (each row's live kv blocks, as the ring
decode kernel's grid walks them) and ``sched.decode_kv_slots_ring``. A
read that fetched every row's whole ring reads 100."""
from metrics._counters import delta


def read(ctx):
    fetched = delta(ctx, "sched.decode_kv_slots_read")
    ring = delta(ctx, "sched.decode_kv_slots_ring")
    if not fetched or not ring:
        return None
    return 100.0 * fetched / ring
