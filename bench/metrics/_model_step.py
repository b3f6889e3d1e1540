"""Device seconds of the model's own compiled programs in the traced
window (``bench/trace_reduce.py``), by the names the program's jitted
entry points take: a prefill chunk, a whole prompt, a decode step."""

PREFILL = ("jit_prefill_chunk", "jit_prefill")
DECODE = ("jit_decode_step",)


def device_s(ctx, programs):
    """Seconds the device spent in ``programs``; None without a trace or
    where none of them ran."""
    if ctx.trace is None:
        return None
    t = sum(ctx.trace.module_s.get(p, 0.0) for p in programs)
    return t if t > 0 else None
