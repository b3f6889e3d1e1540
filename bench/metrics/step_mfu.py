"""Model operations of the window's work over the device time of the
model's programs times the chip's bf16 peak, in percent: every prefill
and decode token at its own context length, and the output head for
every emitted token (``bench/flops.py``), over the traced seconds of the
prefill and decode programs. Pool copies, scatters and host time are not
in the denominator, so a faster model step raises it and a faster pool
does not."""
import flops
from metrics._model_step import DECODE, PREFILL, device_s


def read(ctx):
    t = device_s(ctx, PREFILL + DECODE)
    ops = sum(flops.token_flops(ctx.cfg, c)
              for st in ctx.steps for c in st.prefill_ctx + st.decode_ctx)
    ops += sum(st.emitted for st in ctx.steps) * flops.head_flops(ctx.cfg)
    if t is None or not ops:
        return None
    return 100.0 * ops / (t * ctx.peaks["bf16_flops_per_s"])
