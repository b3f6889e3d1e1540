"""Bytes the window's decode steps must read from HBM over the device
time of the decode program times the chip's HBM bandwidth, in percent:
per decode step the weights it multiplies by, once, plus each live row's
keys and values at its real length (``bench/flops.py``), over the traced
seconds of ``jit_decode_step``."""
import flops
from metrics._model_step import DECODE, device_s


def read(ctx):
    t = device_s(ctx, DECODE)
    kv = flops.kv_bytes_per_token(ctx.cfg)
    w = flops.decode_weight_bytes(ctx.cfg)
    need = sum(w + kv * sum(st.decode_ctx)
               for st in ctx.steps if st.decode_ctx)
    if t is None or not need:
        return None
    return 100.0 * need / (t * ctx.peaks["hbm_bytes_per_s"])
