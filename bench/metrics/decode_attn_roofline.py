"""The ring decode attention kernel's share of its roofline, in percent:
the least time the chip could take for the window's decode attention
over the device seconds of the kernel ``decode_attn`` (its Pallas name in
the trace). The least time is the larger of the live keys and values at
their real lengths (``bench/flops.py``, the bytes ``decode_hbm_share``
counts) over the HBM bandwidth and the scores and weighted values (four
operations per query lane and key) over the bf16 peak; the bytes bound
it. The kernel reads whole blocks, so this reads below its true share."""
import flops
from metrics._scope import device_s

KERNEL = "decode_attn"


def read(ctx):
    t = device_s(ctx, KERNEL)
    keys = sum(sum(st.decode_ctx) for st in ctx.steps)
    if t is None or not keys:
        return None
    cfg = ctx.cfg
    nbytes = flops.kv_bytes_per_token(cfg) * keys
    ops = (4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
           * cfg["head_dim"] * keys)
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                ops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / t
