"""``bench/flops.py`` against counts made by hand."""

import conftest
import flops

DENSE = dict(reference="dense", hidden_size=8, intermediate_size=16,
             num_hidden_layers=2, num_attention_heads=2,
             num_key_value_heads=1, head_dim=4, vocab_size=10,
             tie_word_embeddings=False)
MOE = dict(DENSE, reference="moe", num_local_experts=4,
           num_experts_per_tok=2, tie_word_embeddings=True)


def test_dense_token_flops():
    # per layer: wq 8x8, wk 8x4, wv 8x4, wo 8x8 = 192 weights; swiglu
    # 3 x 8 x 16 = 384; so 2 x 576 = 1152 ops; attention at ctx 5:
    # 4 x 2 heads x 4 x 5 = 160; two layers
    assert flops.token_flops(DENSE, 5) == 2 * (1152 + 160)


def test_moe_token_flops_count_router_and_top_k_experts():
    # router 8 x 4 = 32, two experts of 3 x 8 x 16 = 768: 800 weights
    assert flops.token_flops(MOE, 1) == 2 * (2 * (192 + 800) + 4 * 2 * 4)


def test_head_flops():
    assert flops.head_flops(DENSE) == 2 * 8 * 10


def test_weight_bytes():
    # dense: (192 + 384) bf16 + 2 norms x 8 float32 per layer; the head
    # 80 bf16 (the untied embedding is gathered by row, not read whole);
    # final norm 8 float32
    assert flops.decode_weight_bytes(DENSE) == \
        2 * (576 * 2 + 16 * 4) + 160 + 32
    # moe: 192 + 4 x 384 bf16, router 32 + norms 16 float32 per layer;
    # tied embedding 80 bf16
    assert flops.decode_weight_bytes(MOE) == \
        2 * ((192 + 1536) * 2 + 48 * 4) + 160 + 32


def test_kv_bytes_per_token():
    assert flops.kv_bytes_per_token(DENSE) == 2 * 2 * 1 * 4 * 2


def _ctx(module_s, steps):
    """A reading with a traced window of 10 s whose model programs ran
    for ``module_s`` seconds, on a chip of 1e12 operations and 1e11
    bytes per second."""
    import types

    import trace_reduce
    trace = trace_reduce.Reduced(1.0, 10.0, [], [], module_s, {})
    return types.SimpleNamespace(
        cfg=DENSE, steps=steps, trace=trace,
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def _step(prefill_ctx, decode_ctx, emitted):
    import types
    return types.SimpleNamespace(prefill_ctx=prefill_ctx,
                                 decode_ctx=decode_ctx, emitted=emitted)


def test_model_step_metrics_divide_by_the_programs_device_time():
    import run
    steps = [_step([1, 2], [5], 2), _step([], [6, 7], 2)]
    ctx = _ctx({"jit_prefill_chunk": 0.5, "jit_decode_step": 1.5,
                "jit_scatter": 7.0}, steps)
    ops = (sum(flops.token_flops(DENSE, c) for c in (1, 2, 5, 6, 7))
           + 4 * flops.head_flops(DENSE))
    assert run.reader(run.ROOT, "step_mfu")(ctx) == \
        100.0 * ops / (2.0 * 1e12)
    need = (2 * flops.decode_weight_bytes(DENSE)
            + flops.kv_bytes_per_token(DENSE) * (5 + 6 + 7))
    assert run.reader(run.ROOT, "decode_hbm_share")(ctx) == \
        100.0 * need / (1.5 * 1e11)


def test_model_step_metrics_read_nothing_without_the_programs():
    import run
    steps = [_step([1], [5], 2)]
    for ctx in (_ctx({"jit_scatter": 1.0}, steps), _ctx({}, steps)):
        assert run.reader(run.ROOT, "step_mfu")(ctx) is None
        assert run.reader(run.ROOT, "decode_hbm_share")(ctx) is None
    ctx = _ctx({"jit_decode_step": 1.0}, steps)
    ctx.trace = None
    assert run.reader(run.ROOT, "step_mfu")(ctx) is None


#: counts of the held files at full size, as the benchmark has made them
#: since its first cells: token_flops at context 1 and 768, head_flops,
#: decode_weight_bytes, kv_bytes_per_token
HELD_COUNTS = {
    "phi3-mini-3.8b": (7248150528, 7549747200, 197001216, 7445557248,
                       393216),
    "granite-moe-3b-a800m": (1614741504, 1765539840, 151004160,
                             6601718784, 65536),
}


def _counts(cfg):
    return (flops.token_flops(cfg, 1), flops.token_flops(cfg, 768),
            flops.head_flops(cfg), flops.decode_weight_bytes(cfg),
            flops.kv_bytes_per_token(cfg))


def test_held_files_keep_their_counts():
    for name, want in HELD_COUNTS.items():
        assert _counts(conftest.load_config(name)) == want, name


def test_granite_as_published_counts_router_and_top_8_experts():
    cfg = conftest.granite_published()
    # per layer: wq 1536x1536 + wo 1536x1536 + wk, wv 1536x512 each;
    # router 1536 x 40; 8 of the 40 experts, each 3 x 1536 x 512
    attn = 2 * 1536 * 1536 + 2 * 1536 * 512
    ffn = 1536 * 40 + 8 * 3 * 1536 * 512
    assert flops.token_flops(cfg, 1) == 32 * (2 * (attn + ffn)
                                              + 4 * 24 * 64)
    assert _counts(cfg) == HELD_COUNTS["granite-moe-3b-a800m"]
