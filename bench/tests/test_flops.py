"""``bench/flops.py`` against counts made by hand."""

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
    trace = trace_reduce.Reduced(1.0, 10.0, [], [], module_s)
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
