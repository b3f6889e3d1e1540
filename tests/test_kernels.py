"""Pallas kernels vs pure-jnp oracles: shape/dtype/flag sweeps in
interpret mode (CPU)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import decode_attention as rd
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.kernels.ref import (
    decode_attention_ref,
    flash_attention_ref,
    paged_decode_attention_ref,
    ssd_scan_ref,
)

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (2, 4, 2, 64, 32),
    (1, 4, 4, 96, 64),
    (2, 8, 1, 33, 16),     # ragged seq (padding path)
    (1, 2, 2, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(b, hq, hkv, s, d, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d)).astype(dtype)
    out = flash_attention_pallas(q, k, v, scale=d ** -0.5, block_q=32, block_k=32,
                                 interpret=True)
    ref = flash_attention_ref(q, k, v, scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window,cap,causal", [
    (None, None, True),
    (32, None, True),
    (None, 30.0, True),
    (16, 50.0, True),
    (None, None, False),
])
def test_flash_attention_flags(window, cap, causal):
    b, hq, hkv, s, d = 2, 4, 2, 80, 32
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    out = flash_attention_pallas(q, k, v, scale=0.2, causal=causal,
                                 window=window, logit_cap=cap,
                                 block_q=16, block_k=16, interpret=True)
    ref = flash_attention_ref(q, k, v, scale=0.2, causal=causal,
                              window=window, logit_cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# paged decode: page-table-driven kernel vs gather ref
# ---------------------------------------------------------------------------


def _paged_inputs(key, b, hq, hkv, d, page, n_pool):
    ks = jax.random.split(jax.random.key(key), 5)
    q = jax.random.normal(ks[0], (b, hq, d))
    kp = jax.random.normal(ks[1], (n_pool, b, hkv, page, d))
    vp = jax.random.normal(ks[2], (n_pool, b, hkv, page, d))
    kt = jax.random.normal(ks[3], (b, hkv, page, d))
    vt = jax.random.normal(ks[4], (b, hkv, page, d))
    return q, kp, vp, kt, vt


@pytest.mark.parametrize("hq,hkv", [(2, 2), (8, 2), (4, 1)])   # GQA groups
@pytest.mark.parametrize("cap", [None, 30.0])
def test_paged_decode_gqa_and_softcap(hq, hkv, cap):
    b, d, page = 2, 32, 8
    q, kp, vp, kt, vt = _paged_inputs(10, b, hq, hkv, d, page, 5)
    table = jnp.asarray([3, 0, 4], jnp.int32)     # scrambled, non-contiguous
    args = (q, kp, vp, table, kt, vt, jnp.int32(5))
    out = paged_decode_attention_pallas(*args, scale=d ** -0.5, logit_cap=cap,
                                        interpret=True)
    ref = paged_decode_attention_ref(*args, scale=d ** -0.5, logit_cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("table,tail_len", [
    ((0, 1, 2, 3, 4), 5),   # all pages, partial tail
    ((2, 4), 0),            # tail empty
    ((1, 3), 8),            # tail exactly full (just-flushed boundary)
    ((), 3),                # tail-only attention (no pages yet)
    ((), 1),                # single-token tail
])
def test_paged_decode_tail_boundaries(table, tail_len):
    """Ring-slot validity at the page boundary (ISSUE satellite): the
    fused kernel must reproduce the two-segment merged softmax when the
    tail is empty, partial, and exactly full."""
    b, hq, hkv, d, page = 2, 4, 2, 32, 8
    q, kp, vp, kt, vt = _paged_inputs(11, b, hq, hkv, d, page, 5)
    args = (q, kp, vp, jnp.asarray(table, jnp.int32), kt, vt,
            jnp.int32(tail_len))
    out = paged_decode_attention_pallas(*args, scale=d ** -0.5,
                                        interpret=True)
    ref = paged_decode_attention_ref(*args, scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_decode_ref_is_bitwise_the_gather_path():
    """The lowering-free ref path IS the legacy gather/concat math — this
    identity is what makes codec-"none" fused serving token-identical."""
    from repro.offload.kvcache import _paged_attend
    b, hq, hkv, d, page = 2, 4, 2, 32, 8
    q, kp, vp, kt, vt = _paged_inputs(12, b, hq, hkv, d, page, 6)
    for table, tl in [((5, 1, 2), 4), ((0,), 0), ((), 7)]:
        t = jnp.asarray(table, jnp.int32)
        ref = paged_decode_attention_ref(q, kp, vp, t, kt, vt,
                                         jnp.int32(tl), scale=d ** -0.5)
        gather = _paged_attend(q, kp[t], vp[t], kt, vt, jnp.int32(tl),
                               scale=d ** -0.5)
        assert bool(jnp.all(ref == gather))


# ---------------------------------------------------------------------------
# ring decode: the stacked-cache kernel vs the jnp decode read
# ---------------------------------------------------------------------------

#: (Hq, Hkv, D, C): phi3-mini's 32 heads of 96 (MHA; a head does not tile
#: the 128 lanes) and Granite 3.0's 24 query over 8 kv heads of 64 (GQA),
#: over rings of several kv blocks
DECODE_SHAPES = {"phi3": (32, 32, 96, 768), "granite": (24, 8, 64, 2048)}
#: the layer read, of a two-layer stack
LAYER = 1


def _stack(shape, b, dtype, seed):
    """(q (B, Hq·D), k, v (2, B, C, Hkv·D), head_dim, bk) at ``shape``."""
    hq, hkv, hd, c = DECODE_SHAPES[shape]
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, hq * hd)).astype(dtype)
    k = jax.random.normal(ks[1], (2, b, c, hkv * hd)).astype(dtype)
    v = jax.random.normal(ks[2], (2, b, c, hkv * hd)).astype(dtype)
    return q, k, v, hd, rd.block_k(c, hkv * hd, jnp.dtype(dtype).itemsize)


def _kernel_and_jnp(q, k, v, pos, hd, cap, scale=0.1):
    """The kernel (interpreted) and the jnp decode read of
    ``attention_decode`` on the same stack and positions, as arrays."""
    from repro.models import attention as A
    kw = dict(hd=hd, scale=scale, cap=cap)
    pos = jnp.asarray(pos, jnp.int32)
    got = A._attend_kernel(q, k, v, jnp.int32(LAYER), pos, **kw,
                           interpret=True)
    want = A._attend_xla(q, k, v, jnp.int32(LAYER), pos, **kw)
    return np.asarray(got), np.asarray(want)


def _length(name, bk, c):
    return {"0": 0, "1": 1, "bk-1": bk - 1, "bk": bk, "bk+1": bk + 1,
            "C-1": c - 1, "C": c}[name]


@pytest.mark.parametrize("length", ["0", "1", "bk-1", "bk", "bk+1", "C-1",
                                    "C"])
@pytest.mark.parametrize("cap", [None, 30.0])
@pytest.mark.parametrize("shape", DECODE_SHAPES)
def test_decode_attention(shape, cap, length):
    """Rows of the length under test around an empty row and a longer
    one: a live row equals the jnp read, which scores every slot and
    masks the dead ones, and an empty row reads as zeros. In float32 one
    key gained or lost moves a row by far more than the tolerance."""
    q, k, v, hd, bk = _stack(shape, 4, jnp.float32, seed=20)
    c = k.shape[2]
    n = _length(length, bk, c)
    lens = np.array([n, 0, c // 2 + 3, n])
    got, want = _kernel_and_jnp(q, k, v, lens - 1, hd, cap)
    live = lens > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=2e-5)
    assert not got[~live].any()


@pytest.mark.parametrize("shape", DECODE_SHAPES)
def test_decode_attention_bf16(shape):
    """bfloat16 operands, float32 sums, as served: within bfloat16
    rounding of the jnp read, which weighs values by normalised
    probabilities where the kernel weighs them before dividing."""
    q, k, v, hd, bk = _stack(shape, 4, jnp.bfloat16, seed=21)
    c = k.shape[2]
    pos = np.array([c // 3, bk, -1, c + 7])
    got, want = _kernel_and_jnp(q, k, v, pos, hd, None, hd ** -0.5)
    np.testing.assert_allclose(got[pos >= 0], want[pos >= 0], atol=4e-3)


@pytest.mark.parametrize("pos", ["C-1", "C", "C+1", "C+bk-1", "2C+5",
                                 "3C-1"])
def test_decode_attention_ring_wrap_mid_block(pos):
    """A ring past its wrap (``pos >= C``): slot j holds token
    ``pos - ((pos - j) mod C)`` and every slot is live, so the kernel reads
    every block. One scalar position for the batch, as
    ``ServeEngine.generate`` decodes, broadcast to the rows."""
    q, k, v, hd, bk = _stack("phi3", 2, jnp.float32, seed=22)
    c = k.shape[2]
    p = {"C-1": c - 1, "C": c, "C+1": c + 1, "C+bk-1": c + bk - 1,
         "2C+5": 2 * c + 5, "3C-1": 3 * c - 1}[pos]
    got, want = _kernel_and_jnp(q, k, v, jnp.broadcast_to(p, (2,)), hd, 50.0)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_decode_attention_ops_wrapper_jits():
    """``ops.decode_attention`` (scalar or per-row positions) against
    ``kernels.ref``'s ring oracle on the layer, in heads layout."""
    q, k, v, hd, bk = _stack("granite", 3, jnp.float32, seed=23)
    b, c, x = k.shape[1:]
    heads = lambda a: a[LAYER].reshape(b, c, x // hd, hd).transpose(0, 2, 1, 3)
    for pos in (jnp.int32(bk + 2), jnp.asarray([5, -1, c + 3], jnp.int32)):
        out = ops.decode_attention(q, k, v, jnp.int32(LAYER), pos,
                                   head_dim=hd, scale=hd ** -0.5)
        want = decode_attention_ref(q.reshape(b, -1, hd), heads(k), heads(v),
                                    pos, scale=hd ** -0.5)
        live = np.broadcast_to(np.asarray(pos) >= 0, (b,))
        np.testing.assert_allclose(np.asarray(out)[live],
                                   np.asarray(want).reshape(b, -1)[live],
                                   atol=2e-5)


@pytest.mark.parametrize("lens", [
    (300, 0, 0, 17, 768, 1, 0, 255),
    (0, 0, 256, 0, 512, 513, 0, 0),
    (768, 768, 768, 768),
    (1, 0, 1, 0),
    (0, 0, 0),
])
def test_decode_kv_index_map_fetches_only_live_blocks(lens):
    """Walk the grid through the kernel's own index map: the pipeline
    fetches a K/V block whenever the index differs from the step
    before's. No fetched block lies past its row's last live block, an
    empty row fetches none, and ``slots_read`` (the scheduler's
    ``decode_kv_slots_read``) counts exactly the fetched slots."""
    bk, c = 256, 768
    lens = np.asarray(lens, np.int32)
    sched = rd.kv_schedule(jnp.asarray(lens), bk)
    refs = (np.zeros(1, np.int32), lens) + tuple(np.asarray(a) for a in sched)
    fetched, prev = [], None
    for b in range(len(lens)):
        for ik in range(c // bk):
            idx = tuple(int(i) for i in rd.kv_block_index(b, ik, *refs))
            if idx != prev:
                fetched.append(idx)
            prev = idx
    want = [(b, blk) for b in range(len(lens))
            for blk in range(-(-int(lens[b]) // bk))]
    got = [(row, blk) for _, row, blk, _ in fetched]
    # the first grid step fetches a block whatever the lengths
    assert got == (want if lens.any() else [(0, 0)])
    assert rd.slots_read(lens, bk) == len(fetched) * bk


@pytest.mark.parametrize("c,x,itemsize,want", [
    (768, 3072, 2, 256),     # phi3 at the chat cell's ring
    (768, 512, 2, 768),      # Granite: the whole ring fits
    (2048, 512, 2, 1024),
    (768, 3072, 4, 128),
    (100, 128, 4, 100),      # no sublane-tiled divisor: one block
])
def test_decode_block_size_follows_width_and_vmem(c, x, itemsize, want):
    bk = rd.block_k(c, x, itemsize)
    assert bk == want and c % bk == 0
    assert bk == c or 4 * bk * x * itemsize <= rd.KV_VMEM_BYTES


def _kernel_read(monkeypatch):
    """Make every decode read the interpreted kernel, as on a TPU."""
    from repro.models import attention as A
    monkeypatch.setattr(A, "_attend_xla",
                        functools.partial(A._attend_kernel, interpret=True))


@pytest.mark.parametrize("arch,kv_heads", [("gemma2-9b", 2),
                                           ("phi3-mini-3.8b", None)])
@pytest.mark.parametrize("per_row", [False, True])
def test_decode_step_kernel_read_matches_jnp(monkeypatch, arch, kv_heads,
                                             per_row):
    """Whole decode steps of a tiny model through the kernel against the
    jnp read: gemma2's softcap, query scale and sliding-window rings that
    wrap (an 8-slot window beside full rings), with GQA; scalar positions
    as ``ServeEngine`` decodes, or per-row ones with an empty slot."""
    from repro.configs import REGISTRY
    from repro.models.model import Model
    cfg = REGISTRY[arch].reduced()
    if kv_heads:
        cfg = dataclasses.replace(cfg, n_kv_heads=kv_heads)
    model = Model(cfg=cfg, swa_override=8)
    params = model.init(jax.random.key(0))
    b, max_seq = 3, 24
    cache = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(1), a.shape, a.dtype),
        model.init_cache(b, max_seq))
    tok = jax.random.randint(jax.random.key(2), (b, 1), 0, cfg.vocab_size)
    start = np.array([5, -1, 13]) if per_row else np.full(b, 5)

    def run():
        step = jax.jit(lambda *a: model.decode_step(*a))
        c, out = cache, []
        for i in range(6):
            pos = np.where(start >= 0, start + 2 * i, -1)
            pos = jnp.asarray(pos if per_row else pos[0], jnp.int32)
            logits, c = step(params, c, tok, pos)
            out.append(np.asarray(logits[start >= 0]))
        return np.stack(out)

    want = run()
    _kernel_read(monkeypatch)
    np.testing.assert_allclose(run(), want, atol=1e-4, rtol=1e-4)


def test_paged_decode_ops_wrapper_jits():
    b, hq, hkv, d, page = 1, 4, 2, 16, 8
    q, kp, vp, kt, vt = _paged_inputs(14, b, hq, hkv, d, page, 3)
    t = jnp.asarray([1, 2], jnp.int32)
    out = ops.paged_decode_attention(q, kp, vp, t, kt, vt, jnp.int32(2),
                                     scale=d ** -0.5)
    ref = paged_decode_attention_ref(q, kp, vp, t, kt, vt, jnp.int32(2),
                                     scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 128, 4, 32, 16, 32),
    (1, 256, 2, 64, 32, 64),
    (2, 64, 8, 16, 8, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssd_scan(b, s, h, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.key(3), 4)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    a = -jnp.abs(jax.random.normal(ks[1], (b, s, h))) * 0.1
    bm = jax.random.normal(ks[2], (b, s, h, n)) * 0.3
    cm = jax.random.normal(ks[3], (b, s, h, n)) * 0.3
    y, st = ops.ssd_scan(x, a, bm, cm, chunk)
    yr, sr = ssd_scan_ref(x, a, bm, cm, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=3e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), atol=3e-5, rtol=1e-4)


def test_model_level_pallas_equivalence():
    from repro.configs import REGISTRY
    from repro.models.model import build_model
    from repro.models.runtime import use_attention_impl

    for name in ("gemma2-9b", "mamba2-370m"):
        cfg = REGISTRY[name].reduced()
        m = build_model(cfg)
        params = m.init(jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
        l1, _ = m.forward(params, {"tokens": toks, "targets": toks})
        with use_attention_impl("pallas"):
            l2, _ = m.forward(params, {"tokens": toks, "targets": toks})
        assert float(jnp.max(jnp.abs(l1 - l2))) < 5e-5
