"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp oracles in
kernels/ref.py, swept over shapes, dtypes, and feature flags."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.expert_linear import grouped_matmul
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.quant_attention import SCHEDULES, streaming_attention


def _t(rng, *shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _grid(rng, *shape):
    """Normal draws rounded to a 1/4 grid: a q.k sum over such values (or
    over int8 keys) is exact in any order, so no log-sqrt2 code flips
    between a tiled kernel and the one-shot reference. At 197 tokens a
    plain normal draw leaves some score within an ulp of a code step."""
    return jnp.round(_t(rng, *shape) * 4.0) / 4.0


# ---------------------------------------------------------------------------
# Streaming quantized attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (B, Sq, Sk, H, KVH, hd, causal, quant_bits, softcap, window)
    (2, 64, 64, 4, 2, 64, True, 0, 0.0, 0),
    (2, 64, 64, 4, 2, 64, True, 4, 0.0, 0),
    (1, 128, 128, 4, 4, 64, False, 4, 0.0, 0),  # ViT-style bidirectional
    (1, 96, 96, 8, 1, 32, True, 3, 0.0, 0),  # MQA, 3-bit
    (2, 48, 96, 4, 1, 64, True, 0, 50.0, 32),  # softcap + local window
    (2, 48, 96, 4, 2, 64, True, 4, 30.0, 16),
    (1, 1, 128, 8, 2, 64, True, 4, 0.0, 0),  # decode
    (3, 17, 33, 2, 2, 16, True, 4, 0.0, 0),  # ragged (padding paths)
    # Sk > 128: block_k 128 takes the two-pass schedule, 256 the one-visit
    # (K tiles are lane multiples, so every row above fits one at either)
    (2, 197, 197, 6, 6, 64, False, 4, 0.0, 0),  # M3ViT-S attention
    (1, 160, 160, 4, 2, 64, True, 4, 30.0, 48),  # causal prefill
    (2, 1, 200, 8, 2, 64, True, 4, 0.0, 0),  # decode over a 200-key cache
]


@pytest.mark.parametrize("block_k", [128, 256])
@pytest.mark.parametrize(
    "B,Sq,Sk,H,KVH,hd,causal,qb,cap,win", ATTN_CASES
)
def test_attention_matches_ref(rng, B, Sq, Sk, H, KVH, hd, causal, qb, cap,
                               win, block_k):
    qk = _grid if Sk > 128 else _t  # long rows: see _grid
    q, k, v = qk(rng, B, Sq, H, hd), qk(rng, B, Sk, KVH, hd), _t(rng, B, Sk, KVH, hd)
    off = Sk - Sq if causal else 0
    valid = jnp.full((B,), Sk, jnp.int32)
    kw = dict(causal=causal, q_offset=off, quant_bits=qb, logit_softcap=cap,
              local_window=win, kv_valid_len=valid)
    out_k = streaming_attention(q, k, v, block_q=32, block_k=block_k,
                                interpret=True, **kw)
    out_r = ref.flash_attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(out_k, out_r, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("int8_kv", [False, True], ids=["vit", "int8_kv"])
def test_one_visit_matches_two_pass(rng, int8_kv):
    """When one K tile holds the key row, the 4-bit kernel fuses the max
    pass into the codes pass. Its output must be the two-pass schedule's
    (the same codes against the same exact max) to the last ulp, and both
    must be the reference's. ``int8_kv``: a cache of 192 keys with per-slot
    fill levels and dequant scales, a causal chunk at each slot's end."""
    from repro.models.layers import quantize_kv

    if int8_kv:
        B, Sq, Sk, H, KVH, hd = 2, 8, 192, 4, 2, 64
        q = _grid(rng, B, Sq, H, hd)
        kq, ks = quantize_kv(_t(rng, B, Sk, KVH, hd))
        vq, vs = quantize_kv(_t(rng, B, Sk, KVH, hd))
        valid = jnp.asarray([150, 192], jnp.int32)
        k, v = kq, vq
        kw = dict(causal=True, q_offset=valid - Sq, quant_bits=4,
                  k_scale=ks, v_scale=vs, kv_valid_len=valid)
    else:
        B, Sq, Sk, H, KVH, hd = 2, 197, 197, 6, 6, 64
        q, k = _grid(rng, B, Sq, H, hd), _grid(rng, B, Sk, KVH, hd)
        v = _t(rng, B, Sk, KVH, hd)
        kw = dict(causal=False, quant_bits=4)
    out_r = np.asarray(ref.flash_attention_ref(q, k, v, **kw))
    outs = {}
    for block_k, schedule in [(256, "one_visit"), (128, "two_pass")]:
        before = SCHEDULES[schedule]
        outs[schedule] = np.asarray(streaming_attention(
            q, k, v, block_q=256, block_k=block_k, interpret=True, **kw))
        assert SCHEDULES[schedule] == before + 1
        np.testing.assert_allclose(outs[schedule], out_r, atol=2e-5,
                                   rtol=2e-5, err_msg=schedule)
    np.testing.assert_allclose(outs["one_visit"], outs["two_pass"],
                               atol=1e-6, rtol=0)


def test_attention_bf16_inputs(rng):
    B, Sq, Sk, H, KVH, hd = 2, 32, 32, 4, 2, 64
    q = _t(rng, B, Sq, H, hd, dtype=jnp.bfloat16)
    k = _t(rng, B, Sk, KVH, hd, dtype=jnp.bfloat16)
    v = _t(rng, B, Sk, KVH, hd, dtype=jnp.bfloat16)
    out_k = streaming_attention(q, k, v, causal=True, quant_bits=4,
                                block_q=16, block_k=16, interpret=True)
    out_r = ref.flash_attention_ref(q, k, v, causal=True, quant_bits=4)
    assert out_k.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        out_k.astype(np.float32), out_r.astype(np.float32), atol=2e-2
    )


def test_attention_int8_kv_cache(rng):
    from repro.models.layers import quantize_kv

    B, Sq, Sk, H, KVH, hd = 2, 1, 96, 4, 2, 64
    q, k, v = _t(rng, B, Sq, H, hd), _t(rng, B, Sk, KVH, hd), _t(rng, B, Sk, KVH, hd)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    valid = jnp.asarray([64, 96], jnp.int32)
    kw = dict(causal=True, q_offset=63, quant_bits=4, k_scale=ks, v_scale=vs,
              kv_valid_len=valid)
    out_k = streaming_attention(qq := q, k=kq, v=vq, block_q=8, block_k=32,
                                interpret=True, **kw)
    out_r = ref.flash_attention_ref(q, kq, vq, **kw)
    np.testing.assert_allclose(out_k, out_r, atol=2e-5)


def test_attention_per_slot_offsets(rng):
    """Continuous batching: vector q_offset (per-slot positions)."""
    B, Sk, H, KVH, hd = 3, 64, 4, 2, 32
    q, k, v = _t(rng, B, 1, H, hd), _t(rng, B, Sk, KVH, hd), _t(rng, B, Sk, KVH, hd)
    offs = jnp.asarray([5, 20, 63], jnp.int32)
    valid = offs + 1
    out_k = streaming_attention(q, k, v, causal=True, q_offset=offs,
                                quant_bits=4, kv_valid_len=valid,
                                block_q=8, block_k=16, interpret=True)
    out_r = ref.flash_attention_ref(q, k, v, causal=True, q_offset=offs,
                                    quant_bits=4, kv_valid_len=valid)
    np.testing.assert_allclose(out_k, out_r, atol=2e-5)
    # each slot must equal its own single-sequence computation
    for i, (o, vl) in enumerate(zip([5, 20, 63], [6, 21, 64])):
        solo = ref.flash_attention_ref(
            q[i:i+1], k[i:i+1], v[i:i+1], causal=True, q_offset=o,
            quant_bits=4, kv_valid_len=jnp.asarray([vl], jnp.int32))
        np.testing.assert_allclose(out_k[i:i+1], solo, atol=2e-5)


def test_attention_mixed_slot_offsets_and_fill_levels(rng):
    """Continuous-batching admission: multi-token q chunks where every slot
    sits at a *different* fill level — per-batch q_offset [B] mixed with
    per-batch kv_valid_len [B], over an int8 K/V cache with dequant scales
    (the state ServeEngine decodes from after ragged prefills)."""
    from repro.models.layers import quantize_kv

    B, Sq, Sk, H, KVH, hd = 3, 4, 64, 4, 2, 32
    q = _t(rng, B, Sq, H, hd)
    k, v = _t(rng, B, Sk, KVH, hd), _t(rng, B, Sk, KVH, hd)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    offs = jnp.asarray([0, 17, 60], jnp.int32)  # slot fill levels
    valid = offs + Sq  # cache valid through each slot's chunk
    kw = dict(causal=True, q_offset=offs, quant_bits=4,
              k_scale=ks, v_scale=vs, kv_valid_len=valid)
    out_k = streaming_attention(q, kq, vq, block_q=8, block_k=16,
                                interpret=True, **kw)
    out_r = ref.flash_attention_ref(q, kq, vq, **kw)
    np.testing.assert_allclose(out_k, out_r, atol=2e-5)
    # per-slot equivalence: each slot must match its solo run at its own
    # (offset, fill) pair — the batched kernel adds no cross-slot coupling
    for i in range(B):
        solo = ref.flash_attention_ref(
            q[i:i+1], kq[i:i+1], vq[i:i+1], causal=True,
            q_offset=int(offs[i]), quant_bits=4,
            k_scale=ks[i:i+1], v_scale=vs[i:i+1],
            kv_valid_len=valid[i:i+1])
        np.testing.assert_allclose(out_k[i:i+1], solo, atol=2e-5)


@pytest.mark.parametrize("qb", [0, 4])
def test_attention_segment_ids_packed_prefill(rng, qb):
    """Packed prefill (DESIGN.md section 10): several prompts concatenated
    into one batch row, attention confined to equal segment ids. Every
    segment of the packed output must equal its own solo causal run —
    contiguous segments make buffer-index causality equal within-segment
    causality, so no cross-prompt leakage and no position skew."""
    lens = [24, 40, 32]
    S, H, KVH, hd = sum(lens), 4, 2, 32
    q, k, v = _t(rng, 1, S, H, hd), _t(rng, 1, S, KVH, hd), _t(rng, 1, S, KVH, hd)
    seg = jnp.asarray(np.repeat(np.arange(len(lens)), lens)[None], jnp.int32)
    kw = dict(causal=True, quant_bits=qb,
              q_segment_ids=seg, kv_segment_ids=seg)
    out_k = streaming_attention(q, k, v, block_q=16, block_k=32,
                                interpret=True, **kw)
    out_r = ref.flash_attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(out_k, out_r, atol=2e-5, rtol=2e-5)
    o = 0
    for L in lens:
        solo = ref.flash_attention_ref(
            q[:, o:o+L], k[:, o:o+L], v[:, o:o+L],
            causal=True, quant_bits=qb)
        np.testing.assert_allclose(out_k[:, o:o+L], solo, atol=2e-5,
                                   err_msg=f"segment at offset {o}")
        o += L


# ---------------------------------------------------------------------------
# Unified sparse/dense grouped matmul
# ---------------------------------------------------------------------------

GROUP_CASES = [
    (4, 64, 96, [40, 0, 17, 71]),
    (1, 128, 64, [200]),  # dense mode (the paper's mode switch)
    (8, 32, 32, [0, 0, 5, 0, 123, 1, 0, 16]),
    (3, 256, 512, [128, 128, 128]),
    (5, 64, 64, [0, 300, 0, 0, 1]),
    (2, 16, 16, [1, 1]),
]


@pytest.mark.parametrize("G,Din,Dout,sizes", GROUP_CASES)
def test_grouped_matmul_matches_ref(rng, G, Din, Dout, sizes):
    T = sum(sizes)
    x = _t(rng, T, Din)
    w = _t(rng, G, Din, Dout)
    gs = jnp.asarray(sizes, jnp.int32)
    y = grouped_matmul(x, w, gs, block_m=32, block_n=64, interpret=True)
    yr = ref.grouped_matmul_ref(x, w, gs)
    np.testing.assert_allclose(y, yr, atol=1e-4)


def test_grouped_matmul_int8_experts(rng):
    """Per-expert int8 weights + per-channel dequant scale (W8 experts)."""
    G, Din, Dout = 4, 64, 64
    sizes = [33, 12, 0, 55]
    T = sum(sizes)
    x = _t(rng, T, Din)
    wf = rng.standard_normal((G, Din, Dout)).astype(np.float32)
    wsc = np.abs(wf).max(axis=1) / 127.0
    wq = np.clip(np.round(wf / wsc[:, None, :]), -127, 127).astype(np.int8)
    y = grouped_matmul(x, jnp.asarray(wq), jnp.asarray(sizes, jnp.int32),
                       w_scale=jnp.asarray(wsc), block_m=32, interpret=True)
    yr = ref.grouped_matmul_ref(
        x, jnp.asarray(wq.astype(np.float32) * wsc[:, None, :]),
        jnp.asarray(sizes, jnp.int32))
    np.testing.assert_allclose(y, yr, atol=1e-3)


def test_grouped_matmul_matches_ragged_dot(rng):
    """The XLA fast path (lax.ragged_dot) and the Pallas kernel agree."""
    G, Din, Dout = 4, 32, 48
    sizes = [10, 30, 0, 24]
    x = _t(rng, sum(sizes), Din)
    w = _t(rng, G, Din, Dout)
    gs = jnp.asarray(sizes, jnp.int32)
    y_pl = grouped_matmul(x, w, gs, block_m=16, interpret=True)
    y_xla = jax.lax.ragged_dot(x, w, gs)
    np.testing.assert_allclose(y_pl, y_xla, atol=1e-4)


# ---------------------------------------------------------------------------
# INT8 tiled matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N,bias", [
    (100, 200, 150, True),
    (32, 64, 32, False),
    (7, 500, 13, True),  # ragged tiles
])
def test_int8_matmul_matches_ref(rng, M, K, N, bias):
    xq = jnp.asarray(rng.integers(-127, 128, (M, K)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
    xs = jnp.float32(0.013)
    ws = jnp.asarray(np.abs(rng.standard_normal(N)) * 0.01, jnp.float32)
    b = jnp.asarray(rng.standard_normal(N), jnp.float32) if bias else None
    y = int8_matmul(xq, wq, xs, ws, b, block_m=32, block_n=64, block_k=64,
                    interpret=True)
    yr = ref.int8_matmul_ref(xq, wq, xs, ws, b)
    np.testing.assert_allclose(y, yr, atol=1e-3)


# ---------------------------------------------------------------------------
# Selective scan (Mamba-1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,di,N,bs,bd", [
    (2, 64, 32, 8, 16, 16),
    (1, 100, 64, 16, 32, 32),  # ragged S (padding = identity steps)
    (2, 17, 16, 4, 8, 16),
])
def test_selective_scan_matches_ref(rng, B, S, di, N, bs, bd):
    from repro.kernels.selective_scan import selective_scan

    x = _t(rng, B, S, di)
    dt = jnp.abs(_t(rng, B, S, di)) * 0.1
    b = _t(rng, B, S, N)
    c = _t(rng, B, S, N)
    a = -jnp.abs(_t(rng, di, N))
    d = _t(rng, di)
    y, h_last = selective_scan(x, dt, b, c, a, d, block_s=bs, block_d=bd,
                               interpret=True)
    yr = ref.selective_scan_ref(x, dt, b, c, a, d)
    np.testing.assert_allclose(y, yr, atol=1e-4)
    assert h_last.shape == (B, di, N)
    assert bool(jnp.isfinite(h_last).all())


def test_mamba1_kernel_path_equals_chunked(monkeypatch):
    """Full falcon-mamba forward: Pallas kernel (interpret) == chunked scan."""
    import os

    import repro.models as M
    from repro.configs import smoke_config

    cfg = smoke_config("falcon-mamba-7b").replace(remat=False)
    mod = M.module_for(cfg)
    params = M.init_model_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0,
                             cfg.vocab_size)
    monkeypatch.setenv("REPRO_PALLAS", "ref")
    ref_logits, _ = mod.forward(params, cfg, tok)
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    k_logits, _ = mod.forward(params, cfg, tok)
    np.testing.assert_allclose(np.asarray(k_logits), np.asarray(ref_logits),
                               atol=1e-3)


def test_int8_matmul_exact_integer_accumulation(rng):
    """int32 accumulation is exact — unlike f32 fake-quant, big-K sums must
    not lose integer precision."""
    M, K, N = 4, 8192, 4
    xq = jnp.asarray(rng.integers(-127, 128, (M, K)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
    y = int8_matmul(xq, wq, jnp.float32(1.0), jnp.ones((N,), jnp.float32),
                    interpret=True)
    exact = (np.asarray(xq, np.int64) @ np.asarray(wq, np.int64)).astype(np.float64)
    np.testing.assert_allclose(np.asarray(y, np.float64), exact, rtol=1e-6)
