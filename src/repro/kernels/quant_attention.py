"""Pallas TPU streaming attention kernel — CoQMoE sections 4.2(a) + 4.3.

The paper's FPGA design broadcasts one K/V stream to all PEs while each PE
holds a distinct Q row, so off-chip traffic is O(1) in PE count; softmax is a
fused 3-pass (max -> numerator+denominator -> shift-based P.V with one final
recip(l) rescale). On TPU that layout IS the flash-attention grid
decomposition: the grid walks Q blocks (the "PEs"); every grid step streams
the *same* K/V HBM tiles through VMEM while its Q tile stays resident.

Three execution schedules, chosen from ``quant_bits`` and the number of K
tiles alone:

  * ``online`` (quant_bits == 0) — classic single-pass flash (running
    max/denom).
  * ``two_pass`` (quant_bits > 0, more than one K tile) — the paper's
    3-pass schedule: pass 1 over K computes the exact row max (the
    log-sqrt2 codes must be taken against the *final* max, as on the FPGA,
    or the power-of-two grid shifts per block); pass 2 computes codes, the
    exact denominator, and the P.V accumulation; the recip(l) rescale
    happens once at the flush (the paper's Pass 3 trick).
  * ``one_visit`` (quant_bits > 0, one K tile holds the whole key row) —
    the tile's row max *is* the final max, so passes 1-3 fuse into one grid
    step per Q tile: QK^T once, then pass 2's arithmetic in pass 2's order,
    and acc / l written out directly. Same values as ``two_pass``, half the
    grid steps and one score matmul instead of two (a 197-token ViT row).

The log-sqrt2 quantizer (Eqs. 17-21) is fused in affine-code form:
codes = clip(round(-2 log2(e) (s - m)), 0, 2^b - 1) — identical math to
-2 log2(exp(s - m)) with no transcendental. A_hat = 2^{-ceil(c/2)} scaled by
the parity LUT (1, sqrt2-1): powers of two are exact in f32/bf16, so the MXU
P.V matmul is exact w.r.t. the quantizer (the TPU answer to the FPGA's
``V_q >> c/2`` shifter; DESIGN.md section 2).

Supports GQA (KVH-native), causal/local/softcap masking, int8 K/V cache with
per-position dequant scales, and a per-batch valid length (decode fill level).
"""
from __future__ import annotations

import collections
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.expert_linear import LANE, _round_up, _sublane

LOG2E = 1.4426950408889634
SQRT2M1 = 0.41421356237309515  # sqrt(2) - 1

# Trace-time tally: schedule name -> number of ``streaming_attention``
# calls traced with it: counted as a program traces, not as it runs, so a
# call inside a scanned layer body counts once however many layers run it.
SCHEDULES: collections.Counter = collections.Counter()


def legal_attn_blocks(block_q: int, block_k: int, Sq: int, Sk: int,
                      q_dtype=jnp.float32) -> Tuple[int, int]:
    """Clamp a requested (block_q, block_k) to the sequence, then round UP
    to legal TPU tile multiples (shared rule: expert_linear._sublane).

    ``min(block_q, Sq)`` alone produces a 1-row Q tile for decode (Sq=1),
    which is illegal/wasteful on TPU; the clamped block rounds up to the Q
    dtype's sublane multiple (8 f32 / 16 bf16) and block_k to the lane
    multiple (128, which also covers the int8 K/V sublane minimum of 32).
    Padded rows/keys are masked (``kpos < valid``) and sliced off, so the
    rounding changes layout only, never values. The autotuner uses the
    same function so candidate tiles match the kernel's effective tiles."""
    bq = _round_up(max(1, min(block_q, max(Sq, 1))), _sublane(q_dtype))
    bk = _round_up(max(1, min(block_k, max(Sk, 1))), LANE)
    return bq, bk


def _attn_kernel(
    # scalar prefetch
    meta_ref,  # [B] int32: q_offset per batch row (continuous batching)
    valid_ref,  # [B] int32: kv valid length per batch row
    # blocked operands
    q_ref,  # [1, 1, bq, hd]
    k_ref,  # [1, 1, bk, hd]
    v_ref,  # [1, 1, bk, hd]
    *rest,  # (k_scale?, v_scale?, qseg?, kseg?, out, m_s?, l_s?, acc_s?)
    causal: bool,
    local_window: int,
    logit_softcap: float,
    quant_bits: int,
    has_scales: bool,
    has_segs: bool,
    block_q: int,
    block_k: int,
    n_k: int,
    schedule: str,
    sm_scale: float,
):
    rest = list(rest)
    ks_ref = vs_ref = qseg_ref = kseg_ref = None
    if has_scales:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    if has_segs:
        qseg_ref, kseg_ref = rest[0], rest[1]
        rest = rest[2:]
    o_ref, *scratch = rest  # one_visit keeps no scratch

    b = pl.program_id(0)
    iq = pl.program_id(2)
    ikp = pl.program_id(3)
    nk_total = pl.num_programs(3)
    two_pass = schedule == "two_pass"
    phase = ikp // n_k if two_pass else 0
    ik = ikp % n_k if two_pass else ikp

    q_off = meta_ref[b]
    valid = jnp.minimum(valid_ref[b], jnp.int32(n_k * block_k))

    qpos = (
        q_off
        + iq * block_q
        + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    )
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = kpos < valid
    if causal:
        mask &= kpos <= qpos
    if local_window > 0:
        mask &= (qpos - kpos) < local_window
    if has_segs:
        # Packed variable-length prefill (DESIGN.md section 10): a position
        # only attends within its own segment. Padded tails carry id -1 on
        # the q side and -2 on the k side so they can never match.
        mask &= qseg_ref[0][:, None] == kseg_ref[0][None, :]

    # f32 operands contract at full f32 precision. Mosaic's default rounds
    # them to bf16 in one pass, which moves a score by ~2^-9 of its size and
    # flips log-sqrt2 codes that sit on a step boundary; bf16 and int8
    # operands are exact in that one pass.
    prec = (jax.lax.Precision.HIGHEST
            if jnp.float32 in (q_ref.dtype, k_ref.dtype, v_ref.dtype)
            else jax.lax.Precision.DEFAULT)

    def scores():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [bq, bk]
        if has_scales:
            s = s * ks_ref[0, 0]  # [1, bk] per-position dequant
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        return jnp.where(mask, s, -jnp.inf)

    def quantized_pv(s, m):
        # Pass 2 (paper section 4.3): log-sqrt2 codes against the final row
        # max m [bq, 1]; returns the exact denominator and f_hat.V.
        v = v_ref[0, 0].astype(jnp.float32)
        f_exact = jnp.exp(s - m)
        codes = jnp.clip(
            jnp.round(-2.0 * LOG2E * (s - m)),
            0.0,
            2.0**quant_bits - 1.0,
        ).astype(jnp.int32)
        shift = (codes + 1) // 2  # ceil(c / 2)
        parity = (codes & 1).astype(jnp.float32)
        f_hat = jnp.exp2(-shift.astype(jnp.float32)) * (
            1.0 + parity * SQRT2M1
        )
        f_hat = jnp.where(mask, f_hat, 0.0)
        l = jnp.sum(f_exact, axis=1, keepdims=True)
        if has_scales:
            f_hat = f_hat * vs_ref[0, 0]
        return l, jax.lax.dot(
            f_hat, v, precision=prec, preferred_element_type=jnp.float32
        )

    if schedule == "one_visit":
        # The one K tile holds every live key of the row, so its row max is
        # the final max: passes 1-3 in this one step. A row with no live key
        # reads m = -1e30, l = 0 and writes 0, as the flush below does.
        s = scores()
        m = jnp.maximum(jnp.max(s, axis=1, keepdims=True), -1e30)
        l, pv = quantized_pv(s, m)
        o_ref[0, 0, :, :] = (pv / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return

    m_s, l_s, acc_s = scratch

    @pl.when(ikp == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, -1e30)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # Block-level skip: nothing in this K tile can be visible.
    row0 = q_off + iq * block_q  # first (smallest) q position of the tile
    block_alive = jnp.logical_and(
        ik * block_k < valid,
        (ik * block_k <= row0 + block_q - 1) if causal else True,
    )
    if local_window > 0:
        block_alive = jnp.logical_and(
            block_alive, (ik + 1) * block_k > row0 - local_window + 1
        )

    @pl.when(block_alive)
    def _compute():
        s = scores()
        if two_pass:
            @pl.when(phase == 0)
            def _pass1():
                # Pass 1 (paper section 4.3): exact row max only.
                m_blk = jnp.max(s, axis=1, keepdims=True)  # [bq, 1]
                m_s[...] = jnp.maximum(m_s[...], jnp.maximum(m_blk, -1e30))

            @pl.when(phase == 1)
            def _pass2():
                l_blk, pv = quantized_pv(s, m_s[:, :1])
                l_s[...] += l_blk
                acc_s[...] += pv
        else:
            # Online single-pass flash (running max / denominator).
            v = v_ref[0, 0].astype(jnp.float32)
            m_old = m_s[:, :1]
            m_blk = jnp.maximum(jnp.max(s, axis=1, keepdims=True), -1e30)
            m_new = jnp.maximum(m_old, m_blk)
            corr = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new)
            l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
            if has_scales:
                p = p * vs_ref[0, 0]
            acc_s[...] = acc_s[...] * corr + jax.lax.dot(
                p, v, precision=prec, preferred_element_type=jnp.float32
            )
            m_s[...] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(ikp == nk_total - 1)
    def _flush():
        # Pass 3: one recip(l) per row (all of a row's outputs share it).
        l = jnp.maximum(l_s[:, :1], 1e-30)
        o_ref[0, 0, :, :] = (acc_s[...] / l).astype(o_ref.dtype)


def streaming_attention(
    q: jnp.ndarray,  # [B, Sq, H, hd]
    k: jnp.ndarray,  # [B, Sk, KVH, hd] (fp or int8)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset=0,  # scalar or [B]: per-batch absolute position of q[0]
                 # (continuous batching: every serving slot decodes at its
                 # own fill level; pairs with per-slot kv_valid_len)
    quant_bits: int = 0,
    logit_softcap: float = 0.0,
    local_window: int = 0,
    k_scale: Optional[jnp.ndarray] = None,  # [B, Sk, KVH]
    v_scale: Optional[jnp.ndarray] = None,
    kv_valid_len: Optional[jnp.ndarray] = None,  # [B]
    q_segment_ids: Optional[jnp.ndarray] = None,  # [B, Sq] packed prefill
    kv_segment_ids: Optional[jnp.ndarray] = None,  # [B, Sk]
    block_q: int = 128,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Sq, H, hd = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    assert H % KVH == 0, (H, KVH)
    group = H // KVH

    block_q, block_k = legal_attn_blocks(block_q, block_k, Sq, Sk, q.dtype)
    has_segs = q_segment_ids is not None
    if has_segs:
        # Segment ids ride along as 2D [B, S] blocked inputs; their minor
        # dim is the block size, so the Q block must be lane-rounded to keep
        # the (1, block_q) tile legal (block_k is already a LANE multiple).
        block_q = _round_up(block_q, LANE)
    n_q = pl.cdiv(Sq, block_q)
    n_k = pl.cdiv(Sk, block_k)
    sq_pad, sk_pad = n_q * block_q, n_k * block_k

    # [B, heads, S, hd] layout for clean (b, h, s-block) tiling.
    qt = jnp.pad(
        q.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, sq_pad - Sq), (0, 0))
    )
    kt = jnp.pad(
        k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, sk_pad - Sk), (0, 0))
    )
    vt = jnp.pad(
        v.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, sk_pad - Sk), (0, 0))
    )

    meta = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))
    valid = (
        jnp.full((B,), Sk, jnp.int32)
        if kv_valid_len is None
        else kv_valid_len.astype(jnp.int32)
    )

    has_scales = k_scale is not None
    if quant_bits <= 0:
        schedule = "online"
    else:
        schedule = "one_visit" if n_k == 1 else "two_pass"
    SCHEDULES[schedule] += 1
    two_pass = schedule == "two_pass"
    grid = (B, H, n_q, (2 * n_k) if two_pass else n_k)

    # NB: with PrefetchScalarGridSpec, index maps receive
    # (*grid_indices, *scalar_prefetch_refs) — scalars LAST.
    def kmap(b, h, iq, ikp, m, vl):
        return (b, h // group, ikp % n_k if two_pass else ikp, 0)

    def vmap_(b, h, iq, ikp, m, vl):
        # V is consumed only in pass 2; pin pass-1 visits to tile 0 so the
        # max pass issues no V HBM traffic (K streams twice, V once — the
        # paper's Pass-1/Pass-2 split).
        if two_pass:
            return (b, h // group, jnp.where(ikp < n_k, 0, ikp - n_k), 0)
        return (b, h // group, ikp, 0)

    def smap(b, h, iq, ikp, m, vl):
        return (b, h // group, 0, ikp % n_k if two_pass else ikp)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ikp, m, vl: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_k, hd), kmap),
        pl.BlockSpec((1, 1, block_k, hd), vmap_),
    ]
    args = [qt, kt, vt]
    if has_scales:
        # [B, KVH, 1, sk_pad]: the block's last two dims are (1, block_k)
        # — the full unit dim and a lane multiple, a legal TPU tile (a
        # (1, 1, block_k) block over [B, KVH, sk_pad] is refused for KVH > 1)
        kst = jnp.pad(
            k_scale.transpose(0, 2, 1), ((0, 0), (0, 0), (0, sk_pad - Sk))
        ).astype(jnp.float32)[:, :, None, :]
        vst = jnp.pad(
            v_scale.transpose(0, 2, 1), ((0, 0), (0, 0), (0, sk_pad - Sk))
        ).astype(jnp.float32)[:, :, None, :]
        in_specs += [
            pl.BlockSpec((1, 1, 1, block_k), smap),
            pl.BlockSpec((1, 1, 1, block_k), smap),
        ]
        args += [kst, vst]
    if has_segs:
        kv_seg = (
            kv_segment_ids if kv_segment_ids is not None else q_segment_ids
        )
        qsegp = jnp.pad(
            q_segment_ids.astype(jnp.int32), ((0, 0), (0, sq_pad - Sq)),
            constant_values=-1,
        )
        ksegp = jnp.pad(
            kv_seg.astype(jnp.int32), ((0, 0), (0, sk_pad - Sk)),
            constant_values=-2,  # != q pad id: padded tails never match
        )
        in_specs += [
            pl.BlockSpec((1, block_q), lambda b, h, iq, ikp, m, vl: (b, iq)),
            pl.BlockSpec(
                (1, block_k),
                lambda b, h, iq, ikp, m, vl: (b, ikp % n_k if two_pass else ikp),
            ),
        ]
        args += [qsegp, ksegp]

    kernel = functools.partial(
        _attn_kernel,
        causal=causal,
        local_window=local_window,
        logit_softcap=logit_softcap,
        quant_bits=quant_bits,
        has_scales=has_scales,
        has_segs=has_segs,
        block_q=block_q,
        block_k=block_k,
        n_k=n_k,
        schedule=schedule,
        sm_scale=1.0 / math.sqrt(hd),
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, 1, block_q, hd), lambda b, h, iq, ikp, m, vl: (b, h, iq, 0)
            ),
            scratch_shapes=[] if schedule == "one_visit" else [
                pltpu.VMEM((block_q, 128), jnp.float32),  # running max
                pltpu.VMEM((block_q, 128), jnp.float32),  # running denom
                pltpu.VMEM((block_q, hd), jnp.float32),  # P.V accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, sq_pad, hd), q.dtype),
        interpret=interpret,
        name="streaming_attention",
    )(meta, valid, *args)

    return out[:, :, :Sq, :].transpose(0, 2, 1, 3)
