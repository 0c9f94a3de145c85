"""Pallas TPU tiled W8A8 matmul (paper Eqs. 7/9).

int8 x int8 -> int32 tiles accumulate on the MXU's int8 datapath (2x bf16
throughput on TPU — the MXU analogue of the paper's INT8 DSP packing); the
single product-of-scales rescale of Eq. 9 (per-tensor activation scale x
per-output-channel weight scale) is applied once on the int32 accumulator at
the flush, exactly as the FPGA design applies it once after the systolic
array. Bias add is fused into the same flush.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.expert_linear import _INT_PRECISION


def _int8_mm_kernel(
    x_ref,  # [bm, bk] int8
    w_ref,  # [bk, bn] int8
    xs_ref,  # [1, 1] f32 per-tensor activation scale
    ws_ref,  # [1, bn] f32 per-channel weight scale
    *rest,  # (bias_ref?, o_ref, acc)
    n_k: int,
    has_bias: bool,
):
    if has_bias:
        b_ref, o_ref, acc = rest
    else:
        b_ref = None
        o_ref, acc = rest
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot(
        x_ref[...], w_ref[...], precision=_INT_PRECISION,
        preferred_element_type=jnp.int32,
    )

    @pl.when(ik == n_k - 1)
    def _flush():
        y = acc[...].astype(jnp.float32) * (xs_ref[0, 0] * ws_ref[0][None, :])
        if has_bias:
            y = y + b_ref[0][None, :]
        o_ref[...] = y.astype(o_ref.dtype)


def int8_matmul(
    x_q: jnp.ndarray,  # int8 [M, K]
    w_q: jnp.ndarray,  # int8 [K, N]
    x_scale: jnp.ndarray,  # f32 scalar
    w_scale: jnp.ndarray,  # f32 [N]
    bias: Optional[jnp.ndarray] = None,  # f32 [N]
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    M, K = x_q.shape
    _, N = w_q.shape
    block_m = min(block_m, max(M, 1))
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    n_m, n_n, n_k = pl.cdiv(M, block_m), pl.cdiv(N, block_n), pl.cdiv(K, block_k)
    mp, np_, kp = n_m * block_m, n_n * block_n, n_k * block_k

    xp = jnp.pad(x_q, ((0, mp - M), (0, kp - K)))
    wp = jnp.pad(w_q, ((0, kp - K), (0, np_ - N)))
    xs = jnp.asarray(x_scale, jnp.float32).reshape(1, 1)
    ws = jnp.pad(w_scale.astype(jnp.float32).reshape(1, -1), ((0, 0), (0, np_ - N)))
    has_bias = bias is not None

    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda m, n, k: (m, k)),
        pl.BlockSpec((block_k, block_n), lambda m, n, k: (k, n)),
        pl.BlockSpec((1, 1), lambda m, n, k: (0, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((1, block_n), lambda m, n, k: (0, n)),
    ]
    args = [xp, wp, xs, ws]
    if has_bias:
        bp = jnp.pad(bias.astype(jnp.float32).reshape(1, -1), ((0, 0), (0, np_ - N)))
        in_specs.append(pl.BlockSpec((1, block_n), lambda m, n, k: (0, n)))
        args.append(bp)

    out = pl.pallas_call(
        functools.partial(_int8_mm_kernel, n_k=n_k, has_bias=has_bias),
        grid=(n_m, n_n, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        name="int8_matmul",
    )(*args)
    return out[:M, :N]
