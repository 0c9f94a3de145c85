"""Pallas TPU unified sparse/dense grouped matmul — CoQMoE section 4.2(b).

The paper deploys N_L compute units behind one round-robin router that
streams token tiles to CUs while each expert's weights are fetched from
off-chip exactly once per layer (temporal locality, Fig. 5(c)); a
runtime-reconfigurable selection policy switches the same hardware between
sparse (MoE expert) and dense (MLP) modes.

TPU-native realization: tokens arrive pre-sorted by expert id (the sort is
the router); the kernel walks *work items* = (group, m-tile) pairs built from
``group_sizes`` and streamed in via scalar prefetch. For each work item the
group's weight tile stays HBM-resident exactly as long as its token rows
need it — each expert's weights cross HBM->VMEM once per layer regardless of
token parallelism (the paper's O(1) weight-traffic property). Dense mode is
the same kernel with num_groups == 1.

Work-item construction (the "router table"): group g covers sorted rows
[start_g, end_g); it touches m-tiles floor(start/bm) .. floor((end-1)/bm).
Total work items <= nm + G (each group adds at most one partial tile), a
static bound, so the grid is static while the routing stays fully dynamic.

Grid is (n_tiles_n, n_work): n outer so all visits to one output tile are
consecutive; a VMEM accumulator carries partial sums across the (<=2) groups
sharing a tile and flushes on the last visit. Optional ``w_scale`` [G, N]
applies per-expert per-channel dequant (int8 expert weights) to each
partial before accumulation; optional ``a_scale`` (scalar, SMEM) applies the
per-tensor activation dequant once at the flush — together they realize the
single product-of-scales rescale of Eq. 9 on the int32 accumulator, so the
expert weights are never dequantized outside the kernel (the executable
QuantizedParams contract, DESIGN.md section 4).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128  # TPU vector-lane width (last tile dim)
# Integer products are exact in one pass; an explicit DEFAULT keeps an outer
# jax.default_matmul_precision("highest") from asking Mosaic for an fp32
# contraction, which it refuses for integer operands.
_INT_PRECISION = jax.lax.Precision.DEFAULT


def _round_up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def _sublane(dtype) -> int:
    """Min second-to-last tile dim for a dtype: 8 f32, 16 bf16, 32 int8
    (8 * packing factor vs 4-byte lanes)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def legal_gmm_blocks(block_m: int, block_n: int, T: int, Dout: int,
                     x_dtype=jnp.float32) -> Tuple[int, int]:
    """Clamp a requested (block_m, block_n) to the problem, then round UP
    to legal TPU tile multiples.

    A bare ``min(block_m, T)`` clamp yields TPU-illegal or wasteful tiny
    tiles (T=1 decode -> a 1-row m-tile); instead the clamped block rounds
    up to the x tile's sublane multiple (8 f32 / 16 bf16 / 32 int8 rows)
    and the lane multiple (128) — the kernel pads the operands to the
    rounded tile and slices the padding off, which is free, while the
    tile stays legal. The autotuner (kernels/autotune.py) uses the same
    function so its candidate grid and the kernel's effective tiles can
    never drift."""
    bm = _round_up(max(1, min(block_m, max(T, 1))), _sublane(x_dtype))
    bn = _round_up(max(1, min(block_n, max(Dout, 1))), LANE)
    return bm, bn


def _route_metadata(group_sizes: jnp.ndarray, block_m: int, n_work: int):
    """Work-item table of length ``n_work``: (g_ids, m_ids, row_start,
    row_end) per item. Padding items ride on the final tile with an *empty*
    row range so they contribute nothing and trigger no extra tile visits."""
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    n_m = jnp.maximum((ends[-1] + block_m - 1) // block_m, 1)
    first = starts // block_m
    last = jnp.where(sizes > 0, (ends - 1) // block_m, first)
    tiles = jnp.where(sizes > 0, last - first + 1, 0)
    off = jnp.cumsum(tiles)  # inclusive prefix
    w = jnp.arange(n_work, dtype=jnp.int32)
    active = w < off[-1]
    g = jnp.searchsorted(off, w, side="right").astype(jnp.int32)
    g = jnp.clip(g, 0, sizes.shape[0] - 1)
    off_excl = off - tiles  # exclusive prefix per group
    m = jnp.clip(first[g] + (w - off_excl[g]), 0, n_m - 1)
    row_start = jnp.where(active, starts[g], 0)
    row_end = jnp.where(active, ends[g], 0)
    return g, m.astype(jnp.int32), row_start, row_end


def _gmm_kernel(
    g_ids,  # [n_work] scalar prefetch
    m_ids,  # [n_work]
    row_start,  # [n_work] first sorted row of this work item's group
    row_end,  # [n_work] one-past-last row (start == end for padding)
    x_ref,  # [bm, Din]
    w_ref,  # [1, Din, bn] (int4_packed: [1, Din//2, bn] uint8 nibble pairs)
    *rest,  # (w_scale_ref?, a_scale_ref?, o_ref, acc)
    block_m: int,
    n_work: int,
    has_scale: bool,
    has_ascale: bool,
    int8_full: bool,
    int4_packed: bool,
):
    rest = list(rest)
    ws_ref = rest.pop(0) if has_scale else None
    as_ref = rest.pop(0) if has_ascale else None
    o_ref, acc = rest
    w = pl.program_id(1)
    g = g_ids[w]
    m = m_ids[w]

    prev = jnp.where(w > 0, m_ids[jnp.maximum(w - 1, 0)], -1)
    nxt = jnp.where(w < n_work - 1, m_ids[jnp.minimum(w + 1, n_work - 1)], -2)

    @pl.when(prev != m)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    rows = m * block_m + jax.lax.broadcasted_iota(
        jnp.int32, (block_m, 1), 0
    )
    in_group = (rows >= row_start[w]) & (rows < row_end[w])  # [bm, 1]

    if int4_packed:
        # Unpack the nibble-packed int4 tile in-register, right where the
        # fused w_scale/a_scale flush already lives: low nibble = even input
        # row 2p, high nibble = odd row 2p+1 (DESIGN.md section 13). Sign
        # extension of a 4-bit field: v - 16*(v>>3). The unpacked tile only
        # ever exists at [Din//2*2, bn] VMEM-tile granularity — no full
        # int8 expert stack is materialized anywhere.
        xi = jnp.where(in_group, x_ref[...], 0).astype(jnp.int8)
        wq = w_ref[0].astype(jnp.int32)  # [P, bn] packed nibble pairs
        lo = wq & 0xF
        hi = (wq >> 4) & 0xF
        lo = lo - ((lo & 0x8) << 1)
        hi = hi - ((hi & 0x8) << 1)
        wu = jnp.stack([lo, hi], axis=1)  # [P, 2, bn]
        wu = wu.reshape(2 * wq.shape[0], wq.shape[1]).astype(jnp.int8)
        part = jax.lax.dot(
            xi, wu, precision=_INT_PRECISION,
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
    elif int8_full:
        xi = jnp.where(in_group, x_ref[...], 0).astype(jnp.int8)
        part = jax.lax.dot(
            xi, w_ref[0], precision=_INT_PRECISION,
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
    else:
        xm = jnp.where(in_group, x_ref[...].astype(jnp.float32), 0.0)
        part = jax.lax.dot(
            xm, w_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
    if has_scale:
        part = part * ws_ref[0]  # [1, bn] row of this expert's scales
    acc[...] += part

    @pl.when(nxt != m)
    def _flush():
        out = acc[...]
        if has_ascale:
            out = out * as_ref[0, 0]
        o_ref[...] = out.astype(o_ref.dtype)


def grouped_matmul(
    x: jnp.ndarray,  # [T, Din] rows sorted by group
    w: jnp.ndarray,  # [G, Din, Dout]; uint8 = nibble-packed int4 [G, ceil(Din/2), Dout]
    group_sizes: jnp.ndarray,  # [G] int32, sum == T
    *,
    w_scale: Optional[jnp.ndarray] = None,  # [G, Dout] per-expert dequant
    a_scale: Optional[jnp.ndarray] = None,  # f32 scalar activation dequant
    out_dtype=None,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    T, Din = x.shape
    G, _, Dout = w.shape
    int4_packed = w.dtype == jnp.uint8
    if int4_packed:
        if x.dtype != jnp.int8:
            raise TypeError(
                "nibble-packed int4 weights require int8 activations "
                f"(W4A8); got x dtype {x.dtype}"
            )
        P = w.shape[1]
        if -(-Din // 2) != P:
            raise ValueError(
                f"packed weight dim {P} does not match input dim {Din} "
                f"(expected ceil(Din/2) = {-(-Din // 2)})"
            )
        if Din != 2 * P:  # odd Din: the packed pad row pairs with a zero col
            x = jnp.pad(x, ((0, 0), (0, 2 * P - Din)))
            Din = 2 * P
    int8_in = int4_packed or (x.dtype == jnp.int8 and w.dtype == jnp.int8)
    if T == 0:  # all groups empty: nothing routed this step
        return jnp.zeros(
            (0, Dout),
            out_dtype or (jnp.float32 if int8_in else x.dtype),
        )
    block_m, block_n = legal_gmm_blocks(block_m, block_n, T, Dout, x.dtype)
    n_m = pl.cdiv(T, block_m)
    n_n = pl.cdiv(Dout, block_n)
    t_pad, n_pad = n_m * block_m, n_n * block_n
    n_work = n_m + G

    xp = jnp.pad(x, ((0, t_pad - T), (0, 0)))
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, n_pad - Dout)))

    g_ids, m_ids, row_start, row_end = _route_metadata(
        group_sizes, block_m, n_work
    )

    int8_full = x.dtype == jnp.int8 and w.dtype == jnp.int8
    if out_dtype is None:
        out_dtype = jnp.float32 if (int8_full or int4_packed) else x.dtype
    has_scale = w_scale is not None
    has_ascale = a_scale is not None

    w_rows = w.shape[1]  # Din, or ceil(Din/2) packed
    in_specs = [
        pl.BlockSpec((block_m, Din), lambda n, wk, g_, m_, s_, e_: (m_[wk], 0)),
        pl.BlockSpec((1, w_rows, block_n), lambda n, wk, g_, m_, s_, e_: (g_[wk], 0, n)),
    ]
    args = [xp, wp]
    if has_scale:
        # [G, 1, n_pad]: the block's last two dims are (1, block_n) — the
        # full middle dim and a lane multiple, which the TPU tiling accepts
        # (a (1, block_n) block over [G, n_pad] is refused for G > 1)
        wsp = jnp.pad(w_scale.astype(jnp.float32),
                      ((0, 0), (0, n_pad - Dout)))[:, None, :]
        in_specs.append(
            pl.BlockSpec((1, 1, block_n),
                         lambda n, wk, g_, m_, s_, e_: (g_[wk], 0, n))
        )
        args.append(wsp)
    if has_ascale:
        in_specs.append(
            pl.BlockSpec((1, 1), lambda n, wk, g_, m_, s_, e_: (0, 0),
                         memory_space=pltpu.SMEM)
        )
        args.append(jnp.asarray(a_scale, jnp.float32).reshape(1, 1))

    kernel = functools.partial(
        _gmm_kernel,
        block_m=block_m,
        n_work=n_work,
        has_scale=has_scale,
        has_ascale=has_ascale,
        int8_full=int8_full,
        int4_packed=int4_packed,
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_n, n_work),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (block_m, block_n), lambda n, wk, g_, m_, s_, e_: (m_[wk], n)
            ),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t_pad, n_pad), out_dtype),
        interpret=interpret,
        name="grouped_matmul",
    )(g_ids, m_ids, row_start, row_end, *args)

    return out[:T, :Dout]
