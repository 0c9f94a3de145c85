"""jit'd dispatch wrappers for the Pallas kernels.

Backend selection (``REPRO_PALLAS``, read at trace time):
  * TPU backend          -> pl.pallas_call kernels (VMEM-tiled)
  * CPU / tests          -> pure-jnp reference (ref.py; ragged_dot for the
                            grouped matmul on CPU)
  * REPRO_PALLAS=ref     -> the reference on any backend (on TPU: the
                            on-chip oracle for the kernels)
  * REPRO_PALLAS=interpret -> pallas kernels in interpret mode (correctness
                              validation of the kernel bodies on CPU)
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as _ref


def kernel_mode() -> str:
    """'pallas', 'interpret' or 'ref': ``REPRO_PALLAS`` when set, else
    pallas on a TPU backend and ref elsewhere."""
    env = os.environ.get("REPRO_PALLAS", "auto")
    if env in ("interpret", "pallas", "ref"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# Mesh over which the operands of kernel calls are replicated while a
# forward is traced (the expert-parallel mesh: distributed/expert_parallel
# .use_ep_mesh installs it). XLA's partitioner cannot split a Mosaic kernel,
# so under such a mesh each call runs per device (``_per_device``).
_REPLICA_MESH = None


@contextlib.contextmanager
def replicated_over(mesh):
    """Trace kernel calls in this scope per device of ``mesh``."""
    global _REPLICA_MESH
    prev = _REPLICA_MESH
    _REPLICA_MESH = mesh
    try:
        yield mesh
    finally:
        _REPLICA_MESH = prev


def _per_device(fn):
    """``fn`` as it must be called for a Pallas kernel on this trace.

    Under ``replicated_over(mesh)`` and outside a ``shard_map`` the call is
    wrapped in one over ``mesh`` and every device runs the kernel on its own
    copy of the operands; inside one (the EP shard body) the operands are
    already per-device and ``fn`` is returned as is."""
    mesh = _REPLICA_MESH
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset=0,
    quant_bits: int = 0,
    logit_softcap: float = 0.0,
    local_window: int = 0,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    kv_valid_len: Optional[jnp.ndarray] = None,
    q_segment_ids: Optional[jnp.ndarray] = None,  # [B, Sq] packed prefill
    kv_segment_ids: Optional[jnp.ndarray] = None,  # [B, Sk]
) -> jnp.ndarray:
    """Streaming attention; GQA-native (k/v carry KVH heads)."""
    mode = kernel_mode()
    if mode in ("pallas", "interpret"):
        from repro.kernels import autotune
        from repro.kernels.quant_attention import streaming_attention

        # trace-time tile lookup: tuned entry for this shape bucket when a
        # tuning table is active (kernels/autotune.py), defaults otherwise
        bq, bk = autotune.attn_blocks(
            q.shape[0], q.shape[2], k.shape[2], q.shape[3],
            q.shape[1], k.shape[1],
            causal=causal, quant_bits=quant_bits,
            scaled=k_scale is not None, q_dtype=q.dtype, k_dtype=k.dtype,
            local_window=local_window,
        )
        def call(q, k, v, q_offset, k_scale, v_scale, kv_valid_len,
                 q_segment_ids, kv_segment_ids):
            return streaming_attention(
                q, k, v,
                causal=causal, q_offset=q_offset, quant_bits=quant_bits,
                logit_softcap=logit_softcap, local_window=local_window,
                k_scale=k_scale, v_scale=v_scale, kv_valid_len=kv_valid_len,
                q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
                block_q=bq, block_k=bk,
                interpret=(mode == "interpret"),
            )

        return _per_device(call)(
            q, k, v, jnp.asarray(q_offset, jnp.int32), k_scale, v_scale,
            kv_valid_len, q_segment_ids, kv_segment_ids)
    return _ref.flash_attention_ref(
        q, k, v,
        causal=causal, q_offset=q_offset, quant_bits=quant_bits,
        logit_softcap=logit_softcap, local_window=local_window,
        k_scale=k_scale, v_scale=v_scale, kv_valid_len=kv_valid_len,
        q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
    )


def grouped_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    group_sizes: jnp.ndarray,
    *,
    w_scale: Optional[jnp.ndarray] = None,  # [G, Dout] per-expert dequant
    a_scale: Optional[jnp.ndarray] = None,  # f32 scalar activation scale
    a_bits: int = 8,
) -> jnp.ndarray:
    """Unified sparse/dense linear: y[t] = x[t] @ w[group(t)].

    int8 weights (QuantizedParams expert stacks) execute as stored: an fp
    ``x`` is quantized here with the folded ``a_scale``, the contraction
    accumulates int8 x int8 -> int32, and the product-of-scales dequant
    lands once on the accumulator — the full-precision expert weights are
    never materialized outside the kernel.
    """
    mode = kernel_mode()
    int8_w = w.dtype == jnp.int8
    int4_w = w.dtype == jnp.uint8  # nibble-packed int4 stack (W4A8)
    if (int8_w or int4_w) and x.dtype != jnp.int8:
        if a_scale is None:
            raise ValueError(
                f"{'int4' if int4_w else 'int8'} grouped weights need the "
                "folded activation scale (a PTQ QuantizedParams tree "
                "carries it as the `wi_as` / `wo_a_scale` leaf — was the "
                "model calibrated with taps?)"
            )
        from repro.core.quant.qtypes import quantize_sym

        x = quantize_sym(x.astype(jnp.float32), a_scale, a_bits)
    if mode in ("pallas", "interpret"):
        from repro.kernels import autotune
        from repro.kernels.expert_linear import grouped_matmul as gmm

        bm, bn = autotune.gmm_blocks(
            x.shape[0], w.shape[0], x.shape[1], w.shape[2],
            x_dtype=x.dtype, w_dtype=w.dtype,
            scaled=w_scale is not None, ascaled=a_scale is not None,
        )
        def call(x, w, group_sizes, w_scale, a_scale):
            return gmm(x, w, group_sizes, w_scale=w_scale, a_scale=a_scale,
                       block_m=bm, block_n=bn,
                       interpret=(mode == "interpret"))

        return _per_device(call)(x, w, group_sizes, w_scale, a_scale)
    if jax.default_backend() == "tpu":
        # ragged_dot lowers to a Pallas kernel on TPU: the ref mode there
        # runs the plain jnp oracles
        if int4_w:
            return _ref.grouped_matmul_q4_ref(x, w, group_sizes, w_scale,
                                              a_scale)
        if int8_w:
            return _ref.grouped_matmul_q_ref(x, w, group_sizes, w_scale,
                                             a_scale)
        return _ref.grouped_matmul_ref(x, w, group_sizes)
    # ragged_dot is the fast XLA path on CPU (grouped_matmul_ref is the
    # oracle used by tests; ragged_dot matches it exactly). The oracles are
    # not used here: their per-group loop does G dense products where
    # ragged_dot does one, and the int4 oracle unpacks the full-width int8
    # stack that test_int4_path's jaxpr check forbids.
    if int4_w:
        # Nibble-planar contraction: the low-nibble plane multiplies the
        # even activation columns, the high-nibble plane the odd columns —
        # two half-width ragged_dots whose int32 sum equals the unpacked
        # contraction exactly. The full-width int8 expert stack is never
        # materialized (the jaxpr only holds [G, Din/2, Dout] planes).
        P = w.shape[1]
        xp = x if x.shape[1] == 2 * P else jnp.pad(
            x, ((0, 0), (0, 2 * P - x.shape[1])))
        w32 = w.astype(jnp.int32)
        lo = ((w32 & 0xF) - ((w32 & 0x8) << 1)).astype(jnp.int8)
        h4 = (w32 >> 4) & 0xF
        hi = (h4 - ((h4 & 0x8) << 1)).astype(jnp.int8)
        gs = group_sizes.astype(jnp.int32)
        acc = (
            jax.lax.ragged_dot(xp[:, 0::2], lo, gs,
                               preferred_element_type=jnp.int32)
            + jax.lax.ragged_dot(xp[:, 1::2], hi, gs,
                                 preferred_element_type=jnp.int32)
        )
        y = acc.astype(jnp.float32)
        seg = _ref.row_groups(group_sizes, x.shape[0])
        if w_scale is not None:
            y = y * w_scale[seg]
        if a_scale is not None:
            y = y * a_scale
        return y
    if int8_w:
        acc = jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                                 preferred_element_type=jnp.int32)
        y = acc.astype(jnp.float32)
        seg = _ref.row_groups(group_sizes, x.shape[0])
        if w_scale is not None:
            y = y * w_scale[seg]
        if a_scale is not None:
            y = y * a_scale
        return y
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32))


def grouped_mlp(
    x: jnp.ndarray,
    wi: jnp.ndarray,
    wo: jnp.ndarray,
    group_sizes: jnp.ndarray,
    *,
    act: str = "silu",
    glu: bool = True,
    bi: Optional[jnp.ndarray] = None,  # [G, hid] per-expert fc1 bias
    bo: Optional[jnp.ndarray] = None,  # [G, out] per-expert fc2 bias
    taps=None,  # PTQ calibration collector (records the fc2 input site)
    mid_a_scale: Optional[jnp.ndarray] = None,  # PTQ runtime fc2-input scale
    a_bits: int = 8,  # activation quantizer width (fc1 + fc2 inputs)
    wi_scale: Optional[jnp.ndarray] = None,  # [G, hid] int8 fc1 dequant
    wo_scale: Optional[jnp.ndarray] = None,  # [G, out] int8 fc2 dequant
    wi_a_scale: Optional[jnp.ndarray] = None,  # folded fc1 input scale
) -> jnp.ndarray:
    from repro.core.quant.calibrate import maybe_record
    from repro.models.layers import act_fn

    seg = None
    if bi is not None or bo is not None:
        seg = _ref.row_groups(group_sizes, x.shape[0])
    h = grouped_matmul(x, wi, group_sizes, w_scale=wi_scale,
                       a_scale=wi_a_scale, a_bits=a_bits)
    if bi is not None:
        h = h + bi[seg]
    if glu:
        g, u = jnp.split(h, 2, axis=-1)
        h = act_fn(act)(g) * u
    else:
        h = act_fn(act)(h)
    maybe_record(taps, "moe_mid", h)
    if wo.dtype in (jnp.int8, jnp.uint8):
        # real-int8/packed-int4 fc2: mid_a_scale is the *actual* quantizer
        # here (same value the fake-quant oracle clips to — identical grids)
        y = grouped_matmul(h, wo, group_sizes, w_scale=wo_scale,
                           a_scale=mid_a_scale, a_bits=a_bits)
    else:
        if mid_a_scale is not None:
            from repro.core.quant.linear_quant import fake_quant_activation

            h = fake_quant_activation(
                h.astype(jnp.float32), mid_a_scale, bits=a_bits
            ).astype(h.dtype)
        y = grouped_matmul(h, wo, group_sizes)
    if bo is not None:
        y = y + bo[seg]
    return y


def selective_scan(x, dt, b, c, a, d):
    """Mamba-1 selective scan: VMEM-resident state on TPU (O(S*d) HBM).

    Returns (y [B,S,di], h_last [B,di,N]). The ref path exists for the
    kernel tests; the model's CPU lowering keeps the chunked associative
    scan in models/ssm.py (bounded memory without a kernel).
    """
    mode = kernel_mode()
    if mode in ("pallas", "interpret"):
        from repro.kernels.selective_scan import selective_scan as ss

        return ss(x, dt, b, c, a, d, interpret=(mode == "interpret"))
    y = _ref.selective_scan_ref(x, dt, b, c, a, d)
    # ref h_last for parity (small shapes only — test/debug path)
    decay = jnp.exp(dt[..., None].astype(jnp.float32) * a)
    u = (dt * x)[..., None].astype(jnp.float32) * b[:, :, None, :].astype(jnp.float32)
    import jax as _jax

    def op(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    _, h = _jax.lax.associative_scan(op, (decay, u), axis=1)
    return y, h[:, -1]


def int8_matmul(
    x_q: jnp.ndarray,
    w_q: jnp.ndarray,
    x_scale: jnp.ndarray,
    w_scale: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    mode = kernel_mode()
    if mode in ("pallas", "interpret"):
        from repro.kernels.int8_matmul import int8_matmul as imm

        call = lambda *a: imm(*a, interpret=(mode == "interpret"))
        return _per_device(call)(x_q, w_q, x_scale, w_scale, bias)
    return _ref.int8_matmul_ref(x_q, w_q, x_scale, w_scale, bias)
