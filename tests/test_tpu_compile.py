"""The main path's Pallas kernels compile for a TPU v5e, at M3ViT-small
widths (d_model 384, 6 heads of 64, 16 experts, expert d_ff 1536, 197
tokens).

Nothing runs: the chip is described, not attached, and the TPU compiler
refuses here what it would refuse on the chip — block shapes that break the
(8, 128) tiling, more VMEM than a kernel may hold — which interpret mode
cannot show. The kernels are reached through ``kernels.ops`` with
``REPRO_PALLAS=pallas``, as a TPU run reaches them.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D, HEADS, HD, E, FF, TOKENS = 384, 6, 64, 16, 1536, 197
B = 8  # a serving bucket
ROWS = B * TOKENS * 2  # routed rows of one MoE layer (top-2)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip, monkeypatch):
    """``compile_for_chip(fn, *(shape, dtype))`` -> the compiled program's
    HLO text. The persistent compile cache is off meanwhile: an entry
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("REPRO_PALLAS", "pallas")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in specs]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("din,dout", [(D, FF), (FF, D)], ids=["fc1", "fc2"])
def test_grouped_matmul_int8_with_scales(compile_for_chip, din, dout):
    hlo = compile_for_chip(
        lambda x, w, g, s, a: ops.grouped_matmul(x, w, g, w_scale=s,
                                                 a_scale=a),
        ((ROWS, din), jnp.float32), ((E, din, dout), jnp.int8),
        ((E,), jnp.int32), ((E, dout), jnp.float32), ((), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_grouped_matmul_int4_packed(compile_for_chip):
    hlo = compile_for_chip(
        lambda x, w, g, s, a: ops.grouped_matmul(x, w, g, w_scale=s,
                                                 a_scale=a),
        ((ROWS, D), jnp.float32), ((E, D // 2, FF), jnp.uint8),
        ((E,), jnp.int32), ((E, FF), jnp.float32), ((), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_streaming_attention_197_tokens_4bit(compile_for_chip):
    """Buckets 8 and 32 at the default tiles: one 200-row Q tile over one
    256-key tile, the one-visit schedule."""
    from repro.kernels.quant_attention import SCHEDULES

    for batch in (B, 32):
        qkv = ((batch, TOKENS, HEADS, HD), jnp.float32)
        before = SCHEDULES["one_visit"]
        hlo = compile_for_chip(
            lambda q, k, v: ops.attention(q, k, v, causal=False,
                                          quant_bits=4),
            qkv, qkv, qkv)
        assert "tpu_custom_call" in hlo
        assert f"f32[{batch},{HEADS},200,{HD}]" in hlo
        assert SCHEDULES["one_visit"] == before + 1


def test_decode_attention_over_int8_kv_cache(compile_for_chip):
    max_len = 512
    kv = ((B, max_len, HEADS, HD), jnp.int8)
    scale = ((B, max_len, HEADS), jnp.float32)
    hlo = compile_for_chip(
        lambda q, k, v, ks, vs, n: ops.attention(
            q, k, v, causal=True, q_offset=n, k_scale=ks, v_scale=vs,
            kv_valid_len=n + 1),
        ((B, 1, HEADS, HD), jnp.float32), kv, kv, scale, scale,
        ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_one_visit_attention_over_int8_kv_cache(compile_for_chip):
    """4-bit attention of an 8-token chunk over an int8 cache of 256 keys
    with dequant scales: one K tile, so the one-visit schedule."""
    from repro.kernels.quant_attention import SCHEDULES

    max_len = 256
    kv = ((B, max_len, HEADS, HD), jnp.int8)
    scale = ((B, max_len, HEADS), jnp.float32)
    before = SCHEDULES["one_visit"]
    hlo = compile_for_chip(
        lambda q, k, v, ks, vs, n: ops.attention(
            q, k, v, causal=True, q_offset=n, quant_bits=4, k_scale=ks,
            v_scale=vs, kv_valid_len=n + 8),
        ((B, 8, HEADS, HD), jnp.float32), kv, kv, scale, scale,
        ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo
    assert SCHEDULES["one_visit"] == before + 1


@pytest.mark.parametrize("m,k,n", [(B * TOKENS, D, D), (B, D, FF)],
                         ids=["qkv", "decode"])
def test_int8_matmul(compile_for_chip, m, k, n):
    hlo = compile_for_chip(
        ops.int8_matmul, ((m, k), jnp.int8), ((k, n), jnp.int8),
        ((), jnp.float32), ((n,), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_integer_kernels_compile_under_highest_precision(compile_for_chip):
    """An outer ``default_matmul_precision("highest")`` (how the jnp
    reference is run on the chip) must not reach the integer dots: Mosaic
    refuses an fp32 contraction of int8 operands."""
    with jax.default_matmul_precision("highest"):
        hlo = compile_for_chip(
            lambda x, w, g, s, a, xq, wq, xs, ws: (
                ops.grouped_matmul(x, w, g, w_scale=s, a_scale=a),
                ops.int8_matmul(xq, wq, xs, ws)),
            ((ROWS, D), jnp.float32), ((E, D, FF), jnp.int8),
            ((E,), jnp.int32), ((E, FF), jnp.float32), ((), jnp.float32),
            ((B * TOKENS, D), jnp.int8), ((D, D), jnp.int8),
            ((), jnp.float32), ((D,), jnp.float32))
    assert hlo.count("tpu_custom_call") >= 2
