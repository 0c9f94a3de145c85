"""Plain reference of W8A8 M3ViT classification, written from the model's
published description and the served tree's leaf contract, in straight
jax.numpy. It imports nothing of the program under test.

The served tree (what ``m3vit_weights.make_tree`` builds) holds, per linear
site ``w``: an int8 weight ``w`` [..., in, out], a per-output-channel f32
dequant scale ``w_scale`` and, where the site's input is quantized, the
per-tensor activation scale ``w_as`` (``wo_a_scale`` for the out-projections
and the fc2 inputs). Every norm carries the per-tensor scale ``a_scale`` of
its quantized output. The maths, layer by layer:

  embed   x = [cls; (patches @ dequant(patch_proj)) + patch_bias] + pos
  block   h = Q(LN1(x));  q,k,v = Qlin(h) + b;  heads of 64
          s = q k^T / sqrt(64);  A = 2^-ceil(c/2) (1 + odd(c)(sqrt2 - 1)),
          c = clip(round(-2 log2(e) (s - max s)), 0, 15);  l = sum exp(s - max)
          x += Qlin(Q((A v) / l)) + bo
          h = Q(LN2(x));  dense: x += Qlin(Q(gelu(Qlin(h) + bi))) + bo
          MoE: logits = Qlin(h) + gate_b, top-2, weights softmax(top-2),
               x += sum_k w_k (Qlin_e(Q(gelu(Qlin_e(h) + bi_e))) + bo_e)
  head    logits = Qlin(Q(LN(x_cls))) + head_b

Q(x) = clip(round(x / s), -128, 127) (times s where a value is needed);
Qlin multiplies the int8 codes exactly in int32 and rescales the sum once by
the activation and weight scales (an expert's by its weight scale, then the
activation scale). Each formula is written in the order of its published
form, so that the reference rounds where the description does. The float
dots (patch projection, q k^T and A v) run at the precision ``prec``
names: ``"highest"`` (float32, what the configuration states) or
``"bfloat16"`` (operands rounded to bfloat16, float32 accumulation: the
control).

With ``calibrate=True`` a scale missing from the tree is set from the
activation the site sees and recorded in the tree (max|x| / 127; a site fed
by a norm takes the norm's): ``m3vit_weights`` sets the scales so from a
calibration batch.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LOG2E = 1.4426950408889634
SQRT2 = 1.4142135623730951
QMAX = 127.0


def _fdot(a, b, prec: str):
    """Float contraction of the last axis of ``a`` with the first of ``b``."""
    if prec == "bfloat16":
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _feinsum(spec, a, b, prec: str):
    if prec == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _scale(node: dict, key: str, x, calibrate: bool):
    if key not in node:
        if not calibrate:
            raise KeyError(key)
        node[key] = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / QMAX
    return node[key]


def codes(x, s):
    """Symmetric 8-bit codes of ``x`` on the grid ``s`` (float values)."""
    return jnp.clip(jnp.round(x / s), -QMAX - 1, QMAX)


def layernorm(x, p, calibrate: bool = False):
    """LayerNorm (eps 1e-6) then the norm's output quantizer."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]
    return codes(y, _scale(p, "a_scale", y, calibrate)) * p["a_scale"]


def qlinear(x, node: dict, key: str, calibrate: bool = False,
            a_key: str | None = None):
    """int8 site ``node[key]``: quantize ``x`` with its activation scale,
    multiply exactly in int32, rescale by the activation and weight scales."""
    a_key = a_key or key + "_as"
    s_a = _scale(node, a_key, x, calibrate)
    xq = codes(x, s_a).astype(jnp.int8)
    acc = jnp.dot(xq, node[key], preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (s_a * node[key + "_scale"])


def gelu(x):
    """GELU, tanh form: x * 0.5 (1 + tanh(sqrt(2/pi) (x + 0.044715 x^3)))."""
    return jax.nn.gelu(x, approximate=True)


def logsqrt2(s, m, bits: int):
    """Dequantized log-sqrt2 codes of the softmax numerator exp(s - m)."""
    c = jnp.clip(jnp.round(-2.0 * LOG2E * (s - m)), 0, 2 ** bits - 1)
    c = c.astype(jnp.int32)
    return jnp.exp2(-((c + 1) // 2).astype(jnp.float32)) * (
        1.0 + (c % 2).astype(jnp.float32) * (SQRT2 - 1.0))


def attention(h, p: dict, cfg: dict, prec: str, calibrate: bool):
    B, S, D = h.shape
    H, hd = cfg["num_heads"], cfg["head_dim"]
    q, k, v = (
        (qlinear(h, p, w, calibrate) + p["b" + w[1]]).reshape(B, S, H, hd)
        for w in ("wq", "wk", "wv"))
    s = _feinsum("bqhd,bkhd->bhqk", q, k, prec) / math.sqrt(hd)
    m = jnp.max(s, axis=-1, keepdims=True)
    l = jnp.sum(jnp.exp(s - m), axis=-1, keepdims=True)
    a = logsqrt2(s, m, cfg["attn_bits"])
    o = _feinsum("bhqk,bkhd->bqhd", a, v, prec)
    o = (o / jnp.transpose(l, (0, 2, 1, 3))).reshape(B, S, H * hd)
    return qlinear(o, p, "wo", calibrate, a_key="wo_a_scale") + p["bo"]


def mlp(h, p: dict, calibrate: bool):
    t = gelu(qlinear(h, p, "wi", calibrate) + p["bi"])
    return qlinear(t, p, "wo", calibrate, a_key="wo_a_scale") + p["bo"]


def moe(h, p: dict, cfg: dict, calibrate: bool):
    """Top-k routed experts, every expert computed densely over all tokens
    and combined with the router's weights where it was chosen."""
    B, S, D = h.shape
    x = h.reshape(B * S, D)
    logits = qlinear(x, p, "gate", calibrate) + p["gate_b"]
    top_v, top_i = jax.lax.top_k(logits, cfg["top_k"])
    w = jax.nn.softmax(top_v, axis=-1)
    if calibrate:
        # fc2's input scale over every routed (token, expert) pair
        mids = []
        for e in range(cfg["num_experts"]):
            t = gelu(_expert_fc1(x, p, e))
            chosen = jnp.any(top_i == e, axis=-1, keepdims=True)
            mids.append(jnp.max(jnp.where(chosen, jnp.abs(t), 0.0)))
        p["wo_a_scale"] = jnp.maximum(jnp.max(jnp.stack(mids)), 1e-8) / QMAX
    y = jnp.zeros_like(x)
    for e in range(cfg["num_experts"]):
        t = gelu(_expert_fc1(x, p, e))
        ye = _expert_fc2(t, p, e)
        we = jnp.sum(jnp.where(top_i == e, w, 0.0), axis=-1, keepdims=True)
        y = y + we * ye
    return y.reshape(B, S, D)


def _expert_fc1(x, p, e):
    xq = codes(x, p["wi_as"]).astype(jnp.int8)
    acc = jnp.dot(xq, p["wi"][e], preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * p["wi_scale"][e] * p["wi_as"] + p["bi"][e]


def _expert_fc2(t, p, e):
    tq = codes(t, p["wo_a_scale"]).astype(jnp.int8)
    acc = jnp.dot(tq, p["wo"][e], preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * p["wo_scale"][e] * p["wo_a_scale"]
            + p["bo"][e])


def block(x, lp: dict, cfg: dict, prec: str = "highest",
          calibrate: bool = False):
    """One encoder block on [B, S, D]; ``lp`` has ``ln1``, ``attn``, ``ln2``
    and ``mlp`` or ``moe``."""
    h = layernorm(x, lp["ln1"], calibrate)
    if calibrate:
        _consumers(lp["ln1"], lp["attn"], ("wq", "wk", "wv"))
    x = x + attention(h, lp["attn"], cfg, prec, calibrate)
    h = layernorm(x, lp["ln2"], calibrate)
    if "moe" in lp:
        if calibrate:
            _consumers(lp["ln2"], lp["moe"], ("gate", "wi"))
        return x + moe(h, lp["moe"], cfg, calibrate)
    if calibrate:
        _consumers(lp["ln2"], lp["mlp"], ("wi",))
    return x + mlp(h, lp["mlp"], calibrate)


def _consumers(norm: dict, node: dict, keys) -> None:
    """A site fed by a norm quantizes on the norm's own grid."""
    for k in keys:
        node.setdefault(k + "_as", norm["a_scale"])


def embed(tree: dict, patches, prec: str = "highest"):
    B, T, P = patches.shape
    w = tree["patch_proj"].astype(jnp.float32)
    x = _fdot(patches.reshape(B * T, P), w, prec) * tree["patch_proj_scale"]
    x = x.reshape(B, T, -1) + tree["patch_bias"]
    cls = jnp.broadcast_to(tree["cls_token"], (B, 1, x.shape[-1]))
    return jnp.concatenate([cls, x], axis=1) + tree["pos_embed"]


def head(tree: dict, x, calibrate: bool = False):
    """Class logits from the class token of the last block's output."""
    h = layernorm(x[:, 0, :], tree["final_norm"], calibrate)
    if calibrate:
        _consumers(tree["final_norm"], tree, ("head",))
    return qlinear(h, tree, "head", calibrate) + tree["head_b"]


def logits(tree: dict, cfg: dict, patches, prec: str = "highest"):
    """Class logits [B, num_classes] of patches [B, tokens - 1, patch_dim]:
    the blocks run dense, MoE, dense, ... from the stacked pairs."""
    x = embed(tree, patches, prec)
    for i in range(cfg["num_layers"] // 2):
        for kind in ("pairs_dense", "pairs_moe"):
            x = block(x, jax.tree.map(lambda a: a[i], tree[kind]), cfg, prec)
    return head(tree, x)
