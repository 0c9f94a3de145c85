"""The served W8A8 M3ViT tree, made on the device from a seed in one jitted
call, in the form the engine serves it: int8 weights with per-output-channel
scales, per-tensor activation scales, float32 norms, biases and embeddings.
Imports nothing of the program under test.

Initialisation (no checkpoint is public for this deployment, so the sizes
alone come from the paper and these choices are the benchmark's own):

  * linear weights N(0, 1/fan_in); the projections that write to the
    residual stream (attention out, fc2 of the MLP and of every expert)
    are further scaled by 1/sqrt(2 * num_layers), as in GPT-2;
  * the router N(0, 1/d_model), so its logits have unit spread;
  * biases, class token and position embedding N(0, 0.02^2); LayerNorm gain
    1 + N(0, 0.05^2), shift N(0, 0.02^2);
  * weights quantized per output channel to int8 by their absmax;
  * activation scales from one calibration batch of ``CALIB_IMAGES``
    standard-normal images: absmax / 127 at every quantized site (over the
    routed pairs only at an expert's fc2), with each site that a norm
    feeds quantizing on the norm's grid.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from families.m3vit_reference import QMAX, block, embed, head

CALIB_IMAGES = 4


def key_data(seed: int) -> np.ndarray:
    """Threefry key words of any non-negative seed below 2**64."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def _quantize(w):
    """Per-output-channel int8 of a [..., in, out] weight."""
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2), 1e-8) / QMAX
    return jnp.clip(jnp.round(w / s[..., None, :]), -QMAX, QMAX).astype(
        jnp.int8), s


class _Keys:
    def __init__(self, key):
        self.key, self.n = key, 0

    def normal(self, shape, std):
        self.n += 1
        return std * jax.random.normal(jax.random.fold_in(self.key, self.n),
                                       shape, jnp.float32)


def _linear(node: dict, name: str, keys: _Keys, shape, std):
    node[name], node[name + "_scale"] = _quantize(keys.normal(shape, std))


def _norm(keys: _Keys, d: int) -> dict:
    return {"scale": 1.0 + keys.normal((d,), 0.05),
            "bias": keys.normal((d,), 0.02)}


def _layer(keys: _Keys, cfg: dict, moe: bool) -> dict:
    d, f = cfg["d_model"], cfg["d_ff"]
    res = 1.0 / math.sqrt(2 * cfg["num_layers"])
    attn = {}
    for w in ("wq", "wk", "wv"):
        _linear(attn, w, keys, (d, d), d ** -0.5)
        attn["b" + w[1]] = keys.normal((d,), 0.02)
    _linear(attn, "wo", keys, (d, d), res * d ** -0.5)
    attn["bo"] = keys.normal((d,), 0.02)
    lp = {"ln1": _norm(keys, d), "attn": attn, "ln2": _norm(keys, d)}
    if moe:
        E, fe = cfg["num_experts"], cfg["expert_d_ff"]
        m = {}
        _linear(m, "gate", keys, (d, E), d ** -0.5)
        m["gate_b"] = keys.normal((E,), 0.02)
        _linear(m, "wi", keys, (E, d, fe), d ** -0.5)
        m["bi"] = keys.normal((E, fe), 0.02)
        _linear(m, "wo", keys, (E, fe, d), res * fe ** -0.5)
        m["bo"] = keys.normal((E, d), 0.02)
        lp["moe"] = m
    else:
        m = {}
        _linear(m, "wi", keys, (d, f), d ** -0.5)
        m["bi"] = keys.normal((f,), 0.02)
        _linear(m, "wo", keys, (f, d), res * f ** -0.5)
        m["bo"] = keys.normal((d,), 0.02)
        lp["mlp"] = m
    return lp


def _stack(dicts: list) -> dict:
    return jax.tree.map(lambda *a: jnp.stack(a), *dicts)


def _make(kd, cfg: dict) -> dict:
    keys = _Keys(jax.random.wrap_key_data(kd))
    d = cfg["d_model"]
    tree = {
        "patch_bias": keys.normal((d,), 0.02),
        "cls_token": keys.normal((1, 1, d), 0.02),
        "pos_embed": keys.normal((cfg["image_tokens"], d), 0.02),
        "final_norm": _norm(keys, d),
        "head_b": keys.normal((cfg["num_classes"],), 0.02),
    }
    _linear(tree, "patch_proj", keys, (cfg["patch_dim"], d),
            cfg["patch_dim"] ** -0.5)
    _linear(tree, "head", keys, (d, cfg["num_classes"]), d ** -0.5)
    pairs = cfg["num_layers"] // 2
    dense = _stack([_layer(keys, cfg, moe=False) for _ in range(pairs)])
    moe = _stack([_layer(keys, cfg, moe=True) for _ in range(pairs)])

    def calibrate(x, pair):
        # each block records its scales on the output of the one before
        lps = jax.tree.map(lambda a: a, pair)  # fresh dicts to record into
        for lp in lps:
            x = block(x, lp, cfg, calibrate=True)
        return x, lps

    x = embed(tree, keys.normal(
        (CALIB_IMAGES, cfg["image_tokens"] - 1, cfg["patch_dim"]), 1.0))
    x, (tree["pairs_dense"], tree["pairs_moe"]) = jax.lax.scan(
        calibrate, x, (dense, moe))
    head(tree, x, calibrate=True)
    return tree


@functools.lru_cache(maxsize=None)
def _jitted(cfg_items: tuple):
    cfg = dict(cfg_items)
    return jax.jit(lambda kd: _make(kd, cfg))


def sizes(cfg: dict) -> tuple:
    """The scalar entries of a configuration, hashable."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def make_tree(cfg: dict, seed: int) -> dict:
    """The served tree of ``cfg`` (a configuration file's sizes) for
    ``seed``, on the default device."""
    return _jitted(sizes(cfg))(key_data(seed))
