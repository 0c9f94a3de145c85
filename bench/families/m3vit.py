"""M3ViT image classification, served W8A8 through the repository's
``VisionEngine``; the configuration file's sizes are the model.

The system under test is ``VisionEngine(...)`` with its default admission
(``max_wait_s`` 2 ms, two batches in flight, introspection on) and the
configuration's batch buckets, serving the int8 tree that
``m3vit_weights`` makes from the seed. Requests carry images from a pool
made from the seed before the window (standard-normal patches, the recipe
of the repository's ``serving.vision.synth_requests``).

Correctness: after the window, up to ``SAMPLE`` distinct images that were
served are drawn from the seed, one served answer each, and compared with
``m3vit_reference`` at float32 ``highest`` on the same tree. An answer's
error is the largest gap between a served top-k log-probability and the
reference's for that class. Over the sample:

  * ``logp_err``: the median error;
  * ``logp_err_geomean``: the geometric mean of the errors, each plus
    ``FLOOR``;
  * ``near_share``: the share of answers within 1e-4 of the reference;
  * ``top1_gap``: the largest amount by which the reference's logit of the
    served first class lies below its best, in units of the reference
    logits' standard deviation.

The configuration's ``check`` names which of them a run is held to, each
with its limit; the others are read for ``bench/control.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from families import m3vit_reference as ref
from families import m3vit_weights

SAMPLE = 256
POOL = 512
REF_CHUNK = 32
FLOOR = 1e-7  # added to each error before the geometric mean


def program_config(spec: dict):
    from repro.configs.base import (
        AttnConfig,
        ModelConfig,
        MoEConfig,
        QuantConfig,
    )
    from repro.models.vit import PATCH_DIM

    if spec["patch_dim"] != PATCH_DIM or spec["moe_every"] != 2:
        raise ValueError("the program's ViT takes 768-wide patches and an "
                         "expert layer every other block")
    return ModelConfig(
        name=spec["name"], family="vit_moe", num_layers=spec["num_layers"],
        d_model=spec["d_model"], d_ff=spec["d_ff"], vocab_size=0,
        norm="layernorm", act="gelu", glu=False,
        attn=AttnConfig(num_heads=spec["num_heads"],
                        num_kv_heads=spec["num_heads"],
                        head_dim=spec["head_dim"], rope_theta=0.0),
        moe=MoEConfig(num_experts=spec["num_experts"], top_k=spec["top_k"],
                      d_ff=spec["expert_d_ff"], moe_every=spec["moe_every"]),
        num_classes=spec["num_classes"], image_tokens=spec["image_tokens"],
        quant=QuantConfig(enable=True, w_bits=spec["w_bits"],
                          a_bits=spec["a_bits"],
                          attn_bits=spec["attn_bits"]),
        optimizer="adamw", remat=False,
    )


def make_pool(spec: dict, seed: int, n: int = POOL) -> np.ndarray:
    rng = np.random.default_rng([seed, 1])
    return rng.standard_normal(
        (n, spec["image_tokens"] - 1, spec["patch_dim"]), dtype=np.float32)


class System:
    """The engine, its tree and the image pool, built from the seed."""

    def __init__(self, spec: dict, seed: int):
        from repro.serving.scheduler import Backpressure
        from repro.serving.vision import VisionEngine, VisionRequest

        self.spec = spec
        self._request, self._refused = VisionRequest, Backpressure
        self.reseed(seed)
        self.engine = VisionEngine(
            program_config(spec), self.tree,
            batch_buckets=tuple(spec["batch_buckets"]),
            top_k=spec["answers"])
        self.engine.warmup()

    def reseed(self, seed: int) -> None:
        """Weights, images and bookkeeping of ``seed``; a built engine
        serves the new weights with the programs it has."""
        self.seed = seed
        self.tree = m3vit_weights.make_tree(self.spec, seed)
        self.pool = make_pool(self.spec, seed)
        self.order = np.random.default_rng([seed, 2]).permutation(len(self.pool))
        jax.block_until_ready(self.tree)
        if getattr(self, "engine", None) is not None:
            self.engine.params = self.tree
        self.delivered = {}  # uid -> delivery time
        self.answers = {}  # uid -> (classes, probs)

    def image_of(self, uid: int) -> int:
        return int(self.order[uid % len(self.order)])

    def _done(self, req) -> None:
        self.delivered[req.uid] = req.submitted_at + req.latency_s
        self.answers[req.uid] = (req.classes, req.probs)

    def submit(self, uid: int, due: float) -> bool:
        req = self._request(uid=uid, patches=self.pool[self.image_of(uid)],
                            submitted_at=due, on_done=self._done)
        try:
            self.engine.submit(req)
        except self._refused:
            return False
        return True

    def pump(self) -> None:
        self.engine.step()

    @property
    def busy(self) -> bool:
        return not self.engine.idle

    @property
    def queued(self) -> int:
        """Requests admitted and not yet in a dispatched batch."""
        return self.engine.scheduler.depth

    def drain(self) -> None:
        self.engine.flush()

    @property
    def counters(self) -> dict:
        return dict(self.engine.metrics.counters)

    def release(self) -> None:
        """Drop the engine and its compiled programs; the tree stays for the
        reference."""
        self.engine = None

    # -- correctness -------------------------------------------------------

    def sample(self) -> list:
        """Up to SAMPLE served uids of distinct images, drawn from the seed."""
        by_image = {}
        for uid in sorted(self.answers):
            by_image.setdefault(self.image_of(uid), []).append(uid)
        rng = np.random.default_rng([self.seed, 3])
        images = sorted(by_image)
        pick = rng.permutation(len(images))[:SAMPLE]
        return [by_image[images[i]][rng.integers(len(by_image[images[i]]))]
                for i in sorted(pick)]

    def reference_logits(self, uids: list, prec: str = "highest") -> np.ndarray:
        """Reference logits of the images of ``uids``, REF_CHUNK at a time,
        block by block."""
        embed, block, head = _reference_programs(
            m3vit_weights.sizes(self.spec), prec)
        out = []
        for i in range(0, len(uids), REF_CHUNK):
            idx = [self.image_of(u) for u in uids[i:i + REF_CHUNK]]
            n = len(idx)
            idx += [idx[0]] * (REF_CHUNK - n)  # one shape for every chunk
            x = embed(self.tree, jnp.asarray(self.pool[idx]))
            for j in range(self.spec["num_layers"] // 2):
                x = block(x, self.tree["pairs_dense"], j)
                x = block(x, self.tree["pairs_moe"], j)
            out.append(np.asarray(head(self.tree, x))[:n])
        return np.concatenate(out)

    def served(self, uids: list) -> tuple:
        classes = np.stack([self.answers[u][0] for u in uids])
        probs = np.stack([self.answers[u][1] for u in uids])
        return classes, probs


@functools.lru_cache(maxsize=None)
def _reference_programs(spec_items: tuple, prec: str) -> tuple:
    spec = {k: v for k, v in spec_items}

    def block(x, stack, i):
        return ref.block(x, jax.tree.map(lambda a: a[i], stack), spec, prec)

    return (jax.jit(lambda t, x: ref.embed(t, x, prec)), jax.jit(block),
            jax.jit(ref.head))


def compare(classes, probs, ref_logits) -> dict:
    """``top1_gap`` and the ``logp_err`` statistics of served answers
    against reference logits (see the module docstring)."""
    lg = ref_logits.astype(np.float64)
    std = float(np.std(lg))
    rows = np.arange(len(lg))
    gap = (lg.max(-1) - lg[rows, classes[:, 0]]) / std
    m = lg.max(-1, keepdims=True)
    logp = lg - (m + np.log(np.sum(np.exp(lg - m), -1, keepdims=True)))
    with np.errstate(divide="ignore"):
        served = np.log(probs.astype(np.float64))
    err = np.max(np.abs(served - np.take_along_axis(logp, classes, -1)), -1)
    err = np.where(np.isfinite(err), err, np.inf)
    return {"top1_gap": float(np.max(gap)),
            "logp_err": float(np.median(err)),
            "logp_err_geomean": float(np.exp(np.mean(np.log(err + FLOOR)))),
            "near_share": float(np.mean(err <= 1e-4))}


def answers_of(logits: np.ndarray, k: int) -> tuple:
    """Top-k classes and their probabilities of a batch of logits, as an
    engine would hand them back (for the control)."""
    lg = logits.astype(np.float64)
    p = np.exp(lg - lg.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    cls = np.argsort(-p, axis=-1, kind="stable")[:, :k]
    return cls, np.take_along_axis(p, cls, -1)


def check(system: System) -> dict:
    uids = system.sample()
    if not uids:
        return {k: float("inf") for k in system.spec["check"]}
    classes, probs = system.served(uids)
    ok = (np.all(np.isfinite(probs)) and np.all(probs > 0)
          and np.all(np.diff(probs, axis=-1) <= 0)
          and np.all((classes >= 0) & (classes < system.spec["num_classes"])))
    out = compare(classes, probs, system.reference_logits(uids))
    if not ok:
        out = {k: float("inf") for k in out}
    out["n"] = len(uids)
    return out


# -- work per image and per kernel call --------------------------------------

def flops_per_image(spec: dict) -> int:
    """Model operations of one real image: multiply-adds of every matmul
    counted twice, routed top-k expert work only."""
    d, S, P = spec["d_model"], spec["image_tokens"], spec["patch_dim"]
    H, hd = spec["num_heads"], spec["head_dim"]
    L, E, k = spec["num_layers"], spec["num_experts"], spec["top_k"]
    attn = 2 * S * d * 3 * H * hd + 4 * S * S * H * hd + 2 * S * H * hd * d
    mlp = 2 * 2 * S * d * spec["d_ff"]
    moe = 2 * S * d * E + k * 2 * 2 * S * d * spec["expert_d_ff"]
    n_moe = L // spec["moe_every"]
    return (2 * (S - 1) * P * d + L * attn + (L - n_moe) * mlp + n_moe * moe
            + 2 * d * spec["num_classes"])


def kernel_flops(spec: dict, batch: int) -> dict:
    """Per kernel, the operations of each call one classify step at
    ``batch`` images makes: the algorithm's work at the call's shapes, with
    no tile padding (int8 matmul 2MKN; grouped 2 rows K N over the routed
    rows; attention 4 B H S^2 head_dim)."""
    d, S = spec["d_model"], spec["image_tokens"]
    H, hd = spec["num_heads"], spec["head_dim"]
    L, E, k = spec["num_layers"], spec["num_experts"], spec["top_k"]
    fe, C = spec["expert_d_ff"], spec["num_classes"]
    M = batch * S
    n_moe = L // spec["moe_every"]
    mm = lambda m, kk, n: 2 * m * kk * n
    int8 = [mm(M, d, H * hd)] * 3 * L + [mm(M, H * hd, d)] * L
    int8 += [mm(M, d, spec["d_ff"]), mm(M, spec["d_ff"], d)] * (L - n_moe)
    int8 += [mm(M, d, E)] * n_moe + [mm(batch, d, C)]
    attn = [4 * batch * H * S * S * hd] * L
    grouped = [mm(M * k, d, fe), mm(M * k, fe, d)] * n_moe
    return {"int8_matmul": int8, "streaming_attention": attn,
            "grouped_matmul": grouped}
