"""Kernel autotuner (kernels/autotune.py): cache round-trip determinism,
interpret-mode parity across every swept candidate (int8 bit-identical),
graceful stale/corrupt-cache fallback, and the warmup cache-hit contract
(second warmup on the same device kind sweeps nothing)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AutotuneConfig
from repro.kernels import autotune, ref
from repro.kernels.expert_linear import grouped_matmul, legal_gmm_blocks
from repro.kernels.quant_attention import (
    legal_attn_blocks,
    streaming_attention,
)


@pytest.fixture(autouse=True)
def _isolated_table():
    """Never leak an active table (process-global state) across tests."""
    autotune.deactivate()
    yield
    autotune.deactivate()


def _gmm_req(int8=True):
    dt = jnp.int8 if int8 else jnp.float32
    return autotune.gmm_request(100, 4, 32, 48, x_dtype=dt, w_dtype=dt,
                                scaled=int8, ascaled=int8)


# ---------------------------------------------------------------------------
# Tile legality (the clamp-rounding satellite)
# ---------------------------------------------------------------------------

def test_clamped_blocks_round_up_to_legal_tiles():
    # T=1 decode used to clamp to a 1-row tile; now sublane-rounded
    assert legal_gmm_blocks(128, 128, 1, 48, jnp.float32) == (8, 128)
    assert legal_gmm_blocks(128, 128, 1, 48, jnp.bfloat16) == (16, 128)
    assert legal_gmm_blocks(128, 128, 1, 48, jnp.int8) == (32, 128)
    assert legal_gmm_blocks(256, 300, 1000, 300, jnp.float32) == (256, 384)
    assert legal_attn_blocks(128, 256, 1, 16) == (8, 128)
    assert legal_attn_blocks(128, 256, 1, 16, jnp.bfloat16) == (16, 128)
    assert legal_attn_blocks(48, 200, 1000, 1000) == (48, 256)


def test_decode_shaped_grouped_matmul_still_exact(rng):
    """T=1 (the shape the old clamp made a 1-row tile for)."""
    x = jnp.asarray(rng.standard_normal((1, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 16, 24)), jnp.float32)
    gs = jnp.asarray([0, 1, 0, 0], jnp.int32)
    y = grouped_matmul(x, w, gs, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.grouped_matmul_ref(x, w, gs)),
                               rtol=1e-5, atol=1e-5)


def test_candidate_grids_are_legal_and_default_first():
    req = _gmm_req(int8=True)
    cands = autotune.gmm_candidates(req)
    assert cands[0] == legal_gmm_blocks(*autotune.GMM_DEFAULT, req.get("T"),
                                        req.get("dout"), jnp.int8)
    for bm, bn in cands:
        assert bm % 32 == 0 and bn % 128 == 0  # int8 sublane + lane
    areq = autotune.attn_request(2, 2, 2, 32, 8, 64, causal=True,
                                 quant_bits=0, scaled=False,
                                 q_dtype=jnp.float32, k_dtype=jnp.float32)
    acands = autotune.attn_candidates(areq)
    assert acands[0] == legal_attn_blocks(*autotune.ATTN_DEFAULT, 8, 64)
    for bq, bk in acands:
        assert bq % 8 == 0 and bk % 128 == 0
    # candidate lists are deduped
    assert len(set(cands)) == len(cands)
    assert len(set(acands)) == len(acands)


def _vit_attn_blocks(Sq, Sk):
    return autotune.attn_blocks(32, 6, 6, 64, Sq, Sk, causal=False,
                                quant_bits=4, scaled=False,
                                q_dtype=jnp.float32, k_dtype=jnp.float32)


def test_attention_default_q_tile_covers_a_one_tile_row():
    """Where one K tile holds the key row, the default Q tile takes the
    whole query length (one grid step per (image, head) at 197 tokens);
    longer rows keep ATTN_DEFAULT."""
    assert legal_attn_blocks(*_vit_attn_blocks(197, 197), 197, 197) == (200, 256)
    assert legal_attn_blocks(*_vit_attn_blocks(1, 197), 1, 197) == (8, 256)
    assert _vit_attn_blocks(2048, 256) == (256, 256)  # the grid's largest
    for Sq, Sk in [(197, 257), (1, 512), (1024, 1024)]:
        assert _vit_attn_blocks(Sq, Sk) == autotune.ATTN_DEFAULT
    # the autotuner's baseline candidate is the same default
    req = autotune.attn_request(32, 6, 6, 64, 197, 197, causal=False,
                                quant_bits=4, scaled=False,
                                q_dtype=jnp.float32, k_dtype=jnp.float32)
    best = autotune.attn_candidates(req)[0]
    assert legal_attn_blocks(*best, 197, 197) == (200, 256)


def test_active_table_overrides_attention_default():
    req = autotune.attn_request(32, 6, 6, 64, 197, 197, causal=False,
                                quant_bits=4, scaled=False,
                                q_dtype=jnp.float32, k_dtype=jnp.float32)
    table = autotune.TuningTable("cpu")
    table.put(req.key, (64, 128), None, "override")
    autotune.activate(table)
    try:
        assert _vit_attn_blocks(197, 197) == (64, 128)
    finally:
        autotune.deactivate()
    assert _vit_attn_blocks(197, 197) == (197, 256)


# ---------------------------------------------------------------------------
# Interpret-mode parity across every swept candidate
# ---------------------------------------------------------------------------

def test_int8_gmm_bit_identical_across_all_candidates(rng):
    """Tile choice is a layout decision only: the int8 contraction is
    exact, so every candidate config must produce the *bit-identical*
    output the default config produces."""
    req = _gmm_req(int8=True)
    T, G, Din, Dout = 100, 4, 32, 48
    x = jnp.asarray(rng.integers(-127, 128, (T, Din)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 128, (G, Din, Dout)), jnp.int8)
    gs = jnp.asarray([40, 0, 25, 35], jnp.int32)
    ws = jnp.asarray(rng.uniform(0.01, 0.1, (G, Dout)), jnp.float32)
    a = jnp.float32(0.037)
    outs = {}
    for blocks in autotune.gmm_candidates(req):
        y = grouped_matmul(x, w, gs, w_scale=ws, a_scale=a,
                           block_m=blocks[0], block_n=blocks[1],
                           interpret=True)
        outs[blocks] = np.asarray(y)
    base = outs[autotune.gmm_candidates(req)[0]]
    for blocks, y in outs.items():
        np.testing.assert_array_equal(y, base, err_msg=str(blocks))


def test_attention_parity_across_all_candidates(rng):
    """fp accumulation order shifts with block_k, so allclose (not
    bit-identical) across the candidate grid; int8 K/V + 4-bit codes."""
    req = autotune.attn_request(2, 2, 2, 32, 16, 48, causal=True,
                                quant_bits=4, scaled=True,
                                q_dtype=jnp.float32, k_dtype=jnp.int8)
    B, Sq, Sk, H, hd = 2, 16, 48, 2, 32
    q = jnp.asarray(rng.standard_normal((B, Sq, H, hd)), jnp.float32)
    k = jnp.asarray(rng.integers(-127, 128, (B, Sk, H, hd)), jnp.int8)
    v = jnp.asarray(rng.integers(-127, 128, (B, Sk, H, hd)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.05, (B, Sk, H)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.05, (B, Sk, H)), jnp.float32)
    offs = jnp.full((B,), Sk - Sq, jnp.int32)
    base = None
    for blocks in autotune.attn_candidates(req):
        y = np.asarray(streaming_attention(
            q, k, v, causal=True, q_offset=offs, quant_bits=4,
            k_scale=ks, v_scale=vs, block_q=blocks[0], block_k=blocks[1],
            interpret=True))
        if base is None:
            base = y
        np.testing.assert_allclose(y, base, atol=1e-5, err_msg=str(blocks))


# ---------------------------------------------------------------------------
# Table persistence: round trip, corrupt, stale
# ---------------------------------------------------------------------------

def test_table_round_trip_is_deterministic(tmp_path):
    path = str(tmp_path / "t.json")
    t = autotune.TuningTable("cpu", path)
    t.put("grouped_matmul|T=64|x", (64, 128), 1.25, "swept")
    t.put("streaming_attention|sq=8|y", (8, 256), None, "default")
    t.save()
    t2 = autotune.TuningTable.load(path, "cpu")
    assert t2.entries == t.entries
    assert t2.stats == {"hits": 0, "misses": 0, "swept": 0}
    t2.save()  # second save round-trips byte-identically
    assert autotune.TuningTable.load(path, "cpu").entries == t.entries


def test_corrupt_cache_falls_back_to_empty(tmp_path):
    path = str(tmp_path / "t.json")
    with open(path, "w") as f:
        f.write("{this is not json")
    t = autotune.TuningTable.load(path, "cpu")
    assert t.entries == {}
    t.put("grouped_matmul|k", (128, 128), None, "default")
    t.save()  # save over the corrupt file works
    assert autotune.TuningTable.load(path, "cpu").entries != {}


def test_stale_kernel_version_and_foreign_device_dropped(tmp_path):
    path = str(tmp_path / "t.json")
    t = autotune.TuningTable("cpu", path)
    t.put("grouped_matmul|a", (64, 128), 1.0, "swept")
    t.put("streaming_attention|b", (64, 256), 2.0, "swept")
    raw = t.to_json()
    raw["kernel_versions"]["grouped_matmul"] -= 1  # stale gmm entries
    with open(path, "w") as f:
        json.dump(raw, f)
    t2 = autotune.TuningTable.load(path, "cpu")
    assert "grouped_matmul|a" not in t2.entries
    assert "streaming_attention|b" in t2.entries
    # device-kind mismatch discards everything
    assert autotune.TuningTable.load(path, "TPU v4").entries == {}
    # malformed entry blocks are dropped, not fatal
    raw = t.to_json()
    raw["entries"]["grouped_matmul|a"]["blocks"] = "nope"
    with open(path, "w") as f:
        json.dump(raw, f)
    t3 = autotune.TuningTable.load(path, "cpu")
    assert "grouped_matmul|a" not in t3.entries


# ---------------------------------------------------------------------------
# Sweep selection + ops threading
# ---------------------------------------------------------------------------

def test_sweep_picks_fastest_candidate_with_injected_timer():
    req = _gmm_req(int8=True)
    want = autotune.gmm_candidates(req)[2]
    timer = lambda fn, blocks, reps=1: 1.0 if blocks == want else 5.0
    entry = autotune.sweep_request(req, AutotuneConfig(budget=32), timer=timer)
    assert tuple(entry["blocks"]) == want
    assert entry["source"] == "swept" and entry["ms"] == 1.0


def test_sweep_skips_failing_candidates_but_not_the_default():
    """A candidate the hardware refuses is skipped; a failing default
    (what serving runs without a table) is a broken kernel and raises."""
    req = _gmm_req(int8=True)
    default = autotune.gmm_candidates(req)[0]

    def refuse(bad):
        def timer(fn, blocks, reps=1):
            if blocks in bad:
                raise RuntimeError("refused")
            return 1.0 if blocks == default else 2.0
        return timer

    others = set(autotune.gmm_candidates(req)[1:])
    entry = autotune.sweep_request(req, AutotuneConfig(budget=32),
                                   timer=refuse(others))
    assert tuple(entry["blocks"]) == default
    with pytest.raises(RuntimeError, match="refused"):
        autotune.sweep_request(req, AutotuneConfig(budget=32),
                               timer=refuse({default}))


def test_sweep_without_tpu_returns_deterministic_defaults():
    req = _gmm_req(int8=False)
    e1 = autotune.sweep_request(req, AutotuneConfig())
    e2 = autotune.sweep_request(req, AutotuneConfig())
    assert e1 == e2
    assert e1["source"] == "default" and e1["ms"] is None
    assert tuple(e1["blocks"]) == autotune.gmm_candidates(req)[0]


def test_active_table_threads_blocks_into_kernel(monkeypatch):
    """An override entry for a shape bucket must reach the Pallas kernel's
    block_m/block_n arguments through kernels.ops."""
    import repro.kernels.expert_linear as el
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    T, G, Din, Dout = 20, 4, 32, 48
    req = autotune.gmm_request(T, G, Din, Dout, x_dtype=jnp.float32,
                               w_dtype=jnp.float32, scaled=False,
                               ascaled=False)
    table = autotune.TuningTable("cpu")
    table.put(req.key, (64, 256), None, "override")
    seen = {}
    orig = el.grouped_matmul

    def spy(*a, **kw):
        seen["blocks"] = (kw.get("block_m"), kw.get("block_n"))
        return orig(*a, **kw)

    monkeypatch.setattr(el, "grouped_matmul", spy)
    x = jnp.ones((T, Din), jnp.float32)
    w = jnp.ones((G, Din, Dout), jnp.float32)
    gs = jnp.asarray([5, 5, 5, 5], jnp.int32)
    autotune.activate(table)
    ops.grouped_matmul(x, w, gs)
    assert seen["blocks"] == (64, 256)
    autotune.deactivate()
    ops.grouped_matmul(x, w, gs)
    assert seen["blocks"] == autotune.GMM_DEFAULT


# ---------------------------------------------------------------------------
# Warmup integration: collect -> fill -> pure cache hit
# ---------------------------------------------------------------------------

def _tiny_lm_cfg(tmp_path):
    import repro.models as M
    from repro.configs import smoke_config

    cfg = smoke_config("olmoe-1b-7b").replace(
        remat=False, num_layers=2,
        autotune=AutotuneConfig(enable=True, cache_dir=str(tmp_path)))
    params = M.init_model_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_warmup_tunes_then_second_warmup_is_pure_cache_hit(
        tmp_path, monkeypatch):
    """Acceptance: warmup collects this replica's kernel keys and fills the
    table; a second warmup (same engine, a fresh engine, or a table
    reloaded from disk) sweeps nothing."""
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    from repro.serving.engine import ServeEngine

    cfg, params = _tiny_lm_cfg(tmp_path)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=16)
    eng.warmup()
    table = autotune.active_table()
    assert table is not None
    swept = table.stats["swept"]
    assert swept > 0  # decode + prefill keys for both kernels
    assert any(k.startswith("grouped_matmul|") for k in table.entries)
    assert any(k.startswith("streaming_attention|") for k in table.entries)
    assert os.path.exists(autotune.table_path(cfg.autotune))

    eng.warmup()  # same engine again
    assert table.stats["swept"] == swept, "re-sweep on warm table"

    eng2 = ServeEngine(cfg, params, batch_slots=2, max_len=16)
    eng2.warmup()  # fresh replica, same device kind
    assert table.stats["swept"] == swept

    autotune.deactivate()  # simulate a new process: reload from disk
    eng3 = ServeEngine(cfg, params, batch_slots=2, max_len=16)
    eng3.warmup()
    t2 = autotune.active_table()
    assert t2 is not table and t2.stats["swept"] == 0
    assert t2.entries == table.entries


def test_warmup_survives_corrupt_cache_file(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    from repro.serving.engine import ServeEngine

    cfg, params = _tiny_lm_cfg(tmp_path)
    path = autotune.table_path(cfg.autotune)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write("]]corrupt[[")
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=16)
    eng.warmup()  # no raise: rebuilt from scratch
    assert autotune.active_table().stats["swept"] > 0
    assert autotune.TuningTable.load(path, autotune.device_kind()).entries


def test_overrides_take_precedence_and_persist(tmp_path):
    req = _gmm_req(int8=False)
    cfg = AutotuneConfig(enable=True, cache_dir=str(tmp_path),
                         overrides=((req.key, (64, 256)),))
    table = autotune.ensure_tuned(cfg, None)
    assert table.get(req.key) == {"blocks": [64, 256], "ms": None,
                                  "source": "override"}
    reloaded = autotune.TuningTable.load(autotune.table_path(cfg),
                                         autotune.device_kind())
    assert reloaded.get(req.key)["source"] == "override"


def test_ensure_tuned_disabled_is_inert(tmp_path):
    cfg = AutotuneConfig(enable=False, cache_dir=str(tmp_path))
    assert autotune.ensure_tuned(cfg, None) is None
    assert not os.listdir(tmp_path)
