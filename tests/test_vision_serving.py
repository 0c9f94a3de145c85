"""VisionEngine end to end: the MoE-ViT request path over fp, fake-quant,
and materialized-int8 QuantizedParams trees (DESIGN.md section 6).

The fidelity contract mirrors tests/test_int8_path.py: the fake-quant tree
(quantize-dequantize executed in f32) is the numerical oracle for the
stored-int8 execution — served top-1 classes must agree.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models as M
from repro.configs import get_shape, smoke_config
from repro.core.quant.ptq import calibrate_model, ptq_model, quantized_config
from repro.serving.scheduler import Backpressure
from repro.serving.vision import VisionEngine, VisionRequest, synth_requests


@pytest.fixture(scope="module")
def moe_vit_trees():
    cfg = smoke_config("m3vit-small").replace(remat=False)
    shape = get_shape("train_4k").replace(seq_len=24, global_batch=2)
    params = M.init_model_params(cfg, jax.random.PRNGKey(0))
    batches = [M.synth_batch(cfg, shape, jax.random.PRNGKey(i))
               for i in range(2)]
    taps = calibrate_model(cfg, params, batches)
    return (cfg, params, ptq_model(cfg, params, taps),
            ptq_model(cfg, params, taps, materialize="int8"))


def _serve(cfg, params, reqs, **kw):
    eng = VisionEngine(cfg, params, **kw)
    for r in reqs:
        eng.submit(r)
        eng.step()
    eng.flush()
    assert all(r.done for r in reqs)
    return eng


def test_vision_engine_serves_and_meters(moe_vit_trees):
    """Responses are well-formed; counters, FPS window, and per-expert
    occupancy are all populated."""
    cfg, params, _, _ = moe_vit_trees
    reqs = synth_requests(cfg, 7, seed=3)
    eng = _serve(cfg, params, reqs, batch_buckets=(1, 4), max_wait_s=0.0,
                 top_k=3)
    for r in reqs:
        assert r.classes.shape == (3,) and r.probs.shape == (3,)
        assert all(0 <= c < cfg.num_classes for c in r.classes)
        assert np.all(np.diff(r.probs) <= 0), "probs must be descending"
        assert r.latency_s is not None and r.latency_s >= 0
    snap = eng.metrics.snapshot()
    assert snap["counters"]["submitted"] == 7
    assert snap["counters"]["completed"] == 7
    assert snap["counters"]["frames"] == 7
    assert snap["latency_ms"]["n"] == 7
    assert np.isfinite(snap["fps"]) and snap["fps"] > 0
    # every MoE layer routes top_k slots per token: occupancy accumulated
    assert sum(snap["expert_tokens"]) > 0
    assert sum(snap["expert_occupancy"]) == pytest.approx(1.0)


def test_engine_results_match_direct_forward(moe_vit_trees):
    """Batched/padded engine serving must return exactly the classes of the
    bare jitted forward on each single image (padding never leaks)."""
    cfg, params, _, _ = moe_vit_trees
    reqs = synth_requests(cfg, 5, seed=11)
    _serve(cfg, params, reqs, batch_buckets=(4,), max_wait_s=0.0, top_k=4)
    for r in reqs:
        out = M.classify(params, cfg, jnp.asarray(r.patches)[None], top_k=4)
        np.testing.assert_array_equal(r.classes, np.asarray(out["classes"])[0])
        np.testing.assert_allclose(r.probs, np.asarray(out["probs"])[0],
                                   rtol=1e-5, atol=1e-6)


def test_int8_serving_matches_fake_quant_oracle_top1(moe_vit_trees):
    """End-to-end: serving the materialized-int8 tree reproduces the f32
    fake-quant oracle's top-1 class per image (same quantization grid)."""
    cfg, _, p_fake, p_int8 = moe_vit_trees
    qcfg = quantized_config(cfg)
    reqs_a = synth_requests(cfg, 9, seed=5)
    reqs_b = synth_requests(cfg, 9, seed=5)
    _serve(qcfg, p_fake, reqs_a, batch_buckets=(1, 4), max_wait_s=0.0)
    _serve(qcfg, p_int8, reqs_b, batch_buckets=(1, 4), max_wait_s=0.0)
    top1_fake = [int(r.classes[0]) for r in reqs_a]
    top1_int8 = [int(r.classes[0]) for r in reqs_b]
    assert top1_fake == top1_int8
    for a, b in zip(reqs_a, reqs_b):
        np.testing.assert_allclose(a.probs, b.probs, rtol=1e-3, atol=1e-4)


def test_int8_serving_materializes_no_fp_expert_copy(moe_vit_trees):
    """The engine's jitted unit of work consumes the int8 expert stacks
    directly — no f32/bf16 dequantized expert-weight copy in the program."""
    cfg, _, _, p_int8 = moe_vit_trees
    qcfg = quantized_config(cfg)
    x = jnp.zeros((2, cfg.image_tokens - 1, 768), jnp.float32)
    jaxpr = str(jax.make_jaxpr(
        lambda p, b: M.classify(p, qcfg, b, top_k=5)
    )(p_int8, x))
    n_pairs = qcfg.num_layers // 2
    E, D = qcfg.moe.num_experts, qcfg.d_model
    hid = qcfg.moe.d_ff * (2 if qcfg.glu else 1)
    fp_expert_shapes = [
        f"{dt}[{dims}]"
        for dt in ("f32", "bf16")
        for dims in (
            f"{E},{D},{hid}", f"{n_pairs},{E},{D},{hid}",
            f"{E},{qcfg.moe.d_ff},{D}", f"{n_pairs},{E},{qcfg.moe.d_ff},{D}",
        )
    ]
    leaked = [s for s in fp_expert_shapes if s in jaxpr]
    assert not leaked, f"fp dequantized expert weight copies found: {leaked}"
    assert f"i8[{n_pairs},{E},{D},{hid}]" in jaxpr


def test_backpressure_surfaces_to_clients(moe_vit_trees):
    cfg, params, _, _ = moe_vit_trees
    eng = VisionEngine(cfg, params, batch_buckets=(4,), max_wait_s=100.0,
                       max_pending=2)
    reqs = synth_requests(cfg, 3)
    eng.submit(reqs[0])
    eng.submit(reqs[1])
    with pytest.raises(Backpressure):
        eng.submit(reqs[2])
    assert eng.metrics.counters["rejected"] == 1
    eng.flush()  # queued work still completes
    assert reqs[0].done and reqs[1].done


def test_double_buffered_dispatch_keeps_batches_in_flight(moe_vit_trees):
    """With enough queued work, a second batch is dispatched before the
    first is retired (the enqueue-N+1-while-N-in-flight property)."""
    cfg, params, _, _ = moe_vit_trees
    eng = VisionEngine(cfg, params, batch_buckets=(2,), max_wait_s=0.0,
                       max_inflight=2)
    for r in synth_requests(cfg, 4, seed=1):
        eng.submit(r)
    eng._dispatch_ready()
    assert len(eng._inflight) == 2, "both batches should be in flight"
    eng.flush()
    assert eng.metrics.counters["frames"] == 4


def test_plain_vit_family_serves_without_expert_metrics():
    cfg = smoke_config("vit-tiny").replace(remat=False)
    params = M.init_model_params(cfg, jax.random.PRNGKey(0))
    reqs = synth_requests(cfg, 3, seed=2)
    eng = _serve(cfg, params, reqs, batch_buckets=(1, 2), max_wait_s=0.0)
    assert eng.metrics.snapshot()["expert_tokens"] == []
    assert all(r.done for r in reqs)


# -- host-phase spans, retraces and step timing -----------------------------

LEAF_SPANS = ("engine.pad", "engine.h2d", "engine.launch", "engine.wait",
              "engine.fetch", "engine.deliver")


def test_host_span_is_the_shared_null_context_without_a_profiler():
    from repro.serving.trace import host_span

    a, b = host_span("engine.pad", batch=0, b=4, n=1), host_span("x")
    assert a is b
    with a as span:
        assert span is None


def _host_events(log_dir):
    """(name, start_ns, end_ns, stats) of the engine's spans in a trace."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return out


def test_engine_host_spans_land_in_the_profiler_trace(moe_vit_trees,
                                                      tmp_path):
    """Every batch leaves each leaf span once on the trace's host plane,
    with its dispatch number; pad, H2D and launch follow each other; the
    launch after a cleared compile cache carries retrace=1 and counts."""
    cfg, params, _, _ = moe_vit_trees
    eng = VisionEngine(cfg, params, batch_buckets=(1, 4), max_wait_s=0.0)
    eng.warmup()
    assert eng.metrics.counters.get("retraces", 0) == 0
    reqs = synth_requests(cfg, 6, seed=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for r in reqs[:5]:
            eng.submit(r)
        eng.flush()  # one batch of 4, one of 1
        assert eng.metrics.counters.get("retraces", 0) == 0
        jax.clear_caches()  # no bucket is warm any more
        eng.submit(reqs[5])
        eng.flush()
    finally:
        jax.profiler.stop_trace()
    assert all(r.done for r in reqs)
    assert eng.metrics.counters["retraces"] == 1
    events = _host_events(tmp_path)
    by = {}
    for name, s, e, stats in events:
        by.setdefault(stats["batch"], {}).setdefault(name, []).append(
            (s, e, stats))
    assert sorted(by) == [0, 1, 2]
    for batch, spans in by.items():
        assert sorted(spans) == sorted(LEAF_SPANS), batch
        assert all(len(v) == 1 for v in spans.values()), batch
        (pad,), (h2d,), (launch,) = (spans["engine.pad"],
                                     spans["engine.h2d"],
                                     spans["engine.launch"])
        assert pad[1] <= h2d[0] and h2d[1] <= launch[0]
        assert launch[2]["retrace"] == (1 if batch == 2 else 0)
    assert [by[i]["engine.pad"][0][2]["b"] for i in range(3)] == [4, 1, 1]
    assert [by[i]["engine.pad"][0][2]["n"] for i in range(3)] == [4, 1, 1]


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _FakeResult:
    """A device result that is ready at ``done_at`` on the fake clock;
    reading it blocks (moves the clock) until then."""

    def __init__(self, clock, done_at, value):
        self.clock, self.done_at, self.value = clock, done_at, value

    def is_ready(self):
        return self.clock.t >= self.done_at

    def __array__(self, dtype=None, copy=None):
        self.clock.t = max(self.clock.t, self.done_at)
        return self.value


def test_step_timing_covers_each_batchs_device_interval(moe_vit_trees):
    """With two batches in flight, a batch's step runs from its dispatch or
    the previous batch's ready time, whichever is later, to its own ready
    time (first is_ready, or the blocking wait's return) — one device step,
    not the dispatch-to-retire window that spans two."""
    from repro.configs.base import TraceConfig

    cfg, params, _, _ = moe_vit_trees
    clock = _FakeClock()
    eng = VisionEngine(cfg.replace(trace=TraceConfig(enable=True)), params,
                       batch_buckets=(2,), max_wait_s=0.0, max_inflight=2,
                       clock=clock)
    done_at = iter([10.0, 20.0, 30.0])  # one chip: the batches in order

    def classify(prm, x):
        t = next(done_at)
        clock.t += 1.0  # the launch itself takes host time
        b = x.shape[0]
        return {"classes": _FakeResult(clock, t, np.zeros((b, 5), np.int32)),
                "probs": _FakeResult(clock, t, np.zeros((b, 5), np.float32))}

    eng._classify = classify
    reqs = synth_requests(cfg, 6, seed=2)
    for r in reqs[:4]:
        eng.submit(r)
    eng.step()  # dispatches A at t=0 and B at t=1
    clock.t = 12.0
    for r in reqs[4:]:
        eng.submit(r)
    eng.step()  # retires A (ready seen at 12), dispatches C at t=12
    clock.t = 14.0
    eng.step()  # window full: waits for B until 20
    eng.flush()  # waits for C until 30
    assert all(r.done for r in reqs)
    steps = [(s.t0, s.t1) for s in eng.tracer.recorder.spans()
             if s.kind == "step"]
    assert steps == [(0.0, 12.0), (12.0, 20.0), (20.0, 30.0)]
    for tracker in (eng.metrics.step_latency["classify|b=2"],
                    eng.metrics.batch_latency):
        assert len(tracker) == 3
        assert tracker.percentile(50) == pytest.approx(10.0)
        assert tracker.snapshot()["max"] == pytest.approx(12e3)


def test_garbage_collections_are_host_spans_while_a_profiler_captures(
        tmp_path):
    import gc
    import glob

    from jax.profiler import ProfileData

    from repro.serving.trace import install_gc_spans

    install_gc_spans()
    install_gc_spans()  # idempotent: one callback per process
    assert sum(cb.__name__ == "_gc_phase" for cb in gc.callbacks) == 1
    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect(1)
    finally:
        jax.profiler.stop_trace()
    gc.collect()  # after the session: no span, nothing left open
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    gens = [dict(e.stats)["gen"]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name == "host.gc"]
    assert 1 in gens
