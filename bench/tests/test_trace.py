"""The trace reduction on a small synthetic profile: innermost ops only,
busy union and idle gaps named by the host span, per-kernel sums and the
roofline and step readings built on them."""
import json
import types

import pytest

from families import m3vit
from harness import main
from harness import trace as tr

INT8 = ('%c.1 = f32[6400,512] custom-call(...), custom_call_target='
        '"tpu_custom_call", operand_layout_constraints={s8[6400,384]{1,0}, '
        's8[384,512]{1,0}, f32[1,1]{1,0}, f32[1,512]{1,0}}')
ATTN = ('%c.2 = f32[32,6,256,64] custom-call(...), custom_call_target='
        '"tpu_custom_call", operand_layout_constraints={s32[32]{0}, '
        's32[32]{0}, f32[32,6,256,64]{3,2,1,0}, f32[32,6,256,64]{3,2,1,0}, '
        'f32[32,6,256,64]{3,2,1,0}}')
GMM = ('%c.3 = f32[12672,1536] custom-call(...), custom_call_target='
       '"tpu_custom_call", operand_layout_constraints={s32[115]{0}, '
       's32[115]{0}, s32[115]{0}, s32[115]{0}, s8[12672,384]{1,0}, '
       's8[16,384,1536]{2,1,0}, f32[16,1,1536]{2,1,0}, f32[1,1]{1,0}}')

FUSION = "%fusion.4 = f32[8] fusion(...)"
# (name, start_us, duration_us) on the device, named by HLO text as a TPU
# trace names its ops; the window is [100, 1100) us on the host clock,
# which the trace shares
OPS = [
    ("%while.9 = while(...)", 150, 500),
    (INT8, 150, 100),
    (ATTN, 260, 200),
    (GMM, 470, 100),
    (FUSION, 600, 40),
    (INT8, 800, 100),
    (FUSION, 1150, 10),  # after the window
]
MODULES = [("jit__lambda(77)", 150, 500), ("jit__lambda(77)", 800, 100)]
HOST = [("window", 100, 1000), ("step", 100, 560), ("gen_sleep", 660, 120),
        ("submit", 780, 320)]


def xspace(ops=OPS, modules=MODULES, host=HOST) -> str:
    names = sorted({o[0] for o in ops} | {m[0] for m in modules}
                   | {h[0] for h in host})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "".join(f"event_metadata {{ key: {i} value {{ id: {i} name: "
                   f"{json.dumps(n)} }} }}\n" for n, i in ids.items())

    def ev(name, start, dur):
        return (f"events {{ metadata_id: {ids[name]} offset_ps: "
                f"{int(start * 1e6)} duration_ps: {int(dur * 1e6)} }}\n")

    dev = "".join(ev(n, s, d) for n, s, d in ops)
    mod = "".join(ev(n, s, d) for n, s, d in modules)
    hst = "".join(ev(n, s, d) for n, s, d in host)
    return (f'planes {{ id: 1 name: "/device:TPU:0"\n'
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n{dev}}}\n'
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0\n{mod}}}\n'
            f'{meta}}}\n'
            f'planes {{ id: 2 name: "/host:CPU"\n'
            f'lines {{ id: 1 name: "python" timestamp_ns: 0\n{hst}}}\n'
            f'{meta}}}\n')


@pytest.fixture
def trace():
    from jax.profiler import ProfileData

    return tr.read(ProfileData.from_text_proto(xspace()))


def test_only_innermost_ops(trace):
    kept = [tr.short(trace.names[i]) for i in trace.op]
    assert kept == ["c.1", "c.2", "c.3", "fusion.4", "c.1", "fusion.4"]
    assert trace.window == (100_000, 1_100_000)
    assert trace.device == "/device:TPU:0"


def test_busy_union_and_idle_gaps(trace):
    # busy 150-250, 260-460, 470-570, 600-640, 800-900 us; the last op
    # falls after the window
    assert tr.busy_s(trace) == pytest.approx(540e-6)
    assert tr.window_s(trace) == pytest.approx(1000e-6)
    gaps = tr.gaps(trace)
    assert [(g[0], round(g[1] * 1e6)) for g in gaps] == [
        ("submit", 200), ("gen_sleep", 160), ("step", 50), ("step", 30),
        ("step", 10), ("step", 10)]


def test_op_time_sums_device_time_inside_the_window(trace):
    seconds, calls = tr.op_time(trace)
    by = {tr.short(n): (s, c) for n, s, c in zip(trace.names, seconds, calls)}
    assert by["c.1"] == (pytest.approx(200e-6), 2)
    assert by["fusion.4"] == (pytest.approx(40e-6), 1)
    assert by["while.9"] == (0.0, 0)


def ctx_for(trace):
    spec = {"d_model": 384, "image_tokens": 197, "num_heads": 6,
            "head_dim": 64, "num_layers": 12, "num_experts": 16, "top_k": 2,
            "expert_d_ff": 1536, "d_ff": 1536, "num_classes": 1000,
            "moe_every": 2, "patch_dim": 768, "batch_buckets": [1, 8, 32]}
    return types.SimpleNamespace(
        trace=trace, spec=spec, family=m3vit,
        counters={"batches": 2, "frames": 64, "padded_frames": 0},
        peaks={"ops_per_s": 393e12, "bytes_per_s": 819e9},
        window={"seconds": 1e-3, "completed_in_window": 64})


@pytest.mark.parametrize("kernel,n_ops,took_us", [
    ("int8_matmul", 2, 200), ("streaming_attention", 1, 200),
    ("grouped_matmul", 1, 100)])
def test_roofline_share_per_kernel(trace, kernel, n_ops, took_us):
    from harness.measures import hbm_bytes

    ctx = ctx_for(trace)
    flops = m3vit.kernel_flops(ctx.spec, 32)[kernel]
    text = {"int8_matmul": INT8, "streaming_attention": ATTN,
            "grouped_matmul": GMM}[kernel]
    least = max(sum(flops) * n_ops / len(flops) / 393e12,
                n_ops * hbm_bytes(text) / 819e9)
    got = main.metric_module(kernel + "_roofline").read(ctx)
    assert got == pytest.approx(100 * least / (took_us * 1e-6))
    assert 0 < got < 100


def test_roofline_needs_full_buckets(trace):
    ctx = ctx_for(trace)
    ctx.counters = {"batches": 2, "frames": 40, "padded_frames": 24}
    assert main.metric_module("int8_matmul_roofline").read(ctx) is None


def test_step_time_idle_share_and_mfu(trace):
    ctx = ctx_for(trace)
    assert main.metric_module("step_ms.b32").read(ctx) == pytest.approx(0.3)
    assert main.metric_module("device_idle_share").read(ctx) == \
        pytest.approx(46.0)
    mfu = main.metric_module("mfu").read(ctx)
    assert mfu == pytest.approx(100 * 64 * 12_000_976_896 / 1e-3 / 393e12)


def test_breakdown_names_kernels(trace):
    kernels = main.kernel_patterns(["int8_matmul_roofline",
                                    "grouped_matmul_roofline",
                                    "streaming_attention_roofline"])
    b = main.breakdown(trace, kernels)
    ops = dict(b["device_ops"])
    assert ops["int8_matmul"] == pytest.approx(200e-6)
    assert ops["streaming_attention"] == pytest.approx(200e-6)
    assert ops["grouped_matmul"] == pytest.approx(100e-6)
    assert ops["fusion.4"] == pytest.approx(40e-6)
    assert b["idle_gaps"][0] == ["submit", pytest.approx(200e-6)]
