"""One run of one cell: set-up, the measured window, the metrics, the check.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration file there, its traffic mix in
``bench/traffic/<traffic>.json``, the configuration's family in
``bench/families/<family>.py`` and each metric in
``bench/metrics/<metric>.py`` (a ``read(ctx)`` that returns a number, or
None where the run holds nothing to read).

The last line of standard output is the result as one JSON object; the
numbers compared with the reference, each with its limit, are the last
lines of standard error and the last key of the result. With no TPU, or
fewer chips than the cell asks for, the run exits 1 and prints no result.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    pass


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell named ``workload`` with its configuration, traffic and
    metric lists, read from their files."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        spec = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {"cell": cell, "spec": spec, "traffic": traffic,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"]),
            "all_per_layer": [m["name"] for m in bench["per_layer"]]}


def load_file(path: str, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_module(name: str):
    return load_file(os.path.join(BENCH, "metrics", name + ".py"),
                     "bench_metric_" + name.replace(".", "_"))


def family_module(family: str):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module("families." + family)


def devices(chips: int):
    """The local TPUs, or NoChip."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs


def enable_cache() -> str:
    """The program's persistent compile cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set), holding every program."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def read_metrics(metrics: list, ctx) -> dict:
    out = {}
    for m in metrics:
        value = metric_module(m["name"]).read(ctx)
        if value is None:
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def kernel_patterns(metric_names: list) -> dict:
    """Kernel name -> the pattern its roofline metric file matches ops by."""
    out = {}
    for name in metric_names:
        mod = metric_module(name)
        if hasattr(mod, "KERNEL"):
            out[mod.KERNEL] = mod.MATCH
    return out


def breakdown(trace, kernels: dict) -> dict:
    """The ten largest device-time entries (each kernel summed over its
    calls, other ops by HLO name) and the ten longest idle gaps, named by
    the host span around them."""
    from harness import trace as tr

    seconds, _ = tr.op_time(trace)
    label = [tr.short(n) for n in trace.names]
    for kernel, pattern in kernels.items():
        for i in map(int, tr.matching(trace, pattern).nonzero()[0]):
            label[i] = kernel
    tot = {}
    for i, t in enumerate(seconds):
        if t > 0:
            tot[label[i]] = tot.get(label[i], 0.0) + float(t)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
    gaps = [[g[0], g[1]] for g in tr.gaps(trace)[:10]]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": gaps}


def run(args, t_start: float) -> tuple:
    """(result dict, check lines) of one run."""
    bench = load_benchmark()
    cell = resolve(bench, args.workload)
    devs = devices(cell["cell"]["chips"])
    cache = enable_cache()
    from harness import loop, peaks
    from harness import trace as tr

    fam = family_module(cell["spec"]["family"])
    system = fam.System(cell["spec"], args.seed)
    setup_s = time.monotonic() - t_start
    print(f"set-up {setup_s:.3f} s (compile cache {cache})", file=sys.stderr)

    ctx = types.SimpleNamespace(
        spec=cell["spec"], family=fam, system=system, setup_s=setup_s,
        trace=None,
        peaks=peaks.peaks(devs[0].device_kind) if args.trace else None)
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        ctx.window = loop.run(system, cell["traffic"], args.seconds,
                              args.seed, tracer=tr.capture(trace_dir),
                              span=tr.span)
        t0 = time.monotonic()
        ctx.trace = tr.reduce(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace: {len(ctx.trace.start)} device ops read in "
              f"{time.monotonic() - t0:.1f} s", file=sys.stderr)
    else:
        ctx.window = loop.run(system, cell["traffic"], args.seconds,
                              args.seed)
    ctx.counters = system.counters
    ctx.engine_metrics = system.engine.metrics
    metrics = read_metrics(cell["per_layer"] if args.trace
                           else cell["end_to_end"], ctx)
    stats = [d.memory_stats() or {} for d in devs[:cell["cell"]["chips"]]]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["cell"]["chips"],
              "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                       for s in stats)}
    result = {"correct": False, "attempted": ctx.window["offered"],
              "failed": ctx.window["offered"] - len(system.delivered),
              "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = tr.busy_s(ctx.trace)
        device["window_s"] = tr.window_s(ctx.trace)
        result["breakdown"] = breakdown(
            ctx.trace, kernel_patterns(cell["all_per_layer"]))
    info = {k: ctx.window.get(k) for k in ("submit_lag_p95_s",
                                           "offered_at_close")}
    print(f"window: {json.dumps(info)}; counters {json.dumps(ctx.counters)}",
          file=sys.stderr)

    system.release()
    t0 = time.monotonic()
    numbers = fam.check(system)
    print(f"check: {time.monotonic() - t0:.1f} s", file=sys.stderr)
    limits = cell["spec"]["check"]
    compared = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    result["correct"] = bool(
        result["failed"] == 0
        and all(v["value"] <= v["limit"] for v in compared.values()))
    result["check"] = {k: [_finite(v["value"]), v["limit"]]
                       for k, v in compared.items()}
    lines = [f"check {k}: {v['value']!r} (limit {v['limit']!r}, over "
             f"{numbers.get('n')} answers)" for k, v in compared.items()]
    return result, lines


def _finite(x: float) -> float:
    return x if math.isfinite(x) else 1e300


def main(argv=None, t_start: float = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    try:
        result, lines = run(args, t_start)
    except NoChip as e:
        print(f"bench: {e}; nothing measured", file=sys.stderr)
        return 1
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result))
    return 0
