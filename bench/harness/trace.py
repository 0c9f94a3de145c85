"""Profiler capture of the measured window and its reduction.

``capture(dir)`` wraps the window in a JAX profiler trace (host tracer on,
Python tracer off); ``span(name)`` puts the harness's own host spans into
it. ``reduce(dir)`` reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData`` and keeps, on the trace's one clock (ns):

  * ``ops``: every device operation of the first chip's ``XLA Ops`` line
    that holds no other (a ``while`` holds its body's ops), as arrays of
    ``start``, ``end`` and ``op`` (an index into ``names``). On a TPU an
    op's name is its HLO text, operand shapes and layouts included, so a
    kernel's custom calls are told apart by pattern;
  * ``modules``: the same chip's ``XLA Modules`` line, one event per
    program execution, ``(name, start, end)``;
  * ``spans``: the harness's host spans (``loop.span`` names);
  * ``window``: the ``window`` span's bounds.

``busy_s`` is the length of the union of the op intervals inside the
window; ``gaps`` are the idle intervals between them, each named by the
host span that covers most of it.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from typing import NamedTuple

import numpy as np

HOST_SPANS = ("window", "submit", "step", "gen_sleep", "drain")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def capture(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


class Trace(NamedTuple):
    start: np.ndarray  # innermost device ops, sorted by start (ns)
    end: np.ndarray
    op: np.ndarray  # index into names
    names: list  # op names (HLO text on a TPU)
    modules: list  # (name, start_ns, end_ns)
    spans: list  # (name, start_ns, end_ns)
    window: tuple  # (start_ns, end_ns)
    device: str


def read(profile) -> Trace:
    """The first TPU's ops and modules and the harness's host spans of a
    ``ProfileData``."""
    index, start, end, op = {}, [], [], []
    modules, spans = [], []
    device = None
    for plane in profile.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and device is None:
            if not any(line.name == OPS_LINE for line in plane.lines):
                continue
            device = name
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        op.append(index.setdefault(e.name, len(index)))
                        start.append(e.start_ns)
                        end.append(e.end_ns)
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        modules.append((e.name, e.start_ns, e.end_ns))
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        spans.append((e.name, e.start_ns, e.end_ns))
    wins = [s for s in spans if s[0] == "window"]
    window = (wins[0][1], wins[0][2]) if wins else (0.0, 0.0)
    start, end = np.asarray(start, np.float64), np.asarray(end, np.float64)
    op = np.asarray(op, np.int64)
    order = np.lexsort((-end, start))
    start, end, op = start[order], end[order], op[order]
    # an op that holds the next one is a parent (while, call): drop it
    holds = np.zeros(len(start), bool)
    holds[:-1] = (start[1:] < end[:-1]) & (end[1:] <= end[:-1])
    keep = ~holds
    modules.sort(key=lambda m: m[1])
    return Trace(start[keep], end[keep], op[keep], list(index), modules,
                 spans, window, device or "")


def reduce(log_dir: str) -> Trace:
    from jax.profiler import ProfileData

    return read(ProfileData.from_file(find_xplane(log_dir)))


def in_window(trace: Trace, events):
    w0, w1 = trace.window
    return [e for e in events if e[1] >= w0 and e[2] <= w1]


def _clipped(trace: Trace):
    w0, w1 = trace.window
    s, e = np.maximum(trace.start, w0), np.minimum(trace.end, w1)
    live = e > s
    return s[live], e[live], trace.op[live]


def busy_intervals(trace: Trace) -> np.ndarray:
    """Union of the device op intervals inside the window, [n, 2] ns."""
    s, e, _ = _clipped(trace)
    if not len(s):
        return np.zeros((0, 2))
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    return np.stack([s[first], reach[last]], axis=1)


def busy_s(trace: Trace) -> float:
    b = busy_intervals(trace)
    return float(np.sum(b[:, 1] - b[:, 0])) * 1e-9


def window_s(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) * 1e-9


def gaps(trace: Trace) -> list:
    """Idle intervals of the window, ``(host span, seconds, start_ns)``,
    longest first; each is named by the host span (other than ``window``)
    that overlaps it most, or ``host`` where none does."""
    w0, w1 = trace.window
    b = busy_intervals(trace)
    g0 = np.concatenate([[w0], b[:, 1]])
    g1 = np.concatenate([b[:, 0], [w1]])
    host = sorted((s for s in trace.spans if s[0] != "window"),
                  key=lambda s: s[1])
    out, first = [], 0
    for a, z in zip(g0, g1):
        if z <= a:
            continue
        while first < len(host) and host[first][2] <= a:
            first += 1
        cover = {}
        for j in range(first, len(host)):
            name, s, e = host[j]
            if s >= z:
                break
            ov = min(e, z) - max(s, a)
            if ov > 0:
                cover[name] = cover.get(name, 0.0) + ov
        label = max(cover, key=cover.get) if cover else "host"
        out.append((label, float(z - a) * 1e-9, float(a)))
    out.sort(key=lambda g: -g[1])
    return out


def matching(trace: Trace, pattern: str) -> np.ndarray:
    """Mask over ``names``: the ops whose text the pattern finds."""
    pat = re.compile(pattern)
    return np.array([bool(pat.search(n)) for n in trace.names], bool)


def op_time(trace: Trace) -> tuple:
    """(seconds, calls) inside the window, per index of ``names``."""
    s, e, op = _clipped(trace)
    n = len(trace.names)
    return (np.bincount(op, weights=(e - s) * 1e-9, minlength=n),
            np.bincount(op, minlength=n))


def short(name: str) -> str:
    """An op's HLO name without its text: ``fusion.230``."""
    return name.split(" = ", 1)[0].lstrip("%")
