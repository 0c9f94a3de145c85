"""Kernel tile autotuner — per-device search with persistent tuning tables
(DESIGN.md section 9).

CoQMoE re-synthesizes its FPGA accelerator per deployment to balance latency
against the resource budget (section 4); the TPU analogue is picking Pallas
tile sizes per (shape bucket, dtype, device kind). Auto-ViT-Acc (PAPERS.md)
shows automatic hardware-aware search over acceleration configs beats
hand-tuned ones — here the search space is the ``(block_m, block_n)`` grid
of ``grouped_matmul`` and the ``(block_q, block_k)`` grid of
``streaming_attention``.

Pipeline (engine ``warmup()`` drives it, before admission opens):

  1. **collect** — the replica's programs are traced abstractly
     (``jax.eval_shape``: no compile, no device work); every
     ``kernels.ops`` dispatch records the shape-bucket key it would look
     up (tokens/sequence lengths bucket to the next power of two, so one
     entry covers a range of runtime shapes);
  2. **sweep** — for each key missing from the table, legal candidate tile
     configs are benchmarked on the actual device (default config is
     always candidate #1, so the winner is never slower than the default);
     on CPU / interpret backends there is nothing meaningful to time and
     the key is filled with the deterministic default tiles;
  3. **persist** — winners land in a versioned JSON table keyed by device
     kind (one file per kind under ``AutotuneConfig.cache_dir``). A later
     ``ensure_tuned`` on the same device kind is a pure cache hit: zero
     re-sweep. Stale (kernel-version bump), corrupt, or
     foreign-device tables are discarded gracefully — the tuner never
     fails a serving launch, it falls back to defaults.

At serving time ``kernels.ops`` consults the ambient active table at trace
time (tile sizes are jit-static); a lookup miss costs nothing but the
default tiles — sweeps only ever run inside ``ensure_tuned``.
"""
from __future__ import annotations

import contextlib
import json
import os
import re
import time
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AutotuneConfig
from repro.kernels.expert_linear import legal_gmm_blocks
from repro.kernels.quant_attention import legal_attn_blocks

# Bumped when a kernel's tiling/legality logic changes (the sublane/lane
# clamp-rounding fix shipped as version 2; nibble-packed int4 weights and
# the ``pk`` key facet shipped as grouped_matmul version 3; the one-visit
# 4-bit schedule as streaming_attention version 3): entries swept against an
# older kernel are dropped at load so a tuned table can never pin obsolete
# tiles.
KERNEL_VERSIONS: Dict[str, int] = {
    "grouped_matmul": 3,
    "streaming_attention": 3,
}
TABLE_VERSION = 1

GMM_DEFAULT = (128, 128)  # the former hard-coded expert_linear tiles
ATTN_DEFAULT = (128, 256)  # the former hard-coded quant_attention tiles

# candidate grids (clamped + legal-rounded per shape before timing)
_GMM_BLOCK_M = (32, 64, 128, 256, 512)
_GMM_BLOCK_N = (128, 256, 512)
_ATTN_BLOCK_Q = (32, 64, 128, 256)
_ATTN_BLOCK_K = (128, 256, 512)

_VMEM_BUDGET = 12 * 1024 * 1024  # leave headroom under the ~16 MB/core VMEM


def attn_default(Sq: int, Sk: int) -> Tuple[int, int]:
    """Untuned (block_q, block_k) for one attention shape: ``ATTN_DEFAULT``,
    except that where one default K tile holds the whole key row the Q tile
    widens to the whole query length, up to the grid's largest Q tile. The
    4-bit kernel then takes its one-visit schedule in as few grid steps as
    the rows allow: one per (image, head) for a 197-token ViT row."""
    bq, bk = ATTN_DEFAULT
    if Sk <= bk:
        bq = max(bq, min(Sq, _ATTN_BLOCK_Q[-1]))
    return bq, bk


# ---------------------------------------------------------------------------
# Shape-bucket keys
# ---------------------------------------------------------------------------

def bucket_pow2(n: int, lo: int = 8, hi: int = 1 << 20) -> int:
    """Next power of two >= n, clamped to [lo, hi] — one tuning entry
    covers every runtime shape that rounds to the same bucket."""
    n = max(int(n), 1)
    b = 1
    while b < n:
        b <<= 1
    return max(lo, min(b, hi))


class TuneRequest(NamedTuple):
    """One (kernel, shape-bucket) tuning unit. ``params`` is a sorted
    tuple of (name, value) pairs — everything needed to synthesize sweep
    inputs and to rebuild the entry key deterministically."""

    kernel: str
    params: Tuple[Tuple[str, object], ...]

    @property
    def key(self) -> str:
        parts = [f"{k}={v}" for k, v in self.params]
        return "|".join([self.kernel] + parts)

    def get(self, name: str):
        return dict(self.params)[name]


def _dt(dtype) -> str:
    return jnp.dtype(dtype).name


def gmm_request(T: int, G: int, Din: int, Dout: int, *, x_dtype, w_dtype,
                scaled: bool, ascaled: bool) -> TuneRequest:
    # ``din`` is always the LOGICAL input dim (== x.shape[1]); ``pk`` marks
    # nibble-packed int4 weights (uint8 storage, rows = ceil(din/2)) so the
    # packed and int8 paths can never share a tuning entry even though the
    # wdt facet already differs — the packed layout is part of the key
    # contract (DESIGN.md sections 9/13).
    packed = jnp.dtype(w_dtype) == jnp.uint8
    return TuneRequest("grouped_matmul", (
        ("T", bucket_pow2(T)),
        ("G", int(G)),
        ("din", int(Din)),
        ("dout", int(Dout)),
        ("xdt", _dt(x_dtype)),
        ("wdt", _dt(w_dtype)),
        ("ws", int(bool(scaled))),
        ("as", int(bool(ascaled))),
        ("pk", int(packed)),
    ))


def attn_request(B: int, H: int, KVH: int, hd: int, Sq: int, Sk: int, *,
                 causal: bool, quant_bits: int, scaled: bool,
                 q_dtype, k_dtype, local_window: int = 0) -> TuneRequest:
    return TuneRequest("streaming_attention", (
        ("B", bucket_pow2(B, lo=1)),
        ("H", int(H)),
        ("kvh", int(KVH)),
        ("hd", int(hd)),
        ("sq", bucket_pow2(Sq, lo=1)),
        ("sk", bucket_pow2(Sk, lo=8)),
        ("causal", int(bool(causal))),
        # the sliding window changes which K tiles a Q tile visits
        # (block-level skip), so it is a tile-choice facet; it is a config
        # constant, not a runtime shape — no bucketing
        ("lw", int(local_window)),
        ("qb", int(quant_bits)),
        ("ks", int(bool(scaled))),
        ("qdt", _dt(q_dtype)),
        ("kdt", _dt(k_dtype)),
    ))


# ---------------------------------------------------------------------------
# Candidate grids (legal, deduped, default first)
# ---------------------------------------------------------------------------

def _bytes(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def gmm_candidates(req: TuneRequest) -> List[Tuple[int, int]]:
    """Effective (block_m, block_n) candidates for one grouped_matmul key:
    clamp-rounded to the problem, VMEM-bounded, deduped; the effective
    default config is always first."""
    T, Din, Dout = req.get("T"), req.get("din"), req.get("dout")
    xdt = jnp.dtype(req.get("xdt"))
    xb, wb = _bytes(req.get("xdt")), _bytes(req.get("wdt"))
    out: List[Tuple[int, int]] = []
    seen = set()
    for bm, bn in [GMM_DEFAULT] + [
        (m, n) for m in _GMM_BLOCK_M for n in _GMM_BLOCK_N
    ]:
        eff = legal_gmm_blocks(bm, bn, T, Dout, xdt)
        if eff in seen:
            continue
        # resident tiles: x [bm, Din] + w [Din, bn] + f32 acc/out [bm, bn]
        # (packed int4: the w tile holds ceil(Din/2) nibble-pair rows).
        # The default (first) candidate is exempt: it is what an untuned
        # process runs, so it must stay in the sweep as the baseline —
        # dropping it would let a "tuned" pick be slower than untuned.
        w_rows = -(-Din // 2) if req.get("pk") else Din
        vmem = (eff[0] * Din * xb + w_rows * eff[1] * wb
                + 2 * eff[0] * eff[1] * 4)
        if out and vmem > _VMEM_BUDGET:
            continue
        seen.add(eff)
        out.append(eff)
    return out


def attn_candidates(req: TuneRequest) -> List[Tuple[int, int]]:
    """Effective (block_q, block_k) candidates for one attention key."""
    Sq, Sk, hd = req.get("sq"), req.get("sk"), req.get("hd")
    qdt, kdt = jnp.dtype(req.get("qdt")), jnp.dtype(req.get("kdt"))
    out: List[Tuple[int, int]] = []
    seen = set()
    for bq, bk in [attn_default(Sq, Sk)] + [
        (q, k) for q in _ATTN_BLOCK_Q for k in _ATTN_BLOCK_K
    ]:
        eff = legal_attn_blocks(bq, bk, Sq, Sk, qdt)
        if eff in seen:
            continue
        # q tile + k/v tiles + m/l scratch (bq, 128) + acc (bq, hd), all f32
        # in-kernel plus the dtype-sized HBM tiles; the default (first)
        # candidate is exempt — see gmm_candidates
        vmem = (eff[0] * hd * 4 + 2 * eff[1] * hd * max(4, kdt.itemsize)
                + 2 * eff[0] * 128 * 4 + eff[0] * hd * 4)
        if out and vmem > _VMEM_BUDGET:
            continue
        seen.add(eff)
        out.append(eff)
    return out


def candidates_for(req: TuneRequest) -> List[Tuple[int, int]]:
    if req.kernel == "grouped_matmul":
        return gmm_candidates(req)
    if req.kernel == "streaming_attention":
        return attn_candidates(req)
    raise KeyError(f"unknown kernel {req.kernel!r}")


def default_blocks_for(req: TuneRequest) -> Tuple[int, int]:
    return candidates_for(req)[0]


# ---------------------------------------------------------------------------
# Tuning table (persistent, versioned, per device kind)
# ---------------------------------------------------------------------------

def device_kind() -> str:
    d = jax.devices()[0]
    return getattr(d, "device_kind", None) or d.platform


def _sanitize(kind: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]+", "-", kind).strip("-") or "unknown"


def table_path(cfg: AutotuneConfig, kind: Optional[str] = None) -> str:
    base = cfg.cache_dir or os.environ.get("REPRO_AUTOTUNE_CACHE",
                                           ".repro_autotune")
    return os.path.join(base, f"autotune_{_sanitize(kind or device_kind())}.json")


class TuningTable:
    """In-memory tuning table bound to one device kind + cache file.

    ``entries`` maps the key string to
    ``{"blocks": [a, b], "ms": float|None, "source": "swept"|"default"|
    "override"}``. ``stats`` counts lookup ``hits``/``misses`` and
    ``swept`` (new entries created) — the cache-hit acceptance check is
    "a second warmup leaves ``swept`` unchanged"."""

    def __init__(self, kind: str, path: Optional[str] = None) -> None:
        self.device_kind = kind
        self.path = path
        self.entries: Dict[str, dict] = {}
        self.stats = {"hits": 0, "misses": 0, "swept": 0}
        self.dirty = False

    # -- lookups ------------------------------------------------------------

    def lookup(self, key: str) -> Optional[Tuple[int, int]]:
        e = self.entries.get(key)
        if e is None:
            self.stats["misses"] += 1
            return None
        self.stats["hits"] += 1
        return tuple(e["blocks"])  # type: ignore[return-value]

    def get(self, key: str) -> Optional[dict]:
        return self.entries.get(key)

    def put(self, key: str, blocks: Tuple[int, int], ms: Optional[float],
            source: str) -> None:
        entry = {"blocks": [int(blocks[0]), int(blocks[1])],
                 "ms": None if ms is None else float(ms),
                 "source": source}
        if self.entries.get(key) != entry:
            self.entries[key] = entry
            self.dirty = True

    # -- persistence --------------------------------------------------------

    def to_json(self) -> dict:
        return {
            "table_version": TABLE_VERSION,
            "device_kind": self.device_kind,
            "kernel_versions": dict(KERNEL_VERSIONS),
            "entries": self.entries,
        }

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        assert path, "tuning table has no cache path"
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        self.dirty = False
        return path

    @classmethod
    def load(cls, path: Optional[str], kind: str) -> "TuningTable":
        """Load a table, discarding anything unusable: a corrupt file, a
        version or device-kind mismatch, stale per-kernel entries, or
        malformed blocks. Never raises — worst case is an empty table
        (deterministic default tiles)."""
        table = cls(kind, path)
        if not path or not os.path.exists(path):
            return table
        try:
            with open(path) as f:
                raw = json.load(f)
        except (OSError, ValueError):
            return table
        if not isinstance(raw, dict):
            return table
        if raw.get("table_version") != TABLE_VERSION:
            return table
        if raw.get("device_kind") != kind:
            return table
        file_kv = raw.get("kernel_versions") or {}
        for key, entry in (raw.get("entries") or {}).items():
            kernel = str(key).split("|", 1)[0]
            if file_kv.get(kernel) != KERNEL_VERSIONS.get(kernel):
                continue  # swept against an older kernel: stale
            try:
                blocks = [int(entry["blocks"][0]), int(entry["blocks"][1])]
                ms = entry.get("ms")
                source = str(entry.get("source", "swept"))
            except (TypeError, KeyError, IndexError, ValueError):
                continue
            table.entries[key] = {
                "blocks": blocks,
                "ms": None if ms is None else float(ms),
                "source": source,
            }
        return table


# ---------------------------------------------------------------------------
# Ambient state: active table + collection scope
# ---------------------------------------------------------------------------

_ACTIVE: Optional[TuningTable] = None
_COLLECT: Optional[Dict[str, TuneRequest]] = None


def active_table() -> Optional[TuningTable]:
    return _ACTIVE


def activate(table: Optional[TuningTable]) -> None:
    """Install (or clear, with None) the process-wide active table —
    consulted by every ``kernels.ops`` dispatch at trace time."""
    global _ACTIVE
    _ACTIVE = table


deactivate = lambda: activate(None)  # noqa: E731 — test/teardown sugar


@contextlib.contextmanager
def collecting():
    """Scope in which ops dispatches *record* the tuning keys they would
    look up (used around ``jax.eval_shape`` traces of replica programs).
    Yields the key -> TuneRequest dict being filled."""
    global _COLLECT
    prev, _COLLECT = _COLLECT, {}
    try:
        yield _COLLECT
    finally:
        keys, _COLLECT = _COLLECT, prev
        if prev is not None:
            prev.update(keys)  # nested scopes fold outward


def _resolve(req: TuneRequest, default: Tuple[int, int]) -> Tuple[int, int]:
    if _COLLECT is not None:
        _COLLECT.setdefault(req.key, req)
    if _ACTIVE is None:
        return default
    return _ACTIVE.lookup(req.key) or default


def gmm_blocks(T: int, G: int, Din: int, Dout: int, *, x_dtype, w_dtype,
               scaled: bool, ascaled: bool) -> Tuple[int, int]:
    """Tile config for one grouped_matmul dispatch: the tuned entry when
    the active table has this shape bucket, the defaults otherwise."""
    req = gmm_request(T, G, Din, Dout, x_dtype=x_dtype, w_dtype=w_dtype,
                      scaled=scaled, ascaled=ascaled)
    return _resolve(req, GMM_DEFAULT)


def attn_blocks(B: int, H: int, KVH: int, hd: int, Sq: int, Sk: int, *,
                causal: bool, quant_bits: int, scaled: bool,
                q_dtype, k_dtype, local_window: int = 0) -> Tuple[int, int]:
    """Tile config for one streaming_attention dispatch (see gmm_blocks;
    the default is ``attn_default``)."""
    req = attn_request(B, H, KVH, hd, Sq, Sk, causal=causal,
                       quant_bits=quant_bits, scaled=scaled,
                       q_dtype=q_dtype, k_dtype=k_dtype,
                       local_window=local_window)
    return _resolve(req, attn_default(Sq, Sk))


# ---------------------------------------------------------------------------
# Sweeping
# ---------------------------------------------------------------------------

def should_time() -> bool:
    """Real timing only makes sense on the compiled TPU path; interpret
    mode is a python emulation and the ref path ignores tiles entirely."""
    from repro.kernels.ops import kernel_mode

    return jax.default_backend() == "tpu" and kernel_mode() == "pallas"


def _balanced_sizes(T: int, G: int) -> jnp.ndarray:
    base = T // G
    sizes = [base] * G
    sizes[0] += T - base * G
    return jnp.asarray(sizes, jnp.int32)


def build_candidate(req: TuneRequest, blocks: Tuple[int, int], *,
                    interpret: bool = False) -> Callable[[], jax.Array]:
    """A zero-arg jitted callable running the kernel for this request at
    the given tiles, over synthetic operands (committed to device once)."""
    import functools

    if req.kernel == "grouped_matmul":
        from repro.kernels.expert_linear import grouped_matmul

        T, G = req.get("T"), req.get("G")
        Din, Dout = req.get("din"), req.get("dout")
        xdt, wdt = jnp.dtype(req.get("xdt")), jnp.dtype(req.get("wdt"))
        x = jnp.ones((T, Din), xdt)
        if req.get("pk"):  # nibble-packed int4: uint8 rows of ceil(Din/2)
            w = jnp.full((G, -(-Din // 2), Dout), 0x11, jnp.uint8)
        else:
            w = jnp.ones((G, Din, Dout), wdt)
        gs = _balanced_sizes(T, G)
        kw = dict(block_m=blocks[0], block_n=blocks[1], interpret=interpret)
        if req.get("ws"):
            kw["w_scale"] = jnp.ones((G, Dout), jnp.float32)
        if req.get("as"):
            kw["a_scale"] = jnp.float32(1.0)
        fn = jax.jit(functools.partial(grouped_matmul, **kw))
        return lambda: fn(x, w, gs)

    if req.kernel == "streaming_attention":
        from repro.kernels.quant_attention import streaming_attention

        B, H, KVH, hd = (req.get("B"), req.get("H"), req.get("kvh"),
                         req.get("hd"))
        Sq, Sk = req.get("sq"), req.get("sk")
        qdt, kdt = jnp.dtype(req.get("qdt")), jnp.dtype(req.get("kdt"))
        q = jnp.ones((B, Sq, H, hd), qdt)
        k = jnp.ones((B, Sk, KVH, hd), kdt)
        v = jnp.ones((B, Sk, KVH, hd), kdt)
        kw = dict(
            causal=bool(req.get("causal")), quant_bits=req.get("qb"),
            local_window=req.get("lw"),
            block_q=blocks[0], block_k=blocks[1], interpret=interpret,
        )
        if req.get("ks"):
            kw["k_scale"] = jnp.ones((B, Sk, KVH), jnp.float32)
            kw["v_scale"] = jnp.ones((B, Sk, KVH), jnp.float32)
        fn = jax.jit(functools.partial(streaming_attention, **kw))
        return lambda: fn(q, k, v)

    raise KeyError(f"unknown kernel {req.kernel!r}")


def wall_timer(fn: Callable[[], jax.Array], blocks: Tuple[int, int], *,
               reps: int = 5) -> float:
    """Median wall-time (ms) of ``fn`` after one untimed compile+run.

    ``blocks`` identifies the candidate being timed; the real timer does
    not need it, but it is part of the ``timer(fn, blocks, reps=)``
    injection contract so tests/benchmarks can rank candidates
    deterministically without executing them."""
    jax.block_until_ready(fn())  # compile + warm
    times = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return times[len(times) // 2]


def sweep_request(req: TuneRequest, cfg: AutotuneConfig, *,
                  timer=None, collect_all: bool = False):
    """Pick the fastest legal tile config for one tuning key.

    Returns the entry dict (``collect_all=True`` additionally returns the
    full ``[(blocks, ms), ...]`` candidate list, default first — the
    benchmark consumes it). ``timer(fn, blocks, reps=)`` can be injected
    (tests, benchmarks); with the default timer nothing is timed off-TPU
    and the entry is the deterministic default config."""
    cands = candidates_for(req)[: max(1, int(cfg.budget))]
    if timer is None and not should_time():
        entry = {"blocks": list(cands[0]), "ms": None, "source": "default"}
        return (entry, [(cands[0], None)]) if collect_all else entry
    timer = timer or wall_timer
    results: List[Tuple[Tuple[int, int], float]] = []
    for i, blocks in enumerate(cands):
        try:
            ms = timer(build_candidate(req, blocks), blocks, reps=cfg.reps)
        except Exception:
            if i == 0:
                # the default tiles are what serving runs without a table:
                # if they fail, the kernel is broken on this device
                raise
            continue  # illegal on this hardware: skip the candidate
        results.append((blocks, float(ms)))
    best = min(results, key=lambda r: r[1])
    entry = {"blocks": list(best[0]), "ms": best[1], "source": "swept"}
    return (entry, results) if collect_all else entry


# ---------------------------------------------------------------------------
# ensure_tuned — the warmup entry point
# ---------------------------------------------------------------------------

def _apply_overrides(table: TuningTable, cfg: AutotuneConfig) -> None:
    for key, blocks in cfg.overrides:
        table.put(str(key), (int(blocks[0]), int(blocks[1])), None,
                  "override")


def ensure_tuned(cfg: AutotuneConfig,
                 trace_fn: Optional[Callable[[], None]] = None, *,
                 timer=None) -> Optional[TuningTable]:
    """Load (or reuse) this device kind's tuning table, collect the keys
    ``trace_fn`` touches, sweep the missing ones, persist, and leave the
    table active for every subsequent kernel dispatch.

    Engine ``warmup()`` calls this once per replica before admission
    opens; the table is process-global and persisted per device kind, so
    the second replica (or a relaunch on the same device kind) is a pure
    cache hit — ``stats['swept']`` does not move."""
    global _ACTIVE
    if not cfg.enable:
        return _ACTIVE
    kind = device_kind()
    path = table_path(cfg, kind)
    if _ACTIVE is None or _ACTIVE.device_kind != kind \
            or _ACTIVE.path != path:
        _ACTIVE = TuningTable.load(path, kind)
    table = _ACTIVE
    _apply_overrides(table, cfg)
    if trace_fn is not None:
        with collecting() as reqs:
            trace_fn()
        for req in reqs.values():
            if table.get(req.key) is not None:
                table.stats["hits"] += 1
                continue
            entry = sweep_request(req, cfg, timer=timer)
            table.put(req.key, tuple(entry["blocks"]), entry["ms"],
                      entry["source"])
            table.stats["swept"] += 1
    if table.dirty and table.path:
        table.save()
    return table


def summary(table: Optional[TuningTable] = None) -> str:
    """One-line human summary for launchers."""
    t = table or _ACTIVE
    if t is None:
        return "autotune: inactive"
    swept = sum(1 for e in t.entries.values() if e["source"] == "swept")
    return (f"autotune[{t.device_kind}]: {len(t.entries)} entries "
            f"({swept} swept) hits={t.stats['hits']} "
            f"swept_now={t.stats['swept']} table={t.path}")
