"""Vision serving engine: dynamic-batching MoE-ViT inference
(DESIGN.md section 6 — the serving half of the paper's headline FPS result).

Request path:

  submit(VisionRequest) -> MicroBatcher (bucketed admission, max-wait
  deadline, backpressure) -> padded bucket batch -> jitted
  ``models.classify`` forward (fp / fake-quant / materialized-int8
  QuantizedParams trees all flow through the same ``quant_linear`` seam)
  -> top-k class responses + per-expert routed-token occupancy.

Dispatch is **double-buffered**: up to ``max_inflight`` device batches are
outstanding at once — batch N+1 is padded, transferred, and enqueued while
batch N's device work is still in flight (JAX async dispatch), so the host
never serializes the device. Results are only synchronized (``np.asarray``)
when a batch is *retired* — when the in-flight window is full or at drain.

Batch shapes are quantized to the ``batch_buckets`` ladder (pad rows of
zeros), so the engine compiles exactly ``len(batch_buckets)`` programs and
never re-traces at serving time; call ``warmup()`` to move all compiles out
of the measured path. A launch that compiles anyway counts in the
``retraces`` counter.

Host phases (serving/trace.py ``host_span``, each with ``batch=<dispatch
sequence number>``): ``engine.pad`` (bucket buffer and row copies; ``b``,
``n``), ``engine.h2d`` (the input transfer), ``engine.launch`` (the
classify call up to its futures; ``retrace``), ``engine.wait`` (the host
blocked on the batch's classes), ``engine.fetch`` (probabilities and
routed-token counts to host) and ``engine.deliver`` (metrics, result fill,
callbacks).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import models
from repro.configs.base import ModelConfig
from repro.models import vit
from repro.serving.engine import serving_config
from repro.serving.events import EventLog
from repro.serving.metrics import EngineMetrics
from repro.serving.scheduler import MicroBatcher
from repro.serving.trace import host_span, install_gc_spans, make_tracer


@dataclasses.dataclass
class VisionRequest:
    """One image to classify. ``patches`` is the flattened patch sequence
    [image_tokens - 1, PATCH_DIM]; results are filled in at retirement."""

    uid: int
    patches: np.ndarray
    classes: Optional[np.ndarray] = None  # [k] int32, most-probable first
    probs: Optional[np.ndarray] = None  # [k] f32, descending
    latency_s: Optional[float] = None
    # None = not yet admitted; a 0.0 stamp from a fake clock is a real stamp
    submitted_at: Optional[float] = None
    # span-timeline identity (serving/trace.py); cluster-assigned, falls
    # back to uid on a standalone engine. None with tracing off.
    trace_id: Optional[int] = None
    # terminal-delivery callback (same contract as engine.Request.on_done):
    # fired exactly once at retirement, off the dispatch path; the chaos
    # benchmark counts terminal callbacks per accepted request through it
    on_done: Optional[Callable[["VisionRequest"], None]] = None
    # lifecycle + eviction bookkeeping, mirroring engine.Request (the
    # cluster's at-most-once/re-dispatch machinery is engine-agnostic)
    status: str = dataclasses.field(default="pending", repr=False)
    redispatched: int = dataclasses.field(default=0, repr=False)
    evicted: bool = dataclasses.field(default=False, repr=False)

    @property
    def done(self) -> bool:
        return self.classes is not None


@dataclasses.dataclass
class _InFlight:
    seq: int  # dispatch sequence number (the host spans' ``batch``)
    reqs: tuple  # the real requests in this device batch
    pad_to: int  # padded batch size actually dispatched
    out: dict  # device arrays from classify (not yet synchronized)
    dispatched_at: float
    # when the host first saw the results ready: _head_ready read true or
    # the blocking wait returned
    ready_at: Optional[float] = None


class VisionEngine:
    """Dynamic-batching MoE-ViT classifier engine (single-host driver)."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        batch_buckets: Sequence[int] = (1, 4, 8),
        max_wait_s: float = 2e-3,
        max_pending: int = 1024,
        top_k: int = 5,
        max_inflight: int = 2,
        mesh: Optional[Mesh] = None,
        events: Optional[EventLog] = None,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        if cfg.family not in ("vit", "vit_moe"):
            raise ValueError(f"vision families only, got {cfg.family!r}")
        # dropless grouped MoE for serving, same rule as the LM engine
        self.cfg = serving_config(cfg)
        self.params = params
        # observability (DESIGN.md section 11): vision timelines are
        # queue -> infer -> retire (one batched forward is the service)
        self.tracer = make_tracer(self.cfg.trace, clock=clock)
        self.events = events
        # step timing serves tracing AND the introspection MFU join
        self._step_times = ((self.tracer.enabled
                             and self.cfg.trace.step_times)
                            or self.cfg.introspect.enable)
        self.top_k = min(top_k, cfg.num_classes)
        self.n_patches = cfg.image_tokens - 1
        self._clock = clock
        self.scheduler = MicroBatcher(
            batch_sizes=batch_buckets, max_wait_s=max_wait_s,
            max_pending=max_pending, clock=clock,
        )
        self.metrics = EngineMetrics(
            num_experts=cfg.moe.num_experts if cfg.moe is not None else 0,
            clock=clock,
        )
        self.expert_health = None
        if self.cfg.introspect.enable and cfg.moe is not None:
            from repro.serving.introspect import ExpertHealthMonitor

            self.expert_health = ExpertHealthMonitor(
                cfg.moe.num_experts,
                window_tokens=self.cfg.introspect.drift_window_tokens,
                drift_threshold=self.cfg.introspect.drift_threshold,
                baseline_alpha=self.cfg.introspect.baseline_alpha,
                events=events, label="vision", clock=clock,
                on_drift=lambda info: self.metrics.inc("expert_drift"))
            self.metrics.expert_health = self.expert_health
        self.max_inflight = max(1, int(max_inflight))
        self._inflight: deque = deque()
        self._seq = 0  # batches dispatched
        # ready time of the last retired batch: the next one's device
        # work starts no earlier (one chip runs its batches in order)
        self._last_ready = float("-inf")
        self.metrics.inc("retraces", 0)  # exported as 0 until one happens
        install_gc_spans()
        self.mesh = mesh
        self._ep = (cfg.moe is not None
                    and cfg.moe.moe_exec == "expert_parallel")
        cfg_c, k = self.cfg, self.top_k
        fwd = lambda prm, x: models.classify(prm, cfg_c, x, top_k=k)
        self._ep_scope = contextlib.nullcontext
        if mesh is None:
            if self._ep:
                raise ValueError(
                    "moe_exec='expert_parallel' needs mesh= (a 'model'-axis "
                    "mesh whose size divides num_experts)")
            self._classify = jax.jit(fwd)
            self._lowerable = self._classify
        else:
            # pin this replica to its mesh slice: params device_put with
            # per-leaf specs (expert stacks sharded over 'model' under EP,
            # everything replicated otherwise), forward jitted against them
            from repro.distributed.expert_parallel import (
                use_ep_mesh,
                validate_ep,
            )
            from repro.distributed.sharding_rules import (
                EXPERT_PARALLEL_RULES,
                fit_specs_to_tree,
                param_specs,
            )

            if self._ep:
                validate_ep(self.cfg, mesh)
                specs = fit_specs_to_tree(
                    param_specs(self.cfg, mesh, rules=EXPERT_PARALLEL_RULES),
                    params,
                )
            else:
                specs = jax.tree.map(lambda _: P(), params)
            named = lambda tree: jax.tree.map(
                lambda s: NamedSharding(mesh, s), tree,
                is_leaf=lambda x: isinstance(x, P),
            )
            self.params = jax.device_put(params, named(specs))
            jitted = jax.jit(fwd, in_shardings=(
                named(specs), NamedSharding(mesh, P())))
            self._lowerable = jitted  # warmup AOT-lowers it for cost rows
            ep_scope = (
                (lambda: use_ep_mesh(mesh)) if self._ep
                else contextlib.nullcontext
            )
            self._ep_scope = ep_scope

            def call(prm, x):
                # the EP mesh is ambient trace-time state; entering the
                # scope on every call keeps retraces (new bucket shapes)
                # correct and costs nothing once compiled
                with ep_scope():
                    return jitted(prm, x)

            self._classify = call

    # -- lifecycle ----------------------------------------------------------

    def _tune_trace(self) -> None:
        """Abstract (eval_shape) trace of every bucket's classify program,
        so the autotuner collects this replica's kernel shape keys without
        compiling anything. Under EP the trace runs in the replica's mesh
        scope and sees the per-shard local shapes."""
        for b in self.scheduler.batch_sizes:
            x = jax.ShapeDtypeStruct((b, self.n_patches, vit.PATCH_DIM),
                                     jnp.float32)
            with self._ep_scope():
                jax.eval_shape(
                    lambda prm, xx: models.classify(prm, self.cfg, xx,
                                                    top_k=self.top_k),
                    self.params, x)

    def warmup(self) -> None:
        """Tune tile configs for this replica's shapes (pure cache hit
        after the first warmup on a device kind), then compile every
        bucket size up front (keeps XLA compiles out of the measured
        serving path; the benchmark calls this before timing)."""
        if self.cfg.autotune.enable:
            from repro.kernels import autotune

            autotune.ensure_tuned(self.cfg.autotune, self._tune_trace)
        for b in self.scheduler.batch_sizes:
            x = jnp.zeros((b, self.n_patches, vit.PATCH_DIM), jnp.float32)
            jax.block_until_ready(self._classify(self.params, x))
        if self.cfg.introspect.enable:
            # AOT-lower each bucket program once, purely to read its cost
            # surfaces (warmup is untimed). A compile that fails raises;
            # only the reading of the surfaces degrades (capture_cost)
            programs = {}
            for b in self.scheduler.batch_sizes:
                x = jax.ShapeDtypeStruct(
                    (b, self.n_patches, vit.PATCH_DIM), jnp.float32)
                with self._ep_scope():
                    programs[f"classify|b={b}"] = self._lowerable.lower(
                        self.params, x).compile()
            from repro.serving import introspect

            devices = (list(self.mesh.devices.flat)
                       if self.mesh is not None else None)
            introspect.install(self.metrics, cfg=self.cfg,
                               programs=programs, params=self.params,
                               devices=devices)

    @property
    def inflight(self) -> int:
        """Requests inside dispatched (not yet retired) device batches —
        the public in-flight surface (the cluster never reads
        ``_inflight``)."""
        return sum(len(f.reqs) for f in self._inflight)

    @property
    def load(self) -> int:
        """Queued + in-flight requests — the cluster's least-loaded routing
        signal (DESIGN.md section 7)."""
        return self.scheduler.depth + self.inflight

    @property
    def idle(self) -> bool:
        return self.scheduler.depth == 0 and not self._inflight

    @property
    def free_room(self) -> float:
        """Admission slots left before ``submit`` raises ``Backpressure``
        (inf when unbounded)."""
        return self.scheduler.room

    def reset_metrics(self) -> None:
        """Fresh ``EngineMetrics`` (cluster replica leave — the old one was
        folded into the retired accumulator)."""
        old = self.metrics
        self.metrics = EngineMetrics(
            num_experts=old.expert_tokens.size, clock=self._clock)
        self.metrics.adopt_static(old)

    def submit(self, req: VisionRequest) -> None:
        """Enqueue one image; raises ``scheduler.Backpressure`` when the
        pending queue is at ``max_pending``. A ``submitted_at`` already
        stamped upstream (the cluster front-end) is preserved so request
        latency includes admission-queue wait, not just replica time."""
        if req.submitted_at is None:
            req.submitted_at = self._clock()
        try:
            self.scheduler.submit(req)
        except Exception:
            self.metrics.inc("rejected")
            if self.events is not None:
                self.events.emit("reject", uid=req.uid,
                                 reason="backpressure",
                                 depth=self.scheduler.depth)
            raise
        self.metrics.inc("submitted")
        if self.tracer.enabled:
            if req.trace_id is None:
                req.trace_id = req.uid
            self.tracer.begin(req.trace_id, "queue", t=req.submitted_at)
        self.metrics.observe_queue_depth(self.scheduler.depth)

    def step(self) -> None:
        """One pump: retire finished batches (device results already
        materialized — no blocking), force-retire the oldest if the
        in-flight window is still full, then dispatch every ready batch the
        window has room for. Call from the submit loop to overlap host and
        device."""
        while self._inflight and self._head_ready():
            self._retire_one()
        if len(self._inflight) >= self.max_inflight:
            self._retire_one()
        self._dispatch_ready()

    def flush(self) -> None:
        """Drain: release partial batches immediately, dispatch everything
        queued, and retire every in-flight batch."""
        self.scheduler.drain(True)
        try:
            while self.scheduler.depth or self._inflight:
                self._dispatch_ready()
                if self._inflight:
                    self._retire_one()
        finally:
            self.scheduler.drain(False)

    run_until_drained = flush

    def evict(self) -> List[VisionRequest]:
        """Quarantine support (serving/cluster.py): strand-and-return every
        request this replica holds — queued plus in dispatched batches —
        without waiting on (possibly wedged) device work. Dispatched device
        batches are abandoned unsynchronized; their requests are marked
        ``evicted`` so a late retirement of the same batch object is a
        no-op."""
        stranded = list(self.scheduler.clear())
        for ent in self._inflight:
            stranded.extend(ent.reqs)
        self._inflight.clear()
        out = []
        for req in stranded:
            if req.status != "pending":
                continue  # terminal before the eviction: nothing to redo
            req.evicted = True
            out.append(req)
        return out

    # -- internals ----------------------------------------------------------

    def _head_ready(self) -> bool:
        """Whether the oldest in-flight batch's device work has finished —
        retiring it then stamps request latency at actual completion, not
        at the next forced sync (open-loop percentiles stay honest). The
        first true reading stamps the batch's ``ready_at``."""
        head = self._inflight[0]
        if head.ready_at is None:
            is_ready = getattr(head.out["classes"], "is_ready", None)
            if is_ready is None or not is_ready():
                return False
            head.ready_at = self._clock()
        return True

    def _dispatch_ready(self) -> None:
        while len(self._inflight) < self.max_inflight:
            batch = self.scheduler.poll()
            if batch is None:
                return
            reqs = batch.items
            seq = self._seq
            self._seq += 1
            with host_span("engine.pad", batch=seq, b=batch.pad_to,
                           n=len(reqs)):
                x = np.zeros((batch.pad_to, self.n_patches, vit.PATCH_DIM),
                             np.float32)
                for i, r in enumerate(reqs):
                    x[i] = r.patches
            t0 = self._clock()
            for r in reqs:
                # per-request admission wait measured from the submitted_at
                # stamp (cluster front-end or engine submit) to dispatch —
                # the same semantics ServeEngine records before prefill, so
                # queue_wait_ms compares across engine families
                self.metrics.queue_wait.record(max(0.0, t0 - r.submitted_at))
                if self.tracer.enabled:
                    self.tracer.transition(r.trace_id, "queue", "infer",
                                           t=t0, pad_to=batch.pad_to)
            with host_span("engine.h2d", batch=seq):
                xd = jnp.asarray(x)
            # async dispatch: returns device futures; nothing blocks here
            with host_span("engine.launch", batch=seq) as span:
                compiled = self._lowerable._cache_size()
                out = self._classify(self.params, xd)
                # a launch that grew the program's cache compiled
                retrace = self._lowerable._cache_size() > compiled
                if span is not None:
                    span.set_metadata(retrace=int(retrace))
            if retrace:
                self.metrics.inc("retraces")
            self._inflight.append(
                _InFlight(seq, reqs, batch.pad_to, out, t0))
            self.metrics.inc("batches")
            self.metrics.inc("padded_frames", batch.pad_to - len(reqs))
            # padding-waste observability in *token* units, comparable with
            # the LM engine's pack buffer counters (DESIGN.md section 10):
            # every row carries n_patches patch tokens, pad rows included
            self.metrics.inc("pack_real_tokens", len(reqs) * self.n_patches)
            self.metrics.inc("pack_pad_tokens",
                             (batch.pad_to - len(reqs)) * self.n_patches)
            self.metrics.observe_queue_depth(self.scheduler.depth)

    def _retire_one(self) -> None:
        ent = self._inflight.popleft()
        with host_span("engine.wait", batch=ent.seq):
            classes = np.asarray(ent.out["classes"])  # synchronizes the batch
        if ent.ready_at is None:
            ent.ready_at = self._clock()
        with host_span("engine.fetch", batch=ent.seq):
            probs = np.asarray(ent.out["probs"])
            # the answers are whole: request latency ends here, before the
            # routed-token counts (metrics only) come over
            now = self._clock()
            et = ent.out.get("expert_tokens")
            et = np.asarray(et) if et is not None and et.size else None
        with host_span("engine.deliver", batch=ent.seq):
            self._deliver(ent, now, classes, probs, et)

    def _deliver(self, ent: _InFlight, now: float, classes, probs,
                 et) -> None:
        # the batch's estimated device interval: its work starts when it
        # was dispatched or when the batch before it finished, whichever
        # is later, and ends when the host first saw it ready
        start = max(ent.dispatched_at, self._last_ready)
        self._last_ready = ent.ready_at
        step_s = ent.ready_at - start
        self.metrics.batch_latency.record(step_s)
        trace = self.tracer.enabled
        if self._step_times:
            # per-bucket step latency, keyed like the autotune/program-key
            # namespace so cluster snapshots read as one schema
            self.metrics.record_step(f"classify|b={ent.pad_to}", step_s)
        if trace:
            self.tracer.record_span(f"classify|b={ent.pad_to}",
                                    start, ent.ready_at,
                                    n=len(ent.reqs), pad_to=ent.pad_to)
        if et is not None:
            # NB: includes the pad rows' routed tokens — interpret together
            # with counters["padded_frames"] (DESIGN.md section 6)
            self.metrics.add_expert_tokens(et)
        for i, req in enumerate(ent.reqs):
            if req.evicted or req.status != "pending":
                # evicted mid-flight (the cluster owns it) or a duplicate
                # retirement of an already-terminal request — exactly-once
                if not req.evicted:
                    self.metrics.inc("duplicate_retirements")
                continue
            req.classes = classes[i]
            req.probs = probs[i]
            req.latency_s = now - req.submitted_at
            req.status = "completed"
            self.metrics.request_latency.record(req.latency_s)
            self.metrics.inc("completed")
            if req.on_done is not None:
                try:
                    req.on_done(req)
                except Exception as e:
                    self.metrics.inc("callback_errors")
                    if self.events is not None:
                        self.events.emit("callback_error", uid=req.uid,
                                         error=repr(e))
            if trace:
                # infer ends at the SAME `now` the latency record uses —
                # queue+infer sums to latency_s; retire is result fill-in
                self.tracer.transition(req.trace_id, "infer", "retire",
                                       t=now)
                self.tracer.end(req.trace_id, "retire",
                                latency_s=req.latency_s)
        self.metrics.work_done(len(ent.reqs), "frames")


def synth_requests(cfg: ModelConfig, n: int, seed: int = 0,
                   scale: float = 1.0) -> List[VisionRequest]:
    """n synthetic image-patch requests for benchmarks/examples/tests."""
    rng = np.random.default_rng(seed)
    T = cfg.image_tokens - 1
    return [
        VisionRequest(
            uid=i,
            patches=(scale * rng.standard_normal((T, vit.PATCH_DIM)))
            .astype(np.float32),
        )
        for i in range(n)
    ]
