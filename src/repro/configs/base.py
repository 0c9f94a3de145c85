"""Config dataclasses for the repro framework.

Every selectable ``--arch`` is a ``ModelConfig``; every benchmark/dry-run
input shape is a ``ShapeConfig``. Configs are frozen dataclasses so they can
be hashed into jit static args.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class AttnConfig:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10_000.0
    local_window: int = 0  # 0 = global attention
    alternate_local_global: bool = False  # gemma2: layer pairs (local, global)
    logit_softcap: float = 0.0  # gemma2 attention logit soft-capping
    qk_norm: bool = False  # qwen3 / olmoe per-head RMS QK-norm

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff: int  # per-expert hidden dim
    moe_every: int = 1  # every Nth layer is MoE (1 = all layers)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # grouped = sort-based unified kernel (the paper's orchestration);
    # gshard  = capacity dispatch/combine einsums (GSPMD-native EP at scale)
    impl: str = "grouped"
    # single          = every device holds the full expert stack (default);
    # expert_parallel = grouped path under shard_map: expert stacks sharded
    #                   over the 'model' mesh axis, tokens exchanged with
    #                   all_to_all (distributed/expert_parallel.py). Serving
    #                   only (requires impl="grouped"); the mesh is supplied
    #                   via distributed.expert_parallel.use_ep_mesh.
    moe_exec: str = "single"


@dataclass(frozen=True)
class SSMConfig:
    state_dim: int
    version: int = 1  # 1 = Mamba-1 (falcon-mamba), 2 = Mamba-2 (zamba2)
    expand: int = 2
    conv_width: int = 4
    head_dim: int = 64  # mamba2 only
    dt_rank: int = 0  # mamba1; 0 = ceil(d_model / 16)
    scan_chunk: int = 128  # chunked selective-scan chunk length

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def num_ssm_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class QuantConfig:
    """The paper's dual-stage quantization scheme (CoQMoE §3)."""

    enable: bool = False
    w_bits: int = 8
    a_bits: int = 8
    attn_bits: int = 4  # post-softmax log-sqrt2 quantizer bits
    post_norm_reparam: bool = True  # Eqs. 10-16
    softmax_log_sqrt2: bool = True  # Eqs. 17-21
    kv_cache_int8: bool = True  # serving: int8 K/V cache
    # Per-site mixed-scheme map for ``ptq_model(materialize="int4")``
    # (DESIGN.md section 13): (dotted-path-suffix pattern, scheme) pairs,
    # e.g. (("moe.wi", "int4"), ("moe.wo", "int4")). Longest-suffix match
    # wins; unmatched sites stay int8. Empty = the documented experts-only
    # default (ptq.DEFAULT_INT4_SCHEME) when int4 materialization is
    # requested. Also honored by ``materialize="fake"`` to build the
    # fake-quant oracle of a mixed tree.
    scheme_map: Tuple[Tuple[str, str], ...] = ()


@dataclass(frozen=True)
class AutotuneConfig:
    """Per-device Pallas tile-size autotuning (kernels/autotune.py,
    DESIGN.md section 9) — the TPU analogue of re-synthesizing the FPGA
    kernels per deployment (CoQMoE section 4).

    When enabled, engine ``warmup()`` traces every program the replica will
    compile (``jax.eval_shape`` — no device work), collects the kernel
    shape-bucket keys those programs hit, benchmarks candidate tile grids
    for each missing key on the actual device, and persists the winners in
    a versioned JSON table keyed by device kind. A later warmup on the same
    device kind is a pure cache hit (zero re-sweep). On CPU / interpret
    backends no timing happens — keys are filled with the deterministic
    default tiles."""

    enable: bool = False
    # sweep budget: max candidate tile configs timed per (kernel,
    # shape-bucket) key (the default config is always candidate #1, so the
    # chosen config is never slower than the default by construction)
    budget: int = 12
    # timing repetitions per candidate (median is recorded)
    reps: int = 5
    # directory holding one table file per device kind; None falls back to
    # $REPRO_AUTOTUNE_CACHE or ".repro_autotune"
    cache_dir: Optional[str] = None
    # pre-pinned entries applied on top of the loaded table, as
    # (entry_key, (block_a, block_b)) pairs — the ship-a-pretuned-table
    # hook (keys are the strings kernels/autotune.py builds; see DESIGN.md
    # section 9 for the key contract)
    overrides: Tuple[Tuple[str, Tuple[int, int]], ...] = ()


@dataclass(frozen=True)
class AutoscaleConfig:
    """Target-range admission autoscaling for ``ServingCluster``
    (serving/autoscaler.py — DESIGN.md section 8).

    The controller reacts to two pressure signals: front-end queue depth
    per active replica and the *windowed* pooled p95 request latency vs the
    SLO. Hysteresis comes from patience (consecutive breached evaluations
    before acting) plus a post-action cooldown, so a bursty arrival process
    does not flap the replica set."""

    min_replicas: int = 1
    max_replicas: int = 8
    # pre-warmed standby pool size ServingCluster should hold (replicas
    # beyond it are spawned + warmed on demand, which is much slower)
    standby: int = 1
    # scale-up triggers: front-end depth per active replica, or pooled
    # windowed p95 over the SLO
    depth_high: float = 4.0
    slo_p95_ms: float = 250.0
    up_patience: int = 2
    # scale-down triggers: total load at/below depth_low AND p95 under
    # down_margin * SLO, sustained for down_patience evaluations
    depth_low: float = 0.0
    down_margin: float = 0.5
    down_patience: int = 16
    # evaluations to wait after any scale action before the next one
    cooldown: int = 8
    # samples needed before the windowed p95 advances (below it the window
    # keeps accumulating and the previous estimate holds)
    min_window_samples: int = 8
    # evaluations without a window close before the p95 estimate expires to
    # NaN — a breach measured during a surge must not keep scaling (or pin
    # the replica count) once traffic has stopped
    p95_ttl: int = 32


@dataclass(frozen=True)
class TraceConfig:
    """Serving-stack tracing/profiling knobs (serving/trace.py,
    DESIGN.md section 11).

    With ``enable`` off (the default) engines hold the no-op
    ``NULL_TRACER`` and every instrumentation site reduces to one boolean
    attribute read — the disabled-path overhead contract the trace-overhead
    benchmark measures. With it on, every request gets a typed span
    timeline (queue/pack/prefill/decode/retire) in a bounded flight
    recorder, and the engines record per-program step wall times keyed by
    the section-10 AOT program key into ``EngineMetrics`` histograms."""

    enable: bool = False
    # flight-recorder ring capacity in spans; the oldest spans evict first
    # (recorder.dropped counts them)
    capacity: int = 65536
    # per-program step-latency histograms (decode tick + packed-prefill
    # dispatch, keyed serve/<prog>|B=..|S=..|... — the per-bucket step
    # latency signal the ROADMAP autotuner-drift item reads)
    step_times: bool = True


@dataclass(frozen=True)
class IntrospectConfig:
    """Live performance-introspection knobs (serving/introspect.py,
    DESIGN.md section 12).

    With ``enable`` on (the default), ``warmup()`` captures a per-program
    ``ProgramCost`` row (cost_analysis + memory_analysis + call-graph HLO
    metrics, analytic fallback marked ``estimated``) for every AOT program,
    attaches a memory-watermark probe, and — for MoE configs — runs the
    windowed expert-routing health monitor that emits ``expert_drift``
    events into the engine's ``EventLog``. Capture happens entirely at
    warmup; the only steady-state cost is the drift monitor's histogram
    accumulation, bounded by the trace-overhead contract."""

    enable: bool = True
    # routed tokens per drift-monitor window; a window closes (and drift is
    # evaluated) once this many (token, expert) routings accumulate
    drift_window_tokens: int = 4096
    # total-variation distance (L1/2) between a closed window's occupancy
    # and the reference occupancy above which an expert_drift event fires
    drift_threshold: float = 0.25
    # EMA weight folding each non-drifting window into the reference
    # occupancy (slow tracking, so gradual shift is not repeatedly flagged)
    baseline_alpha: float = 0.1


@dataclass(frozen=True)
class FaultConfig:
    """Serving fault model: chaos injection + watchdog/recovery knobs
    (serving/faults.py, DESIGN.md section 14).

    Two independent halves share the config:

    **Injection** (``inject`` — default off): the deterministic chaos
    harness. With it on, every replica the cluster builds is wrapped in a
    ``FaultyReplica`` decorator whose seeded ``FaultInjector`` raises step
    exceptions / OOM-shaped allocation failures, stalls steps (fake-clock
    compatible), rejects submits, and poisons ``on_done`` callbacks at the
    configured rates and schedule. With it off nothing is wrapped — the
    injection path literally does not exist at runtime (the NULL-injector
    discipline of ``NULL_TRACER``).

    **Watchdog / recovery** (``watchdog`` — default on): the per-replica
    health monitor and the quarantine/re-dispatch machinery in
    ``ServingCluster``. Budgets below decide when a replica is evicted and
    how often one request may be re-dispatched before it fails terminally.
    """

    # -- chaos injection (all rates are per-boundary Bernoulli draws from
    #    a replica-ordinal-seeded generator; 0.0 everywhere = no faults
    #    even when inject=True) --------------------------------------------
    inject: bool = False
    seed: int = 0
    step_error_rate: float = 0.0  # step() raises InjectedFault
    oom_rate: float = 0.0  # step() raises InjectedOOM (RESOURCE_EXHAUSTED)
    step_stall_rate: float = 0.0  # step() stalls stall_s before running
    stall_s: float = 0.25  # injected stall duration (clock seconds)
    submit_reject_rate: float = 0.0  # replica submit() raises Backpressure
    callback_poison_rate: float = 0.0  # wrap on_done to raise after running
    # deterministic schedule: (replica_ordinal, local_step, kind) triples,
    # kind in {"error", "oom", "stall", "dead"}. "dead" kills the replica
    # permanently — every later step raises too (a crashed process, not a
    # transient fault). Scheduled entries override the random draws.
    kill_schedule: Tuple[Tuple[int, int, str], ...] = ()

    # -- watchdog / recovery ----------------------------------------------
    watchdog: bool = True
    # absolute step wall-time ceiling; one step slower than this counts as
    # a stall regardless of history
    step_timeout_s: float = 30.0
    # relative stall detector: step slower than stall_threshold x the EMA
    # of healthy steps (StragglerMonitor), armed after warmup_steps. Steps
    # under stall_floor_s never count as relative stalls: a serving pump
    # spins through idle no-op ticks whose microsecond durations would
    # otherwise seed an EMA that makes any real batch dispatch look like
    # an 8x stall
    stall_threshold: float = 8.0
    warmup_steps: int = 5
    stall_floor_s: float = 0.05
    # consecutive-fault budgets before quarantine (an OOM-classified error
    # evicts immediately — retrying into a full allocator wedges the pump)
    error_budget: int = 3
    stall_budget: int = 2
    # re-dispatches one request may consume across evictions before it is
    # terminally failed (its on_done fires exactly once with status
    # "failed" instead of retrying forever)
    retry_budget: int = 2


@dataclass(frozen=True)
class ContinuousBatchingConfig:
    """Continuous-batching knobs for ``ServeEngine`` (DESIGN.md section 10).

    Packed prefill concatenates up to ``batch_slots`` variable-length
    prompts into one ``[1, bucket]`` token buffer (segment-masked attention)
    so mixed-length admissions share a single prefill dispatch; buffer
    lengths bucket to a power-of-two ladder so the AOT program cache stays
    small. ``async_retire`` moves token materialization (device->host),
    EOS checks, and completion callbacks onto a retirement thread fed by a
    device-array queue, keeping the decode tick free of host syncs."""

    packed_prefill: bool = True
    # token budget of one packed prefill dispatch; 0 = the engine max_len
    # (must not exceed max_len — ServeEngine validates at construction)
    max_prefill: int = 0
    # smallest pack-buffer bucket (ladder doubles from here to max_prefill)
    min_bucket: int = 32
    # retirement thread on/off (off = inline retirement, same ordering)
    async_retire: bool = True
    # pre-compile every (bucket x prompt-count, decode) program at warmup()
    aot_warmup: bool = True


@dataclass(frozen=True)
class ModelConfig:
    name: str
    # dense | moe | ssm | hybrid | encdec | vlm | vit | vit_moe
    family: str
    num_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    act: str = "silu"  # silu | gelu | geglu_gelu | relu2
    glu: bool = True  # gated linear unit MLP (silu->swiglu, gelu->geglu)
    attn: Optional[AttnConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid (zamba2): one *shared* attention block applied every N ssm layers
    shared_attn_every: int = 0
    # enc-dec (seamless)
    encoder_layers: int = 0
    decoder_layers: int = 0
    # modality frontend stub: 'patch' (vlm) | 'frame' (audio) | None
    frontend: Optional[str] = None
    frontend_tokens: int = 0  # tokens contributed by the frontend embeds
    frontend_dim: int = 0  # raw embedding dim provided by the stub
    tie_embeddings: bool = False
    embed_scale: bool = False  # gemma: scale embeds by sqrt(d_model)
    post_block_norm: bool = False  # gemma2 sandwich norms
    final_logit_softcap: float = 0.0
    # vit classifier head (paper archs)
    num_classes: int = 0
    image_tokens: int = 0  # e.g. 197 for 224/16 ViT (196 patches + cls)
    quant: QuantConfig = field(default_factory=QuantConfig)
    # per-device kernel tile autotuning (serving warmup; kernels/autotune.py)
    autotune: AutotuneConfig = field(default_factory=AutotuneConfig)
    # continuous-batching serving path (serving/engine.py; DESIGN.md §10)
    serve: ContinuousBatchingConfig = field(
        default_factory=ContinuousBatchingConfig)
    # serving tracing/profiling (serving/trace.py; DESIGN.md §11)
    trace: TraceConfig = field(default_factory=TraceConfig)
    # live performance introspection (serving/introspect.py; DESIGN.md §12)
    introspect: IntrospectConfig = field(default_factory=IntrospectConfig)
    # serving fault model: chaos injection + watchdog (serving/faults.py;
    # DESIGN.md §14)
    faults: FaultConfig = field(default_factory=FaultConfig)
    dtype: str = "bfloat16"
    # training knobs
    remat: bool = True
    optimizer: str = "adamw"  # adamw | adafactor (big archs)
    microbatch_size: int = 0  # 0 = no gradient accumulation

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---- derived sizes ----------------------------------------------------
    def param_count(self) -> int:
        """Approximate parameter count (embedding + blocks + head)."""
        d = self.d_model
        n = 0
        n += self.vocab_size * d  # embedding
        if not self.tie_embeddings and self.family not in ("vit", "vit_moe"):
            n += self.vocab_size * d  # lm head
        layers = self.num_layers
        if self.family == "encdec":
            layers = self.encoder_layers + self.decoder_layers
        per_layer = 0
        # hybrid: attention/MLP live only in the single shared block
        shared_only = bool(self.shared_attn_every)
        if self.attn is not None and not shared_only:
            a = self.attn
            per_layer += d * (a.q_dim + 2 * a.kv_dim)  # qkv
            per_layer += a.q_dim * d  # out proj
        if self.ssm is not None:
            s = self.ssm
            di = s.d_inner(d)
            per_layer += d * 2 * di  # in_proj (x, z)
            per_layer += di * s.conv_width  # conv
            if s.version == 1:
                dtr = s.dt_rank or -(-d // 16)
                per_layer += di * (dtr + 2 * s.state_dim)  # x_proj
                per_layer += dtr * di  # dt_proj
                per_layer += di * s.state_dim  # A
            else:
                nh = s.num_ssm_heads(d)
                per_layer += d * (2 * s.state_dim + nh)  # B,C,dt proj
                per_layer += nh  # A
            per_layer += di * d  # out_proj
        mlp_mult = 3 if self.glu else 2
        if self.moe is not None:
            moe_layers = layers // self.moe.moe_every
            dense_layers = layers - moe_layers
            per_layer_moe = (
                self.moe.num_experts * mlp_mult * d * self.moe.d_ff
                + d * self.moe.num_experts
            )
            n += moe_layers * per_layer_moe
            if self.d_ff and not shared_only:
                n += dense_layers * mlp_mult * d * self.d_ff
            n += layers * per_layer
        else:
            if self.d_ff and not shared_only:
                per_layer += mlp_mult * d * self.d_ff
            n += layers * per_layer
        if self.family == "encdec":
            # decoder cross-attention
            a = self.attn
            n += self.decoder_layers * (d * (a.q_dim + 2 * a.kv_dim) + a.q_dim * d)
        if self.shared_attn_every and self.attn is not None:
            a = self.attn
            n += d * (a.q_dim + 2 * a.kv_dim) + a.q_dim * d  # one shared block
            n += mlp_mult * d * self.d_ff
        if self.num_classes:
            n += d * self.num_classes
        return n

    def active_param_count(self) -> int:
        """Params active per token (MoE: top_k of num_experts)."""
        if self.moe is None:
            return self.param_count()
        full = self.param_count()
        layers = self.num_layers
        moe_layers = layers // self.moe.moe_every
        mlp_mult = 3 if self.glu else 2
        expert_params = moe_layers * self.moe.num_experts * mlp_mult * self.d_model * self.moe.d_ff
        active_expert = moe_layers * self.moe.top_k * mlp_mult * self.d_model * self.moe.d_ff
        return full - expert_params + active_expert


@dataclass(frozen=True)
class ShapeConfig:
    """One benchmark/dry-run input shape cell."""

    name: str
    kind: str  # train | prefill | decode
    seq_len: int
    global_batch: int

    def replace(self, **kw) -> "ShapeConfig":
        return dataclasses.replace(self, **kw)


TRAIN_4K = ShapeConfig("train_4k", "train", 4_096, 256)
PREFILL_32K = ShapeConfig("prefill_32k", "prefill", 32_768, 32)
DECODE_32K = ShapeConfig("decode_32k", "decode", 32_768, 128)
LONG_500K = ShapeConfig("long_500k", "decode", 524_288, 1)

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}

# Families for which full attention makes long_500k intractable (skip per spec).
FULL_ATTENTION_FAMILIES = ("dense", "moe", "encdec", "vlm", "vit", "vit_moe")


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether a (arch x shape) cell is runnable; returns (ok, reason)."""
    if shape.name == "long_500k" and cfg.family in FULL_ATTENTION_FAMILIES:
        # gemma2 alternates local/global: global layers are still full attention.
        return False, "full-attention arch: 500k decode KV is not sub-quadratic-safe"
    if cfg.family in ("vit", "vit_moe") and shape.kind != "train":
        return False, "encoder-only classifier: no decode/prefill step"
    return True, ""
