"""Inference-v2 engine configuration.

Reference: ``inference/v2/config_v2.py`` (``RaggedInferenceEngineConfig``
with nested state-manager / KV-cache / tensor-parallel pydantic models).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp


@dataclasses.dataclass
class StateManagerConfig:
    max_tracked_sequences: int = 2048
    max_ragged_sequence_count: int = 512
    max_ragged_batch_size: int = 768       # token budget per forward
    memory_fraction: float = 0.8           # of free HBM, for the KV cache


@dataclasses.dataclass
class KVCacheUserConfig:
    page_size: int = 64
    num_pages: Optional[int] = None        # None -> sized from memory_fraction
    dtype: Any = jnp.bfloat16
    #: pages of the window group's pool, for a model with two page groups
    #: (ragged/manager.py); None -> what every tracked sequence can hold
    #: live at once, ``max_tracked_sequences x (window / page_size + 2)``
    window_num_pages: Optional[int] = None


@dataclasses.dataclass
class QuantizationConfig:
    """Weight-only quantized inference (reference v2 core_ops FP6/FP8
    quantized GEMM + ``quantization_mode`` engine config)."""
    enabled: bool = False
    fmt: str = "fp8_e4m3"   # fp8_e4m3|fp8_e5m2|fp6_e3m2|fp4_e2m1|int8


@dataclasses.dataclass
class ServingOptimizationConfig:
    """Fused serving-step knobs (ISSUE 2): one scheduler step = one
    compiled device program + one token-sized host transfer.  Each flag
    is an independent escape hatch back to the seed behavior (per-Q-
    bucket programs, host-side sampling over [n, V] logits, synchronous
    stepping); ``{"enabled": False}`` in a config dict flips all three."""
    #: one compiled program per mixed prefill+decode step (off: the
    #: per-Q-bucket split with host-side logits re-assembly)
    fused_step: bool = True
    #: sample inside the compiled step; only int32 tokens cross d2h
    on_device_sampling: bool = True
    #: double-buffered scheduler: step k+1 dispatches (device-chained
    #: token gather) while step k's tokens are in flight — token values
    #: reach the host one step late
    async_scheduling: bool = True
    #: automatic prefix cache over the paged KV pool (ISSUE 3): shared
    #: full prompt pages are ref-count-attached across sequences and
    #: completed sequences' pages are retained (LRU-evicted under pool
    #: pressure), so warm-prefix admission only prefills the uncached
    #: suffix.  Off: every request re-prefills its whole prompt (seed)
    prefix_caching: bool = True
    #: graceful degradation (ISSUE 7), 0/False = seed behavior:
    #: bounded admission queue — submits past this many pending
    #: requests are shed with a structured error (0 = unbounded)
    max_queue_depth: int = 0
    #: shed new submits while observed queue-wait p90 exceeds this
    #: (telemetry-fed SLO histogram; 0 = off)
    shed_queue_wait_ms: float = 0.0
    #: default per-request TTL seconds; expired requests terminate with
    #: a structured error instead of hanging (0 = no deadline)
    default_ttl_s: float = 0.0
    #: on a would-be scheduler deadlock, shed the most demanding
    #: request with a structured "oom" error instead of raising
    shed_unservable: bool = False
    #: preemption tolerance (ISSUE 8): grace budget in seconds for the
    #: SIGTERM drain->snapshot path; past it live requests terminate
    #: with a structured "migrated" error instead of vanishing
    snapshot_grace_s: float = 5.0
    #: bundle path the SIGTERM handler writes (with
    #: DS_DRAIN_ON_SIGTERM=1); empty = snapshot() explicit calls only
    snapshot_path: str = ""
    # -- speculative decoding (ISSUE 10), default OFF: enabling changes
    # nothing but throughput and the ds_fastgen_spec_* metrics ---------
    #: model-free speculative decoding: draft up to ``spec_max_draft``
    #: tokens per decode row from an n-gram/prompt-lookup suffix index
    #: over the request's own prompt + committed tokens (no draft
    #: model, no extra device memory) and verify them all in ONE fused
    #: Q>1 program; accepted drafts commit as a block at drain.
    #: Requires fused_step + on_device_sampling (the split path never
    #: speculates)
    speculative: bool = False
    #: drafted tokens per decode row per program (the verify segment is
    #: one ragged Q = 1 + spec_max_draft bucket)
    spec_max_draft: int = 3
    #: shortest trailing n-gram the prompt-lookup drafter matches on
    #: (longer n-grams are tried first; raise to cut false drafts on
    #: low-repetition traffic)
    spec_ngram_min: int = 2
    # -- model-drafted speculation (ISSUE 17) ---------------------------
    #: which drafter proposes tokens: "ngram" (the model-free prompt-
    #: lookup index, seed behavior), "model" (a same-family draft trunk
    #: runs a device-resident draft loop inside the fused step — wins
    #: on LOW-repetition traffic where n-gram is break-even), or
    #: "auto" (per-request adaptive selection: an EWMA accept rate
    #: switches each request ngram -> model -> off).  "model"/"auto"
    #: build the draft trunk + a second paged KV pool at engine build
    spec_drafter: str = "ngram"
    #: draft trunk depth: the first N target layers (embed/final-norm/
    #: lm-head always shared, so the draft adds NO new weights).  0 =
    #: self-draft — the draft shares EVERY target layer; drafts are
    #: near-exact, and the win is k+1 committed tokens per program
    #: dispatch instead of one (the same dispatch-amortization as the
    #: n-gram drafter, without needing repetitive output)
    spec_draft_layers: int = 0
    # -- disaggregated prefill/decode serving (ISSUE 13) ----------------
    #: scheduler role: "both" (the fused single engine), "prefill"
    #: (prompt chunks + FIRST token only; finished requests park as
    #: handoff-ready for a DisaggPool to stream to a decode pool), or
    #: "decode" (admits handoff imports only — a plain submit is
    #: rejected with a structured RequestError(code="misrouted"))
    role: str = "both"
    #: schedule-invariant sampling: each sampled token's RNG key is
    #: derived from (base key, request uid, generation position) on
    #: device instead of one per-step key, so sampled output is
    #: independent of batch composition/step count — required for a
    #: disagg handoff (or migration) to continue SAMPLED requests
    #: tokenwise identical to the fused engine.  Engine-build-time
    #: (changes compiled program signatures); default off
    keyed_sampling: bool = False
    # -- recompile-proof cold starts (ISSUE 14) -------------------------
    #: where the persistent XLA compile cache goes when
    #: JAX_COMPILATION_CACHE_DIR is not set (the env var wins and JAX
    #: places the cache itself; "" = the fixed <repo>/.jax_cache/ — see
    #: utils/compile_cache.py).  A second process compiling the same
    #: step keys LOADS executables from disk — restore()/scale_up cold
    #: starts become loads, not compiles.  Unwritable/corrupt dirs
    #: degrade to plain compiles with a warning
    compile_cache_dir: str = ""
    #: bucket lattice: "" = the power-of-two default;
    #: "auto:<path>" consumes a mined lattice artifact
    #: (tools/analyze_trace.py --emit-lattice) or a raw workload-trace
    #: ledger — non-power bucket tops fitted to observed traffic, a
    #: smaller precompiled program set, tokenwise identical output.
    #: A config-digest mismatch refuses at engine build (LatticeError)
    lattice: str = ""
    # -- tiered KV at fleet scale (ISSUE 16) ----------------------------
    #: KV page storage format: "none" (fp pages at the cache dtype) or
    #: "int8" (block-scaled codes + fp32 scale per head_dim block) —
    #: ~2x resident sequences per chip at a bounded greedy-agreement
    #: cost (see DESIGN.md "Tiered KV").  Engine-build-time: it shapes
    #: the cache arrays and every compiled step program
    kv_quantization: str = "none"
    #: host DRAM prefix tier: parked pages that eviction would free are
    #: demoted into a bounded host ring (this many pages; 0 = tier off)
    #: keyed by the same chained prefix digests, and promoted back on a
    #: prefix match — a flushed prefix is a warm hit, not a recompute
    kv_tier_host_pages: int = 0
    #: disk prefix tier below the host ring (pages; 0 = off): host-ring
    #: overflow spills to ``kv_tier_dir`` via the in-tree AIO path
    kv_tier_disk_pages: int = 0
    #: directory for the disk tier's page files ("" = a per-process
    #: temp dir, deleted with the store)
    kv_tier_dir: str = ""
    # -- sharded fused serving (ISSUE 18) -------------------------------
    #: tensor-parallel degree for the ONE compiled serving program:
    #: weights shard along a ``tp`` mesh axis, KV pages partition along
    #: KV heads (page ids/tables stay replicated — the allocator,
    #: prefix cache, tiering, and chained digests are shard-invariant),
    #: and sampling stays on-device behind an in-program logits
    #: all-gather.  1 = single-device (the pre-ISSUE-18 engine).
    #: Engine-build-time: part of the compile-cache digest, so a mesh
    #: change is a cache MISS, never a wrong executable
    tp_degree: int = 1
    #: encoding for the in-program cross-shard logits collective:
    #: "none" (fp all-gather, tokenwise identical to tp=1) or "int8"
    #: (block-scaled int8 codes + one fp32 scale per row per shard —
    #: ~4x fewer interconnect bytes; argmax is preserved whenever the
    #: top-1 margin exceeds half the largest per-shard quantization
    #: step, see DESIGN.md "Sharded serving")
    tp_collective_quantization: str = "none"


@dataclasses.dataclass
class TelemetryConfig:
    """Serving-side view of the process-wide telemetry spine
    (``deepspeed_tpu/telemetry``), mirroring the runtime config's
    ``telemetry`` block.  ``enabled=None`` inherits the process state
    (``DS_TELEMETRY`` / ``telemetry.enable()``); ``metrics_port``
    starts the Prometheus endpoint (0 = off); ``trace_buffer`` resizes
    the span ring (0 = keep current capacity).  ISSUE 5 watchdog /
    flight-recorder knobs, the ISSUE 9 workload-trace knobs
    (``workload_trace_path`` / ``workload_trace_max_mb``), and the
    ISSUE 11 fleet-observatory knobs (``timeseries_interval_s`` /
    ``timeseries_retention_s`` / ``fleet_targets`` /
    ``slo_objectives``; ``metrics_port=-1`` = ephemeral port) follow
    the same keep-current convention (see the runtime config's
    ``TelemetryConfig`` for semantics)."""
    enabled: Optional[bool] = None
    metrics_port: int = 0
    trace_buffer: int = 0
    watchdog: Optional[bool] = None
    watchdog_threshold: float = 0.0
    watchdog_warmup: int = -1
    postmortem_dir: str = ""
    flight_recorder_events: int = 0
    workload_trace_path: str = ""
    workload_trace_max_mb: int = 0
    timeseries_interval_s: float = 0.0
    timeseries_retention_s: float = 0.0
    fleet_targets: str = ""
    slo_objectives: list = dataclasses.field(default_factory=list)

    def apply(self) -> None:
        from ...telemetry import apply_settings
        apply_settings(self.enabled, self.metrics_port, self.trace_buffer,
                       watchdog=self.watchdog,
                       watchdog_threshold=self.watchdog_threshold,
                       watchdog_warmup=self.watchdog_warmup,
                       postmortem_dir=self.postmortem_dir,
                       flight_recorder_events=self.flight_recorder_events,
                       workload_trace_path=self.workload_trace_path,
                       workload_trace_max_mb=self.workload_trace_max_mb,
                       timeseries_interval_s=self.timeseries_interval_s,
                       timeseries_retention_s=self.timeseries_retention_s,
                       fleet_targets=self.fleet_targets,
                       slo_objectives=self.slo_objectives)


@dataclasses.dataclass
class FaultInjectionConfig:
    """Serving-side view of the deterministic chaos registry
    (``runtime/fault_injection.py``), mirroring the runtime config's
    ``fault_injection`` block.  ``enabled=False`` leaves the process
    registry alone (a default-config engine build must not disarm a
    ``DS_CHAOS`` env arming)."""
    enabled: bool = False
    seed: int = 0
    sites: dict = dataclasses.field(default_factory=dict)

    def apply(self) -> None:
        from ...runtime.fault_injection import apply_fault_injection
        apply_fault_injection(self.enabled, self.seed, self.sites)


@dataclasses.dataclass
class RaggedInferenceEngineConfig:
    state_manager: StateManagerConfig = dataclasses.field(
        default_factory=StateManagerConfig)
    kv_cache: KVCacheUserConfig = dataclasses.field(
        default_factory=KVCacheUserConfig)
    quantization: QuantizationConfig = dataclasses.field(
        default_factory=QuantizationConfig)
    serving: ServingOptimizationConfig = dataclasses.field(
        default_factory=ServingOptimizationConfig)
    telemetry: TelemetryConfig = dataclasses.field(
        default_factory=TelemetryConfig)
    fault_injection: FaultInjectionConfig = dataclasses.field(
        default_factory=FaultInjectionConfig)
    tp_size: int = 1

    @classmethod
    def from_dict(cls, d: dict) -> "RaggedInferenceEngineConfig":
        cfg = cls()
        sm = d.get("state_manager", {})
        for k, v in sm.items():
            if hasattr(cfg.state_manager, k):
                setattr(cfg.state_manager, k, v)
        kv = d.get("kv_cache", {})
        for k, v in kv.items():
            if hasattr(cfg.kv_cache, k):
                setattr(cfg.kv_cache, k, v)
        for k, v in d.get("quantization", {}).items():
            if hasattr(cfg.quantization, k):
                setattr(cfg.quantization, k, v)
        srv = d.get("serving_optimization", {})
        if not srv.get("enabled", True):
            # the master escape hatch wins over individual flags
            cfg.serving = ServingOptimizationConfig(
                fused_step=False, on_device_sampling=False,
                async_scheduling=False, prefix_caching=False)
        else:
            for k, v in srv.items():
                if hasattr(cfg.serving, k):
                    setattr(cfg.serving, k, v)
        for k, v in d.get("telemetry", {}).items():
            if hasattr(cfg.telemetry, k):
                setattr(cfg.telemetry, k, v)
        for k, v in d.get("fault_injection", {}).items():
            if hasattr(cfg.fault_injection, k):
                setattr(cfg.fault_injection, k, v)
        cfg.tp_size = d.get("tensor_parallel", {}).get("tp_size", 1)
        return cfg
