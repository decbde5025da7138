"""InferenceEngineV2 — ragged continuous-batching inference engine.

Reference contract: ``inference/v2/engine_v2.py:30`` —
``put(uids, tokens)`` runs ONE ragged forward returning last-token
logits per sequence; ``query``/``can_schedule`` expose KV/token
occupancy to the scheduler; ``flush(uid)`` frees sequence state.

TPU deltas: by default (``serving.fused_step``) a mixed put() of prefill
chunks and decode tokens lowers into ONE compiled program over a unified
ragged layout — the superbucket the ragged Pallas kernel serves in a
single launch — with logits rows already in uid order.  The escape hatch
(``fused_step=False``) restores the seed behavior: one compiled program
per Q-bucket with host-side logits re-assembly.  On top of the logits
contract, ``step_sample``/``step_decode_chained`` run forward + sampling
as one program so only int32 tokens ever cross device->host (the
FastGenScheduler's double-buffered hot path).
"""

from __future__ import annotations

import dataclasses
import enum
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ...ops.paged_attention import slots_held
from ...telemetry import metrics as tm
from ...telemetry import trace_span
from ...telemetry.watchdog import StepMeter, install_collector
from ...utils.comms_logging import serving_counters
from .config import RaggedInferenceEngineConfig
from .lattice import (POWER_LATTICE, BucketLattice, enumerate_lattice_keys,
                      resolve_lattice)
from .model import RaggedInferenceModel
from .ragged import (KVCacheConfig, StateManager, build_batch,
                     pages_for_memory, placeholder)
from .step_key import (LATTICE_KINDS, STEP_KINDS, StepKey,
                       lattice_kind_of)


class SchedulingResult(enum.Enum):
    Success = 0
    EngineSequenceLimitExceeded = 1
    BatchSequenceLimitExceeded = 2
    BatchTokenLimitExceeded = 3
    KVCacheLimitExceeded = 4


class SchedulingError(RuntimeError):
    def __init__(self, result: SchedulingResult):
        super().__init__(f"cannot schedule batch: {result.name}")
        self.result = result


def _validate_kinds(kinds: Sequence[str]) -> None:
    unknown = set(kinds) - set(LATTICE_KINDS)
    if unknown:
        raise ValueError(
            f"unknown lattice kinds {sorted(unknown)} "
            f"(expected a subset of {LATTICE_KINDS})")


def lattice_keys(max_prompt: int, max_new_tokens: int,
                 max_concurrency: int, page_size: int,
                 max_ragged_batch_size: int, has_fresh: bool,
                 sampling: bool, spec_max_draft: int = 0,
                 kinds: Optional[Sequence[str]] = None,
                 draft: bool = False) -> List[Tuple]:
    """Every step key (``docs/DESIGN.md``, "The step program's key") the
    default power-of-two lattice contains for this geometry — the ONE
    enumeration shared by ``InferenceEngineV2.precompile`` (which
    compiles it) and ``tools/analyze_trace.py`` (which reports observed
    traffic's coverage against it), so the two can't drift.

    ``kinds`` (ISSUE 13) restricts the enumeration to a subset of
    :data:`LATTICE_KINDS` so a disaggregated pool compiles only its
    role's programs: a prefill pool takes ``("prefill", "decode")``
    (decode-geometry keys cover budget-shrunk 1-token chunks and the
    first-token sample; the chain/spec families drop), a decode pool
    takes ``("decode", "chain", "spec")`` (every Q>1 prefill bucket
    and its fresh variants drop).  None = the full fused lattice.

    The key-family rules themselves (fresh variants, chain
    cross-products, the spec bucket) live in
    ``lattice.enumerate_lattice_keys`` — shared with mined
    :class:`~..lattice.BucketLattice` artifacts (ISSUE 14), so the
    power-of-two default and an auto lattice can't drift."""
    if kinds is not None:
        _validate_kinds(kinds)
    lat = POWER_LATTICE

    def doubling(lo: int, hi: int) -> List[int]:
        vals = []
        while lo <= hi:
            vals.append(lo)
            lo *= 2
        return vals

    s_vals = doubling(lat.bucket_s(1), lat.bucket_s(max_concurrency))
    q_vals = [1] + doubling(2, lat.bucket_q(max_prompt))
    total = max_prompt + max_new_tokens  # decode growth headroom
    p_vals = doubling(lat.bucket_p(1), lat.bucket_p(-(-total // page_size)))

    # speculative verification buckets (ISSUE 10): decode rows
    # dispatched as ragged Q = 1 + spec_max_draft segments.  One Q
    # bucket covers every draft length (q_lens is dynamic); the
    # same S*Q <= batch-size skip rule applies — a spec superbucket
    # the scheduler can't form under strict shapes drops to the
    # normal decode path, exactly like the mixed-step keys.
    spec_q = lat.bucket_q(1 + spec_max_draft) if spec_max_draft > 0 else 0
    keys = enumerate_lattice_keys(
        s_vals, q_vals, p_vals, page_size=page_size,
        max_ragged_batch_size=max_ragged_batch_size,
        has_fresh=has_fresh, sampling=sampling, spec_q=spec_q,
        draft=draft)
    if kinds is not None:
        want = set(kinds)
        keys = [k for k in keys if lattice_kind_of(k) in want]
    return keys


class InferenceEngineV2:
    def __init__(self, model: RaggedInferenceModel,
                 config: Optional[RaggedInferenceEngineConfig] = None):
        self._config = config or RaggedInferenceEngineConfig()
        self._model = model
        # sharded fused serving (ISSUE 18): the mesh must land FIRST —
        # before weight quantization (quantized leaves carry no
        # logical-axis metadata to shard by) and before anything that
        # traces or sizes against the params/KV layout.  tp=1 with no
        # pre-built mesh keeps the engine byte-identical to pre-18.
        svtp = self._config.serving
        tp = int(getattr(svtp, "tp_degree", 1) or 1)
        tpq = getattr(svtp, "tp_collective_quantization", "none") or "none"
        if tpq not in ("none", "int8"):
            raise ValueError(
                f"serving_optimization.tp_collective_quantization={tpq!r}"
                " is not a supported encoding — choose 'none' (fp "
                "all-gather) or 'int8' (block-scaled codes + scales)")
        if model.kv_config.latent and max(tp, model.tp_degree) > 1:
            raise ValueError(
                "a latent page pool cannot be served under tp_degree > 1 "
                "yet: a latent plane has no heads to divide (data-parallel "
                "attention is the deployment's answer) — use tp_degree=1")
        if model.window_kv_config is not None:
            # a model with two page groups: what is not built for it
            # raises here, before anything is sized
            sv_ = self._config.serving
            if max(tp, model.tp_degree) > 1:
                raise ValueError(
                    "a window page group cannot be served under "
                    "tp_degree > 1 yet (its pool and its kind's head "
                    "count are not sharded) — use tp_degree=1")
            if (getattr(sv_, "kv_quantization", "none") or "none") != "none":
                raise ValueError(
                    "a window page group has no int8 page format yet: "
                    "kv_quantization must be 'none' for this model")
            if getattr(sv_, "speculative", False) and (
                    getattr(sv_, "spec_drafter", "ngram") or "ngram") \
                    in ("model", "auto"):
                raise ValueError(
                    "model-drafted speculation is not built for a model "
                    "of two attention kinds: use spec_drafter='ngram'")
        if model.state_config is not None:
            # a model with a state pool (sequence state that is not
            # pages): what is not built for it raises here, before
            # anything is sized.  The prefix cache is built off
            # (StateManager), as for a window page group
            sv_ = self._config.serving
            if max(tp, model.tp_degree) > 1:
                raise ValueError(
                    "inference/v2/engine.py: a state pool cannot be served "
                    "under tp_degree > 1 yet (the pool and the kernels of "
                    "ops/ssm.py and ops/delta_rule.py are not sharded over "
                    "d_inner or heads) — use tp_degree=1")
            if (getattr(sv_, "kv_quantization", "none") or "none") != "none":
                raise ValueError(
                    "inference/v2/engine.py: a model with a state pool has "
                    "no int8 page format yet (ragged/kv_cache.py: its "
                    "attention layers' pool is carried beside the state "
                    "pool unquantized): kv_quantization must be 'none'")
            if int(getattr(sv_, "kv_tier_host_pages", 0) or 0) \
                    or int(getattr(sv_, "kv_tier_disk_pages", 0) or 0):
                raise ValueError(
                    "inference/v2/engine.py: KV tiers (ragged/kv_tiers.py) "
                    "hold prefix pages by digest, and a model with a state "
                    "pool has no prefix index: kv_tier_host_pages and "
                    "kv_tier_disk_pages must be 0")
            if getattr(sv_, "speculative", False):
                raise ValueError(
                    "inference/v2/engine.py: speculation (inference/v2/"
                    "spec.py, n-gram or model-drafted) is not built for a "
                    "model with a state pool: a rejected draft would need "
                    "the recurrent state rolled back — use "
                    "speculative=False")
        if tp > 1 and model.mesh is None:
            devs = jax.devices()
            if len(devs) < tp:
                raise ValueError(
                    f"serving_optimization.tp_degree={tp} needs {tp} "
                    f"devices but only {len(devs)} are visible — on a "
                    "chipless box simulate a mesh with XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={tp} "
                    "(set BEFORE jax import)")
            model.apply_mesh(jax.sharding.Mesh(
                np.asarray(devs[:tp]).reshape(tp), ("tp",)))
        # the collective encoding shapes every traced program (like
        # keyed_sampling) — set before any precompile
        model.tp_collective_quantization = tpq
        self._tp_degree = model.tp_degree
        if tp > 1 and self._tp_degree != tp:
            raise ValueError(
                f"serving_optimization.tp_degree={tp} but the model's "
                f"mesh shards the tp axis {self._tp_degree}-way — the "
                "pre-built mesh and the serving config disagree")
        tm.FASTGEN_SHARD_COUNT.set(float(self._tp_degree))
        if self._config.quantization.enabled:
            # NOTE: the engine takes ownership of the model — this
            # rewrites model.params in place (quantize_weights is
            # idempotent per format and refuses a format change)
            model.quantize_weights(self._config.quantization.fmt)
        # model-drafted speculation (ISSUE 17): the draft trunk's facts
        # are needed BEFORE KV sizing (the draft pool shares the memory
        # budget) and before the compile-cache digest (the draft shapes
        # the draft_spec/draft_fill programs)
        sv0 = self._config.serving
        drafter = getattr(sv0, "spec_drafter", "ngram") or "ngram"
        if drafter not in ("ngram", "model", "auto"):
            raise ValueError(
                f"serving_optimization.spec_drafter={drafter!r} is not "
                "a supported drafter — choose 'ngram' (prompt-lookup), "
                "'model' (device-resident draft loop), or 'auto' "
                "(per-request adaptive selection)")
        self._draft_enabled = (bool(getattr(sv0, "speculative", False))
                               and drafter in ("model", "auto"))
        if self._draft_enabled and model.cfg.latent_dim:
            raise ValueError(
                "model-drafted speculation is not built for the latent "
                "kind (its layers are two stacks, and a draft module fed "
                "the target's hidden state is another program): use "
                "spec_drafter='ngram'")
        want_layers = int(getattr(sv0, "spec_draft_layers", 0) or 0)
        n_layers = int(model.cfg.num_layers)
        # 0 = self-draft: share EVERY target layer (pure dispatch
        # amortization — the draft loop still needs its own KV pool)
        self._draft_layers = (min(want_layers, n_layers) if want_layers > 0
                              else n_layers) if self._draft_enabled else 0
        kv_user = self._config.kv_cache
        prev_quant = model.kv_config.quantization
        if not model.kv_config_explicit:
            # user config wins over the model's default cache geometry;
            # num_pages=None is sized from free-memory fraction (reference
            # sizes its blocked KV pool the same way)
            # the layout (planes, heads, width) is the model's, which is
            # what its attention kind declares of its cache
            kv_cfg = dataclasses.replace(
                model.kv_config,
                page_size=kv_user.page_size,
                num_pages=kv_user.num_pages or 1, dtype=kv_user.dtype,
                quantization=(
                    getattr(self._config.serving, "kv_quantization",
                            "none") or "none"))
            if kv_user.num_pages is None:
                budget = self._free_device_memory()
                if budget is not None:
                    budget = int(
                        budget * self._config.state_manager.memory_fraction)
                    if self._draft_enabled:
                        # the draft pool is a parallel [L_draft, ...]
                        # array over the SAME pages — shrink the target
                        # budget so target + draft together fit the
                        # fraction
                        budget = int(budget * n_layers
                                     / (n_layers + self._draft_layers))
                    kv_cfg = dataclasses.replace(
                        kv_cfg, num_pages=pages_for_memory(kv_cfg, budget))
                else:
                    kv_cfg = dataclasses.replace(
                        kv_cfg, num_pages=model.kv_config.num_pages)
            model.kv_config = kv_cfg
            if model.window_kv_config is not None:
                # the window group follows the full group's page size and
                # dtype; its pool holds what the tracked sequences can
                # hold live unless the user sizes it
                sm_ = self._config.state_manager
                model.window_kv_config = dataclasses.replace(
                    model.window_kv_config, page_size=kv_cfg.page_size,
                    dtype=kv_cfg.dtype,
                    num_pages=kv_user.window_num_pages or (
                        sm_.max_tracked_sequences
                        * (model.cfg.sliding_window // kv_cfg.page_size
                           + 2)))
        else:
            kv_cfg = model.kv_config
            # an explicit model kv_config still honors the serving
            # knob — quantization is a cache encoding, not geometry
            quant = (getattr(self._config.serving, "kv_quantization",
                             "none") or "none")
            if quant != kv_cfg.quantization:
                kv_cfg = dataclasses.replace(kv_cfg, quantization=quant)
                model.kv_config = kv_cfg
        if model.state_config is not None:
            # one slot a tracked sequence: the slots, not the pages, are
            # what bounds the batch of such a model
            model.state_config = dataclasses.replace(
                model.state_config, num_slots=int(
                    self._config.state_manager.max_tracked_sequences))
        if kv_cfg.quantization != prev_quant:
            # the kv leaf's pytree TYPE changed (ndarray <-> KVPages):
            # programs traced for the old encoding cannot be called
            # with the new one — drop them, like quantize_weights does
            model._step_cache.clear()
            model._program_costs.clear()
        # keyed sampling (ISSUE 13) changes the traced signatures of
        # every sampling-capable step kind, so it is fixed at engine
        # build, before any precompile/lattice work
        model.keyed_sampling = bool(
            getattr(self._config.serving, "keyed_sampling", False))
        # draft trunk construction (ISSUE 17): like keyed_sampling, set
        # on the model BEFORE any precompile — draft_cfg/draft_params
        # shape the traced draft_spec/draft_fill signatures
        if self._draft_enabled:
            self._build_draft(model)
        # mined bucket lattice (ISSUE 14): "auto:<artifact-or-trace>"
        # resolves to non-power bucket tops + a precompile key set,
        # digest-validated against THIS engine's geometry (a mismatch
        # raises LatticeError — never a silent cold lattice).  Fixed at
        # build: it shapes every compiled program the engine serves.
        self._lattice: BucketLattice = resolve_lattice(
            getattr(self._config.serving, "lattice", "") or "",
            page_size=kv_cfg.page_size,
            vocab_size=int(getattr(model.cfg, "vocab_size", 0)),
            max_ragged_batch_size=(
                self._config.state_manager.max_ragged_batch_size))
        prior = model.lattice
        if getattr(model, "_lattice_bound", False) and (
                prior.digest != self._lattice.digest):
            # the lattice is a MODEL attribute (the mixed-step token
            # pad is traced against it): two engines over one model
            # with different lattice configs would desync the earlier
            # engine's bucketing from the model's pad — loud note,
            # last-engine-wins (the compile-cache retarget convention).
            # The sentinel distinguishes a REbind from the model's
            # first engine (power->mined rebinds must warn too)
            from ...utils.logging import logger
            logger.warning(
                "engine build rebinds model.lattice (%s -> %s) — the "
                "mixed-step pad follows the NEWEST engine's lattice; "
                "engines sharing one model must share one lattice "
                "config",
                prior.digest or "<power>",
                self._lattice.digest or "<power>")
        model.lattice = self._lattice
        model._lattice_bound = True
        # persistent compile cache: a second process compiling the
        # same step keys loads executables from disk — restore()/
        # scale_up cold starts become loads, not compiles.  Placement
        # is utils/compile_cache.py's one rule (shared with training)
        from ...utils.compile_cache import ensure_compile_cache
        self._compile_cache_dir = ensure_compile_cache(
            getattr(self._config.serving, "compile_cache_dir", "") or "")
        sv = self._config.serving
        self._state = StateManager(
            kv_cfg,
            max_tracked_sequences=self._config.state_manager.max_tracked_sequences,
            kv_sharding=model.kv_sharding(),
            prefix_caching=self._config.serving.prefix_caching,
            tier_host_pages=int(getattr(sv, "kv_tier_host_pages", 0) or 0),
            tier_disk_pages=int(getattr(sv, "kv_tier_disk_pages", 0) or 0),
            tier_dir=getattr(sv, "kv_tier_dir", None),
            window_kv_config=model.window_kv_config,
            window=(model.cfg.sliding_window
                    if model.window_kv_config is not None else 0),
            state_config=model.state_config)
        # draft KV pool (ISSUE 17): a parallel plain-dtype page array
        # addressed by the TARGET's page ids/page tables — allocation,
        # commit and rollback all ride the existing allocator (the
        # write-before-read overwrite rule needs no draft-side
        # bookkeeping).  Always unquantized: it is its own pool with
        # its own encoding, and the draft trunk reads it every
        # iteration of the in-program draft loop.  Draft pages are
        # never prefix-indexed (index_prefix only sees the target
        # pool), so a shared prefix page can hold stale draft KV —
        # that degrades accept rate until catch-up, never correctness.
        #: the segment table's layout (ragged/cache_kinds.py): a model of
        #: one page group's is the [S, P] it was
        self._table = model.table
        self._draft_kv = None
        self._draft_seen: Dict[int, int] = {}
        self._attended = (0, 0)
        #: the host's clocks around a serving step (ISSUE 52): the
        #: scheduler brackets the step, ``_build_batch`` and ``_dispatch``
        #: add their phases; the process's collector hook goes in with it
        self.step_meter = StepMeter()
        install_collector()
        #: whether the decode rows' contexts are summed for the step span
        #: (``take_attended``; the scheduler asks this too): a model whose
        #: full layers are some of its layers only and whose roofline is
        #: counted from them (two page groups; delta-rule layers beside
        #: full ones)
        self.counts_attended = self._state.window_cache is not None or (
            model.state_config is not None
            and model.state_config.kind == "delta")
        #: the table of the newest decode segment's true rows, for the
        #: step span's page-slot counts (``take_slots_held``, which does
        #: the counting: only a live span asks)
        self._decode_table: Optional[np.ndarray] = None
        #: (previous token vector's length, slots) -> the compiled gather
        #: of a decode segment's token ids (``_form_gathers``)
        self._gathers: Dict[Tuple[int, int], object] = {}
        if self._draft_enabled:
            import jax.numpy as jnp
            dkv = jnp.zeros(kv_cfg.cache_shape(self._draft_layers),
                            kv_cfg.dtype)
            sharding = model.kv_sharding()
            if sharding is not None:
                dkv = jax.device_put(dkv, sharding)
            self._draft_kv = dkv
        self._config.telemetry.apply()
        self._config.fault_injection.apply()
        self._bind_kv_gauges()
        self._pages_dist_cache = None
        self._bind_memory_accountants()
        # flight recorder (ISSUE 5): capture the serving config + a
        # lifecycle event at engine build
        from ...telemetry.flight_recorder import get_flight_recorder
        recorder = get_flight_recorder()
        recorder.set_config("inference_v2", self._config)
        recorder.record("engine.build", engine="fastgen",
                        kv_pages=kv_cfg.num_pages,
                        page_size=kv_cfg.page_size)
        self._bind_digest_source()

    def _build_draft(self, model: RaggedInferenceModel) -> None:
        """Attach the draft trunk to the model: same family at
        ``self._draft_layers`` layers, sharing the target's arrays —
        the whole tree for self-draft, the leading layer slice (scan-
        stacked) or per-layer references otherwise.  Embed, final norm
        and lm head are ALWAYS the target's own."""
        cfg = model.cfg
        L, L_d = int(cfg.num_layers), self._draft_layers
        model.draft_cfg = dataclasses.replace(cfg, num_layers=L_d)
        if L_d == L:
            model.draft_params = model.params
            return
        layers = model.params["layers"]
        if isinstance(layers, dict) and "attn" in layers:   # scan-stacked
            dlayers = jax.tree.map(lambda a: a[:L_d], layers)
        else:                                               # per-layer
            dlayers = {f"layer_{i}": layers[f"layer_{i}"]
                       for i in range(L_d)}
        model.draft_params = dict(model.params, layers=dlayers)

    @property
    def draft_enabled(self) -> bool:
        """Model-drafted speculation is built into this engine
        (``speculative`` on and ``spec_drafter`` is model/auto)."""
        return self._draft_enabled

    @property
    def draft_digest(self) -> str:
        """Identity of the draft trunk ("" = draft off): snapshot
        bundles record it and ``restore()`` refuses a mismatch — a
        draft-KV-free bundle restored under a DIFFERENT draft config
        would silently change which programs serve the workload."""
        if not self._draft_enabled:
            return ""
        import hashlib
        facts = f"{self._draft_layers}:{self._model.draft_cfg!r}"
        return hashlib.blake2b(facts.encode("utf-8"),
                               digest_size=8).hexdigest()

    def draft_lag(self, uid: int) -> int:
        """Committed tokens the draft pool has NOT covered for ``uid``
        (prompt prefill, non-spec commits, prefix hits and restores all
        advance the target without touching the draft pool).  The
        scheduler dispatches a draft_fill catch-up while this is > 0."""
        sd = self._state.get_sequence(uid)
        if sd is None:
            return 0
        return max(sd.seen_tokens - self._draft_seen.get(uid, 0), 0)

    def mark_draft_seen(self, uids: Sequence[int]) -> None:
        """Record that the draft pool now covers each uid's committed
        history — called after :meth:`commit_spec` of a draft_spec
        dispatch (the in-program draft loop wrote KV for every
        committed position, including the full-accept case)."""
        for uid in uids:
            sd = self._state.get_sequence(uid)
            if sd is not None:
                self._draft_seen[uid] = sd.seen_tokens

    def _bind_digest_source(self) -> None:
        """Publish this engine's prefix-cache affinity hints on the
        process metrics endpoint (``/snapshot?digests=1``, ISSUE 12) so
        a pool router can scrape them like any other replica fact.
        Weakref-bound, newest engine wins — the ds_kv_* gauge
        convention."""
        import weakref
        from ...telemetry import server as tserver
        ref = weakref.ref(self)

        def _digests(top_k: int, r=ref) -> dict:
            eng = r()
            if eng is None:
                return {"page_size": 0, "digests": []}
            return {"page_size": eng.model.kv_config.page_size,
                    "digests": eng.export_digests(top_k)}

        tserver.set_digest_source(_digests)

    def _bind_kv_gauges(self) -> None:
        """Bind the ``ds_kv_*`` page-state gauges to this engine's live
        allocator (callback gauges: the hot path never writes them; with
        multiple engines in one process the newest owns the gauges —
        call this again to point them back at an older engine).  Bound
        through a weakref so the process-global registry never keeps a
        discarded engine's pool alive; a dead ref reads as 0."""
        import weakref
        from ...telemetry import metrics as tm
        ref = weakref.ref(self._state.kv_cache.allocator)

        def read(attr):
            def _read(r=ref, a=attr):
                alloc = r()
                return getattr(alloc, a) if alloc is not None else 0
            return _read

        tm.KV_FREE_PAGES.bind(read("free_pages"))
        tm.KV_LIVE_PAGES.bind(read("live_pages"))
        tm.KV_PARKED_PAGES.bind(read("parked_pages"))
        tm.KV_TOTAL_PAGES.bind(read("total_pages"))
        # tier occupancy gauges (ISSUE 16): same weakref discipline,
        # pointing at the manager's tier store (absent => 0)
        tref = weakref.ref(self._state)

        def tier_read(attr):
            def _read(r=tref, a=attr):
                st = r()
                tiers = getattr(st, "tiers", None) if st is not None \
                    else None
                return getattr(tiers, a) if tiers is not None else 0
            return _read

        tm.KV_TIER_HOST_PAGES.bind(tier_read("host_pages"))
        tm.KV_TIER_DISK_PAGES.bind(tier_read("disk_pages"))

    @staticmethod
    def _params_resident_bytes(params) -> int:
        """This process's resident weight bytes: the sum of addressable
        shard footprints (the per-shard slice under tensor parallelism;
        a replicated or unsharded leaf reports its full nbytes)."""
        total = 0
        for leaf in jax.tree.leaves(params):
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                total += sum(int(s.data.nbytes) for s in shards)
            else:
                total += int(getattr(leaf, "nbytes", 0))
        return total

    def _bind_memory_accountants(self) -> None:
        """Register this engine's subsystems with the memory ledger
        (ISSUE 20) — the same weakref/newest-owner discipline as the
        ``ds_kv_*`` gauges.  Weights and pool footprints are computed
        once here (both are fixed post-build); tier/offload accountants
        read the live manager."""
        from ...telemetry.memory import get_memory_ledger
        led = get_memory_ledger()
        wbytes = self._params_resident_bytes(self._model.params)
        led.register_object("weights", self, lambda e, b=wbytes: b)
        kv_bytes = self._model.kv_config.total_bytes() + (
            self._model.window_kv_config.total_bytes()
            if self._model.window_kv_config is not None else 0)
        led.register_object("kv_pages", self._state,
                            lambda st, b=kv_bytes: b)
        state_bytes = (self._model.state_config.total_bytes()
                       if self._model.state_config is not None else 0)
        led.register_object("state_pool", self._state,
                            lambda st, b=state_bytes: b)
        draft_bytes = (int(self._draft_kv.nbytes)
                       if self._draft_kv is not None else 0)
        led.register_object("draft_kv", self,
                            lambda e, b=draft_bytes: b)
        led.register_object(
            "tier_host", self._state,
            lambda st: getattr(getattr(st, "tiers", None),
                               "host_bytes", 0) or 0)
        led.register_object(
            "tier_disk", self._state,
            lambda st: getattr(getattr(st, "tiers", None),
                               "disk_bytes", 0) or 0)
        led.register_object("offload", self._state,
                            lambda st: st.offloaded_blob_bytes)
        # headroom gauge (ISSUE 20): admissible sequences at the
        # observed per-seq page distribution; sampled into the
        # time-series ring so a `capacity` SLO objective can burn on it
        import weakref
        ref = weakref.ref(self)

        def _headroom_seqs(r=ref):
            eng = r()
            if eng is None:
                return 0
            return eng.headroom()["headroom_seqs"]

        tm.MEM_HEADROOM_SEQS.bind(_headroom_seqs)

    # -- headroom model (ISSUE 20) -------------------------------------------
    def headroom(self) -> Dict:
        """How many MORE sequences fit right now: free + parked (and
        tier-demotable) pages divided by the observed p90
        pages-per-sequence, additionally capped by free tracked-
        sequence slots.  The per-seq distribution is mined from the
        workload ledger when capture is on, from live sequences
        otherwise, with a documented 512-token assumption as the cold
        default."""
        alloc = self._state.kv_cache.allocator
        free = int(alloc.free_pages)
        parked = int(alloc.parked_pages)
        tiers = getattr(self._state, "tiers", None)
        demotable = 0
        if tiers is not None:
            spare = max(tiers._host_cap - tiers.host_pages, 0)
            if tiers._disk_cap:
                spare += max(tiers._disk_cap - tiers.disk_pages, 0)
            demotable = min(parked, spare)
        pages = free + parked
        p50, p90, basis = self._pages_per_seq_estimate()
        sm = self._config.state_manager
        slots = max(int(sm.max_tracked_sequences)
                    - self._state.n_tracked_sequences, 0)
        seqs = min(pages // max(p90, 1), slots)
        return {
            "free_pages": free,
            "parked_pages": parked,
            "demotable_pages": demotable,
            "headroom_pages": pages,
            "slot_headroom": slots,
            "pages_per_seq_p50": p50,
            "pages_per_seq_p90": p90,
            "basis": basis,
            "headroom_seqs": max(int(seqs), 0),
        }

    def _pages_per_seq_estimate(self) -> Tuple[int, int, str]:
        """(p50, p90, basis) of pages needed per sequence.  Mined from
        the workload ledger's request tail ("trace"), else the live
        pool's pages-per-tracked-sequence ("live"), else a 512-token
        assumption ("default").  Cached ~10s: the ledger tail is a file
        read and headroom rides every time-series sample."""
        import time as _time
        now = _time.monotonic()
        cached = self._pages_dist_cache
        if cached is not None and now < cached[0]:
            return cached[1], cached[2], cached[3]
        page = int(self._model.kv_config.page_size)
        p50 = p90 = 0
        basis = "default"
        try:
            from ...telemetry.workload_trace import get_workload_trace
            tail = get_workload_trace().tail_text()
        except Exception:
            tail = None
        if tail:
            import json as _json
            lens = []
            for line in tail.splitlines()[-1024:]:
                try:
                    rec = _json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") != "request":
                    continue
                toks = (int(rec.get("prompt_len", 0))
                        + int(rec.get("gen_len", 0)))
                if toks > 0:
                    lens.append(-(-toks // page))
            if lens:
                lens.sort()
                p50 = lens[len(lens) // 2]
                p90 = lens[min(int(len(lens) * 0.9),
                               len(lens) - 1)]
                basis = "trace"
        if not p90:
            alloc = self._state.kv_cache.allocator
            n = self._state.n_tracked_sequences
            if n > 0 and alloc.live_pages > 0:
                p50 = p90 = -(-int(alloc.live_pages) // n)
                basis = "live"
        if not p90:
            p50 = p90 = max(-(-512 // page), 1)
            basis = "default"
        self._pages_dist_cache = (now + 10.0, p50, p90, basis)
        return p50, p90, basis

    def precompile(self, max_prompt: int, max_concurrency: int = 0,
                   max_new_tokens: int = 256,
                   strict: bool = False,
                   sampling: bool = False,
                   spec_max_draft: Optional[int] = None,
                   kinds: Optional[Sequence[str]] = None) -> List[Tuple]:
        """AOT-compile the (S, Q, P) bucket lattice this engine can hit
        (verdict on live serving: a first-use XLA compile is a TTFT
        spike; the reference captures CUDA graphs at engine build).

        S ranges over power-of-two slot counts up to ``max_concurrency``
        (default: the state manager's max_ragged_sequence_count), Q over
        {1} + power-of-two prompt buckets up to ``max_prompt``, P over
        the page buckets needed for ``max_prompt`` + decode headroom.
        Buckets whose S*Q exceeds max_ragged_batch_size are skipped (the
        scheduler can never form them).  With ``strict``, any later
        cache-miss bucket raises instead of compiling on the request
        path.  ``sampling`` additionally lowers each superbucket's fused
        sample variants (greedy + stochastic) and, for decode buckets,
        the chained double-buffer step — the FastGenScheduler's hot path
        when serving_optimization is on.  ``spec_max_draft`` (default:
        the serving config's, 0 when ``speculative`` is off) widens the
        sampling lattice with the speculative Q = 1+draft verification
        buckets so a strict_shapes engine can't recompile on-path when
        speculation is enabled.  ``kinds`` (ISSUE 13) shrinks the
        lattice to a disaggregated role's key classes and GUARDS the
        shrink: a filter that re-enumerates the full lattice raises
        (the whole point of a role-restricted pool is compiling fewer
        programs).  Returns the compiled keys."""
        sm = self._config.state_manager
        if spec_max_draft is None:
            sv = self._config.serving
            spec_max_draft = (int(getattr(sv, "spec_max_draft", 0) or 0)
                              if getattr(sv, "speculative", False) else 0)
        if self._lattice.mined:
            # mined auto lattice (ISSUE 14): the artifact's key set IS
            # the precompile target — filtered to what THIS engine can
            # actually form/serve
            keys = self._auto_lattice_keys(sampling, spec_max_draft,
                                           kinds, strict=strict)
        else:
            kwargs = dict(
                max_prompt=max_prompt, max_new_tokens=max_new_tokens,
                max_concurrency=(max_concurrency
                                 or sm.max_ragged_sequence_count),
                page_size=self._model.kv_config.page_size,
                max_ragged_batch_size=sm.max_ragged_batch_size,
                has_fresh=self._model.has_fresh,
                sampling=sampling, spec_max_draft=spec_max_draft,
                draft=(self._draft_enabled and sampling
                       and spec_max_draft > 0))
            keys = lattice_keys(kinds=kinds, **kwargs)
            if kinds is not None:
                full = len(lattice_keys(**kwargs))
                if len(keys) >= full:
                    raise ValueError(
                        f"precompile(kinds={tuple(kinds)}) enumerated "
                        f"{len(keys)} keys but the full lattice has "
                        f"{full} — the role filter did not shrink the "
                        "compiled set (silently re-enumerating both "
                        "pools' programs defeats disaggregation's "
                        "compile-time win)")
        for key in keys:
            self._precompile_key(key)
        if strict:
            self._model.strict_shapes = True
        return keys

    def _precompile_key(self, key: StepKey) -> None:
        self._model.precompile_step(
            key, self._pool(STEP_KINDS[key.kind].trunk))
        if key.kind == "mixed" or (key.kind == "sample" and key.Q == 1):
            # dispatched ahead of the drain, such a step takes its
            # one-token rows' ids from the step in flight
            self._form_gathers(key.S)

    def _pool(self, trunk: str):
        """The KV operand of a program over ``trunk`` (``STEP_KINDS``):
        the target pool, the draft pool, or the (target, draft) pair."""
        kv = self._state.kv_cache.data
        if self._state.window_cache is not None:
            # two page groups: the pair (full group, window group)
            kv = (kv, self._state.window_cache.data)
        if self._state.state_pool is not None:
            # pages and the state pool's two arrays
            kv = (kv, *self._state.state_pool.data)
        if trunk == "target":
            return kv
        if self._draft_kv is None:
            raise ValueError(
                f"a step program over the {trunk!r} trunk needs the draft "
                "pool but this engine was built without "
                "spec_drafter=model/auto")
        return self._draft_kv if trunk == "draft" else (kv, self._draft_kv)

    def _put_pool(self, trunk: str, pool) -> None:
        """Put back what a program over ``trunk`` returned for the
        pool(s) it was given, which it donated."""
        if trunk == "target" and self._state.state_pool is not None:
            self._state.kv_cache.data, *state = pool
            self._state.state_pool.data = tuple(state)
        elif trunk == "target" and self._state.window_cache is not None:
            self._state.kv_cache.data, self._state.window_cache.data = pool
        elif trunk == "target":
            self._state.kv_cache.data = pool
        elif trunk == "draft":
            self._draft_kv = pool
        else:
            self._state.kv_cache.data, self._draft_kv = pool

    def _auto_lattice_keys(self, sampling: bool, spec_max_draft: int,
                           kinds: Optional[Sequence[str]],
                           strict: bool = False) -> List[Tuple]:
        """The mined lattice's key set, filtered to this engine:
        sampling families only when requested, fresh variants only when
        the model has a fresh path, spec keys only when speculation is
        on, S*Q within this engine's batch budget, and the ISSUE 13
        role filter (with its shrink guard).  ``strict`` drops the
        artifact's mixed-step keys: a strict scheduler forces mixed
        batches onto the split path unconditionally, so compiling them
        would spend precompile wall + cache disk on programs that can
        never dispatch."""
        sm = self._config.state_manager
        has_fresh = self._model.has_fresh
        lat = self._lattice
        keys: List[StepKey] = []
        for key in lat.keys:
            kind = key.kind
            if not sampling and kind != "logits":
                continue
            if strict and kind == "mixed":
                continue
            if kind == "spec":
                if spec_max_draft <= 0:
                    continue
                # the spec bucket this engine will form: Q = the
                # lattice bucket of 1 + spec_max_draft, not whatever
                # draft depth the trace ran with
                if key.Q != lat.bucket_q(1 + spec_max_draft):
                    continue
            if kind in ("draft_spec", "draft_fill"):
                # artifact mined on a model-drafted engine serving an
                # engine without the draft trunk (or with speculation
                # off): the draft programs can't trace — drop them
                if not (self._draft_enabled and spec_max_draft > 0):
                    continue
                if (kind == "draft_spec"
                        and key.Q != lat.bucket_q(1 + spec_max_draft)):
                    continue
            if not has_fresh and key.fresh:
                continue    # fresh variants normalize to False anyway
            if key.padded_tokens > sm.max_ragged_batch_size * (
                    2 if kind == "mixed" else 1):
                continue
            keys.append(key)
            if has_fresh and not lat.has_fresh and not key.fresh and (
                    kind == "mixed"
                    or (key.Q > 1 and kind in ("logits", "sample"))):
                # artifact mined on a fresh-less model (ALiBi capture)
                # serving a fresh-capable engine: live all-new prefills
                # WILL form the True variant — twin it so coverage
                # holds instead of recompiling on path (a mixed key
                # twins on its prefill segment)
                keys.append(key.with_fresh(True))
        if sampling and spec_max_draft > 0:
            # a lattice mined from a spec-free trace still serves an
            # engine with speculation on: generate the spec family
            # over its own tops (same inclusion rules the shared
            # enumeration applies); a draft-capable engine additionally
            # gets the draft_spec twins and the draft_fill catch-up
            # family (one per logits-geometry bucket)
            spec_q = lat.bucket_q(1 + spec_max_draft)
            page = self._model.kv_config.page_size
            have = set(keys)
            for S in lat.s_tops:
                if S * spec_q > sm.max_ragged_batch_size:
                    continue
                for P in lat.p_tops:
                    if P * page < spec_q:
                        continue
                    for greedy in (True, False):
                        for kk in ("spec",) + (
                                ("draft_spec",)
                                if self._draft_enabled else ()):
                            key = StepKey.form(
                                kk, [(S, spec_q, P, False)], greedy)
                            if key not in have:
                                keys.append(key)
                                have.add(key)
            if self._draft_enabled:
                for S in lat.s_tops:
                    for Q in lat.q_tops:
                        if S * Q > sm.max_ragged_batch_size:
                            continue
                        for P in lat.p_tops:
                            if P * page < Q:
                                continue
                            key = StepKey.draft_fill((S, Q, P, False))
                            if key not in have:
                                keys.append(key)
                                have.add(key)
        if kinds is not None:
            _validate_kinds(kinds)
            want = set(kinds)
            filtered = [k for k in keys if lattice_kind_of(k) in want]
            if len(filtered) >= len(keys):
                # unlike the power path (whose full lattice ALWAYS has
                # out-of-role keys, so no shrink = a filter bug), a
                # mined artifact can legitimately be role-pure — e.g.
                # a lattice mined from a decode pool's own ledger has
                # nothing but decode/chain keys.  Note it, don't abort
                # engine startup.
                from ...utils.logging import logger
                logger.info(
                    "precompile(kinds=%s): mined lattice is already "
                    "role-pure (%d keys, nothing filtered)",
                    tuple(kinds), len(keys))
            keys = filtered
        return keys

    # -- compiled-key manifests (ISSUE 14: warm-born replicas) ---------------
    def compiled_keys(self, dispatched_only: bool = True
                      ) -> List[StepKey]:
        """The compiled-key manifest a snapshot bundle / replica
        factory carries so a fresh engine can precompile EXACTLY the
        programs traffic actually needs — against a warm persistent
        compile cache each one is a disk load, not an XLA compile.
        Default: only keys traffic DISPATCHED (a precompiled lattice
        can be hundreds of programs; a restored replica's first steps
        need the dozens its workload formed — the rest stay cache
        loads on demand).  ``dispatched_only=False`` returns the whole
        step cache."""
        # snapshot via the GIL-atomic C-level copy: a threaded pool's
        # stepper may be adding keys while a controller exports the
        # manifest — sorting the live set would raise "set changed
        # size during iteration"
        if dispatched_only:
            return sorted(self._model._dispatched_keys.copy(), key=repr)
        return sorted(dict(self._model._step_cache), key=repr)

    def precompile_keys(self, keys: Sequence[Sequence]) -> int:
        """AOT-compile an explicit key manifest (JSON-round-tripped
        lists accepted).  Unknown/uncompilable keys warn and are
        skipped — a manifest from a slightly different build must never
        block a restore.  Returns the number of keys now compiled."""
        done = 0
        for k in keys:
            try:
                self._precompile_key(StepKey.parse(k))
                done += 1
            except Exception as e:  # noqa: BLE001 — per-key isolation
                from ...utils.logging import logger
                logger.warning(
                    "precompile_keys: skipping manifest key %r "
                    "(%s: %s)", k, type(e).__name__, e)
        return done

    def has_program(self, key: Sequence) -> bool:
        """Whether the step program of ``key`` is formed (the strict
        scheduler's gate on a predicted key)."""
        return key in self._model._step_cache

    def has_kind(self, *kinds: str) -> bool:
        """Whether a program of one of ``kinds`` is formed."""
        return any(k.kind in kinds for k in list(self._model._step_cache))

    def _free_device_memory(self) -> Optional[int]:
        """Free HBM the KV pool can use, or None when the backend doesn't
        report memory stats (CPU/CI): the tightest device of the
        serving mesh, times the number of shards a page is split into
        (KV heads over tp) — every device holds 1/shards of each page."""
        mesh = self._model.mesh
        devices = (list(mesh.devices.flat) if mesh is not None
                   else jax.devices()[:1])
        free = []
        for dev in devices:
            stats = dev.memory_stats()
            if not stats or "bytes_limit" not in stats:
                return None
            free.append(stats["bytes_limit"] - stats.get("bytes_in_use", 0))
        sharding = self._model.kv_sharding()
        shards = (len(devices) if sharding is not None
                  and not sharding.is_fully_replicated else 1)
        return min(free) * shards

    # -- introspection -------------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return self._state.free_pages

    @property
    def free_window_blocks(self) -> int:
        """Free pages of the window group (0 for a model of one group)."""
        return self._state.free_window_pages

    def window_blocks_needed(self, uid: int, n_tokens: int) -> int:
        """Pages of the window group that ``n_tokens`` more tokens of
        ``uid`` need (0 for a model of one group): what admission holds
        against :attr:`free_window_blocks` beside :meth:`query`."""
        if self._state.window_cache is None:
            return 0
        return self._state.window_pages_needed(
            self._state.get_sequence(uid) or placeholder(), n_tokens)

    @property
    def free_state_slots(self) -> int:
        """Free slots of the state pool (0 for a model without one)."""
        return self._state.free_state_slots

    def state_slots_needed(self, uid: int) -> int:
        """Slots of the state pool a step of ``uid`` has to reserve (0
        for a model without one, and for a sequence that holds its
        slot): what admission holds against :attr:`free_state_slots`."""
        return self._state.state_slots_needed(
            self._state.get_sequence(uid) or placeholder())

    @property
    def model(self) -> RaggedInferenceModel:
        return self._model

    @property
    def state_manager(self) -> StateManager:
        return self._state

    def seen_tokens(self, uid: int) -> int:
        sd = self._state.get_sequence(uid)
        return sd.seen_tokens if sd is not None else 0

    def cost_summary(self) -> Dict:
        """Per-program flops/bytes table + window MFU / bytes-per-s
        (ISSUE 9): serving throughput's hardware denominator."""
        return self._model.cost_summary()

    # -- scheduling queries --------------------------------------------------
    def query(self, uid: int, max_request_tokens: int,
              max_request_blocks: int) -> Tuple[int, int]:
        sd = self._state.get_sequence(uid)
        if sd is None:
            if (self._state.n_tracked_sequences
                    >= self._config.state_manager.max_tracked_sequences):
                return (0, 0)
            sd = placeholder()
        return self._model.get_kv_requirements(
            sd.seen_tokens, sd.allocated_capacity,
            max_request_tokens, max_request_blocks)

    def get_remaining_block_capacity(self, uid: int) -> int:
        sd = self._state.get_sequence(uid)
        if sd is None:
            return 0
        page = self._model.kv_config.page_size
        return sd.allocated_capacity * page - sd.seen_tokens

    def can_schedule(self, uids: Sequence[int],
                     lengths: Sequence[int]) -> SchedulingResult:
        sm_cfg = self._config.state_manager
        if len(uids) > sm_cfg.max_ragged_sequence_count:
            return SchedulingResult.BatchSequenceLimitExceeded
        cur_seqs = self._state.n_tracked_sequences
        free = self._state.free_pages
        free_w = self._state.free_window_pages
        free_s = self._state.free_state_slots
        batch_tokens = 0
        for uid, length in zip(uids, lengths):
            sd = self._state.get_sequence(uid)
            if sd is None:
                cur_seqs += 1
                sd = placeholder()
            tokens, pages = self._model.get_kv_requirements(
                sd.seen_tokens, sd.allocated_capacity, length, free)
            free_w -= self._state.window_pages_needed(sd, length)
            free_s -= self._state.state_slots_needed(sd)
            if tokens != length or free_w < 0 or free_s < 0:
                return SchedulingResult.KVCacheLimitExceeded
            batch_tokens += length
            free -= pages
        if cur_seqs > sm_cfg.max_tracked_sequences:
            return SchedulingResult.EngineSequenceLimitExceeded
        if batch_tokens > sm_cfg.max_ragged_batch_size:
            return SchedulingResult.BatchTokenLimitExceeded
        return SchedulingResult.Success

    # -- the forward ---------------------------------------------------------
    def _admit_batch(self, batch_uids, batch_tokens, do_checks):
        """Shared put/step preamble: schedulability check + KV
        reservation + in-flight marking.  Returns the descriptors."""
        with trace_span("engine.admit"):
            if do_checks:
                res = self.can_schedule(batch_uids,
                                        [len(t) for t in batch_tokens])
                if res != SchedulingResult.Success:
                    raise SchedulingError(res)
            descs = []
            for uid, toks in zip(batch_uids, batch_tokens):
                sd = self._state.get_or_create_sequence(uid)
                self._state.allocate_for(sd, len(toks))
                sd.pre_forward(len(toks))
                descs.append(sd)
            return descs

    # dslint: hot-path
    def _commit_batch(self, descs) -> None:
        """Shared put/step epilogue: commit host bookkeeping (the token
        VALUES may still be in flight on device — only counts matter
        here), index newly-full prompt pages into the prefix cache, and
        run sliding-window page eviction (in that order: an indexed page
        the window then releases stays cache-retained)."""
        with trace_span("engine.commit"):
            window = getattr(self._model.cfg, "sliding_window", None)
            if self._state.window_cache is not None:
                # two page groups: nothing is indexed, and the window
                # group's tables give their pages back under one span
                for sd in descs:
                    sd.post_forward()
                self._evict_window_group(descs)
                return
            for sd in descs:
                sd.post_forward()
                self._state.index_prefix(sd)
                if window:
                    # Mistral serving: pages wholly outside the window
                    # are unreachable for every future query — return
                    # them to the pool so live KV is O(window), not
                    # O(context)
                    self._state.evict_window(sd, window)

    def _evict_window_group(self, descs) -> None:
        """Release, from each sequence's window table, the pages its
        window has passed (``StateManager.evict_window``)."""
        with trace_span("kv.evict_window"):
            window = self._state.window
            for sd in descs:
                self._state.evict_window(sd, window)

    def _build_batch(self, descs, tokens, h2d_tokens: bool = True,
                     min_q: int = 1, start_pos=None):
        """Pack one segment; h2d bytes accrue here, program dispatches
        are recorded by ``_dispatch`` (a mixed step feeds TWO segments to
        ONE program).  ``h2d_tokens=False`` for chained steps, whose
        token ids never leave the device (the placeholder token_ids
        array is not an input of the chained program); ``min_q`` floors
        the Q bucket (spec steps pad to the one spec bucket);
        ``start_pos`` as ``build_batch`` takes it."""
        t_build = time.perf_counter()
        with trace_span("engine.build_batch"):
            batch = build_batch(
                descs, tokens, self._model.kv_config.page_size,
                self._lattice,
                fresh_supported=self._model.has_fresh, min_q=min_q,
                start_pos=start_pos,
                table=self._table,
                scratch_slot=(self._state.state_pool.scratch
                              if self._state.state_pool is not None else 0))
            if self.counts_attended and batch.max_q == 1:
                # what the decode rows attend in a layer of each kind
                # (the scheduler's live span carries it: take_attended)
                ctx = batch.start_pos[:len(batch.uids)] + 1
                self._attended = (
                    int(ctx.sum()),
                    int(np.minimum(ctx, self._state.window).sum()))
            if batch.max_q == 1:
                self._decode_table = batch.page_table[:len(batch.uids)]
            nbytes = (batch.q_lens.nbytes + batch.start_pos.nbytes
                      + batch.page_table.nbytes)
            if h2d_tokens:
                nbytes += batch.token_ids.nbytes
            serving_counters.record_h2d(nbytes)
        self.step_meter.build += time.perf_counter() - t_build
        return batch

    def take_attended(self) -> Tuple[int, int]:
        """(tokens, tokens inside the window) that the decode rows of the
        steps built since the last call attend, summed over rows: a full
        layer's and a window layer's context (a model of two page
        groups, or of delta-rule layers beside full ones, whose window
        count is 0; (0, 0) otherwise)."""
        out, self._attended = self._attended, (0, 0)
        return out

    def take_slots_held(self) -> Optional[Tuple[int, int, int]]:
        """(held, live, bucket) page slots of the newest decode segment
        built since the last call, summed over its page groups.  ``live``
        are the slots that hold a page and ``bucket`` all the slots of
        the rows' tables (rows x table width): ``live / bucket`` is the
        share of the page bucket that the decode kernel's walk visits
        (``ops/paged_attention.py::paged_walk_attention``), the rest what
        a grid over the bucket stepped over.  ``held`` are the dead slots
        after a live one for which the GRID form's index maps (int8
        pages, ALiBi) name the block their buffer holds, where the null
        page cost a fetch (``fetch_table``, counted by its ``slots_held``
        at the group a decode row's call takes).  All 0 for a step
        without decode rows; None for the latent kind, whose tables no
        K/V kernel reads."""
        table, self._decode_table = self._decode_table, None
        if self._model.cfg.latent_dim:
            return None
        if table is None:
            return 0, 0, 0
        parts = self._table.split(table, 1)
        held = live = bucket = 0
        for kind in ("full", "window"):
            if kind in parts:
                group = self._model.decode_page_group(
                    parts[kind].shape[1], kind)
                held_here, live_here = slots_held(parts[kind], group)
                held, live = held + held_here, live + live_here
                bucket += parts[kind].size
        return held, live, bucket

    def _prev_len(self, prev_tokens) -> int:
        """A chain key's ``prev_len`` for the previous step's token
        vector: its length without the model's ``step_tail``."""
        return int(prev_tokens.shape[0]) - self._model.step_tail

    def _form_gather(self, n_prev: int, S: int) -> None:
        """Compile the ``[S, 1]`` token ids of a decode segment of ``S``
        slots from one int32 code a slot: a row of a previous token
        vector of ``n_prev`` values, or ``-1 - id`` for an id the host
        holds.  The operand of a program that is no chain program, formed
        on the device like the chain program's own, in one h2d."""
        import jax.numpy as jnp
        mesh = self._model.mesh
        rep = (jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
               if mesh is not None else None)
        self._gathers[(n_prev, S)] = jax.jit(
            lambda tokens, code: jnp.where(
                code >= 0, jnp.take(tokens, jnp.maximum(code, 0)),
                -1 - code)[:, None],
            out_shardings=rep).lower(
            jax.ShapeDtypeStruct((n_prev,), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((S,), jnp.int32, sharding=rep)).compile()

    def _form_gathers(self, S: int) -> None:
        """Form the gather of a decode segment of ``S`` slots for EVERY
        length the previous step's token vector can have: a slot bucket
        (one segment) or the bucket of two (a mixed step's pad), never
        under ``S`` (the rows are a subset of that step's), plus the
        model's ``step_tail``.  A closed set, formed where the first of
        its programs forms or first runs, so that a warm window stays
        warm: JAX counts these compiles like any other."""
        tail, bucket = self._model.step_tail, self._lattice.bucket_s
        top = bucket(2 * bucket(
            self._config.state_manager.max_ragged_sequence_count))
        prev = bucket(S)
        while prev <= top:
            if (prev + tail, S) not in self._gathers:
                self._form_gather(prev + tail, S)
            prev = bucket(prev + 1)

    def _gather_tokens(self, prev_tokens, gather: np.ndarray,
                       token_ids: np.ndarray):
        """``token_ids`` ([S, 1]) with each row that sat in ``gather`` of
        ``prev_tokens`` taken from there (-1: the row keeps its id), on
        the device."""
        shape = (int(prev_tokens.shape[0]), len(gather))
        if shape not in self._gathers:
            self._form_gathers(shape[1])
            if shape not in self._gathers:   # a vector no step returned
                self._form_gather(*shape)
        return self._gathers[shape](
            prev_tokens, np.where(gather >= 0, gather, -1 - token_ids[:, 0]))

    # dslint: hot-path
    def _dispatch(self, kind: str, batches, row_params=None, rng=None,
                  row_pos=None, prev=None):
        """Run ONE step program of ``kind`` over ``batches`` (its
        segments, built and in order): the one place a program is
        dispatched.  For a sampling kind, ``row_params`` (one
        SamplingParams a live row) and ``row_pos`` (each row's
        generation position, read under keyed sampling) follow the
        segments' row order and are padded here to the slot buckets;
        padding rows are greedy and sample garbage nobody reads.  A
        keyed engine stepped without positions passes no keyed rows on,
        so that the model's guard raises instead of this padding
        silently pinning every draw to position 0.  ``prev``:
        ``(prev_tokens, gather index padded to the slot bucket)`` where
        the first segment's token ids are rows of the previous step's
        token vector: operands of a chain program, gathered here ahead
        of any other (``_gather_tokens``), whose key they leave as it
        is.  Counts the program and the h2d bytes of what is made here,
        puts the returned pool(s) back, and returns the program's
        output (None where the kind has none)."""
        model = self._model
        row = STEP_KINDS[kind]
        sampling, greedy, h2d = None, False, 0
        if row.samples:
            n = sum(b.num_slots for b in batches)
            temps = np.zeros(n, np.float32)
            top_ks = np.zeros(n, np.int32)
            top_ps = np.ones(n, np.float32)
            uids = pos = None
            if model.keyed_sampling and row_pos is not None:
                uids, pos = np.zeros(n, np.int32), np.zeros(n, np.int32)
            at = off = 0
            for b in batches:
                live = len(b.uids)
                seg, params = slice(off, off + live), row_params[at:at + live]
                temps[seg] = [p.temperature for p in params]
                top_ks[seg] = [p.top_k for p in params]
                top_ps[seg] = [p.top_p for p in params]
                if uids is not None:
                    uids[seg] = np.fromiter(b.uids, np.int64,
                                            live).astype(np.int32)
                    pos[seg] = row_pos[at:at + live]
                at, off = at + live, off + b.num_slots
            greedy = not bool((temps > 0.0).any())
            sampling = (rng, temps, top_ks, top_ps, uids, pos)
            h2d = temps.nbytes + top_ks.nbytes + top_ps.nbytes
        if prev is not None:
            h2d += prev[1].nbytes
        key = StepKey.form(kind, [b.shape_key for b in batches], greedy,
                           self._prev_len(prev[0]) if prev else 0)
        serving_counters.record_program(h2d_bytes=h2d)
        pool = self._pool(row.trunk)
        t_dispatch = time.perf_counter()
        with trace_span("engine.dispatch") as span:
            if prev is not None and not row.chained:
                batches[0].token_ids = self._gather_tokens(
                    *prev, batches[0].token_ids)
                prev = None
            # a live span is told what it prepared (this gather, the
            # executable's lookup, the operands) and what the executable's
            # call took (h2d of the host arrays, the enqueue)
            out = model.run_step(key, pool, batches, sampling, prev,
                                 span=span)
            out, pool = out if row.output else (None, out)
            self._put_pool(row.trunk, pool)
        self.step_meter.dispatch += time.perf_counter() - t_dispatch
        return out

    def put(self, batch_uids: Sequence[int],
            batch_tokens: Sequence[np.ndarray],
            do_checks: bool = True,
            fused: Optional[bool] = None) -> jax.Array:
        """One ragged forward; returns logits [len(batch_uids), V] in
        input order.  ``fused`` None follows the engine's
        serving_optimization config; True forces the single-program
        superbucket, False the seed per-Q-bucket split."""
        if fused is None:
            fused = self._config.serving.fused_step
        descs = self._admit_batch(batch_uids, batch_tokens, do_checks)

        if fused:
            # ONE program over the unified ragged layout: decode rows
            # (Q=1) and prefill chunks share a [S, Qmax] superbucket;
            # slot order == input order, so no host re-assembly
            batch = self._build_batch(
                descs, [np.asarray(t) for t in batch_tokens])
            logits = self._dispatch("logits", [batch])[:len(batch_uids)]
            self._commit_batch(descs)
            serving_counters.record_logits_exposed(int(logits.size) * 4)
            return logits

        # escape hatch: group by Q bucket — decode (len==1) and prefill
        # groups compile separately so decodes never pad to prefill width
        groups: Dict[int, List[int]] = {}
        for i, toks in enumerate(batch_tokens):
            q = 1
            while q < len(toks):
                q *= 2
            groups.setdefault(q, []).append(i)

        logits_rows: List[Optional[jax.Array]] = [None] * len(batch_uids)
        for q_bucket in sorted(groups):
            idxs = groups[q_bucket]
            batch = self._build_batch(
                [descs[i] for i in idxs],
                [np.asarray(batch_tokens[i]) for i in idxs])
            logits = self._dispatch("logits", [batch])
            for row, i in enumerate(idxs):
                logits_rows[i] = logits[row]

        self._commit_batch(descs)
        import jax.numpy as jnp
        out = jnp.stack(logits_rows)
        serving_counters.record_logits_exposed(int(out.size) * 4)
        return out

    def predict_step_key(self, batch_uids: Sequence[int],
                         batch_tokens: Sequence, kind: str = "logits",
                         greedy: bool = False, prev_tokens=None,
                         min_q: int = 1) -> StepKey:
        """The key a single-segment dispatch of ``kind`` over this batch
        will form, BEFORE admission — the strict-shapes scheduler gates
        fused dispatch on this prediction having a program.  Same
        ``lattice.shape`` and same constructor as the live path
        (``build_batch``, ``_dispatch``); what is predicted is only what
        admission will do to the page counts.  ``greedy`` for a sampling
        kind, ``prev_tokens`` (the in-flight token vector) for a chain
        step, ``min_q`` = the spec bucket floor for the spec kinds."""
        model = self._model
        page = model.kv_config.page_size
        pages, all_new = [], True
        for uid, toks in zip(batch_uids, batch_tokens):
            sd = self._state.get_sequence(uid)
            seen = sd.seen_tokens if sd is not None else 0
            cap = sd.allocated_capacity if sd is not None else 0
            pages.append(max(cap, -(-(seen + len(toks)) // page)))
            if seen:
                all_new = False
        S, Q, P = self._lattice.shape(
            len(batch_uids), max(len(t) for t in batch_tokens),
            max(pages), min_q)
        fresh = all_new and Q > 1 and model.has_fresh
        return StepKey.form(
            kind, [(S, Q, P, fresh)], greedy,
            self._prev_len(prev_tokens) if prev_tokens is not None else 0)

    # -- fused forward+sampling steps (serving_optimization hot path) -------
    def step_sample(self, batch_uids: Sequence[int],
                    batch_tokens: Sequence[np.ndarray],
                    row_params: Sequence, rng: jax.Array,
                    do_checks: bool = True,
                    row_pos: Optional[Sequence[int]] = None,
                    prev: Optional[Tuple[jax.Array, Sequence[int]]] = None
                    ) -> Tuple[jax.Array, List[int]]:
        """One compiled program for a mixed SplitFuse step: fused
        forward + on-device sampling.  Returns (device token array
        int32, row map: output row per input); the [*, V] logits never
        leave the device, and the caller syncs the tokens whenever it
        likes (JAX async dispatch makes this the double-buffer overlap
        point).  A step mixing decode rows with prefill chunks runs as
        ONE program over TWO segment geometries ([S_d, 1] + [S_p, Q]) so
        decode rows never pad to the chunk width (a [S, Qmax] superbucket
        would compute Qmax positions per decode row).  ``row_params`` is
        one SamplingParams per row; rows mid-prefill sample garbage the
        caller ignores.

        ``prev = (prev_tokens, row of each input in it)``: a one-token
        input whose row is not -1 continues a sequence whose token id is
        that row of the previous step's sampled tokens (``prev_tokens``,
        possibly still in flight) and is gathered from it ON DEVICE; its
        ``batch_tokens`` entry is a placeholder, and a longer input's
        row is not read.  No host sync anywhere on this path: the
        double-buffered scheduler drains step k's tokens while step k+1
        executes.  A step of such rows alone runs the chain program, any
        other the program it would run anyway."""
        descs = self._admit_batch(batch_uids, batch_tokens, do_checks)
        dec_idx = [i for i, t in enumerate(batch_tokens) if len(t) == 1]
        pre_idx = [i for i, t in enumerate(batch_tokens) if len(t) > 1]

        # the one-token inputs' rows in the previous step's vector; such
        # rows alone, all of that step: the chain program gathers them
        # itself and their ids never leave the device
        rows = [prev[1][i] for i in dec_idx] if prev is not None else []
        chain = bool(rows) and not pre_idx and min(rows) >= 0

        def build(idx):
            return self._build_batch(
                [descs[i] for i in idx],
                [np.asarray(batch_tokens[i]) for i in idx],
                h2d_tokens=not chain)

        def gathered(batch):
            # the one-token rows are the first segment: their rows,
            # padded to the slot bucket (the chain program reads a row)
            if not rows:
                return None
            gather = np.full(batch.num_slots, 0 if chain else -1, np.int32)
            gather[:len(rows)] = rows
            return prev[0], gather

        if not dec_idx or not pre_idx:       # single-geometry step
            batch = build(range(len(batch_uids)))
            tokens = self._dispatch("chain" if chain else "sample",
                                    [batch], row_params, rng, row_pos,
                                    prev=gathered(batch))
            self._commit_batch(descs)
            return tokens, list(range(len(batch_uids)))

        dec, pre = build(dec_idx), build(pre_idx)
        # tokens come back [S_d + S_p] in segment order, and the sampling
        # rows go in that order
        order = dec_idx + pre_idx
        row_of_input = [0] * len(batch_uids)
        for row, i in enumerate(dec_idx):
            row_of_input[i] = row
        for row, i in enumerate(pre_idx):
            row_of_input[i] = dec.num_slots + row
        tokens = self._dispatch(
            "mixed", [dec, pre], [row_params[i] for i in order], rng,
            None if row_pos is None else [row_pos[i] for i in order],
            prev=gathered(dec))
        self._commit_batch(descs)
        return tokens, row_of_input

    def step_decode_chained(self, batch_uids: Sequence[int],
                            prev_tokens: jax.Array,
                            gather_idx: Sequence[int],
                            row_params: Sequence,
                            rng: jax.Array,
                            row_pos: Optional[Sequence[int]] = None
                            ) -> jax.Array:
        """:meth:`step_sample` over decode rows alone, row i continuing
        the sequence that sat in ``gather_idx[i]`` of ``prev_tokens``."""
        return self.step_sample(
            batch_uids, [np.zeros(1, np.int32)] * len(batch_uids),
            row_params, rng, do_checks=False, row_pos=row_pos,
            prev=(prev_tokens, gather_idx))[0]

    def step_spec(self, batch_uids: Sequence[int],
                  batch_tokens: Sequence[np.ndarray],
                  row_params: Sequence, rng: jax.Array,
                  min_q: int = 1,
                  row_pos: Optional[Sequence[int]] = None) -> jax.Array:
        """Speculative verification step (ISSUE 10): each row's tokens
        are ``[last_committed, draft_1..draft_k]`` (k may differ per
        row, k = 0 allowed) and ONE compiled program verifies every
        draft through the ragged Q>1 path (per-row causal limits),
        returning a device [S, 2] int32 array of (accepted_count,
        corrected_token) per row — the only d2h of the step (the host
        knows the drafts it proposed, so counts + one correction
        reconstruct the committed block).  The commit is DEFERRED: the
        caller reads the accepts and then calls :meth:`commit_spec` with
        each row's committed token count (a step may commit 0..Q tokens
        per row, which the one-shot ``post_forward`` bookkeeping can't
        express)."""
        return self._step_verify("spec", batch_uids, batch_tokens,
                                 row_params, rng, min_q, row_pos)

    def _step_verify(self, kind, batch_uids, batch_tokens, row_params,
                     rng, min_q, row_pos):
        descs = self._admit_batch(batch_uids, batch_tokens,
                                  do_checks=False)
        # pad every spec dispatch to the ONE spec Q bucket (min_q =
        # 1 + spec_max_draft from the caller): a short-draft step must
        # not form a smaller off-lattice key
        batch = self._build_batch(
            descs, [np.asarray(t) for t in batch_tokens], min_q=min_q)
        return self._dispatch(kind, [batch], row_params, rng, row_pos)

    def step_draft_spec(self, batch_uids: Sequence[int],
                        batch_tokens: Sequence[np.ndarray],
                        row_params: Sequence, rng: jax.Array,
                        min_q: int = 1,
                        row_pos: Optional[Sequence[int]] = None
                        ) -> jax.Array:
        """Model-drafted speculative step (ISSUE 17): like
        :meth:`step_spec`, but the host only knows each row's LAST
        COMMITTED token — ``batch_tokens[i] = [last, 0...0]`` with
        ``len == 1 + room`` (room = drafts this row may commit), and
        the draft trunk proposes the rest inside the compiled program
        (a ``lax.scan`` of Q=1 draft forwards against the draft pool,
        feeding the target's verify: draft tokens never cross d2h
        mid-step).  Returns a device [S, 2+k] int32 array: accepted
        count, corrected token, then the k drafted tokens (the host
        slices the first ``accepted`` to reconstruct the committed
        block).  The commit is deferred to :meth:`commit_spec` exactly
        like the n-gram path; call :meth:`mark_draft_seen` after it so
        lag tracking knows the draft pool kept up."""
        return self._step_verify("draft_spec", batch_uids, batch_tokens,
                                 row_params, rng, min_q, row_pos)

    def step_draft_fill(self, batch_uids: Sequence[int],
                        batch_tokens: Sequence[np.ndarray]) -> None:
        """Draft-KV catch-up (ISSUE 17): write the DRAFT pool's KV for
        already-committed history the host still knows —
        ``batch_tokens[i]`` is the slice
        ``history[draft_seen : draft_seen + chunk]`` for uid i — with one
        draft-trunk-only forward (prompt prefill, non-spec commits,
        prefix-cache hits and snapshot restores all advance the target
        without touching the draft pool).  The target pool, seen counts
        and the allocator are untouched (this must NOT ride
        ``_admit_batch``: the tokens are committed, not new), pages are
        the sequence's existing table, and NOTHING crosses d2h.
        Correctness never depends on this running (the verify step gates
        every commit); it only restores the draft's context so its
        proposals are worth accepting.  Advances the engine's per-uid
        draft-seen mark."""
        sds, starts = [], []
        for uid in batch_uids:
            sd = self._state.get_sequence(uid)
            if sd is None:
                raise ValueError(
                    f"step_draft_fill: unknown sequence uid {uid}")
            sds.append(sd)
            starts.append(self._draft_seen.get(uid, 0))
        batch = self._build_batch(sds, batch_tokens, start_pos=starts)
        self._dispatch("draft_fill", [batch])
        for uid, start, toks in zip(batch_uids, starts, batch_tokens):
            self._draft_seen[uid] = start + len(toks)

    # dslint: hot-path
    def commit_spec(self, batch_uids: Sequence[int],
                    committed: Sequence[int]) -> None:
        """Variable-advance commit of a :meth:`step_spec` dispatch:
        each row's ``seen_tokens`` moves by its COMMITTED count (1 +
        accepted drafts, possibly truncated at a stop token), never by
        the dispatched width — rejected drafts' KV slots are simply
        re-written by the next step (write-before-read), and generated
        tokens are never prefix-indexed, so a rolled-back draft can't
        poison a shared cache page."""
        with trace_span("engine.commit"):
            window = getattr(self._model.cfg, "sliding_window", None)
            done = []
            for uid, n in zip(batch_uids, committed):
                sd = self._state.get_sequence(uid)
                if sd is None:
                    continue    # failed/evicted mid-step
                sd.commit_tokens(int(n))
                if self._state.window_cache is not None:
                    done.append(sd)
                    continue
                self._state.index_prefix(sd)
                if window:
                    self._state.evict_window(sd, window)
            if done:
                self._evict_window_group(done)

    # -- prefix cache (ISSUE 3) ---------------------------------------------
    def match_prefix(self, uid: int, prompt: Sequence[int]) -> int:
        """Attach the longest prefix-cache hit for a NEW sequence's
        prompt: matched full pages join its block table read-only
        (allocator refcounts track the sharers) and ``seen_tokens``
        advances past them, so the scheduler only prefills the uncached
        suffix.  Registers the prompt for indexing either way.  Returns
        the number of tokens served from the cache (0 on miss, caching
        off, or an already-started sequence)."""
        if self._state.prefix_cache is None:
            return 0
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if (self._state.get_sequence(uid) is None
                and self._state.n_tracked_sequences
                >= self._config.state_manager.max_tracked_sequences):
            return 0  # don't create a sequence the manager can't track
        sd = self._state.get_or_create_sequence(uid)
        hit = self._state.match_prefix(sd, prompt)
        serving_counters.record_prefix_lookup(len(prompt), hit)
        return hit

    def export_digests(self, top_k: int = 64) -> List[str]:
        """Bounded prefix-cache affinity hint (ISSUE 12): the ``top_k``
        most-recently-used cumulative page digests as hex, most recent
        first (empty when caching is off).  This is the ONLY cache
        introspection a pool router needs — it never scrapes the full
        index or any page contents."""
        return self._state.export_digests(top_k)

    def reset_prefix_cache(self) -> None:
        """Drop every cache entry and return parked pages to the pool
        (bench/test cold-start control)."""
        self._state.reset_prefix_cache()

    def tier_hits(self, uid: int) -> Optional[dict]:
        """Warm-prefix provenance for a tracked sequence (ISSUE 16):
        tokens attached at admission per tier
        (device/host/disk/remote), or None before match_prefix ran —
        the workload ledger's per-request tier-hit fields."""
        sd = self._state.get_sequence(uid)
        return None if sd is None else sd.tier_hits

    # -- cross-replica page fetch (ISSUE 16 tentpole c) ---------------------
    def export_prefix(self, digests_hex: List[str],
                      max_pages: int = 64):
        """Export the KV contents for the leading run of a request's
        cumulative digest chain that this engine's prefix cache holds —
        the page-fetch half a pool streams to an affinity-missed
        placement.  Returns ``(meta, arrays)`` or None when cold."""
        return self._state.export_prefix(digests_hex,
                                         max_pages=max_pages)

    def import_prefix(self, meta: dict, arrays: dict) -> dict:
        """Merge a peer's exported prefix pages into this engine's
        cache as parked indexed pages (the fetched request's admission
        then match_prefix-hits them locally).  Raises the retryable
        :class:`~.ragged.KVAllocationError` when the pool lacks room."""
        return self._state.import_prefix(meta, arrays)

    def flush(self, uid: int) -> None:
        self._state.flush_sequence(uid)
        self._draft_seen.pop(uid, None)

    def offload_sequence(self, uid: int) -> None:
        """Preempt a sequence: its KV moves to host and the pages return
        to the pool (reference BlockedKVCache offload hook,
        inference/v2/ragged/kv_cache.py:166).  put() for this uid is
        invalid until restore_sequence."""
        self._state.offload_sequence(uid)

    def restore_sequence(self, uid: int) -> None:
        self._state.restore_sequence(uid)
