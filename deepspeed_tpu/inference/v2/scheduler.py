"""Continuous-batching scheduler (Dynamic SplitFuse).

The reference keeps this in the MII project and engine_v2 only exposes
the ``query/can_schedule/put/flush`` contract (engine_v2.py:158-251);
SURVEY §3.4 calls for the scheduler in-repo.  Policy (Dynamic SplitFuse,
FastGen blog): every step takes running decodes first (one token each),
then prompt *chunks* from admitted requests up to a fixed token budget,
so long prompts are split across steps and fused with decodes, keeping
per-step latency flat.  The budget is the ceiling; how far a step is
filled under it follows the device's ridge (ISSUE 53, ``_plan_step``):
a step that streams its weights for fewer decode rows than the ridge
takes pending requests only while its padded tokens stay at or under the
ridge, and a request it leaves out rides the next step, which admits it
whatever it costs (``Request.passed_over``).

Admission runs on incremental page/token/sequence counters (O(1) per
candidate) rather than re-validating the whole batch through
``can_schedule`` for each addition.

Serving-optimization paths (engine config ``serving``, ISSUE 2): with
``fused_step + on_device_sampling`` a step dispatches ONE compiled
program (forward + sampling) and only int32 tokens cross device->host;
with ``async_scheduling`` on top the step double-buffers.  THE RULE
(ISSUE 33; ``_inflight_rows``, ``_plan_step``): whenever every decode
row of step k+1 has its input token in the step in flight (it sat in a
sampled row of step k: a decode row, or a prompt whose last piece ran in
k), step k+1 is planned and dispatched FIRST and step k drains while the
device runs it, whichever program k+1 runs (``chain``, ``sample``,
``mixed``): the engine gathers those token ids on the device from the
in-flight vector (``step_sample(prev=...)``), so token values reach the
host one step late (``step()`` returns the PREVIOUS step's tokens).  A
row whose in-flight token is its last by ``max_new_tokens`` is left out
from host counts; requests that hit a stop token are detected at drain
time; the one optimistically-dispatched extra token is discarded and its
KV write is harmless (the flushed pages return to the pool and every
page position is write-before-read for its next owner).  THE DRAIN COMES
FIRST, by what the step is and never by a switch, when: nothing is in
flight or ``async_scheduling`` is off; the speculation gate is open (the
drafter needs committed tokens); a preempted sequence waits, or a
running row finds no page (the preemption ladder needs the drain); a
decode row's token is on the host (restored, handed-off or imported
sequence, a row the step in flight skipped); the plan finds nothing to
run; strict shapes would send the step to the split path, whose
host-side sampling needs logits.  A ``KVAllocationError`` on a dispatch
ahead of the drain drains, rolls the plan back and degrades.

Speculative decoding (ISSUE 10, ``serving_optimization.speculative``,
default off): on steady-state decode steps a host-side prompt-lookup
drafter (spec.py) proposes up to ``spec_max_draft`` tokens per row and
ONE fused program verifies them all as ragged Q>1 segments, returning
``[S, 2]`` int32 (accepted count + corrected token) — a step may then
commit 0..Q tokens per row (``engine.commit_spec`` variable advance,
stop tokens truncate inside accepted blocks).  ``on_token`` is the
complete per-token delivery; the ``step()`` dict keeps one (the last)
token per uid.

Model-drafted speculation (ISSUE 17, ``spec_drafter="model"|"auto"``):
a device-resident draft trunk autoregresses the drafts INSIDE the
fused step (``_dispatch_draft_spec``, ``[S, 2+k]`` transfer), so the
host never proposes and low-repetition traffic speculates too.  Each
request carries its own adaptive drafter state: a per-drafter accept
EWMA plus a dry-spell backoff, and under ``"auto"`` the scheduler
switches a request ngram -> model -> off as its workload phase
demands (``spec.drafter_switch`` flight events).  The draft trunk's
KV trails the target's by construction after restore/handoff/plain
decode runs; ``_dispatch_draft_fill`` catches it up in token-less
steps before model drafting resumes.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ...runtime.fault_injection import (InjectedPreemptionFault,
                                        PoisonedRequestFault,
                                        get_fault_injector)
from ...telemetry import get_tracer, trace_span
from ...telemetry import journey as _journey
from ...telemetry import metrics as tm
from ...telemetry.flight_recorder import get_flight_recorder
from ...telemetry.memory import get_memory_ledger
from ...telemetry.state import state as _telemetry
from ...telemetry.timeseries import get_timeseries
from ...telemetry.watchdog import get_collector
from ...telemetry.workload_trace import get_workload_trace
from ...utils.comms_logging import serving_counters
from .engine import InferenceEngineV2
from .model import serving_tokens_at_ridge
from .ragged.blocked_allocator import KVAllocationError, NULL_PAGE
from .sampling import SamplingParams, sample
from .snapshot import (SNAPSHOT_VERSION, SnapshotError,
                       maybe_install_drain_handler, read_bundle,
                       write_bundle)
from .spec import NgramDrafter

#: the process's collector hook: told which loop is stepping
_collector = get_collector()


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                  # int32 [prompt_len]
    params: SamplingParams
    #: tokens of the prompt already sent to the engine
    prompt_sent: int = 0
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    #: prefix-cache lookup already performed (exactly once per request)
    prefix_checked: bool = False
    #: the request's one set of latency stamps (``time.perf_counter()``
    #: seconds, the span ring's clock; 0.0 = not reached yet), taken
    #: whether or not telemetry is on: submit, first scheduled
    #: admission, first and newest host-visible token.  The latency
    #: histograms, the ``request.*`` spans and the workload ledger's
    #: facts are all derived from them, so a request that was submitted
    #: before telemetry was switched on is still timed from its submit
    submit_s: float = 0.0
    admit_s: float = 0.0
    first_token_s: float = 0.0
    token_s: float = 0.0
    #: absolute ``time.monotonic()`` deadline (ISSUE 7); None = no TTL.
    #: Past it the request drains with a structured "expired" error
    deadline: Optional[float] = None
    #: ``time.monotonic()`` at submit: the clock of the TTL deadlines
    #: and of the shed valve (the CURRENT backlog's age), and the
    #: workload ledger's arrival time
    submit_mono: float = 0.0
    #: speculative decoding facts (ISSUE 10): tokens this request had
    #: drafted for it and tokens verification accepted — the workload
    #: ledger records both so the analyzer can recommend spec_max_draft
    spec_drafted: int = 0
    spec_accepted: int = 0
    #: adaptive drafter state (ISSUE 17) — PER REQUEST, because accept
    #: rate is a property of each request's traffic, not the fleet's:
    #: dry-spell streak + backoff window (the ISSUE 10 globals, moved
    #: here), the active drafter ("" = unresolved; resolved lazily from
    #: config on first spec attempt), per-drafter accept EWMA
    #: ({"ngram","model"} -> rate, -1.0 = untried), and per-drafter
    #: drafted/accepted splits of the ISSUE 10 totals above
    spec_dry: int = 0
    spec_cool: int = 0
    spec_drafter: str = ""
    spec_ewma: Optional[Dict[str, float]] = None
    spec_drafted_ngram: int = 0
    spec_accepted_ngram: int = 0
    spec_drafted_model: int = 0
    spec_accepted_model: int = 0
    #: warm-prefix provenance (ISSUE 16): tokens attached at admission
    #: per tier ({"device","host","disk","remote"} -> tokens), captured
    #: at the one-shot prefix lookup (the sequence may be flushed
    #: before the trace-finish point); None = no lookup / all-cold
    tier_hits: Optional[dict] = None
    #: request journey (ISSUE 19): the end-to-end segment log this
    #: request carries across routers/pools/handoffs/migrations
    #: (telemetry.journey.Journey); None = journeys off at submit.
    #: ``journey_admitted`` latches the per-scheduler queue_wait mark —
    #: a migrated resubmission is a NEW scheduler Request sharing the
    #: SAME journey object, and queues again on the survivor
    journey: Optional[object] = None
    journey_admitted: bool = False
    #: steps whose plan stopped for the ridge while this request was
    #: pending (ISSUE 53); at ``_PASS_OVER_BOUND`` the next step that
    #: plans admits it whatever the step then costs
    passed_over: int = 0

    @property
    def prefill_remaining(self) -> int:
        return len(self.prompt) - self.prompt_sent


@dataclasses.dataclass
class RequestError:
    """Structured terminal error for a request that did not complete
    (ISSUE 7 graceful degradation).  ``code`` is one of:

    - ``"shed"``     — rejected by admission control (bounded queue /
      queue-wait SLO / unservable demand)
    - ``"expired"``  — deadline/TTL passed before completion
    - ``"poisoned"`` — an exception attributable to this request was
      isolated; the step loop kept serving the rest
    - ``"oom"``      — KV pool exhausted after the degradation ladder
      (evict parked pages -> preempt -> shed)
    - ``"closing"``  — submitted after the scheduler stopped admission
      (drain-for-snapshot / shutdown); resubmit to the restored replica
    - ``"migrated"`` — the preemption grace budget expired before a
      snapshot could be written; partial tokens kept (ISSUE 8)
    - ``"misrouted"`` — the request does not fit this scheduler's
      disaggregated role (ISSUE 13): a fresh submit to a decode-only
      pool, or a multi-token submit to a prefill-only pool with no
      handoff sink — rejected immediately so it can never sit forever

    ``tokens`` holds whatever the request generated before
    termination."""
    uid: int
    code: str
    message: str
    tokens: List[int] = dataclasses.field(default_factory=list)


#: what one step scheduled, for the ``fastgen.step`` span: (path, rows,
#: prefill rows, prefill tokens, tokens charged to the budget); taken
#: only while telemetry is on
_IDLE_STEP = ("idle", 0, 0, 0, 0)

#: bounded retention for FastGenScheduler.errors — a long-lived
#: scheduler under sustained shedding must not grow without bound
_MAX_ERROR_RECORDS = 4096


@dataclasses.dataclass
class _Inflight:
    """A dispatched-but-undrained fused step: the device token array and
    the (uid, output row, request) triples of its SAMPLED rows."""
    tokens_dev: jax.Array
    rows: List[Tuple[int, int, Request]]
    #: tokens charged to the budget by the step (the divisor of its
    #: held-experts counts); None for a step dispatched with telemetry off
    step_tokens: Optional[int] = None


#: a decode row's place in ``batch_tokens`` where its token id is a row of
#: the step in flight (``engine.step_sample``'s ``prev``)
_TOKEN_IN_FLIGHT = np.zeros(1, np.int32)


@dataclasses.dataclass
class _StepPlan:
    """What admission put into one step, a row an entry."""
    uids: List[int] = dataclasses.field(default_factory=list)
    #: a prompt piece; a decode row's last token, or the placeholder
    #: where that token is in flight
    tokens: List[np.ndarray] = dataclasses.field(default_factory=list)
    reqs: List[Request] = dataclasses.field(default_factory=list)
    #: a decode row's row in the step in flight (-1: no such row)
    gather: List[int] = dataclasses.field(default_factory=list)
    #: (req, chunk) prompt advances this step — rolled back if the
    #: dispatch fails, so no prompt token is skipped
    advances: List[Tuple[Request, int]] = dataclasses.field(
        default_factory=list)
    #: requests moved pending -> running this step — returned to pending
    #: on a failed dispatch (their engine sequence may not exist yet)
    new_admits: List[Request] = dataclasses.field(default_factory=list)
    #: the pending requests a plan that stopped for the ridge left out;
    #: marked (``Request.passed_over``) only once the plan is the step's
    held: List[Request] = dataclasses.field(default_factory=list)


class _Admission:
    """Incremental per-step budget accounting mirroring the checks of
    ``InferenceEngineV2.can_schedule``."""

    def __init__(self, engine: InferenceEngineV2, token_budget: int):
        sm = engine._config.state_manager
        self.engine = engine
        self.free_pages = engine.free_blocks
        #: the window group's free pages (a model of two page groups)
        self.free_window_pages = engine.free_window_blocks
        #: the state pool's free slots (a model with one)
        self.free_state_slots = engine.free_state_slots
        self.tokens_left = min(token_budget, sm.max_ragged_batch_size)
        self.seqs_left = sm.max_ragged_sequence_count
        self.tracked_left = (sm.max_tracked_sequences
                             - engine.state_manager.n_tracked_sequences)

    def try_admit(self, uid: int, n_tokens: int, is_new: bool) -> bool:
        if (self.seqs_left < 1 or self.tokens_left < n_tokens
                or (is_new and self.tracked_left < 1)):
            return False
        tokens, pages = self.engine.query(uid, n_tokens, self.free_pages)
        if tokens != n_tokens:
            return False
        # both groups or neither (0 of 0 for a model of one group)
        pages_w = self.engine.window_blocks_needed(uid, n_tokens)
        if pages_w > self.free_window_pages:
            return False
        # pages and a slot of the state pool, or neither
        slots = self.engine.state_slots_needed(uid)
        if slots > self.free_state_slots:
            return False
        self.free_state_slots -= slots
        self.free_window_pages -= pages_w
        self.free_pages -= pages
        self.tokens_left -= n_tokens
        self.seqs_left -= 1
        if is_new:
            self.tracked_left -= 1
        return True


def _group_key(p: SamplingParams) -> tuple:
    """Sampling-kernel bucket key: at temperature 0 top_k/top_p are
    no-ops, so every greedy request shares ONE bucket regardless of its
    stochastic knobs (fewer compiled sample() shapes per step)."""
    if p.temperature <= 0.0:
        return (0.0, 0, 1.0)
    return (p.temperature, p.top_k, p.top_p)


class FastGenScheduler:
    """Drives an InferenceEngineV2 with the SplitFuse policy."""

    #: how often the ridge may leave one pending request out (ISSUE 53):
    #: the one constant of the rule.  A request waits at most this many
    #: steps longer for its first token than under admit-all; PERF.md
    #: section 6 has the step histogram that chose it
    _PASS_OVER_BOUND = 1

    def __init__(self, engine: InferenceEngineV2,
                 token_budget: Optional[int] = None,
                 rng: Optional[jax.Array] = None,
                 serving=None, role: Optional[str] = None):
        self._engine = engine
        self._budget = (token_budget or
                        engine._config.state_manager.max_ragged_batch_size)
        sv = serving if serving is not None else engine._config.serving
        self._serving = sv
        self._fused_cfg = bool(sv.fused_step and sv.on_device_sampling)
        self._async_cfg = bool(self._fused_cfg and sv.async_scheduling)
        # -- disaggregated pools (ISSUE 13) ---------------------------
        self._role = str(role if role is not None
                         else getattr(sv, "role", "both") or "both")
        if self._role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"unknown scheduler role {self._role!r} "
                "(expected both|prefill|decode)")
        if self._role == "prefill":
            # a prefill pool never steady-state decodes: the async
            # chain (and speculation below) are decode-pool machinery,
            # and every request leaves after its FIRST token
            self._async_cfg = False
        #: requests that finished prefill + first token on a prefill
        #: role scheduler, awaiting collection by the DisaggPool
        self._handoff_ready: Dict[int, Request] = {}
        #: a DisaggPool registered itself as the handoff consumer; a
        #: prefill role scheduler WITHOUT one rejects multi-token
        #: requests (they could never finish here — satellite: a
        #: misrouted request must not sit forever)
        self._handoff_sink = False
        #: keyed (schedule-invariant) sampling is an ENGINE-build fact:
        #: the compiled programs' signatures carry the per-row (uid,
        #: position) inputs, so follow the model, not the serving view
        self._keyed = bool(getattr(engine.model, "keyed_sampling",
                                   False))
        self._warned_strict_fallback = False
        self._inflight: Optional[_Inflight] = None
        self._pending: List[Request] = []     # waiting for first prefill
        self._preempted: Dict[int, Request] = {}  # KV offloaded to host
        self._preempted_this_step = False
        self._running: Dict[int, Request] = {}
        if rng is None:
            rng = jax.random.key(0)
        elif not jax.dtypes.issubdtype(rng.dtype, jax.dtypes.prng_key):
            # legacy uint32[2] PRNGKey: normalize to a typed key — the
            # AOT-precompiled fused executables are lowered for typed
            # keys and would reject the legacy layout at dispatch
            rng = jax.random.wrap_key_data(rng)
        self._rng = rng
        self.last_step_scheduled = 0
        self._step_shape = _IDLE_STEP
        #: tokens a step carries at the device's ridge, from its published
        #: peaks and the served weights' bytes; None: the device has none
        #: and every step is planned as if there were no ridge
        self._ridge = serving_tokens_at_ridge(engine.model.params)
        #: (pending requests the step considered, of them held back for
        #: the ridge): the ``fastgen.step`` span's ``prompt_offers`` /
        #: ``prompts_held``
        self._step_prompts = (0, 0)
        #: counts past the rows of a sampled-token vector (a model with
        #: held experts: RaggedInferenceModel.step_tail), the last ones
        #: drained, and the tokens of the step they belong to
        self._token_tail = int(getattr(engine.model, "step_tail", 0))
        self._moe_counts = None
        self._moe_tokens: Optional[int] = None
        #: the host's clocks around every step, telemetry on or off
        #: (telemetry/watchdog.py::StepMeter; the engine adds its phases)
        self._meter = engine.step_meter
        #: ``StateManager.window_pages_released`` at the last live span
        self._window_released = 0
        #: (pairs here, fullest expert's pairs, experts touched) of the
        #: last step drained, for a caller that checks them (None: none)
        self.last_moe_counts = None
        #: one-way latch: a strict engine's sampling lattice, once seen,
        #: stays seen (avoids rescanning the step cache every step)
        self._fused_ready = False
        #: scheduler-level prefix-caching gate: a serving= override with
        #: prefix_caching=False must serve the seed full-prefill path
        #: even on an engine whose cache is populated
        self._prefix_cfg = bool(getattr(sv, "prefix_caching", False))
        #: DS_KV_DEBUG=1: run the manager's page-accounting audit after
        #: every step (cheap O(live pages) host check)
        self._kv_debug = os.environ.get("DS_KV_DEBUG", "") not in ("", "0")
        #: telemetry (ISSUE 4): this scheduler's step ordinal for span
        #: labels (independent of other tracer users in the process)
        self._step_ordinal = 0
        # -- graceful degradation (ISSUE 7); getattr: a serving=
        # override may be an older/narrower config object -------------
        self._max_queue_depth = int(getattr(sv, "max_queue_depth", 0)
                                    or 0)
        self._shed_queue_wait_ms = float(
            getattr(sv, "shed_queue_wait_ms", 0.0) or 0.0)
        self._default_ttl_s = float(getattr(sv, "default_ttl_s", 0.0)
                                    or 0.0)
        self._shed_unservable = bool(getattr(sv, "shed_unservable",
                                             False))
        #: structured terminal errors by uid (shed/expired/poisoned/oom)
        self.errors: Dict[int, RequestError] = {}
        #: at least one live request carries a deadline (cheap per-step
        #: guard: deadline-free workloads never scan for expiry)
        self._has_deadlines = False
        #: consecutive steps lost to KV-allocation failure (the
        #: degradation ladder escalates along this streak)
        self._oom_streak = 0
        # -- preemption tolerance (ISSUE 8) ---------------------------
        #: one-way latch: admission stopped (drain-for-snapshot or
        #: shutdown); submit() fails fast with code="closing"
        self._closed = False
        #: workload observatory (ISSUE 9): the process ledger — its
        #: ``active`` attribute is the whole disabled-path cost of every
        #: capture hook below
        self._wtrace = get_workload_trace()
        #: fleet observatory (ISSUE 11): the time-series ring ticks on
        #: the step path (same ``active`` one-attribute-read contract),
        #: so a serving process samples without a background thread
        self._tseries = get_timeseries()
        self._bind_backlog_gauges()
        # -- memory observatory (ISSUE 20): the scheduler owns the
        # handoff staging bytes (prefill KV parked in `_handoff_ready`
        # awaiting a decode-replica fetch) and drives the per-step
        # ledger sample so gauges track the step cadence, not wall time
        self._mledger = get_memory_ledger()
        self._register_staging_accountant()
        # -- speculative decoding (ISSUE 10) --------------------------
        self._spec_cfg = bool(getattr(sv, "speculative", False)
                              and self._role != "prefill")
        self._spec_max_draft = max(
            int(getattr(sv, "spec_max_draft", 3) or 0), 0)
        self._drafter = (NgramDrafter(
            max(int(getattr(sv, "spec_ngram_min", 2) or 1), 1))
            if self._spec_cfg and self._spec_max_draft else None)
        # -- model-drafted speculation (ISSUE 17) ---------------------
        #: configured drafter policy: "ngram" (ISSUE 10 host drafting
        #: only), "model" (device draft trunk forced), "auto" (per-
        #: request state machine ngram -> model -> off)
        self._spec_drafter_cfg = str(
            getattr(sv, "spec_drafter", "ngram") or "ngram")
        #: the engine actually built a draft trunk + draft KV pool —
        #: the capability gate for "model"/"auto" (an engine built
        #: without one silently serves the ngram path: policy follows
        #: the scheduler's serving view, capability follows the engine)
        self._draft_ok = bool(self._spec_cfg and self._spec_max_draft
                              and getattr(engine, "draft_enabled",
                                          False))
        #: strict-shapes latches (the `_fused_ready` pattern): a strict
        #: engine either has spec buckets compiled (positive latch) or
        #: never will (negative latch + one warning)
        self._spec_strict_ready = False
        self._warned_strict_spec = False
        #: cumulative drafted/accepted behind ds_fastgen_spec_accept_rate
        self._spec_drafted_cum = 0
        self._spec_accepted_cum = 0
        #: model-drafter split behind ds_fastgen_spec_draft_accept_rate
        self._spec_draft_drafted_cum = 0
        self._spec_draft_accepted_cum = 0
        self._snapshot_grace_s = float(
            getattr(sv, "snapshot_grace_s", 5.0) or 0.0)
        self._snapshot_path = str(getattr(sv, "snapshot_path", "") or "")
        if self._snapshot_path:
            # the real trigger: DS_DRAIN_ON_SIGTERM=1 wires SIGTERM
            # (spot-VM preemption) to drain->snapshot on this scheduler
            maybe_install_drain_handler(self, self._snapshot_path,
                                        self._snapshot_grace_s)

    def _bind_backlog_gauges(self) -> None:
        """Instantaneous backlog gauges (ISSUE 9 satellite): the SLO
        histograms only record at drain, so a /metrics scraper can't
        see a BUILDING backlog — these callback gauges read the live
        queues at scrape time (weakref: the registry must not keep a
        discarded scheduler alive; with several schedulers in one
        process the newest owns the gauges, the ds_kv_* convention)."""
        import weakref
        ref = weakref.ref(self)

        def read(attr):
            def _read(r=ref, a=attr):
                sched = r()
                return len(getattr(sched, a)) if sched is not None else 0
            return _read

        tm.FASTGEN_QUEUE_DEPTH.bind(read("_pending"))
        tm.FASTGEN_RUNNING.bind(read("_running"))
        tm.FASTGEN_PREEMPTED.bind(read("_preempted"))

    def _register_staging_accountant(self) -> None:
        """Account handoff staging bytes (ISSUE 20): KV pages a prefill
        replica holds parked in ``_handoff_ready`` waiting for a decode
        replica to fetch them.  Those pages live inside the device KV
        pool (already counted by ``kv_pages``), but they are *committed*
        capacity the allocator cannot reclaim — the ledger tracks them
        as their own subsystem so a stuck handoff shows up as a growing
        ``ds_mem_staging_bytes`` instead of mystery KV pressure."""
        kv = self._engine.model.kv_config
        page, bpp = kv.page_size, kv.bytes_per_page

        def staging_bytes(sched, _page=page, _bpp=bpp):
            total = 0
            state = sched._engine.state_manager
            for uid, req in list(sched._handoff_ready.items()):
                try:
                    toks = state.get_sequence(uid).seen_tokens
                except Exception:
                    toks = len(req.prompt)
                total += -(-int(toks) // _page) * _bpp
            return total

        self._mledger.register_object("staging", self, staging_bytes)

    # -- workload trace (ISSUE 9): capture at drain/error points -------------
    def _trace_finish(self, req: Request, outcome: str) -> None:
        """Append one terminated request to the workload ledger:
        lengths, sampling params, latency facts, and the prompt's
        chained page-digest chain (the prefix cache's own hash, so the
        recorded sharing structure is exactly what the cache saw) —
        never token ids.  Callers gate on ``self._wtrace.active``."""
        from .ragged.prefix_cache import PrefixCache
        page = self._engine.model.kv_config.page_size
        prompt = np.asarray(req.prompt)
        digests: List[str] = []
        if outcome not in ("shed", "closing"):
            # the O(prompt) digest chain is skipped on the admission
            # fast-reject path — it exists to fail fast under overload,
            # and shed prompts never touched the engine (replay
            # synthesizes them as unshared full-length prompts)
            d = b""
            for i in range(len(prompt) // page):
                d = PrefixCache.chain(d, prompt[i * page:(i + 1) * page])
                digests.append(d.hex())
        n = len(req.generated)
        p = req.params
        self._wtrace.record_request(
            uid=req.uid, arrival_mono=req.submit_mono,
            prompt_len=len(prompt), gen_len=n, digests=digests,
            page_size=page,
            vocab_size=int(getattr(self._engine.model.cfg,
                                   "vocab_size", 0)),
            temperature=p.temperature, top_k=p.top_k, top_p=p.top_p,
            max_new_tokens=p.max_new_tokens, outcome=outcome,
            ttft_ms=((req.first_token_s - req.submit_s) * 1e3
                     if req.first_token_s else None),
            itl_ms=((req.token_s - req.first_token_s) * 1e3 / (n - 1)
                    if n > 1 and req.first_token_s else None),
            queue_wait_ms=((req.admit_s - req.submit_s) * 1e3
                           if req.admit_s else None),
            spec_drafted=req.spec_drafted,
            spec_accepted=req.spec_accepted,
            spec_drafter=req.spec_drafter,
            spec_ngram=[req.spec_drafted_ngram,
                        req.spec_accepted_ngram],
            spec_model=[req.spec_drafted_model,
                        req.spec_accepted_model],
            hit_device=(req.tier_hits or {}).get("device", 0),
            hit_host=(req.tier_hits or {}).get("host", 0),
            hit_disk=(req.tier_hits or {}).get("disk", 0),
            hit_remote=(req.tier_hits or {}).get("remote", 0),
            journey_ms=(req.journey.bucket_ms()
                        if req.journey is not None
                        and req.journey.segments else None))

    # -- request journeys (ISSUE 19): flush at drain/error -------------------
    def _journey_finish(self, req: Request, outcome: str) -> None:
        """Close and publish the request's journey (exactly once —
        :meth:`telemetry.journey.JourneyLog.publish` is idempotent
        through the ``closed`` latch, so a prefill-side copy whose
        request finished on the decode pool never double-flushes)."""
        j = req.journey
        if j is None or j.closed:
            return
        if req.generated:
            # first_token -> last committed token; a request that died
            # before any token folds straight into drain
            j.mark("decode")
        j.mark("drain")
        _journey.get_journey_log().publish(j, outcome)

    # -- request lifecycle ---------------------------------------------------
    def submit(self, uid: int, prompt: Sequence[int],
               params: Optional[SamplingParams] = None,
               ttl_s: Optional[float] = None,
               journey: Optional[object] = None
               ) -> Optional[RequestError]:
        """Queue a request; returns None on acceptance or the
        structured :class:`RequestError` verdict on immediate
        rejection (also recorded in :attr:`errors`).  ``ttl_s`` (or the
        config's ``default_ttl_s``) sets a deadline past which the
        request terminates with a structured "expired" error instead
        of hanging.  A bounded admission queue (``max_queue_depth``), a
        violated queue-wait SLO (``shed_queue_wait_ms``), or a closed
        scheduler (drain-for-snapshot/shutdown, code="closing") rejects
        the request immediately.  ``journey`` is the caller's existing
        request journey (ISSUE 19: a pool minted it at ITS submit and
        keeps appending placement/migration segments to the same
        object); without one, a fresh journey is minted here — the
        request-scoped trace context every boundary propagates."""
        req = Request(
            uid=uid, prompt=np.asarray(prompt, dtype=np.int32),
            params=params or SamplingParams())
        req.journey = journey if journey is not None \
            else _journey.mint(uid)
        now = time.monotonic()
        req.submit_mono = now
        req.submit_s = time.perf_counter()
        if self._closed:
            # a submit after close/drain-for-snapshot used to enqueue
            # silently — onto a scheduler that will never run it and
            # into no snapshot bundle.  Fail fast instead.
            return self._reject_submit(
                req, "closing",
                "scheduler is draining for snapshot/shutdown — "
                "resubmit to the restored replica")
        # role admission (ISSUE 13): a request the role can never
        # finish is rejected with a structured verdict instead of
        # sitting in a queue nothing will ever drain
        if self._role == "decode":
            return self._reject_submit(
                req, "misrouted",
                "decode-only scheduler: fresh requests need prefill — "
                "submit to the prefill pool (this engine admits "
                "handoff imports only)")
        if self._role == "prefill" and not self._handoff_sink \
                and req.params.max_new_tokens > 1:
            return self._reject_submit(
                req, "misrouted",
                "prefill-only scheduler with no handoff sink attached: "
                f"max_new_tokens={req.params.max_new_tokens} could "
                "never complete here (only the first token is produced "
                "on the prefill pool)")
        ttl = ttl_s if ttl_s is not None else (self._default_ttl_s
                                               or None)
        if ttl:
            req.deadline = now + float(ttl)
            self._has_deadlines = True
        if self._max_queue_depth and \
                len(self._pending) >= self._max_queue_depth:
            return self._reject_submit(
                req, "shed",
                f"admission queue full ({len(self._pending)} pending "
                f">= max_queue_depth={self._max_queue_depth})")
        if self._shed_queue_wait_ms > 0.0 and self._pending:
            # SLO-driven load shedding.  The decisive signal is the
            # CURRENT backlog (oldest pending request already waited
            # past the SLO — always-on submit_mono stamp, so the valve
            # works with telemetry off).  The PR 4 queue-wait histogram
            # confirms when it has data: it is cumulative for the
            # process life, so it may only VETO (a fresh backlog during
            # a healthy period is never shed because of a congestion
            # burst hours ago), never shed on its own.
            h = tm.FASTGEN_QUEUE_WAIT_MS
            oldest_ms = (now - self._pending[0].submit_mono) * 1e3
            if oldest_ms > self._shed_queue_wait_ms and (
                    h.count < 8
                    or h.percentile(90.0) > self._shed_queue_wait_ms):
                return self._reject_submit(
                    req, "shed",
                    f"queue-wait SLO {self._shed_queue_wait_ms:.1f}ms "
                    f"violated (oldest pending {oldest_ms:.1f}ms, "
                    f"observed p90 {h.percentile(90.0):.1f}ms over "
                    f"{h.count} samples)")
        self._pending.append(req)
        return None

    def _reject_submit(self, req: Request, code: str,
                       message: str) -> RequestError:
        """Immediate admission rejection.  When the uid collides with a
        LIVE request (a client retrying its own uid — the "closing"
        message even invites a resubmit elsewhere), the live request
        must NOT be evicted: it keeps its queue slot, KV pages, and
        eventual verdict (it is exactly the state an in-progress
        snapshot exists to capture).  Only the NEW submit is refused,
        with an error record that is returned but not stored (storing
        would clobber the live request's eventual verdict)."""
        live = (req.uid in self._running or req.uid in self._preempted
                or req.uid in self._handoff_ready
                or any(r.uid == req.uid for r in self._pending))
        if live:
            err = RequestError(uid=req.uid, code=code, message=message)
            tm.FASTGEN_SHED.inc()
            get_flight_recorder().record(
                "request.error", uid=req.uid, code=code,
                message=message[:200], tokens=0, duplicate=True)
            return err
        self._fail_request(req, code, message)
        return self.errors.get(req.uid)

    def _fail_request(self, req: Request, code: str,
                      message: str) -> None:
        """Terminate ``req`` with a structured error: engine state is
        flushed, the request leaves every queue, and partial tokens are
        preserved on the error record.  An in-flight async row for this
        uid is discarded at drain (``req.done`` gates it — same
        mechanism as stop-token rollback)."""
        req.done = True
        self._pending = [r for r in self._pending if r.uid != req.uid]
        self._running.pop(req.uid, None)
        self._preempted.pop(req.uid, None)
        self._handoff_ready.pop(req.uid, None)
        if self._drafter is not None:
            self._drafter.drop(req.uid)
        if self._engine.state_manager.get_sequence(req.uid) is not None:
            self._engine.flush(req.uid)
        self.errors[req.uid] = RequestError(
            uid=req.uid, code=code, message=message,
            tokens=list(req.generated))
        while len(self.errors) > _MAX_ERROR_RECORDS:
            # bounded retention on a long-lived scheduler: drop the
            # oldest verdicts (dict preserves insertion order)
            self.errors.pop(next(iter(self.errors)))
        if code in ("shed", "closing"):
            # "closing" IS admission control: the valve is the
            # scheduler's lifecycle instead of queue depth
            tm.FASTGEN_SHED.inc()
        elif code == "misrouted":
            tm.DISAGG_MISROUTED.inc()
        elif code == "expired":
            tm.FASTGEN_EXPIRED.inc()
        elif code == "migrated":
            tm.FASTGEN_MIGRATED.inc()
        else:
            tm.FASTGEN_REQUEST_ERROR.inc()
        get_flight_recorder().record(
            "request.error", uid=req.uid, code=code,
            message=message[:200], tokens=len(req.generated))
        if _telemetry.enabled:
            self._close_request_spans(req)
        # journey flush precedes the ledger record so the ledger's
        # journey_<bucket>_ms fields see the closed chain
        self._journey_finish(req, code)
        if self._wtrace.active:
            # error point of the workload ledger: the outcome code IS
            # the structured error code
            self._trace_finish(req, code)

    def _expire_requests(self) -> None:
        """Terminate every request whose deadline has passed (pending,
        running, and preempted alike) with a structured error."""
        if not self._has_deadlines:
            return
        now = time.monotonic()
        expired = [r for r in (list(self._pending)
                               + list(self._running.values())
                               + list(self._preempted.values())
                               + list(self._handoff_ready.values()))
                   if r.deadline is not None and now >= r.deadline]
        for req in expired:
            self._fail_request(
                req, "expired",
                f"deadline passed ({len(req.generated)} tokens "
                f"generated, {req.prefill_remaining} prompt tokens "
                "unprefilled)")

    @property
    def has_work(self) -> bool:
        return bool(self._pending or self._running or self._preempted
                    or self._inflight is not None)

    @property
    def backlog(self) -> int:
        """Live request count (pending + running + preempted) — the
        pool router's least-backlog placement signal (ISSUE 12; the
        same quantity the ``ds_fastgen_queue_depth``/``_running``/
        ``_preempted`` gauges expose to remote scrapers)."""
        return (len(self._pending) + len(self._running)
                + len(self._preempted))

    @property
    def closed(self) -> bool:
        """Admission stopped (close()/drain-for-snapshot); reversible
        only via :meth:`reopen` while the scheduler is still alive."""
        return self._closed

    @property
    def _fused(self) -> bool:
        """Fused serving, gated on strict-shapes coherence: an engine
        precompiled WITHOUT the fused sample/chain variants
        (``precompile(strict=True)`` with the default ``sampling=False``)
        keeps serving through the seed split path instead of raising a
        strict-miss on the first step — strict mode means "serve only
        precompiled programs", whichever paths those are."""
        if not self._fused_cfg:
            return False
        model = self._engine.model
        if not getattr(model, "strict_shapes", False):
            return True
        if self._fused_ready:
            return True
        if self._warned_strict_fallback:
            return False    # negative latch: don't rescan the cache
        if self._engine.has_kind("sample"):
            self._fused_ready = True
            return True
        from ...utils.logging import logger
        logger.warning(
            "strict_shapes engine has no precompiled fused sampling "
            "buckets — serving through the split path for the life of "
            "this scheduler; precompile with sampling=True (before "
            "constructing the scheduler) for the fused step")
        self._warned_strict_fallback = True
        return False

    @property
    def _async(self) -> bool:
        return self._async_cfg and self._fused

    # -- rng -----------------------------------------------------------------
    def _next_key(self, greedy_only: bool) -> jax.Array:
        """Greedy-only steps never consume RNG state (argmax needs no
        randomness — splitting a key per step would make greedy decode
        depend on how many steps ran before it).  Keyed sampling
        (ISSUE 13) never splits either: the base key is the fixed root
        every per-(uid, position) row key derives from, so the stream
        is independent of step count by construction."""
        if greedy_only or self._keyed:
            return self._rng
        self._rng, key = jax.random.split(self._rng)
        return key

    # -- per-request latency: one set of stamps, always taken ----------------
    def _stamp_token(self, req: Request) -> None:
        """One host-visible token: stamp it (one clock read, telemetry
        on or off) and, with telemetry on, feed what ends here: the
        first token closes TTFT and the ``request.prefill`` span, a later
        one is an inter-token gap.  A request imported mid-life (handoff,
        restore) has no earlier local token: its first gap is skipped."""
        now = time.perf_counter()
        if _telemetry.enabled:
            if len(req.generated) == 1:
                tm.FASTGEN_TTFT_MS.observe((now - req.submit_s) * 1e3)
                self._request_span(req, "request.prefill",
                                   req.admit_s or req.submit_s, now)
            elif req.token_s:
                tm.FASTGEN_ITL_MS.observe((now - req.token_s) * 1e3)
        if req.first_token_s == 0.0:
            req.first_token_s = now
        req.token_s = now

    def _request_span(self, req: Request, name: str, start: float,
                      end: float, attrs: Optional[dict] = None) -> None:
        """One of the three spans that tile a request's life
        (``request.queue_wait`` submit -> first admission,
        ``request.prefill`` -> first token on the host,
        ``request.decode`` -> done), written after the fact from the
        request's stamps at the boundary where it ends.  Callers gate on
        telemetry; the stamps do not, so the span is right for a request
        submitted before telemetry was switched on."""
        get_tracer().record(name, start, end - start, attrs, uid=req.uid)

    def _close_request_spans(self, req: Request) -> None:
        """The request ended (done or failed): close the span that was
        open."""
        now = time.perf_counter()
        if req.first_token_s:
            self._request_span(req, "request.decode",
                               req.first_token_s, now,
                               {"new_tokens": len(req.generated)})
        elif req.admit_s:
            self._request_span(req, "request.prefill", req.admit_s, now)
        else:
            self._request_span(req, "request.queue_wait",
                               req.submit_s, now)

    # -- drain: sync a dispatched step's tokens ------------------------------
    def _deliver_token(self, req: Request, tok: int, out: Dict[int, int],
                       on_token) -> bool:
        """Append ONE committed token and run the delivery sequence
        (SLO stamp, ledger stamp, out dict, callback) shared by every
        drain path — spec blocks included.  Returns True when this
        token terminates the request (max_new_tokens reached or stop
        token hit); the caller then runs :meth:`_finish_request`."""
        req.generated.append(tok)
        # unconditional (the ServingCounters convention): the windowed
        # tok/s the fleet view and SLO evaluator read must exist even
        # telemetry-off — one integer add per token
        tm.FASTGEN_TOKENS.inc()
        self._stamp_token(req)
        if req.journey is not None and len(req.generated) == 1:
            # the first committed token closes prefill; first_token
            # itself is the (~0 ms) delivery instant.  Handoff-imported
            # requests arrive with generated tokens, so these segments
            # are marked exactly once, on the prefill side
            req.journey.mark("prefill")
            req.journey.mark("first_token")
        out[req.uid] = tok
        if on_token is not None:
            on_token(req.uid, tok)
        stop = req.params.stop_token
        return (len(req.generated) >= req.params.max_new_tokens
                or (stop is not None and tok == stop))

    def _finish_request(self, req: Request) -> None:
        """Normal (outcome "ok") request termination, one copy for all
        drain paths: flush engine state, leave the running set, drop
        the drafter index, close the workload-ledger record."""
        req.done = True
        get_flight_recorder().record("request.done", uid=req.uid,
                                     tokens=len(req.generated))
        self._engine.flush(req.uid)
        self._running.pop(req.uid, None)
        if self._drafter is not None:
            self._drafter.drop(req.uid)
        if _telemetry.enabled:
            self._close_request_spans(req)
        self._journey_finish(req, "ok")
        if self._wtrace.active:
            self._trace_finish(req, "ok")

    def _drain(self, on_token) -> Dict[int, int]:
        if self._inflight is None:
            return {}
        with trace_span("fastgen.drain"):
            return self._drain_impl(on_token)

    # dslint: hot-path
    def _drain_impl(self, on_token) -> Dict[int, int]:
        inf, self._inflight = self._inflight, None
        meter, t = self._meter, time.perf_counter()
        with trace_span("fastgen.drain.wait"):
            # the host blocked on the device: not host work
            toks = np.asarray(inf.tokens_dev)   # dslint: d2h [S] int32
        t, then = time.perf_counter(), t
        meter.wait += t - then
        serving_counters.record_d2h(toks.nbytes)
        if self._token_tail:
            # the held-experts counts ride the token vector's tail
            self._moe_counts = self.last_moe_counts = \
                toks[-self._token_tail:]
            self._moe_tokens = inf.step_tokens
        out: Dict[int, int] = {}
        with trace_span("fastgen.drain.deliver"):
            for uid, row, req in inf.rows:
                if req.done:
                    # optimistically chained past a stop token — the
                    # extra sampled token is discarded (its KV write
                    # landed in pages the flush already returned to the
                    # pool)
                    continue
                if self._deliver_token(req, int(toks[row]), out,
                                       on_token):
                    self._finish_request(req)
        meter.deliver += time.perf_counter() - t
        return out

    # -- double buffer: which steps are dispatched ahead of the drain -------
    def _inflight_rows(self) -> Optional[Dict[int, int]]:
        """uid -> row of the step in flight, where the next step can be
        planned and dispatched BEFORE that one drains: every decode row's
        input token is one of that step's sampled rows (a decode row of
        it, or a prompt whose last piece ran in it), so the host has
        nothing to wait for.  None where the drain comes first: nothing
        is in flight, a preempted sequence waits (restoring needs the
        pool as the drain leaves it), a decode row's token is on the
        host (a restored, imported or skipped sequence), or strict
        shapes might send a step with a prompt to the split path, whose
        host-side sampling needs logits."""
        if not self._async or self._inflight is None or self._preempted:
            return None
        slot = {uid: row for uid, row, _ in self._inflight.rows}
        decoding = [uid for uid, req in self._running.items()
                    if req.prefill_remaining == 0]
        if not all(uid in slot for uid in decoding):
            return None
        if self._strict and (self._pending
                             or len(decoding) < len(self._running)):
            return None
        return slot

    @property
    def _strict(self) -> bool:
        """The engine serves only precompiled programs."""
        return getattr(self._engine.model, "strict_shapes", False)

    def _strict_key_ok(self, uids, tokens, kind: str = "logits",
                       **fields) -> bool:
        """Under strict shapes, fused dispatch requires the predicted
        step-cache key to be AOT-compiled.  Slot/Q bucketing can push
        bucket(S) * bucket(Q) past max_ragged_batch_size even when the
        actual token count fits the budget — exactly the superbuckets
        the precompile lattice skips — so membership, not arithmetic, is
        the gate.  ``kind`` and ``fields`` as
        ``engine.predict_step_key`` takes them."""
        if not self._strict:
            return True
        return self._engine.has_program(self._engine.predict_step_key(
            uids, tokens, kind, **fields))

    # -- speculative decoding (ISSUE 10 / ISSUE 17) --------------------------
    #: dry-spell backoff ceiling: after N consecutive fruitless
    #: attempts (nothing drafted, or nothing accepted) a request's
    #: speculation is re-attempted at most every N+1 steps
    _SPEC_BACKOFF_MAX = 8
    #: per-drafter accept-rate EWMA smoothing (ISSUE 17)
    _SPEC_EWMA_ALPHA = 0.3
    #: "auto" switches a request off its current drafter when the
    #: drafter's EWMA sits below this after >= _SPEC_MIN_TRIES drafted
    #: tokens (or after that many consecutive dry attempts)
    _SPEC_SWITCH_BELOW = 0.25
    _SPEC_MIN_TRIES = 4

    @property
    def _spec_on(self) -> bool:
        """Speculation gate, strict-shapes coherent (the `_fused`
        pattern): a strict engine whose precompiled lattice has NO spec
        buckets latches speculation off for the life of this scheduler
        — without the latch every backoff re-probe would drain the
        in-flight chain step and draft for every row just to fail the
        key-membership check, a permanent throughput tax."""
        if self._drafter is None or not self._fused:
            return False
        if not getattr(self._engine.model, "strict_shapes", False):
            return True
        if self._spec_strict_ready:
            return True
        if self._warned_strict_spec:
            return False    # negative latch: don't rescan the cache
        if self._engine.has_kind("spec", "draft_spec"):
            self._spec_strict_ready = True
            return True
        from ...utils.logging import logger
        logger.warning(
            "strict_shapes engine has no precompiled speculative "
            "buckets — speculation disabled for the life of this "
            "scheduler; precompile with sampling=True on an engine "
            "config with serving.speculative=True (or pass "
            "spec_max_draft to precompile) to serve it")
        self._warned_strict_spec = True
        return False

    def _spec_gate(self) -> bool:
        """Preconditions for attempting a speculative step: pure
        steady-state decode (the chained path's membership conditions)
        and at least one request outside its dry-spell cooldown with a
        live drafter.  An attempt costs the async overlap (the
        in-flight step must drain before the host drafter can see
        committed tokens), and a zero-accept dispatch costs a Q-wide
        verify for one token — so each request's fruitless attempts
        back off linearly (capped), and an accepted draft resets its
        backoff.  Cooldowns tick here (once per step)."""
        if not self._spec_on or self._pending or self._preempted \
                or not self._running:
            return False
        if any(r.prefill_remaining > 0 for r in self._running.values()):
            return False
        eligible = False
        for req in self._running.values():
            if req.spec_cool > 0:
                req.spec_cool -= 1
                continue
            if self._drafter_of(req) != "off":
                eligible = True
        return eligible

    # -- adaptive drafter selection (ISSUE 17) -------------------------------
    def _drafter_of(self, req: Request) -> str:
        """Resolve (lazily initializing) the request's active drafter:
        "ngram", "model", or "off".  Config "ngram"/"model" pins the
        answer (capability-gated: a forced "model" on an engine with no
        draft trunk serves ngram); "auto" starts every request on the
        free host drafter and lets :meth:`_maybe_switch_drafter` move
        it.  An "off" request whose backoff expired re-probes its
        historically-best drafter — workloads have phases, and a
        request parked off during a stochastic burst must get another
        chance once its traffic turns draftable."""
        if not req.spec_drafter:
            mode = self._spec_drafter_cfg
            if mode in ("model", "auto") and not self._draft_ok:
                mode = "ngram"
            req.spec_drafter = "ngram" if mode == "auto" else mode
            req.spec_ewma = {"ngram": -1.0, "model": -1.0}
        if (req.spec_drafter == "off" and req.spec_cool == 0
                and self._spec_drafter_cfg == "auto"):
            ew = req.spec_ewma or {}
            cands = ("ngram", "model") if self._draft_ok else ("ngram",)
            self._switch_drafter(
                req, max(cands, key=lambda k: ew.get(k, -1.0)))
        return req.spec_drafter

    def _switch_drafter(self, req: Request, new: str) -> None:
        old, req.spec_drafter = req.spec_drafter, new
        if new == "off":
            # parked: the re-probe in _drafter_of fires when this
            # window expires, so "off" is periodic, not permanent
            req.spec_dry = req.spec_cool = self._SPEC_BACKOFF_MAX
        else:
            req.spec_dry = req.spec_cool = 0
        ew = req.spec_ewma or {}
        get_flight_recorder().record(
            "spec.drafter_switch", uid=req.uid, src=old, dst=new,
            ewma_ngram=round(ew.get("ngram", -1.0), 3),
            ewma_model=round(ew.get("model", -1.0), 3))

    def _maybe_switch_drafter(self, req: Request) -> None:
        """The "auto" state machine: ngram -> model when the free host
        drafter demonstrably isn't paying (low EWMA over enough tries,
        or a pure dry spell — low-repetition traffic never even
        proposes), model -> off when the draft trunk isn't either
        (truncated-trunk drafts on hard traffic).  Forced configs never
        switch."""
        if self._spec_drafter_cfg != "auto":
            return
        ew = req.spec_ewma or {}

        def bad(name: str, tried: int) -> bool:
            return ((tried >= self._SPEC_MIN_TRIES
                     and 0.0 <= ew.get(name, -1.0)
                     < self._SPEC_SWITCH_BELOW)
                    or req.spec_dry >= self._SPEC_MIN_TRIES)

        if req.spec_drafter == "ngram" and self._draft_ok \
                and bad("ngram", req.spec_drafted_ngram):
            self._switch_drafter(req, "model")
        elif req.spec_drafter == "model" \
                and bad("model", req.spec_drafted_model):
            self._switch_drafter(req, "off")

    def _note_spec_dry(self, req: Request) -> None:
        """One fruitless attempt (nothing proposed / nothing accepted):
        extend the request's backoff and let "auto" react."""
        req.spec_dry += 1
        req.spec_cool = min(req.spec_dry, self._SPEC_BACKOFF_MAX)
        self._maybe_switch_drafter(req)

    def _note_spec_result(self, req: Request, drafter: str,
                          drafted: int, accepted: int) -> None:
        """Account one verified draft block against ``drafter``: the
        ISSUE 10 totals, the per-drafter split the ledger records, the
        accept EWMA, and the backoff (reset on any acceptance)."""
        req.spec_drafted += drafted
        req.spec_accepted += accepted
        if drafter == "model":
            req.spec_drafted_model += drafted
            req.spec_accepted_model += accepted
        else:
            req.spec_drafted_ngram += drafted
            req.spec_accepted_ngram += accepted
        if accepted:
            req.spec_dry = req.spec_cool = 0
        else:
            req.spec_dry += 1
            req.spec_cool = min(req.spec_dry, self._SPEC_BACKOFF_MAX)
        if drafted:
            if req.spec_ewma is None:
                req.spec_ewma = {"ngram": -1.0, "model": -1.0}
            rate = accepted / drafted
            prev = req.spec_ewma.get(drafter, -1.0)
            req.spec_ewma[drafter] = (
                rate if prev < 0.0
                else (1.0 - self._SPEC_EWMA_ALPHA) * prev
                + self._SPEC_EWMA_ALPHA * rate)
        self._maybe_switch_drafter(req)

    def _plan_spec(self):
        """Drafter-mode resolution + draft/admission plan for one
        speculative step.  One step runs ONE mode — host n-gram drafts
        and device model drafts can't mix in one program — so any
        eligible model-selecting row pulls the step into model mode
        (cooling / differently-selected rows ride as plain q_len=1
        rows).  Returns ``(mode, rows)`` with mode "ngram"/"model" and
        rows ``[(uid, req, tokens, draft), ...]``, or ``("fill",
        rows)`` when model mode must first catch the draft trunk's KV
        up (``[(uid, tokens), ...]`` token-less plan), or None when
        nothing drafted / budget refused / strict-uncovered — callers
        fall back to the normal paths.  Must run AFTER the in-flight
        step drained (the drafter reads committed tokens)."""
        mode = "ngram"
        for req in self._running.values():
            if req.spec_cool == 0 and self._drafter_of(req) == "model":
                mode = "model"
                break
        if mode == "model":
            # the draft trunk's KV must cover every row's committed
            # history before the device draft loop can extend it — ANY
            # lagging row (restored, handed off, or admitted during an
            # ngram phase) holds the whole step back since all rows
            # ride the one program
            lagged = [(u, r) for u, r in self._running.items()
                      if self._engine.draft_lag(u) > 0]
            if lagged:
                fill = self._plan_draft_fill(lagged)
                if fill is not None:
                    return ("fill", fill)
                mode = "ngram"  # fill bucket never covered: host path
            if mode == "model":
                plan = self._plan_spec_mode("model")
                if plan is not None:
                    return ("model", plan)
                mode = "ngram"  # draft_spec uncovered / budget refused
        plan = self._plan_spec_mode(mode)
        return (mode, plan) if plan is not None else None

    def _plan_spec_mode(self, mode: str):
        """Row plan for one speculative step in ``mode``: every
        running row gets ``[last_committed, draft...]`` tokens (draft
        possibly empty — rows verify raggedly within the one spec
        bucket).  In model mode the draft is placeholder zeros (the
        device drafts in-program; the length shapes the row)."""
        adm = _Admission(self._engine, self._budget)
        max_seq = int(getattr(self._engine.model.cfg, "max_seq_len",
                              1 << 30))
        rows = []
        any_draft = False
        for uid, req in self._running.items():
            drafts_here = (req.spec_cool == 0
                           and self._drafter_of(req) == mode)
            # room for the mandatory 1 corrected/bonus token + drafts:
            # never draft past max_new_tokens or the model context
            room = min(self._spec_max_draft,
                       req.params.max_new_tokens - len(req.generated) - 1,
                       max_seq - self._engine.seen_tokens(uid) - 2) \
                if drafts_here else 0
            if room > 0 and mode == "model":
                draft = np.zeros(room, np.int32)    # device-drafted
            elif room > 0:
                draft = self._drafter.propose(uid, req.prompt,
                                              req.generated, room)
                if not len(draft):
                    # attempted and found nothing: this request's
                    # backoff extends even if the step proceeds on
                    # other rows' drafts
                    self._note_spec_dry(req)
            else:
                draft = np.zeros(0, np.int32)
            last = (req.generated[-1] if req.generated
                    else int(req.prompt[-1]))
            toks = np.concatenate(
                [np.asarray([last], np.int32), draft])
            if not adm.try_admit(uid, len(toks), is_new=False):
                # shrink to a plain decode row before giving up on the
                # whole step
                if len(toks) > 1 and adm.try_admit(uid, 1, is_new=False):
                    toks, draft = toks[:1], draft[:0]
                else:
                    return None     # host path handles preemption
            if len(draft):
                any_draft = True
            rows.append((uid, req, toks, draft))
        if not rows or not any_draft:
            return None
        greedy_only = all(req.params.temperature <= 0.0
                          for _, req, _, _ in rows)
        if not self._strict_key_ok(
                [u for u, _, _, _ in rows],
                [t for _, _, t, _ in rows],
                "draft_spec" if mode == "model" else "spec",
                greedy=greedy_only, min_q=1 + self._spec_max_draft):
            return None
        return rows

    def _plan_draft_fill(self, lagged):
        """Catch-up plan: feed each lagging row's already-committed
        history slice (``draft_seen .. seen_tokens``) through the draft
        trunk so its KV reaches the target's frontier.  Chunked to the
        step token budget (a huge restored backlog fills over several
        steps); under strict shapes the chunk cap halves until a
        compiled ``draft_fill`` bucket covers the batch, or None when
        even the Q=1 bucket isn't there (callers then serve ngram)."""
        budget = self._budget
        rows = []
        for uid, req in lagged:
            lag = self._engine.draft_lag(uid)
            seen = self._engine.seen_tokens(uid)
            hist = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.generated, np.int32)])[:seen]
            chunk = min(lag, max(budget, 1))
            rows.append((uid, hist[seen - lag: seen - lag + chunk]))
            budget -= chunk
            if budget <= 0:
                break               # the rest fills next step
        while rows:
            if self._strict_key_ok([u for u, _ in rows],
                                   [t for _, t in rows], "draft_fill"):
                return rows
            cap = max(len(t) for _, t in rows) // 2
            if cap < 1:
                return None
            rows = [(u, t[:cap]) for u, t in rows]
        return None

    # dslint: hot-path
    def _dispatch_spec(self, rows, on_token) -> Dict[int, int]:
        """Dispatch one speculative verification program and drain it
        in the SAME scheduler step: the device returns [S, 2] int32
        (accepted count, corrected token) per row — the only d2h —
        and the host reconstructs each committed block from the drafts
        it proposed.  Commit is variable-advance: ``seen_tokens`` moves
        by the committed count only; rejected drafts' KV is overwritten
        write-before-read by later steps.  A stop token INSIDE an
        accepted block truncates the commit at the stop (the request
        flushes, so the over-written KV beyond it is unreachable)."""
        uids = [u for u, _, _, _ in rows]
        toks = [t for _, _, t, _ in rows]
        params = [req.params for _, req, _, _ in rows]
        greedy_only = all(p.temperature <= 0.0 for p in params)
        # keyed: position j of a spec row emits generation index
        # len(generated) + j (the device folds per position)
        row_pos = ([len(req.generated) for _, req, _, _ in rows]
                   if self._keyed else None)
        with trace_span("fastgen.dispatch.spec"):
            out_dev = self._engine.step_spec(
                uids, toks, params, self._next_key(greedy_only),
                min_q=1 + self._spec_max_draft, row_pos=row_pos)
        self.last_step_scheduled = len(uids)
        if _telemetry.enabled:
            self._step_shape = ("spec", len(uids), 0, 0,
                                sum(len(t) for t in toks))
        t = time.perf_counter()
        with trace_span("fastgen.drain.wait"):
            av = np.asarray(out_dev)        # dslint: d2h [S, 2] int32
        self._meter.wait += time.perf_counter() - t
        serving_counters.record_d2h(av.nbytes)
        out: Dict[int, int] = {}
        committed: List[int] = []
        drafted = accepted = 0
        with trace_span("fastgen.drain.deliver"):
            for i, (uid, req, _t, draft) in enumerate(rows):
                a = min(int(av[i, 0]), len(draft))
                block = [int(t) for t in draft[:a]] + [int(av[i, 1])]
                c = 0
                for tok in block:
                    c += 1
                    if self._deliver_token(req, tok, out, on_token):
                        # termination deferred: flush needs the descriptor
                        # the variable-advance commit below still updates
                        req.done = True
                        break
                committed.append(c)
                # accepted counts COMMITTED drafts only: a stop-token
                # truncation rolls back verifier-accepted tokens past it,
                # and the accept-rate the analyzer mines must reflect what
                # actually committed (c <= a: all c are drafts; c == a+1:
                # the a drafts plus the correction)
                drafted += len(draft)
                accepted += min(a, c)
                if len(draft):
                    self._note_spec_result(req, "ngram", len(draft),
                                           min(a, c))
        self._engine.commit_spec(uids, committed)
        for uid, req, _t, _d in rows:
            if req.done:
                self._finish_request(req)
        self._spec_drafted_cum += drafted
        self._spec_accepted_cum += accepted
        tm.FASTGEN_SPEC_DRAFTED.inc(drafted)
        tm.FASTGEN_SPEC_ACCEPTED.inc(accepted)
        if self._spec_drafted_cum:
            tm.FASTGEN_SPEC_ACCEPT_RATE.set(
                self._spec_accepted_cum / self._spec_drafted_cum)
        return out

    # dslint: hot-path
    def _dispatch_draft_spec(self, rows, on_token) -> Dict[int, int]:
        """Model-drafted sibling of :meth:`_dispatch_spec` (ISSUE 17):
        ONE fused program runs the draft trunk's k-token greedy loop
        AND the target's ragged verification, returning [S, 2+k] int32
        (accepted count, corrected token, the k device-drafted tokens)
        per row — still the step's only d2h.  The host never proposed
        anything, so it reconstructs each committed block from the
        RETURNED drafts; everything downstream (variable-advance
        commit, stop-token truncation, accept accounting) matches the
        n-gram path, plus ``mark_draft_seen`` records that the draft
        trunk's KV now covers every committed position."""
        uids = [u for u, _, _, _ in rows]
        toks = [t for _, _, t, _ in rows]
        params = [req.params for _, req, _, _ in rows]
        greedy_only = all(p.temperature <= 0.0 for p in params)
        # keyed: position j of a spec row emits generation index
        # len(generated) + j (the device folds per position)
        row_pos = ([len(req.generated) for _, req, _, _ in rows]
                   if self._keyed else None)
        with trace_span("fastgen.dispatch.draft_spec"):
            out_dev = self._engine.step_draft_spec(
                uids, toks, params, self._next_key(greedy_only),
                min_q=1 + self._spec_max_draft, row_pos=row_pos)
        self.last_step_scheduled = len(uids)
        if _telemetry.enabled:
            self._step_shape = ("draft", len(uids), 0, 0,
                                sum(len(t) for t in toks))
        t = time.perf_counter()
        with trace_span("fastgen.drain.wait"):
            av = np.asarray(out_dev)        # dslint: d2h [S, 2+k] int32
        self._meter.wait += time.perf_counter() - t
        serving_counters.record_d2h(av.nbytes)
        out: Dict[int, int] = {}
        committed: List[int] = []
        drafted = accepted = 0
        with trace_span("fastgen.drain.deliver"):
            for i, (uid, req, _t, draft) in enumerate(rows):
                room = len(draft)
                a = min(int(av[i, 0]), room)
                block = [int(t) for t in av[i, 2:2 + a]] + [int(av[i, 1])]
                c = 0
                for tok in block:
                    c += 1
                    if self._deliver_token(req, tok, out, on_token):
                        # termination deferred: flush needs the descriptor
                        # the variable-advance commit below still updates
                        req.done = True
                        break
                committed.append(c)
                drafted += room
                accepted += min(a, c)
                if room:
                    self._note_spec_result(req, "model", room, min(a, c))
        self._engine.commit_spec(uids, committed)
        self._engine.mark_draft_seen(uids)
        for uid, req, _t, _d in rows:
            if req.done:
                self._finish_request(req)
        self._spec_drafted_cum += drafted
        self._spec_accepted_cum += accepted
        self._spec_draft_drafted_cum += drafted
        self._spec_draft_accepted_cum += accepted
        tm.FASTGEN_SPEC_DRAFTED.inc(drafted)
        tm.FASTGEN_SPEC_ACCEPTED.inc(accepted)
        tm.FASTGEN_SPEC_DRAFT_DRAFTED.inc(drafted)
        tm.FASTGEN_SPEC_DRAFT_ACCEPTED.inc(accepted)
        if self._spec_drafted_cum:
            tm.FASTGEN_SPEC_ACCEPT_RATE.set(
                self._spec_accepted_cum / self._spec_drafted_cum)
        if self._spec_draft_drafted_cum:
            tm.FASTGEN_SPEC_DRAFT_ACCEPT_RATE.set(
                self._spec_draft_accepted_cum
                / self._spec_draft_drafted_cum)
        return out

    def _dispatch_draft_fill(self, rows) -> None:
        """Token-less draft-trunk catch-up step: run the committed
        history chunks through the draft trunk's forward so its KV
        reaches the target's frontier.  Nothing commits, nothing
        samples, nothing crosses device->host — the step exists purely
        so the NEXT step's draft loop has valid draft KV to attend
        over."""
        uids = [u for u, _ in rows]
        with trace_span("fastgen.dispatch.draft_fill"):
            self._engine.step_draft_fill(uids, [t for _, t in rows])
        self.last_step_scheduled = len(uids)
        n = int(sum(len(t) for _, t in rows))
        if _telemetry.enabled:
            self._step_shape = ("draft", len(uids), 0, 0, n)
        tm.FASTGEN_SPEC_DRAFT_FILL.inc(n)
        get_flight_recorder().record("spec.draft_fill",
                                     rows=len(uids), tokens=n)

    # -- one engine step -----------------------------------------------------
    def step(self, on_token: Optional[Callable[[int, int], None]] = None
             ) -> Dict[int, int]:
        """Schedule one ragged batch; returns {uid: new_token} for every
        sequence whose token became host-visible this step (with
        async_scheduling that is the PREVIOUS step's tokens — one-step
        lag).  With speculation enabled a step may commit a whole
        accepted BLOCK per row; the dict then holds each row's LAST
        committed token, and ``on_token`` (called once per token, in
        order) is the complete delivery path — stream consumers must
        use it, not the return value."""
        _faults = get_fault_injector()
        if _faults.armed and _faults.fire("serving.preempt"):
            # deterministic SIGTERM-equivalent at a step BOUNDARY
            # (nothing mid-mutation; raised before the crash-forensics
            # wrapper because a controlled preemption is not a crash).
            # The caller handles it like the real signal: catch, run
            # drain_and_snapshot, restore elsewhere.
            raise InjectedPreemptionFault(
                "injected preemption between scheduler steps")
        # the collector's spans are this loop's from here on, and the
        # host's clocks bracket the step whether or not telemetry is on
        # (ISSUE 52): ``meter.end`` feeds the EWMA anomaly detector
        # (ISSUE 5: a recompile or a KV thrash shows up as a step-time
        # spike) and leaves one ``fastgen.stall`` record where the step
        # or the gap before it paused
        _collector.loop = "fastgen"
        meter = self._meter
        meter.begin()
        try:
            if _telemetry.enabled:
                # spans from this step (and everything nested under it)
                # are labelled with THIS scheduler's own step ordinal —
                # not derived from the tracer's current label, which a
                # training engine sharing the process (hybrid RLHF) also
                # writes
                self._step_ordinal += 1
                get_tracer().set_step(self._step_ordinal)
                with trace_span("fastgen.step") as span:
                    out = self._step_impl(on_token)
                    if span.live:
                        self._note_step(span)
            else:
                out = self._step_impl(on_token)
            meter.end(self.last_step_scheduled, self._step_ordinal)
        except Exception as e:
            # crash forensics (ISSUE 5): leave a postmortem bundle
            # before the exception leaves the step loop; never masks it
            get_flight_recorder().on_crash("fastgen.step", e)
            raise
        if self._role == "prefill" and self._running:
            self._sweep_handoff_ready()
        if self._kv_debug:
            self._engine.state_manager.check_invariants()
        if self._tseries.active:
            # opportunistic time-series tick (ISSUE 11): interval-gated
            # inside, so a fast step loop samples at the configured
            # cadence, not per step
            self._tseries.maybe_sample()
        # memory ledger tick (ISSUE 20): watermark peaks track the
        # step cadence (the time-series hook above only fires at its
        # sampling interval — peaks between ticks would be lost)
        self._mledger.sample()
        return out

    def _note_step(self, span) -> None:
        """What the step scheduled and what the KV pool holds at its
        end, as attributes of its live ``fastgen.step`` span (the pool's
        totals walk every sequence).  Each one is read by a per-layer
        metric of the benchmark (PERF.md section 3)."""
        path, rows, prefill_rows, prefill_tokens, tokens = \
            self._step_shape
        pages, held = self._engine.state_manager.kv_occupancy()
        for key, value in (
                ("path", path), ("rows", rows),
                ("prefill_rows", prefill_rows),
                ("prefill_tokens", prefill_tokens), ("tokens", tokens),
                ("budget", self._budget),
                # pending requests the step considered, and those of them
                # the ridge left to the next step
                ("prompt_offers", self._step_prompts[0]),
                ("prompts_held", self._step_prompts[1]),
                ("kv_pages_reserved", pages), ("kv_tokens_held", held),
                # how often the step's (last) program streams its
                # weights: 1 for every kind; 2 would be a mixed step
                # that runs a pass a segment again
                ("trunk_passes", self._engine.model.last_trunk_passes
                 if path != "idle" else 0),
                # the kind of that program: ``path`` says when the step
                # was dispatched (``chain``: ahead of the drain), this
                # what ran it
                ("program", self._engine.model.last_program
                 if path != "idle" else "idle")):
            span.set(key, value)
        slots = self._engine.take_slots_held()
        if slots is not None:
            # page slots of the step's decode rows: live / bucket is the
            # share of the page bucket the decode kernel's walk visits;
            # held / (held + live) the share of page fetches that the null
            # page used to be under the grid form's fetch table
            span.set("kv_slots_held", slots[0])
            span.set("kv_slots_live", slots[1])
            span.set("kv_slots_bucket", slots[2])
        state = self._engine.state_manager
        if state.window_cache is not None:
            # the window group of a model with two page groups: what its
            # tables hold beside the full group's, and what this step's
            # eviction gave back
            pages_w, held_w = state.window_occupancy()
            span.set("kv_pages_reserved_window", pages_w)
            span.set("kv_tokens_held_window", held_w)
            span.set("kv_pages_released_window",
                     state.window_pages_released - self._window_released)
            self._window_released = state.window_pages_released
            full, in_window = self._engine.take_attended()
            span.set("attn_tokens_full", full)
            span.set("attn_tokens_window", in_window)
        if state.state_pool is not None:
            # the state pool of a model with state-space or delta-rule
            # layers: slots held (under the names the pool's first kind
            # gave them, whatever kind holds the slots), and under the
            # kind's own name what this step's two kernels were given (a
            # one-token row is stepped by the update kernel, a prompt
            # piece's true tokens by the scan or the chunked form)
            pool = state.state_pool
            span.set("ssm_slots_held", pool.held_slots)
            span.set(f"{pool.cfg.kind}_rows_decode", rows - prefill_rows)
            span.set(f"{pool.cfg.kind}_tokens_prefill", prefill_tokens)
            span.set("ssm_state_bytes",
                     pool.held_slots * pool.cfg.bytes_per_slot)
            # the layers those bytes are spread over, whatever the kind: a
            # step's state bytes become a roofline without the model's
            # configuration
            span.set(f"{pool.cfg.kind}_layers", pool.cfg.num_layers)
            if self._engine.counts_attended and state.window_cache is None:
                # the context the decode rows attend in the full layers
                span.set("attn_tokens_full",
                         self._engine.take_attended()[0])
        if self._moe_counts is not None:
            # counts of the step drained inside this one, only beside
            # that step's own tokens as their divisor (``_Inflight.
            # step_tokens``): a step dispatched with telemetry off took
            # no count of its tokens, and its counts go on no span
            if self._moe_tokens is not None:
                span.set("moe_pairs_here", int(self._moe_counts[0]))
                span.set("moe_expert_load_max", int(self._moe_counts[1]))
                span.set("moe_experts_touched", int(self._moe_counts[2]))
                span.set("moe_tokens", self._moe_tokens)
            self._moe_counts = None

    def _match_prefix_once(self, req: Request, adm: _Admission) -> None:
        """One-shot prefix-cache lookup before first admission: cached
        full pages attach to the (created) sequence and the scheduler
        only prefills the uncached suffix."""
        if self._engine.state_manager.prefix_cache is None:
            req.prefix_checked = True   # engine has no cache
            return
        if adm.tracked_left < 1:
            return
        state = self._engine.state_manager
        was_tracked = state.get_sequence(req.uid) is not None
        alloc = state.kv_cache.allocator
        parked_before = alloc.parked_pages
        free_before = alloc.free_pages
        hit = self._engine.match_prefix(req.uid, req.prompt)
        # only consume the one-shot once the lookup actually ran —
        # match_prefix registers the sequence when it does (its own
        # tracked-capacity guard can bail first, and that request must
        # retry next step)
        req.prefix_checked = state.get_sequence(req.uid) is not None
        if req.prefix_checked and not was_tracked:
            # the lookup created a tracked sequence that try_admit below
            # won't charge (is_new flips False) — charge it here so
            # later requests' `tracked_left >= 1` gate stays accurate
            adm.tracked_left -= 1
        if hit:
            req.prompt_sent = hit
            req.tier_hits = self._engine.tier_hits(req.uid)
            if req.journey is not None and any(
                    (req.tier_hits or {}).get(t)
                    for t in ("host", "disk", "remote")):
                # a cross-tier promotion paid wall time here; device
                # cache hits are reference attaches and stay unmarked
                req.journey.mark("tier_promote")
            # attached pages that counted as schedulable in this
            # admission's snapshot and are now live must be charged:
            # parked->live transitions (device cache hits) AND
            # free->live transitions (tier promotions land on freshly
            # reserved pages, ISSUE 16); already-live shared pages were
            # never in the snapshot's schedulable count.  Demotions a
            # promotion triggers are parked->free — net zero here
            adm.free_pages -= ((free_before + parked_before)
                               - (alloc.free_pages
                                  + alloc.parked_pages))

    def _padded_tokens(self, decode_rows: int,
                       pieces: Sequence[int]) -> int:
        """What the device pays for a step of ``decode_rows`` one-token
        rows and prompt ``pieces`` (their lengths), by the engine's own
        lattice: the one-token segment's row bucket, plus the prompt
        segment's row bucket times the bucket of its longest piece (a
        piece of one token is a row of the first segment:
        ``engine.step_sample``)."""
        lat = self._engine.model.lattice
        long = [n for n in pieces if n > 1]
        ones = decode_rows + len(pieces) - len(long)
        padded = lat.bucket_s(ones) if ones else 0
        if long:
            padded += lat.bucket_s(len(long)) * lat.bucket_q(max(long))
        return padded

    # dslint: hot-path
    def _plan_step(self, slot: Optional[Dict[int, int]]
                   ) -> Optional[_StepPlan]:
        """Admission, the one plan of a step: every running decode (one
        token each), then partial prefills, then pending requests,
        chunked to the budget.  ``slot`` None: the step in flight has
        drained and a decode row's input token is its last on the host.
        Else (``_inflight_rows``) the plan runs AHEAD of the drain: a
        decode row's token is row ``slot[uid]`` of the step in flight
        and the row is left out where that token is its last by count;
        None comes back, with nothing changed, where a running row finds
        no page (the preemption ladder needs the drain) or strict
        shapes hold no chain program for the rows."""
        with trace_span("fastgen.admission"):
            # resume preempted sequences first when the pool has room
            # again (restore cost = their live page count, plus decode
            # headroom)
            for uid in list(self._preempted):
                sd = self._engine.state_manager.get_sequence(uid)
                if sd is None:  # flushed/cancelled while preempted
                    self._preempted.pop(uid)
                    continue
                need = (sd.host_blob.shape[1]
                        if sd.host_blob is not None else 0)
                need_w = (sd.window_blob.shape[1] + 1
                          if sd.window_blob is not None else 0)
                need_s = int(sd.state_blob is not None)
                if self._engine.free_blocks >= need + 1 \
                        and self._engine.free_window_blocks >= need_w \
                        and self._engine.free_state_slots >= need_s:
                    self._engine.restore_sequence(uid)
                    get_flight_recorder().record("request.restore",
                                                 uid=uid)
                    self._running[uid] = self._preempted.pop(uid)

            adm = _Admission(self._engine, self._budget)
            plan = _StepPlan()
            uids, tokens, reqs = plan.uids, plan.tokens, plan.reqs
            _faults = get_fault_injector()

            # 1. all running decodes (one token each).  Per-request
            # error isolation (ISSUE 7): an exception attributable to
            # one request evicts THAT request; the step keeps serving
            # the rest
            for uid, req in list(self._running.items()):
                if req.prefill_remaining > 0:
                    continue  # mid-prefill requests handled below
                if slot is not None and (len(req.generated) + 1
                                         >= req.params.max_new_tokens):
                    continue  # the in-flight token is its last
                try:
                    if _faults.armed and \
                            _faults.fire("fastgen.poison_request"):
                        raise PoisonedRequestFault(
                            f"injected poisoned request {uid}")
                    if not adm.try_admit(uid, 1, is_new=False):
                        if slot is not None:
                            return None
                        continue
                except Exception as e:
                    self._fail_request(req, "poisoned",
                                       f"{type(e).__name__}: {e}")
                    continue
                if slot is None:
                    last = (req.generated[-1] if req.generated
                            else int(req.prompt[-1]))
                    tokens.append(np.array([last], dtype=np.int32))
                    plan.gather.append(-1)
                else:
                    tokens.append(_TOKEN_IN_FLIGHT)
                    plan.gather.append(slot[uid])
                uids.append(uid)
                reqs.append(req)
            if slot is not None and uids and self._strict \
                    and not self._strict_key_ok(
                        uids, tokens, "chain",
                        greedy=all(r.params.temperature <= 0.0
                                   for r in reqs),
                        prev_tokens=self._inflight.tokens_dev):
                # strict mode serves only precompiled programs: chain
                # only when the EXACT key (incl. the previous step's
                # token-array length) was AOT-lowered
                return None

            decode_rows = len(uids)

            # 2. continue partial prefills, then admit pending, chunked
            # to budget
            def try_prefill(req: Request, is_new: bool) -> bool:
                if adm.tokens_left <= 0 or req.prefill_remaining == 0:
                    return False
                if _faults.armed and \
                        _faults.fire("fastgen.poison_request"):
                    raise PoisonedRequestFault(
                        f"injected poisoned request {req.uid}")
                if req.journey is not None and not req.journey_admitted:
                    # first admission attempt on THIS scheduler closes
                    # queue_wait, so the prefix match / tier promotion
                    # below gets its own segment instead of inheriting
                    # the queue time
                    req.journey_admitted = True
                    req.journey.mark("queue_wait")
                if is_new and self._prefix_cfg and not req.prefix_checked:
                    with trace_span("fastgen.prefix_match"):
                        self._match_prefix_once(req, adm)
                if is_new:
                    # match_prefix tracks the sequence (even on a miss,
                    # to register the prompt for indexing) — admission
                    # must see the engine's view or the tracked-count
                    # gate would double-charge a request that stays
                    # pending
                    is_new = (self._engine.state_manager
                              .get_sequence(req.uid) is None)
                chunk = min(req.prefill_remaining, adm.tokens_left)
                while chunk > 0 and not adm.try_admit(req.uid, chunk,
                                                      is_new):
                    chunk //= 2  # shrink to fit KV headroom
                if chunk == 0:
                    return False
                piece = req.prompt[req.prompt_sent:req.prompt_sent + chunk]
                uids.append(req.uid)
                tokens.append(piece.astype(np.int32))
                plan.gather.append(-1)
                reqs.append(req)
                req.prompt_sent += chunk
                plan.advances.append((req, chunk))
                serving_counters.record_prefill(chunk)
                if req.admit_s == 0.0:
                    # first scheduled admission: close the queue-wait
                    # window opened at submit
                    req.admit_s = time.perf_counter()
                    if _telemetry.enabled:
                        tm.FASTGEN_QUEUE_WAIT_MS.observe(
                            (req.admit_s - req.submit_s) * 1e3)
                        self._request_span(req, "request.queue_wait",
                                           req.submit_s, req.admit_s)
                        get_flight_recorder().record(
                            "request.admit", uid=req.uid,
                            prompt_tokens=len(req.prompt),
                            cached_tokens=req.prompt_sent - chunk)
                return True

            for req in list(self._running.values()):
                try:
                    try_prefill(req, is_new=False)
                except Exception as e:
                    self._fail_request(req, "poisoned",
                                       f"{type(e).__name__}: {e}")
            # the ridge (ISSUE 53): a step with fewer decode rows than the
            # device's ridge streams its weights for them, and tokens that
            # join ride the stream until the padded count passes the
            # ridge; from there each pays in full, and the next step, which
            # runs for the decode rows anyway, carries it for less.  With
            # no decode row that step would exist for the prompt alone, and
            # past the ridge a prompt costs the same in any step: both are
            # planned as without a ridge
            ridge = self._ridge
            if not decode_rows or ridge is None \
                    or self._padded_tokens(decode_rows, ()) >= ridge:
                ridge = None
            while self._pending and adm.tokens_left > 0:
                req = self._pending[0]
                if ridge is not None and plan.advances \
                        and req.passed_over < self._PASS_OVER_BOUND \
                        and self._padded_tokens(
                            decode_rows,
                            [n for _, n in plan.advances]
                            + [min(req.prefill_remaining,
                                   adm.tokens_left)]) > ridge:
                    # held BEFORE anything is asked of the engine for it
                    # (no prefix match, no tracked sequence, no page), and
                    # with it every request that waits behind it
                    plan.held = list(self._pending)
                    break
                try:
                    admitted = try_prefill(req, is_new=True)
                except Exception as e:
                    self._fail_request(req, "poisoned",
                                       f"{type(e).__name__}: {e}")
                    continue
                if not admitted:
                    break
                self._pending.pop(0)
                self._running[req.uid] = req
                plan.new_admits.append(req)
        return plan

    # dslint: hot-path
    def _step_impl(self, on_token: Optional[Callable[[int, int], None]]
                   ) -> Dict[int, int]:
        serving_counters.record_step()
        self._preempted_this_step = False
        self._step_shape = _IDLE_STEP
        self._step_prompts = (0, 0)
        self._expire_requests()

        spec_drained: Optional[Dict[int, int]] = None
        if self._spec_gate():
            # speculation needs the committed token stream on the host
            # (the drafter's n-gram key ends at the LAST token; the
            # draft trunk's catch-up reads committed history), so the
            # in-flight chained step drains first; if nothing drafts,
            # fall through to the normal admission path with the drain
            # already done (the chain plan needs an in-flight step)
            spec_drained = self._drain(on_token)
            plan = self._plan_spec()
            if plan is not None:
                mode, rows = plan
                if mode == "fill":
                    # token-less draft-KV catch-up: model drafting
                    # resumes once the trunk reaches the frontier
                    self._dispatch_draft_fill(rows)
                    return spec_drained
                try:
                    out = (self._dispatch_draft_spec(rows, on_token)
                           if mode == "model"
                           else self._dispatch_spec(rows, on_token))
                except KVAllocationError as e:
                    self._degrade_oom(e, [], [])
                    return spec_drained
                self._oom_streak = 0
                spec_drained.update(out)
                return spec_drained

        # THE double buffer: where every decode row's token is in the
        # step in flight, step k+1 is planned and dispatched FIRST and
        # step k drains while the device runs it; the plan falls back to
        # the drain where it finds nothing to run or no page for a row
        slot = self._inflight_rows() if spec_drained is None else None
        meter, t = self._meter, time.perf_counter()
        plan = self._plan_step(slot) if slot is not None else None
        meter.admission += time.perf_counter() - t
        ahead = plan is not None and bool(plan.uids)
        out_prev = spec_drained     # step k's tokens, once it drained
        if not ahead:
            if out_prev is None:
                out_prev = self._drain(on_token)
            t = time.perf_counter()
            plan = self._plan_step(None)
            meter.admission += time.perf_counter() - t
        uids, tokens, reqs = plan.uids, plan.tokens, plan.reqs
        advances, new_admits = plan.advances, plan.new_admits

        self.last_step_scheduled = len(uids)
        if not uids:
            # nothing schedulable but work remains: preempt the running
            # sequence holding the most KV so the others can finish —
            # its pages go to host via the offload hook and it resumes
            # automatically once the pool frees up
            self._preempt_largest()
            return out_prev

        if new_admits or plan.held:
            # the plan is the step's from here: what it left out for the
            # ridge has been passed over once (``_degrade_oom`` takes it
            # back with the admissions)
            for req in plan.held:
                req.passed_over += 1
            self._step_prompts = (len(new_admits) + len(plan.held),
                                  len(plan.held))
            serving_counters.record_prompt_offers(*self._step_prompts)

        sampled_rows = [i for i, r in enumerate(reqs)
                        if r.prefill_remaining == 0]

        # strict shapes serve only AOT-compiled programs.  Mixed
        # two-segment keys aren't enumerated by the lattice at all, and
        # even single-geometry superbuckets can fall outside it (slot/Q
        # bucket rounding past max_ragged_batch_size) — gate the fused
        # dispatch on predicted-key membership and drop to the seed
        # split path otherwise.  (A step planned ahead of the drain is
        # past this gate: ``_inflight_rows``, ``_plan_step``.)
        strict = self._strict
        strict_mixed = (strict and any(len(t) == 1 for t in tokens)
                        and any(len(t) > 1 for t in tokens))
        greedy_only = all(reqs[i].params.temperature <= 0.0
                          for i in sampled_rows)
        use_fused = ahead or (
            self._fused and not strict_mixed
            and (not strict or self._strict_key_ok(
                uids, tokens, "sample", greedy=greedy_only)))
        if _telemetry.enabled:
            # every prompt piece of the step is one entry of ``advances``
            prefill_tokens = sum(chunk for _, chunk in advances)
            self._step_shape = (
                "chain" if ahead else "fused" if use_fused else "split",
                len(uids),
                len(advances), prefill_tokens,
                len(uids) - len(advances) + prefill_tokens)

        if use_fused:
            # ONE program: fused mixed-batch forward + on-device
            # sampling; only the [S] int32 tokens ever reach the host
            # mid-prefill rows produce no token: pin them greedy so a
            # stochastic param on an unsampled row can't flip the step
            # into the stochastic specialization (or consume RNG);
            # greedy_only above uses the same sampled-rows-only rule
            row_params = [r.params if r.prefill_remaining == 0
                          else SamplingParams() for r in reqs]
            # keyed: a sampled row emits generation index
            # len(generated), one more where its input token is still
            # in flight and not yet counted (mid-prefill rows' draws
            # are ignored)
            row_pos = ([len(r.generated) + (g >= 0)
                        for r, g in zip(reqs, plan.gather)]
                       if self._keyed else None)
            try:
                with trace_span("fastgen.dispatch.chain" if ahead
                                else "fastgen.dispatch.fused"):
                    toks, rowmap = self._engine.step_sample(
                        uids, tokens, row_params,
                        self._next_key(greedy_only), do_checks=False,
                        row_pos=row_pos,
                        prev=((self._inflight.tokens_dev, plan.gather)
                              if ahead else None))
            except KVAllocationError as e:
                # degraded step: drain what's in flight, run the
                # ladder, retry next step
                if ahead:
                    out_prev = self._drain(on_token)
                self._degrade_oom(e, advances, new_admits, plan.held)
                return out_prev
            self._oom_streak = 0
            inflight = _Inflight(
                tokens_dev=toks,
                rows=[(uids[i], rowmap[i], reqs[i])
                      for i in sampled_rows],
                step_tokens=(self._step_shape[4]
                             if self._step_shape is not _IDLE_STEP
                             else None))
            if ahead:
                # the host sync overlaps the device executing the new step
                out_prev = self._drain(on_token)
            self._inflight = inflight
            if not self._async:
                out_prev.update(self._drain(on_token))
            return out_prev

        # escape-hatch split path: host sampling over put() logits.  The
        # forward's fusion follows the SCHEDULER's serving view, not the
        # engine's (a serving= override must reach the seed per-Q-bucket
        # programs, or the escape hatch measures the fused forward);
        # under strict shapes the fused logits superbucket must also be
        # lattice-covered or put() falls back to per-bucket programs
        put_fused = self._serving.fused_step and not strict_mixed
        if put_fused and strict:
            put_fused = self._strict_key_ok(uids, tokens)
        # dslint: disable=hot-path-sync -- split escape hatch: host-side
        # sampling over put() logits is the documented seed fallback; its
        # d2h is counted by serving_counters.record_d2h and surfaced as
        # fastgen_logits_bytes_per_step in the bench
        with trace_span("fastgen.dispatch.split"):
            try:
                logits = self._engine.put(uids, tokens, do_checks=False,
                                          fused=put_fused)
            except KVAllocationError as e:
                self._degrade_oom(e, advances, new_admits, plan.held)
                return out_prev
            self._oom_streak = 0
            groups: Dict[tuple, List[int]] = {}
            for i in sampled_rows:
                groups.setdefault(_group_key(reqs[i].params), []).append(i)
            new_tokens: Dict[int, int] = {}
            for (temp, top_k, top_p), idxs in groups.items():
                if self._keyed and temp > 0.0:
                    # schedule-invariant escape-hatch sampling: one
                    # folded (uid, position) key per row — bit-equal
                    # to the fused keyed path's on-device derivation
                    for i in idxs:
                        req = reqs[i]
                        key = jax.random.fold_in(
                            jax.random.fold_in(self._rng, int(req.uid)),
                            len(req.generated))
                        t = np.asarray(sample(
                            logits[np.asarray([i])], key,
                            temperature=temp, top_k=top_k, top_p=top_p))
                        serving_counters.record_d2h(t.nbytes)
                        new_tokens[i] = int(t[0])
                    continue
                key = self._next_key(greedy_only=temp <= 0.0)
                toks = np.asarray(sample(logits[np.asarray(idxs)], key,
                                         temperature=temp, top_k=top_k,
                                         top_p=top_p))
                serving_counters.record_d2h(toks.nbytes)
                for i, t in zip(idxs, toks):
                    new_tokens[i] = int(t)

        out = dict(out_prev)
        for i, tok in new_tokens.items():
            req = reqs[i]
            if self._deliver_token(req, tok, out, on_token):
                self._finish_request(req)
        return out

    # -- disaggregated handoff (ISSUE 13) ------------------------------------
    @property
    def role(self) -> str:
        return self._role

    @property
    def handoff_backlog(self) -> int:
        """Requests awaiting collection by the DisaggPool (prefill
        role only; always 0 elsewhere)."""
        return len(self._handoff_ready)

    def enable_handoff_sink(self) -> None:
        """Register a handoff consumer (the DisaggPool): a prefill
        role scheduler then admits multi-token requests, trusting the
        sink to stream them onward after their first token."""
        self._handoff_sink = True

    def handoff_ready_uids(self) -> List[int]:
        return list(self._handoff_ready)

    def _sweep_handoff_ready(self) -> None:
        """Prefill role: a running request whose prefill is complete
        and whose FIRST token is host-delivered (TTFT already served —
        the transfer never gates it) leaves the scheduling sets and
        parks as handoff-ready.  Its engine sequence stays live until
        ``complete_handoff``/``_fail_request``."""
        for uid, req in list(self._running.items()):
            if req.done or req.prefill_remaining > 0 or not req.generated:
                continue
            self._running.pop(uid)
            self._handoff_ready[uid] = req
            get_flight_recorder().record(
                "disagg.handoff_ready", uid=uid,
                tokens=len(req.generated))

    def export_handoff(self, uids: Sequence[int]) -> dict:
        """One handoff bundle for handoff-ready ``uids``: the
        sequences' committed KV pages through the selective
        ``export_state`` seam (each distinct page once; full prefix
        pages ride with their chain digests so the importer can dedup
        against its own prefix cache) plus each request's residual
        state — prompt incl. the partial-page tail tokens, committed
        tokens, sampling params, remaining TTL/token budget, spec
        counters.  Non-destructive: the requests stay parked here
        until :meth:`complete_handoff` (import succeeded) or
        :meth:`_fail_request`."""
        missing = [u for u in uids if u not in self._handoff_ready]
        if missing:
            raise ValueError(
                f"export_handoff of non-handoff-ready uids {missing}")
        now = time.monotonic()
        for u in uids:
            req = self._handoff_ready[u]
            if req.journey is not None:
                # the journey travels WHOLE inside the bundle (via
                # _serialize_request below); the fragment keeps the
                # exporting side's view reconstructable even if the
                # importer dies mid-transfer
                req.journey.mark("handoff_export")
                _journey.get_journey_log().publish_fragment(
                    req.journey, where=self._role or "prefill")
        eng_meta, arrays = self._engine.state_manager.export_state(
            seq_ids=list(uids))
        meta = {
            "version": SNAPSHOT_VERSION,
            "handoff": True,
            "requests": [self._serialize_request(self._handoff_ready[u],
                                                 now) for u in uids],
            "engine": eng_meta,
        }
        return {"meta": meta, "arrays": arrays}

    def complete_handoff(self, uids: Sequence[int]) -> None:
        """The bundle landed on the decode pool: flush the local
        sequences (their full prefix pages PARK in this pool's prefix
        cache, so the NEXT same-prefix prompt still prefills only the
        suffix) and drop the parked requests — their remaining
        delivery happens on the importing scheduler."""
        for u in uids:
            req = self._handoff_ready.pop(u, None)
            if req is None:
                continue
            if self._drafter is not None:
                self._drafter.drop(u)
            if self._engine.state_manager.get_sequence(u) is not None:
                self._engine.flush(u)

    def import_handoff(self, bundle: dict) -> dict:
        """Decode-side import of one handoff bundle: merge the
        sequences and pages into the live engine (prefix sharing and
        refcounts reconstructed; already-held shared prefixes attach
        by digest instead of streaming) and enqueue the residual
        requests — straight into the running set, or the preempted
        set when the bundle carried a mid-preemption host blob.
        Raises :class:`SnapshotError` on a non-handoff bundle / uid
        collision / geometry mismatch and
        :class:`~.ragged.blocked_allocator.KVAllocationError` when the
        pool cannot hold the streamed pages yet (retryable
        backpressure — nothing is mutated).  Returns
        ``{"uids", "pages_streamed", "pages_shared"}``."""
        meta, arrays = bundle["meta"], bundle["arrays"]
        if not meta.get("handoff"):
            raise SnapshotError(
                "import_handoff expects a bundle from export_handoff")
        if self._closed:
            raise SnapshotError(
                "import_handoff on a closed scheduler")
        for d in meta["requests"]:
            uid = int(d["uid"])
            if (uid in self._running or uid in self._preempted
                    or uid in self._handoff_ready
                    or any(r.uid == uid for r in self._pending)):
                raise SnapshotError(
                    f"import_handoff: uid {uid} already live on the "
                    "importing scheduler")
        t_import = time.time()     # transfer ends where import begins
        with trace_span("fastgen.import_handoff"):
            stats = self._engine.state_manager.import_state(
                meta["engine"], arrays)
            now = time.monotonic()
            uids: List[int] = []
            for d in meta["requests"]:
                req = self._restore_request(d, now)
                if req.journey is not None:
                    # split the window since handoff_export: the wire/
                    # queue time, then the page-merge + restore work.
                    # at= is the IMPORTING scheduler's role — the pump
                    # thread driving this import carries the exporter's
                    # component label
                    at = self._role or "decode"
                    req.journey.mark("handoff_transfer", at=at,
                                     t=t_import)
                    req.journey.mark("handoff_import", at=at)
                sd = self._engine.state_manager.get_sequence(req.uid)
                if sd is not None and sd.host_blob is not None:
                    # handed off mid-preemption: resumes through the
                    # normal restore path once the pool has room
                    self._preempted[req.uid] = req
                else:
                    self._running[req.uid] = req
                uids.append(req.uid)
        if self._kv_debug:
            self._engine.state_manager.check_invariants()
        stats = dict(stats or {})
        stats["uids"] = uids
        return stats

    # -- graceful degradation (ISSUE 7) --------------------------------------
    def _preempt_largest(self) -> bool:
        """Preempt the sequence holding the most OFFLOADABLE KV
        (window eviction leaves null slots and prefix-shared pages
        stay resident through an offload — neither frees anything, and
        a no-op preemption would spin run_to_completion).  Handoff-
        ready sequences (prefill role) are preferred victims: they
        hold pages while doing no work, and the handoff path carries
        their host blob to the decode pool (mid-preemption handoff)."""

        def live_pages(u):
            state = self._engine.state_manager
            sd = state.get_sequence(u)
            return len(state.offloadable_slots(sd)) if sd else 0

        if self._handoff_ready:
            victim = max(self._handoff_ready, key=live_pages)
            if live_pages(victim) > 0:
                with trace_span("fastgen.preempt"):
                    self._engine.offload_sequence(victim)
                get_flight_recorder().record("request.preempt",
                                             uid=victim, handoff=True)
                self._preempted_this_step = True
                return True
        if not self._running:
            return False
        victim = max(self._running, key=live_pages)
        if live_pages(victim) <= 0:
            return False
        with trace_span("fastgen.preempt"):
            self._engine.offload_sequence(victim)
        get_flight_recorder().record("request.preempt", uid=victim)
        self._preempted[victim] = self._running.pop(victim)
        self._preempted_this_step = True
        return True

    def _most_demanding_request(self) -> Optional[Request]:
        """The request whose remaining demand is largest (prefill
        tokens still owed, then block-table size) — the shed victim
        that frees the most capacity for everyone else."""
        cands = (list(self._pending) + list(self._running.values())
                 + list(self._preempted.values()))
        if not cands:
            return None

        def demand(r: Request):
            sd = self._engine.state_manager.get_sequence(r.uid)
            pages = (len([p for p in sd.pages if p != NULL_PAGE])
                     if sd is not None else 0)
            return (r.prefill_remaining, pages)

        return max(cands, key=demand)

    def _degrade_oom(self, exc: Exception,
                     advances: List[Tuple[Request, int]],
                     new_admits: List[Request],
                     held: Sequence[Request] = ()) -> None:
        """KV allocation failed mid-dispatch: degrade instead of
        crashing the step loop.  The failed step's prompt advances are
        rolled back (no token is silently skipped), then the ladder
        escalates along the consecutive-failure streak: (1) reclaim
        every parked prefix-cache page, (2) preempt the largest
        sequence, (3) shed the most demanding request with a
        structured "oom" error."""
        for req, chunk in advances:
            req.prompt_sent -= chunk
        # the step that passed ``held`` over for the ridge never ran
        for req in held:
            req.passed_over -= 1
        for req in reversed(new_admits):
            # an admit whose engine sequence never materialized goes
            # back to the front of the queue (reversed re-insertion at
            # index 0 preserves FIFO admission order)
            if self._engine.state_manager.get_sequence(req.uid) is None \
                    and not req.generated and req.uid in self._running:
                self._running.pop(req.uid)
                self._pending.insert(0, req)
        self._oom_streak += 1
        tm.KV_ALLOC_FAIL.inc()
        tm.MEM_PRESSURE.inc()
        get_flight_recorder().record(
            "kv.alloc_fail", streak=self._oom_streak,
            error=str(exc)[:200])
        state = self._engine.state_manager
        alloc = state.kv_cache.allocator
        # OOM forensics (ISSUE 20): each rung logs the pages it
        # actually freed so a postmortem shows which lever mattered
        rungs: List[Dict[str, int]] = []
        before = alloc.free_pages
        if alloc.parked_pages:
            # rung 1: parked prefix-cache pages are the otherwise-idle
            # pool — evict them all before touching live requests
            state.ensure_free(alloc.free_pages + alloc.parked_pages)
            self._preempted_this_step = True  # pages freed: progress
            rungs.append({"lever": "reclaim_parked",
                          "pages_freed": alloc.free_pages - before})
        if self._oom_streak >= 2:
            before = alloc.free_pages
            self._preempt_largest()
            rungs.append({"lever": "preempt_largest",
                          "pages_freed": alloc.free_pages - before})
        if self._oom_streak >= 4:
            victim = self._most_demanding_request()
            if victim is not None:
                before = alloc.free_pages
                self._fail_request(
                    victim, "oom",
                    "KV pool exhausted after parked-page eviction and "
                    f"preemption ({self._oom_streak} consecutive "
                    "allocation failures)")
                self._preempted_this_step = True
                rungs.append({"lever": "shed_request",
                              "pages_freed": alloc.free_pages - before})
        freed = sum(max(r["pages_freed"], 0) for r in rungs)
        if freed:
            tm.MEM_DEGRADE_FREED_PAGES.inc(freed)
        if _telemetry.enabled:
            # breakdown snapshot into the flight recorder: who owned
            # the bytes when the allocator starved (the dominant
            # subsystem names the lever a capacity fix should pull)
            bd = self._mledger.breakdown()
            get_flight_recorder().record(
                "mem.breakdown", trigger="kv.alloc_oom",
                streak=self._oom_streak, dominant=bd["dominant"],
                accounted_bytes=bd["accounted_bytes"],
                subsystems=bd["subsystems"], rungs=rungs)
        self.last_step_scheduled = 0
        self._step_shape = _IDLE_STEP
        self._step_prompts = (0, 0)

    # -- live engine snapshot / deterministic restore (ISSUE 8) --------------
    def close(self) -> None:
        """Stop admission permanently (one-way): every later
        ``submit()`` terminates immediately with a structured
        ``RequestError(code="closing")``.  Called first on the
        snapshot path — a scheduler being serialized must not accept
        work the bundle won't contain."""
        self._closed = True

    def reopen(self) -> None:
        """Resume admission on a drained-but-alive scheduler (ISSUE 12
        satellite).  ``close()`` is one-way for the snapshot path — the
        bundle must not race new admissions — but an ABORTED scale-down
        (the pool decided to keep this replica after all, or
        ``drain_and_snapshot`` wrote its bundle and the migration was
        cancelled) used to leave the replica permanently returning
        ``RequestError(code="closing")``.  The scheduler's engine state
        is untouched by close/drain, so reopening is just lifting the
        admission latch; any snapshot taken while closed stays valid
        for the state AT snapshot time."""
        self._closed = False
        get_flight_recorder().record("fastgen.reopen",
                                     backlog=self.backlog)

    @staticmethod
    def _serialize_request(req: Request, now: float) -> dict:
        p = req.params
        return {"uid": int(req.uid),
                "prompt": np.asarray(req.prompt).tolist(),
                "prompt_sent": int(req.prompt_sent),
                "generated": [int(t) for t in req.generated],
                "prefix_checked": bool(req.prefix_checked),
                "params": {"temperature": float(p.temperature),
                           "top_k": int(p.top_k),
                           "top_p": float(p.top_p),
                           "max_new_tokens": int(p.max_new_tokens),
                           "stop_token": (None if p.stop_token is None
                                          else int(p.stop_token))},
                # deadlines are monotonic-clock absolute — only the
                # REMAINING budget survives a process boundary
                "ttl_remaining_s": (None if req.deadline is None
                                    else req.deadline - now),
                # speculation facts ride along so the workload ledger's
                # accept-rate mining stays correct across a migration
                # (spec steps drain in-step, so a snapshot never holds
                # undrained speculative state — committed tokens only)
                "spec_drafted": int(req.spec_drafted),
                "spec_accepted": int(req.spec_accepted),
                # adaptive drafter state (ISSUE 17 bugfix): the
                # backoff/EWMA machine must survive a migration — a
                # restored request used to restart as a fresh probe
                # (drafter re-resolved from config, dry spell
                # forgotten), re-paying the whole exploration it
                # already did on the source replica
                "spec_state": {
                    "drafter": req.spec_drafter,
                    "dry": int(req.spec_dry),
                    "cool": int(req.spec_cool),
                    "ewma": {k: float(v) for k, v
                             in (req.spec_ewma or {}).items()},
                    "ngram": [int(req.spec_drafted_ngram),
                              int(req.spec_accepted_ngram)],
                    "model": [int(req.spec_drafted_model),
                              int(req.spec_accepted_model)]},
                # journey (ISSUE 19): the segment log rides every
                # bundle a request can cross — handoff, snapshot,
                # migration — so the importer appends to the context
                # it received, not a fresh one
                "journey": (req.journey.to_dict()
                            if req.journey is not None else None)}

    def _restore_request(self, d: dict, now: float) -> Request:
        pr = d["params"]
        req = Request(
            uid=int(d["uid"]),
            prompt=np.asarray(d["prompt"], dtype=np.int32),
            params=SamplingParams(
                temperature=float(pr["temperature"]),
                top_k=int(pr["top_k"]), top_p=float(pr["top_p"]),
                max_new_tokens=int(pr["max_new_tokens"]),
                stop_token=(None if pr["stop_token"] is None
                            else int(pr["stop_token"]))),
            prompt_sent=int(d["prompt_sent"]),
            generated=[int(t) for t in d["generated"]],
            prefix_checked=bool(d["prefix_checked"]))
        # the latency stamps are process-relative and deliberately not
        # captured: the request's clocks restart here
        req.submit_mono = now
        req.submit_s = time.perf_counter()
        req.spec_drafted = int(d.get("spec_drafted", 0))
        req.spec_accepted = int(d.get("spec_accepted", 0))
        ss = d.get("spec_state")
        if ss:
            # legacy bundles (no spec_state) keep the old behavior:
            # the drafter re-resolves lazily from config
            req.spec_drafter = str(ss.get("drafter", "") or "")
            req.spec_dry = int(ss.get("dry", 0))
            req.spec_cool = int(ss.get("cool", 0))
            ew = ss.get("ewma") or {}
            req.spec_ewma = ({str(k): float(v) for k, v in ew.items()}
                             if ew else None)
            req.spec_drafted_ngram, req.spec_accepted_ngram = (
                int(x) for x in ss.get("ngram", (0, 0)))
            req.spec_drafted_model, req.spec_accepted_model = (
                int(x) for x in ss.get("model", (0, 0)))
        ttl = d.get("ttl_remaining_s")
        if ttl is not None:
            req.deadline = now + float(ttl)
            self._has_deadlines = True
        jd = d.get("journey")
        if jd:
            # legacy bundles (no journey) restore without one — every
            # touch point is None-gated, so the request just stops
            # contributing segments
            req.journey = _journey.Journey.from_dict(jd)
        return req

    def snapshot(self, path: Optional[str] = None,
                 on_token: Optional[Callable[[int, int], None]] = None
                 ) -> dict:
        """Drain to committed state and serialize everything needed to
        resume generation **tokenwise identical** to the uninterrupted
        run: pending/running/preempted requests (prompts, committed
        tokens, sampling params, remaining TTLs), the scheduler RNG key
        data, every referenced KV page's contents (shared prefix pages
        written once, refcounts reconstructed at restore), the
        prefix-cache digest index in LRU order, scheduler counters, and
        the structured error log.  Admission is closed first (later
        submits fail with code="closing").  ``on_token`` receives the
        tokens the final drain commits — a request COMPLETING at that
        drain leaves the scheduler and is not in the bundle, so this
        callback is its only delivery path (zero committed tokens
        lost).  Returns the bundle as ``{"meta", "arrays"}``; with
        ``path`` also writes the atomic, versioned, checksummed
        on-disk bundle (``snapshot.py``)."""
        t0 = time.perf_counter()
        self.close()
        with trace_span("fastgen.snapshot"):
            self._drain(on_token)   # commit the in-flight chained step
            now = time.monotonic()
            eng_meta, arrays = self._engine.state_manager.export_state()
            arrays["rng_key"] = np.asarray(
                jax.random.key_data(self._rng))
            meta = {
                "version": SNAPSHOT_VERSION,
                "requests": {
                    "pending": [self._serialize_request(r, now)
                                for r in self._pending],
                    "running": [self._serialize_request(r, now)
                                for r in self._running.values()],
                    "preempted": [self._serialize_request(r, now)
                                  for r in self._preempted.values()],
                    # prefill role (ISSUE 13): awaiting collection
                    "handoff_ready": [
                        self._serialize_request(r, now)
                        for r in self._handoff_ready.values()],
                },
                "counters": {
                    "step_ordinal": int(self._step_ordinal),
                    "last_step_scheduled": int(self.last_step_scheduled),
                    "oom_streak": int(self._oom_streak),
                },
                "errors": [dataclasses.asdict(e)
                           for e in self.errors.values()],
                "engine": eng_meta,
                # warm-born replicas (ISSUE 14): the compiled-key
                # manifest — exactly the programs traffic formed on
                # this engine — plus the lattice digest it was bucketed
                # under, so restore() precompiles them up front (disk
                # loads against a warm persistent compile cache) and a
                # restored replica serves its first step warm
                "compiled": {
                    "keys": [list(k)
                             for k in self._engine.compiled_keys()],
                    "lattice_digest": self._engine._lattice.digest,
                },
                # model-drafted spec (ISSUE 17): draft KV deliberately
                # does NOT ride the bundle (catch-up refills it — the
                # drafts never change token values, only commit
                # grouping), but the DRAFTER itself must match at
                # restore: per-request EWMA/backoff state restored
                # against a different draft trunk would be
                # systematically wrong signals
                "draft_digest": getattr(self._engine, "draft_digest",
                                        ""),
            }
            if path is not None:
                write_bundle(path, meta, arrays)
        ms = (time.perf_counter() - t0) * 1e3
        # counted even telemetry-off (ServingCounters convention):
        # snapshots are rare and operationally load-bearing
        tm.FASTGEN_SNAPSHOT_MS.observe(ms)
        get_flight_recorder().record(
            "fastgen.snapshot",
            requests=(len(self._pending) + len(self._running)
                      + len(self._preempted)),
            pages=len(eng_meta["page_ids"]), ms=round(ms, 2),
            path=path or "")
        return {"meta": meta, "arrays": arrays}

    def restore(self, bundle) -> "FastGenScheduler":
        """Reconstruct a snapshotted scheduler into THIS freshly-built
        one (fresh engine — same process or a new one — with the same
        model weights and serving config) and resume tokenwise
        identical to the uninterrupted run, with restored full pages
        re-attached to the prefix cache so warm-TTFT survives the
        restart.  ``bundle`` is a path or the dict ``snapshot()``
        returned.  Raises :class:`SnapshotError` on a corrupt/
        truncated/version-mismatched bundle or a non-fresh target —
        never a hang, never silent partial state."""
        t0 = time.perf_counter()
        with trace_span("fastgen.restore"):
            if isinstance(bundle, (str, os.PathLike)):
                meta, arrays = read_bundle(os.fspath(bundle))
            else:
                meta, arrays = bundle["meta"], bundle["arrays"]
                if meta.get("version") != SNAPSHOT_VERSION:
                    raise SnapshotError(
                        f"unsupported snapshot version "
                        f"{meta.get('version')!r}")
            if (self._pending or self._running or self._preempted
                    or self._handoff_ready
                    or self._inflight is not None or self._closed):
                raise SnapshotError(
                    "restore requires a fresh scheduler (this one has "
                    "queued work or is closed)")
            want = meta.get("draft_digest")
            if want is not None:
                # legacy bundles (field absent) restore as before; a
                # PRESENT digest must match — the restored adaptive
                # drafter state is calibrated against that draft trunk
                have = str(getattr(self._engine, "draft_digest", ""))
                if str(want) != have:
                    raise SnapshotError(
                        f"snapshot was taken with draft trunk "
                        f"{str(want)!r} but this engine runs {have!r} "
                        "— restore onto an engine with the same "
                        "spec_drafter/spec_draft_layers configuration")
            self._engine.state_manager.import_state(meta["engine"],
                                                    arrays)
            # warm birth (ISSUE 14): precompile the bundle's
            # compiled-key manifest BEFORE resuming, so the restored
            # traffic's first steps dispatch warm — with a warm
            # persistent compile cache these are disk loads, not
            # compiles.  A lattice-digest mismatch (restoring onto a
            # differently-bucketed engine) only warns: the manifest
            # keys are then the wrong shapes to precompile usefully,
            # but the restore itself is still correct.
            compiled = meta.get("compiled") or {}
            manifest = compiled.get("keys") or []
            if manifest:
                have = self._engine._lattice.digest
                want = str(compiled.get("lattice_digest", "") or "")
                if have != want:
                    from ...utils.logging import logger
                    logger.warning(
                        "restore: bundle compiled-key manifest was "
                        "recorded under lattice digest %r but this "
                        "engine runs %r — skipping the warm-birth "
                        "precompile (traffic will compile on first "
                        "use)", want, have)
                else:
                    self._engine.precompile_keys(manifest)
            import jax.numpy as jnp
            self._rng = jax.random.wrap_key_data(
                jnp.asarray(arrays["rng_key"], jnp.uint32))
            now = time.monotonic()
            reqs = meta["requests"]
            self._pending = [self._restore_request(d, now)
                             for d in reqs["pending"]]
            self._running = {int(d["uid"]): self._restore_request(d, now)
                             for d in reqs["running"]}
            self._preempted = {int(d["uid"]):
                               self._restore_request(d, now)
                               for d in reqs["preempted"]}
            self._handoff_ready = {
                int(d["uid"]): self._restore_request(d, now)
                for d in reqs.get("handoff_ready", [])}
            # journey (ISSUE 19): the wall time between snapshot and
            # restore IS the migration — close it as one "migrate"
            # segment here (not in _restore_request: the handoff-import
            # path uses that helper too and marks its own transfer/
            # import split) so reconstructed chains stay gap-free
            # across the outage
            for req in (self._pending + list(self._running.values())
                        + list(self._preempted.values())
                        + list(self._handoff_ready.values())):
                if req.journey is not None:
                    req.journey.mark("migrate")
            c = meta["counters"]
            self._step_ordinal = int(c["step_ordinal"])
            self.last_step_scheduled = int(c["last_step_scheduled"])
            self._oom_streak = int(c["oom_streak"])
            self.errors = {
                int(e["uid"]): RequestError(
                    uid=int(e["uid"]), code=e["code"],
                    message=e["message"],
                    tokens=[int(t) for t in e["tokens"]])
                for e in meta["errors"]}
        tm.FASTGEN_RESTORE.inc()
        get_flight_recorder().record(
            "fastgen.restore",
            requests=(len(self._pending) + len(self._running)
                      + len(self._preempted)),
            pages=len(meta["engine"]["page_ids"]),
            ms=round((time.perf_counter() - t0) * 1e3, 2))
        if self._kv_debug:
            self._engine.state_manager.check_invariants()
        return self

    def drain_and_snapshot(self, path: str,
                           grace_s: Optional[float] = None,
                           on_token: Optional[Callable[[int, int],
                                                       None]] = None
                           ) -> Optional[str]:
        """The SIGTERM body (spot-VM preemption): stop admission,
        finish/drain the in-flight chained step (tokens delivered via
        ``on_token``), and snapshot to ``path`` within the grace budget
        (``snapshot_grace_s``).  Returns ``path`` when the bundle was
        written; if the budget expired first (or the write failed
        terminally), every live request is converted to a structured
        ``RequestError(code="migrated")`` with its partial tokens kept,
        and None is returned — clients get a verdict either way."""
        grace = (self._snapshot_grace_s if grace_s is None
                 else float(grace_s))
        deadline = time.monotonic() + grace
        self.close()
        from ...utils.logging import logger
        try:
            self._drain(on_token)
        except Exception as e:    # the device may already be wedged
            logger.warning("drain_and_snapshot: drain failed (%s: %s)",
                           type(e).__name__, e)
        if time.monotonic() < deadline:
            try:
                self.snapshot(path, on_token)
                return path
            except Exception as e:
                logger.warning(
                    "drain_and_snapshot: snapshot failed (%s: %s)",
                    type(e).__name__, e)
        else:
            logger.warning(
                "drain_and_snapshot: grace budget %.2fs expired before "
                "a snapshot could be written", grace)
        live = (list(self._pending) + list(self._running.values())
                + list(self._preempted.values())
                + list(self._handoff_ready.values()))
        for req in live:
            self._fail_request(
                req, "migrated",
                f"preemption grace budget ({grace:.2f}s) expired "
                "before a snapshot could be written "
                f"({len(req.generated)} partial tokens kept)")
        return None

    # -- convenience ---------------------------------------------------------
    def run_to_completion(self) -> Dict[int, List[int]]:
        all_reqs = {r.uid: r for r in self._pending}
        all_reqs.update(self._running)
        all_reqs.update(self._preempted)
        stalls = 0
        while self.has_work:
            out = self.step()
            if self.last_step_scheduled == 0 and not out:
                if self._preempted_this_step:
                    continue  # preemption IS progress: pages were freed
                stalls += 1
                if stalls >= 2:
                    if self._shed_unservable:
                        victim = self._most_demanding_request()
                        if victim is not None:
                            self._fail_request(
                                victim, "oom",
                                "unservable: nothing schedulable with "
                                "this request in the pool")
                            stalls = 0
                            continue
                    err = RuntimeError(
                        "scheduler deadlock: work remains but nothing is "
                        "schedulable (KV cache exhausted or a request "
                        "exceeds engine limits); "
                        f"{len(self._pending)} pending, "
                        f"{len(self._running)} running, "
                        f"{self._engine.free_blocks} free KV pages")
                    # a livelocked serving loop leaves forensics like a
                    # crashed one: postmortem bundle BEFORE raising
                    # (once per process, never masks the error)
                    get_flight_recorder().on_crash(
                        "fastgen.run_to_completion", err)
                    raise err
            else:
                stalls = 0
        return {uid: req.generated for uid, req in all_reqs.items()}


def generate(engine: InferenceEngineV2, prompts: Sequence[Sequence[int]],
             params: Optional[SamplingParams] = None,
             token_budget: Optional[int] = None) -> List[List[int]]:
    """Batch generation convenience over the scheduler.  ``params`` may be
    a single SamplingParams for all prompts or one per prompt."""
    sched = FastGenScheduler(engine, token_budget=token_budget)
    per_prompt = (list(params) if isinstance(params, (list, tuple))
                  else [params] * len(prompts))
    if len(per_prompt) != len(prompts):
        raise ValueError(f"{len(per_prompt)} params for {len(prompts)} prompts")
    for i, (p, sp) in enumerate(zip(prompts, per_prompt)):
        sched.submit(i, p, sp)
    results = sched.run_to_completion()
    return [results[i] for i in range(len(prompts))]
