#!/usr/bin/env python
"""Replay a workload trace against a live FastGenScheduler (ISSUE 9).

Loads a JSONL ledger captured by ``telemetry/workload_trace.py``,
synthesizes **anonymized** token-id prompts that reproduce each
request's recorded length and prefix-sharing structure (a prompt page's
tokens are derived deterministically from its recorded chained digest,
so two requests share a synthesized page exactly when they shared a
page at capture time — the content is new, the structure is identical),
re-issues the requests with original or time-scaled arrival pacing, and
diffs the resulting SLO percentiles and recompile counters against the
recorded run.

This is the harness behind ROADMAP item 5's success metric
(``ds_fastgen_compile_on_path_total == 0`` over a replayed production
trace): capture production traffic, replay it against a candidate
config/lattice, and read the counters.

Usage::

    python tools/replay_trace.py --trace trace.jsonl [--speed 2.0]
        [--limit N] [--tolerance 4] [--check] [--json out.json]

``--speed 0`` (default) replays as fast as the scheduler drains (no
arrival pacing); ``--speed 1`` paces at recorded arrival offsets,
``--speed 2`` twice as fast, etc.  ``--check`` exits non-zero when
structural parity (request count / lengths / share structure / arrival
order) fails — the CI smoke mode.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


# -- simulated-mesh prelude (ISSUE 18) ---------------------------------------
def _tp_from_argv(argv) -> int:
    """Peek ``--tp N`` out of raw argv.  The host platform's device
    count is an env knob jax reads at import, so it must be set before
    argparse runs (argparse imports nothing, but the first lazy
    ``import jax`` below it wins the race otherwise)."""
    for i, a in enumerate(argv):
        if a == "--tp" and i + 1 < len(argv):
            try:
                return int(argv[i + 1])
            except ValueError:
                return 1
        if a.startswith("--tp="):
            try:
                return int(a.split("=", 1)[1])
            except ValueError:
                return 1
    return 1


if __name__ == "__main__":
    _tp_pre = _tp_from_argv(sys.argv[1:])
    if _tp_pre > 1 and "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={_tp_pre}")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")


def percentile(vals, q: float):
    """Nearest-rank percentile over values (None entries dropped);
    None when empty.  The one implementation the replay report, the
    recorded-side diff, and tools/analyze_trace.py all share — a
    rounding change can't silently skew the recorded-vs-replayed
    ratio from one side only."""
    vals = sorted(v for v in vals if v is not None)
    if not vals:
        return None
    k = min(len(vals) - 1, int(round(q / 100.0 * (len(vals) - 1))))
    return round(float(vals[k]), 3)


# -- trace loading -----------------------------------------------------------
def load_trace(path: str) -> Dict[str, Any]:
    """Parse a workload-trace JSONL ledger into
    ``{"meta", "requests", "compiles", "key_counts"}``.  Records of the
    rotated generation (``<path>.1``) are NOT read — the caller decides
    whether to concatenate generations.  The parser itself is the ONE
    in-package implementation (``inference.v2.lattice.load_trace_facts``
    — engine build mines raw ledgers through it too); replay
    additionally requires request records."""
    from deepspeed_tpu.inference.v2.lattice import load_trace_facts
    trace = load_trace_facts(path)
    if not trace["requests"]:
        raise ValueError(f"{path}: no request records")
    return trace


# -- anonymized prompt synthesis ---------------------------------------------
def synthesize_prompts(requests: List[Dict[str, Any]], page_size: int,
                       vocab_size: int, seed: int = 0
                       ) -> List[np.ndarray]:
    """One int32 prompt per request (by record order), reproducing the
    recorded lengths and the prefix-sharing structure: a full page's
    tokens are a pure function of its recorded cumulative digest (equal
    digests — i.e. equal cumulative prefixes at capture — yield equal
    synthesized pages; distinct digests yield distinct pages w.h.p.),
    and the trailing partial page is unique per request (partial pages
    are never shared by the prefix cache's copy-on-write rule, so
    uniqueness there cannot change the structure)."""
    blocks: Dict[str, np.ndarray] = {}
    prompts: List[np.ndarray] = []
    for idx, rec in enumerate(requests):
        parts: List[np.ndarray] = []
        for digest in rec["digests"]:
            blk = blocks.get(digest)
            if blk is None:
                rng = np.random.default_rng(
                    (int(digest[:15], 16) << 17) ^ (seed & 0x1FFFF))
                blk = rng.integers(0, vocab_size, page_size,
                                   dtype=np.int64).astype(np.int32)
                blocks[digest] = blk
            parts.append(blk)
        rem = int(rec["prompt_len"]) - len(parts) * page_size
        if rem > 0:
            rng = np.random.default_rng(
                (seed << 24) ^ (idx * 2654435761 & 0x7FFFFFFF) ^ 0x5A5A)
            parts.append(rng.integers(0, vocab_size, rem,
                                      dtype=np.int64).astype(np.int32))
        prompts.append(np.concatenate(parts) if parts
                       else np.zeros(0, np.int32))
    return prompts


def share_signature_recorded(requests: List[Dict[str, Any]]
                             ) -> List[tuple]:
    """Canonical sharing structure of the RECORDED prompts: digests
    renamed to first-occurrence ordinals, one tuple per request."""
    ids: Dict[str, int] = {}
    return [tuple(ids.setdefault(d, len(ids)) for d in r["digests"])
            for r in requests]


def share_signature_prompts(prompts: List[np.ndarray], page_size: int
                            ) -> List[tuple]:
    """The same canonical structure recomputed from actual token-id
    prompts via the prefix cache's own chained hash."""
    from deepspeed_tpu.inference.v2.ragged.prefix_cache import PrefixCache
    ids: Dict[bytes, int] = {}
    sigs = []
    for p in prompts:
        d = b""
        sig = []
        for i in range(len(p) // page_size):
            d = PrefixCache.chain(d, p[i * page_size:(i + 1) * page_size])
            sig.append(ids.setdefault(d, len(ids)))
        sigs.append(tuple(sig))
    return sigs


# -- engine construction -----------------------------------------------------
def _replay_model_parts(meta: Dict[str, Any],
                        requests: List[Dict[str, Any]],
                        model_size: str = "debug"):
    """(cfg, params, page, need): the model geometry every replay
    engine shares — factored out so the disagg mode can build TWO
    engines over ONE weight tree (tokenwise-identical continuations
    need identical weights across the pools)."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta as flax_meta
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    page = int(meta.get("page_size", 16))
    need = max(int(r["prompt_len"]) + max(1, int(r["gen_len"]))
               for r in requests) + page
    max_seq = 1
    while max_seq < need:
        max_seq *= 2
    model_def = LlamaForCausalLM(model_size, max_seq_len=max(max_seq, 64),
                                 dtype=jnp.float32)
    params = flax_meta.unbox(model_def.init_params(jax.random.key(0)))
    return model_def.cfg, params, page, need


def _build_engine(cfg, params, page: int, need: int, num_pages: int,
                  max_seqs: int, serving=None):
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import (
        InferenceEngineV2, KVCacheConfig, RaggedInferenceEngineConfig,
        RaggedInferenceModel, StateManagerConfig)
    if not num_pages:
        # pool sized for max_seqs concurrent worst-case sequences
        per_seq = -(-need // page)
        num_pages = max(256, max_seqs * per_seq)
    kv_cfg = KVCacheConfig(num_layers=cfg.num_layers,
                           kv_heads=cfg.kv_heads,
                           head_dim=cfg.dims_per_head, page_size=page,
                           num_pages=num_pages, dtype=jnp.float32)
    model = RaggedInferenceModel(cfg, params, kv_config=kv_cfg)
    econf = RaggedInferenceEngineConfig(
        state_manager=StateManagerConfig(
            max_tracked_sequences=max_seqs,
            max_ragged_sequence_count=max_seqs,
            max_ragged_batch_size=max(256, 4 * page)))
    if serving is not None:
        econf.serving = serving
    return InferenceEngineV2(model, econf)


def build_replay_engine(meta: Dict[str, Any],
                        requests: List[Dict[str, Any]],
                        model_size: str = "debug",
                        num_pages: int = 0,
                        max_seqs: int = 32,
                        serving=None):
    """A small engine whose geometry (page size, context, KV pool) fits
    the trace.  The replay measures SCHEDULING/shape behavior — lattice
    coverage, share structure, relative SLOs — so the weights are
    random-init and the model family is the debug config unless a
    larger one is requested."""
    cfg, params, page, need = _replay_model_parts(meta, requests,
                                                  model_size)
    return _build_engine(cfg, params, page, need, num_pages, max_seqs,
                         serving=serving)


def build_disagg_engines(meta: Dict[str, Any],
                         requests: List[Dict[str, Any]],
                         model_size: str = "debug",
                         max_seqs: int = 32,
                         keyed: bool = True):
    """(prefill_engine, decode_engine) for the two-pool replay
    (ISSUE 13): one weight tree, two engines, each with its serving
    role; ``keyed`` turns on schedule-invariant sampling on both so
    sampled requests replay tokenwise identical to the fused engine.
    The decode engine runs a 2x WIDER slot geometry than the prefill
    engine — per-row decode cost is tiny, so the decode pool batches
    far more concurrent sequences per program than a fused engine
    whose one geometry must also fit prompt chunks (exactly the
    per-pool batch-shape freedom disaggregation exists to buy)."""
    from deepspeed_tpu.inference.v2 import ServingOptimizationConfig
    cfg, params, page, need = _replay_model_parts(meta, requests,
                                                  model_size)
    pre = _build_engine(
        cfg, params, page, need, 0, max_seqs,
        serving=ServingOptimizationConfig(role="prefill",
                                          keyed_sampling=keyed))
    dec = _build_engine(
        cfg, params, page, need, 0, 2 * max_seqs,
        serving=ServingOptimizationConfig(role="decode",
                                          keyed_sampling=keyed))
    return pre, dec


# -- the replay loop ---------------------------------------------------------
def replay(engine, requests: List[Dict[str, Any]],
           prompts: List[np.ndarray], speed: float = 0.0,
           token_budget: Optional[int] = None,
           serving=None, on_token=None,
           capture: bool = False) -> Dict[str, Any]:
    """Re-issue the trace against a fresh FastGenScheduler on
    ``engine``.  ``speed=0`` submits everything up front (as fast as
    the scheduler drains); ``speed>0`` paces submissions at the
    recorded arrival offsets divided by ``speed``.  Request ``i``
    replays with ``max_new_tokens = gen_len_i`` (and no stop token), so
    generated lengths reproduce exactly regardless of sampled values.
    Returns the replayed facts: per-request gen lengths, TTFT/queue
    percentiles, decode tok/s, and the measured-window recompile
    counters.  ``capture=True`` leaves the workload ledger LIVE for
    the drive — the caller has configured a private ledger and wants
    the replay's own request records (the tier bench mines the
    per-request ``hit_device/host/disk/remote`` attribution exactly
    the way tools/analyze_trace.py would)."""
    from deepspeed_tpu.inference.v2 import FastGenScheduler, SamplingParams
    from deepspeed_tpu.telemetry import metrics as tm
    from deepspeed_tpu.telemetry.workload_trace import get_workload_trace

    if capture:
        return _replay_impl(FastGenScheduler, SamplingParams, tm,
                            engine, requests, prompts, speed,
                            token_budget, serving, on_token)
    # a live ledger (DS_WORKLOAD_TRACE still exported on the capture
    # machine) must not record the replay's own synthetic traffic into
    # the trace being studied — capture is suspended for the drive
    with get_workload_trace().suspended():
        return _replay_impl(FastGenScheduler, SamplingParams, tm,
                            engine, requests, prompts, speed,
                            token_budget, serving, on_token)


def _replay_impl(FastGenScheduler, SamplingParams, tm, engine, requests,
                 prompts, speed, token_budget, serving,
                 user_on_token=None) -> Dict[str, Any]:
    order = sorted(range(len(requests)),
                   key=lambda i: float(requests[i].get("arrival_s", 0.0)))
    params = [SamplingParams(
        temperature=float(r.get("temperature", 0.0)),
        top_k=int(r.get("top_k", 0)), top_p=float(r.get("top_p", 1.0)),
        max_new_tokens=max(1, int(r["gen_len"]))) for r in requests]

    sched = FastGenScheduler(engine, token_budget=token_budget,
                             serving=serving)
    miss0 = tm.FASTGEN_STEP_CACHE_MISS.value
    comp0 = tm.FASTGEN_COMPILE_ON_PATH.value

    submit_t: Dict[int, float] = {}
    first_t: Dict[int, float] = {}
    gen: Dict[int, int] = {}
    submitted: List[int] = []
    token_count = [0]
    busy_s = 0.0
    nxt = 0
    stalls = 0

    def on_token(uid: int, tok: int) -> None:
        # per-token accounting MUST ride the callback: a speculative
        # step commits a whole accepted block per row per step, so the
        # step() return dict (one entry per uid) undercounts
        token_count[0] += 1
        gen[uid] = gen.get(uid, 0) + 1
        first_t.setdefault(uid, time.perf_counter())
        if user_on_token is not None:
            user_on_token(uid, tok)

    t0 = time.perf_counter()
    while nxt < len(order) or sched.has_work:
        now = time.perf_counter()
        elapsed = (now - t0) * (speed if speed > 0 else 1.0)
        while nxt < len(order) and (
                speed <= 0
                or float(requests[order[nxt]].get("arrival_s", 0.0))
                <= elapsed):
            i = order[nxt]
            verdict = sched.submit(i, prompts[i], params[i])
            if verdict is None:
                submit_t[i] = time.perf_counter()
                submitted.append(i)
            nxt += 1
        if sched.has_work:
            t_step = time.perf_counter()
            out = sched.step(on_token=on_token)
            busy_s += time.perf_counter() - t_step
            stalls = (stalls + 1 if sched.last_step_scheduled == 0
                      and not out else 0)
            if stalls > 64:
                raise RuntimeError(
                    "replay stalled: requests unschedulable (trace "
                    "needs a larger KV pool / context than the replay "
                    "engine has)")
        elif nxt < len(order):
            if speed > 0:
                gap = (float(requests[order[nxt]].get("arrival_s", 0.0))
                       - elapsed) / speed
                time.sleep(min(max(gap, 0.0), 0.01))
    total = time.perf_counter() - t0

    ttfts = [(first_t[i] - submit_t[i]) * 1e3
             for i in submitted if i in first_t]
    return {
        "requests_submitted": len(submitted),
        "submit_order": submitted,
        "gen_lens": {i: gen.get(i, 0) for i in submitted},
        "errors": {int(u): e.code for u, e in sched.errors.items()},
        "wall_s": round(total, 4),
        "busy_s": round(busy_s, 4),
        "decode_tok_s": (round(token_count[0] / total, 1) if total
                         else None),
        "ttft_p50_ms": percentile(ttfts, 50),
        "ttft_p99_ms": percentile(ttfts, 99),
        "step_cache_miss": tm.FASTGEN_STEP_CACHE_MISS.value - miss0,
        "compile_on_path": tm.FASTGEN_COMPILE_ON_PATH.value - comp0,
        "spec_drafted": sched._spec_drafted_cum,
        "spec_accepted": sched._spec_accepted_cum,
        "spec_draft_drafted": sched._spec_draft_drafted_cum,
        "spec_draft_accepted": sched._spec_draft_accepted_cum,
    }


# -- the two-pool (disaggregated) replay loop --------------------------------
def replay_disagg(prefill_engine, decode_engine,
                  requests: List[Dict[str, Any]],
                  prompts: List[np.ndarray],
                  speed: float = 0.0,
                  threaded: bool = False,
                  on_token=None,
                  journeys: bool = False) -> Dict[str, Any]:
    """Re-issue the trace through a fresh :class:`DisaggPool` over the
    two prebuilt engines (ISSUE 13).  Same submission/pacing contract
    and report shape as :func:`replay`, so ``diff_replay`` diffs both
    modes; extra keys carry the handoff facts (count/bytes/latency,
    streamed-vs-shared pages), the per-pool cost facts (prefill-pool
    MFU captured the moment the prefill pool drains — its busy window,
    not the whole run — and decode-pool HBM GB/s over the run), and
    ``lost`` (requests neither completed nor structurally errored; the
    CI smoke asserts 0).  ``threaded`` drives the pool through its
    ``start()`` stepper threads so the two pools genuinely overlap
    (the bench mode; keyed sampling keeps token values deterministic
    regardless of thread interleaving).  ``journeys`` (ISSUE 19)
    enables telemetry for the measured run and verifies request
    journeys end-to-end: every completed request must reconstruct a
    gap-free segment chain that sums to its measured e2e latency, with
    zero orphaned handoff fragments — findings land in the report's
    ``journeys`` block (and in ``--check`` problems)."""
    from deepspeed_tpu.inference.v2 import (FastGenScheduler,
                                            SamplingParams)
    from deepspeed_tpu.serving import DisaggPool
    from deepspeed_tpu.telemetry import metrics as tm
    from deepspeed_tpu.telemetry.workload_trace import get_workload_trace

    order = sorted(range(len(requests)),
                   key=lambda i: float(requests[i].get("arrival_s", 0.0)))
    params = [SamplingParams(
        temperature=float(r.get("temperature", 0.0)),
        top_k=int(r.get("top_k", 0)), top_p=float(r.get("top_p", 1.0)),
        max_new_tokens=max(1, int(r["gen_len"]))) for r in requests]

    submit_t: Dict[int, float] = {}
    first_t: Dict[int, float] = {}
    gen: Dict[int, int] = {}
    submitted: List[int] = []
    token_count = [0]

    def _tap(uid: int, tok: int) -> None:
        token_count[0] += 1
        gen[uid] = gen.get(uid, 0) + 1
        first_t.setdefault(uid, time.perf_counter())
        if on_token is not None:
            on_token(uid, tok)

    pool = DisaggPool(
        lambda: FastGenScheduler(prefill_engine),
        lambda: FastGenScheduler(decode_engine),
        on_token=_tap)

    miss0 = tm.FASTGEN_STEP_CACHE_MISS.value
    comp0 = tm.FASTGEN_COMPILE_ON_PATH.value
    hand0 = tm.DISAGG_HANDOFFS.value
    bytes0 = tm.DISAGG_HANDOFF_BYTES.value
    stream0 = tm.DISAGG_PAGES_STREAMED.value
    share0 = tm.DISAGG_PAGES_SHARED.value
    handoff_ms: List[float] = []
    pool._on_handoff_ms = handoff_ms.append

    jlog = prev_enabled = None
    if journeys:
        # journeys gate on the telemetry switch (mint() is the
        # disabled-path read); enable for the measured window only and
        # start from an empty log so the verdicts below see exactly
        # this run
        import deepspeed_tpu.telemetry as dstel
        from deepspeed_tpu.telemetry import journey as dsjourney
        jlog = dsjourney.get_journey_log()
        jlog.clear()
        prev_enabled = dstel.enabled()
        dstel.enable()

    nxt = 0
    stalls = 0
    with get_workload_trace().suspended():
        t0 = time.perf_counter()
        if threaded:
            pool.start()
        try:
            while nxt < len(order) or not pool.idle:
                now = time.perf_counter()
                elapsed = (now - t0) * (speed if speed > 0 else 1.0)
                while nxt < len(order) and (
                        speed <= 0
                        or float(requests[order[nxt]]
                                 .get("arrival_s", 0.0)) <= elapsed):
                    i = order[nxt]
                    verdict = pool.submit(i, prompts[i], params[i])
                    if verdict is None:
                        submit_t[i] = time.perf_counter()
                        submitted.append(i)
                    nxt += 1
                if threaded:
                    if pool.idle and nxt >= len(order):
                        break
                    time.sleep(0.002)
                    continue
                if not pool.idle:
                    before = token_count[0]
                    pool.step()
                    stalls = (stalls + 1 if token_count[0] == before
                              else 0)
                    if stalls > 512:
                        raise RuntimeError(
                            "disagg replay stalled: requests "
                            "unschedulable (trace needs a larger KV "
                            "pool than the replay engines have)")
                elif nxt < len(order) and speed > 0:
                    gap = (float(requests[order[nxt]]
                                 .get("arrival_s", 0.0)) - elapsed) / speed
                    time.sleep(min(max(gap, 0.0), 0.01))
            total = time.perf_counter() - t0
        finally:
            if threaded:
                pool.stop()
            if journeys:
                import deepspeed_tpu.telemetry as dstel
                dstel.set_enabled(bool(prev_enabled))
    # per-pool cost over each pool's BUSY window (seconds inside its
    # own scheduler steps): the specialization claim is about what a
    # role-shrunk program mix does with the hardware while it runs,
    # independent of how the two pools share a host/thread schedule.
    # ONE implementation (the pool's gauge refresh) feeds both the
    # ds_disagg_* gauges and this report
    cost = pool.refresh_cost_gauges()

    ttfts = [(first_t[i] - submit_t[i]) * 1e3
             for i in submitted if i in first_t]
    lost = [i for i in submitted
            if not pool.request(i).finalized]

    journeys_report = None
    if journeys:
        from deepspeed_tpu.telemetry import journey as dsjourney
        completed = {r["uid"]: r for r in jlog.completed()}
        jproblems: List[str] = []
        for i in submitted:
            preq = pool.request(i)
            if preq is None or not preq.done:
                continue
            rec = completed.get(i)
            if rec is None:
                jproblems.append(f"uid {i}: completed request has no "
                                 "flushed journey")
                continue
            for g in dsjourney.chain_gaps(rec, eps_ms=5.0):
                jproblems.append(f"uid {i}: {g}")
            e2e_ms = (preq.finished_mono - preq.submit_mono) * 1e3
            seg_ms = sum(s["ms"] for s in rec["segments"])
            # ε: the drain mark fires on the scheduler's finish sweep,
            # up to one step after the pool ledger saw the last token
            if abs(seg_ms - e2e_ms) > max(75.0, 0.10 * e2e_ms):
                jproblems.append(
                    f"uid {i}: journey segments sum "
                    f"{round(seg_ms, 1)}ms vs measured e2e "
                    f"{round(e2e_ms, 1)}ms")
        orphans = jlog.orphans()
        if orphans:
            jproblems.append(f"{len(orphans)} orphaned journey "
                             f"fragment(s): {orphans[:4]}")
        journeys_report = {
            "completed_journeys": len(completed),
            "fragments": len(jlog.fragments()),
            "orphans": len(orphans),
            "problems": jproblems,
        }

    return {
        "requests_submitted": len(submitted),
        "submit_order": submitted,
        "gen_lens": {i: gen.get(i, 0) for i in submitted},
        "errors": {int(u): e.code for u, e in pool.errors.items()},
        "lost": len(lost),
        "wall_s": round(total, 4),
        "decode_tok_s": (round(token_count[0] / total, 1) if total
                         else None),
        "ttft_p50_ms": percentile(ttfts, 50),
        "ttft_p99_ms": percentile(ttfts, 99),
        "step_cache_miss": tm.FASTGEN_STEP_CACHE_MISS.value - miss0,
        "compile_on_path": tm.FASTGEN_COMPILE_ON_PATH.value - comp0,
        "spec_drafted": 0,
        "spec_accepted": 0,
        "spec_draft_drafted": 0,
        "spec_draft_accepted": 0,
        "handoffs": tm.DISAGG_HANDOFFS.value - hand0,
        "handoff_bytes": tm.DISAGG_HANDOFF_BYTES.value - bytes0,
        "handoff_p50_ms": percentile(handoff_ms, 50),
        "pages_streamed": tm.DISAGG_PAGES_STREAMED.value - stream0,
        "pages_shared": tm.DISAGG_PAGES_SHARED.value - share0,
        "prefill_mfu": float(cost["prefill_mfu"]),
        "prefill_busy_s": round(pool.prefill_busy_s, 4),
        "decode_hbm_gb_s": float(cost["decode_hbm_gb_s"]),
        "decode_busy_s": round(pool.decode_busy_s, 4),
        "programs_prefill": len(
            prefill_engine.compiled_keys(dispatched_only=False)),
        "programs_decode": len(
            decode_engine.compiled_keys(dispatched_only=False)),
        "journeys": journeys_report,
    }


def run_replay_disagg(trace_path: str, limit: int = 0,
                      include_errors: bool = False, speed: float = 0.0,
                      model_size: str = "debug", seed: int = 0,
                      warmup: bool = True, tolerance: float = 4.0,
                      keyed: bool = True,
                      journeys: bool = False) -> Dict[str, Any]:
    """load → synthesize → (shape-warmup) → measured two-pool replay →
    structural diff: the disagg counterpart of :func:`run_replay`,
    behind the CI disagg smoke."""
    trace = load_trace(trace_path)
    requests = trace["requests"]
    if not include_errors:
        requests = [r for r in requests if r.get("outcome") == "ok"]
    if limit:
        requests = requests[:limit]
    if not requests:
        raise ValueError(f"{trace_path}: no replayable requests")
    meta = trace["meta"]
    page = int(meta.get("page_size", 16))
    pre_eng, dec_eng = build_disagg_engines(meta, requests,
                                            model_size=model_size,
                                            keyed=keyed)
    vocab = min(int(meta.get("vocab_size", 0))
                or pre_eng.model.cfg.vocab_size,
                pre_eng.model.cfg.vocab_size)
    prompts = synthesize_prompts(requests, page, vocab, seed=seed)
    if warmup:
        replay_disagg(pre_eng, dec_eng, requests, prompts, speed=0.0)
        _reset_engine(pre_eng)
        _reset_engine(dec_eng)
    report = replay_disagg(pre_eng, dec_eng, requests, prompts,
                           speed=speed, journeys=journeys)
    verdict = diff_replay(requests, prompts, page, report,
                          tolerance=tolerance)
    return {"trace": trace_path, "meta": meta,
            "requests": len(requests),
            "replay": report, "diff": verdict}


def build_tier_engine(meta: Dict[str, Any],
                      requests: List[Dict[str, Any]],
                      device_pages: int = 4,
                      host_pages: int = 8,
                      disk_pages: int = 256,
                      tier_dir: str = "",
                      model_size: str = "debug",
                      max_seqs: int = 2,
                      quant: str = "none"):
    """A deliberately device-starved replay engine backed by the
    host/disk prefix tier: the device pool is clamped to the smallest
    SCHEDULABLE size >= ``device_pages`` (one worst-case sequence plus
    a landing page — a 7-page request cannot run inside a literal
    4-page pool), so parked prefix pages are evicted -> DEMOTED almost
    immediately and a returning prefix must come back through tier
    promotion, not a device hit.  Keyed sampling makes replayed token
    values schedule-invariant, so callers can assert warm-from-tier ==
    cold tokenwise even on the trace's sampled requests."""
    from deepspeed_tpu.inference.v2 import ServingOptimizationConfig
    cfg, params, page, need = _replay_model_parts(meta, requests,
                                                  model_size)
    per_seq = -(-need // page)
    # every ADMITTED sequence pins its matched/promoted prefix pages,
    # so the schedulable floor is the worst-case active set, not one
    # sequence: below it, warm admissions livelock holding each
    # other's landing pages
    num_pages = max(int(device_pages), max_seqs * (per_seq + 1))
    serving = ServingOptimizationConfig(
        keyed_sampling=True, kv_quantization=quant,
        kv_tier_host_pages=host_pages, kv_tier_disk_pages=disk_pages,
        kv_tier_dir=tier_dir)
    return _build_engine(cfg, params, page, need, num_pages, max_seqs,
                         serving=serving)


def run_tier_smoke(trace_path: str, limit: int = 0,
                   include_errors: bool = False,
                   device_pages: int = 4, host_pages: int = 8,
                   disk_pages: int = 256,
                   model_size: str = "debug", seed: int = 0,
                   tolerance: float = 4.0) -> Dict[str, Any]:
    """The CI tier smoke (ISSUE 16): two replays of the same trace on
    ONE device-starved tiered engine.  Wave 1 prefills cold and every
    parked prefix page demotes (device -> host ring -> disk via AIO);
    wave 2 resubmits the same requests, so every returning prefix must
    be served back through promotion.  ``diff`` carries the usual
    structural-parity verdict plus the tier invariants ``--check``
    enforces: demotions and disk spills actually happened, wave 2
    promoted pages back, wave-2 tokens are exactly wave-1's (keyed
    sampling: warm-from-tier == cold), and the store's accounting
    (host + disk + inflight == indexed) holds."""
    import shutil
    import tempfile

    trace = load_trace(trace_path)
    requests = trace["requests"]
    if not include_errors:
        requests = [r for r in requests if r.get("outcome") == "ok"]
    if limit:
        requests = requests[:limit]
    if not requests:
        raise ValueError(f"{trace_path}: no replayable requests")
    meta = trace["meta"]
    page = int(meta.get("page_size", 16))
    tier_dir = tempfile.mkdtemp(prefix="ds_tier_smoke_")
    engine = None
    try:
        engine = build_tier_engine(
            meta, requests, device_pages=device_pages,
            host_pages=host_pages, disk_pages=disk_pages,
            tier_dir=tier_dir, model_size=model_size)
        vocab = min(int(meta.get("vocab_size", 0))
                    or engine.model.cfg.vocab_size,
                    engine.model.cfg.vocab_size)
        prompts = synthesize_prompts(requests, page, vocab, seed=seed)
        tok1: Dict[int, List[int]] = {}
        tok2: Dict[int, List[int]] = {}
        rep1 = replay(engine, requests, prompts,
                      on_token=lambda u, t: tok1.setdefault(
                          u, []).append(t))
        tiers = engine.state_manager.tiers
        stats1 = tiers.stats()
        rep2 = replay(engine, requests, prompts,
                      on_token=lambda u, t: tok2.setdefault(
                          u, []).append(t))
        stats2 = tiers.stats()
        verdict = diff_replay(requests, prompts, page, rep2,
                              tolerance=tolerance)
        problems = list(verdict["problems"])
        if stats1["demoted_pages"] <= 0:
            problems.append(
                "[tier] wave 1 demoted no pages — the device-starved "
                "pool should have evicted every parked prefix page "
                "into the host tier")
        if disk_pages > 0 and stats2["spilled_pages"] <= 0:
            problems.append(
                "[tier] nothing spilled host -> disk although a disk "
                "tier was configured and the host ring is tiny")
        if stats2["promoted_pages"] <= stats1["promoted_pages"]:
            problems.append(
                "[tier] wave 2 promoted no pages — returning prefixes "
                "recomputed instead of warming from the tier")
        if tok2 != tok1:
            diff_uids = sorted(u for u in tok1
                               if tok1.get(u) != tok2.get(u))
            problems.append(
                f"[tier] warm-from-tier tokens differ from cold for "
                f"request(s) {diff_uids[:8]} — promotion corrupted "
                "page contents")
        try:
            tiers.check_invariants()
        except RuntimeError as e:
            problems.append(f"[tier] store accounting broken: {e}")
        verdict = dict(verdict, problems=problems,
                       structural_ok=not problems)
        return {"trace": trace_path, "meta": meta,
                "requests": len(requests),
                "device_pages": engine.model.kv_config.num_pages,
                "wave1": rep1, "replay": rep2,
                "tier": stats2, "diff": verdict}
    finally:
        if engine is not None:
            engine.state_manager.close()
        shutil.rmtree(tier_dir, ignore_errors=True)


def recorded_percentiles(requests: List[Dict[str, Any]]
                         ) -> Dict[str, Optional[float]]:
    ttfts = [r.get("ttft_ms") for r in requests]
    waits = [r.get("queue_wait_ms") for r in requests]
    return {"ttft_p50_ms": percentile(ttfts, 50),
            "ttft_p99_ms": percentile(ttfts, 99),
            "queue_wait_p50_ms": percentile(waits, 50)}


def diff_replay(requests: List[Dict[str, Any]],
                prompts: List[np.ndarray], page_size: int,
                report: Dict[str, Any],
                tolerance: float = 4.0) -> Dict[str, Any]:
    """Structural-parity + SLO diff of one replay against its trace.
    Structure must match EXACTLY (count, prompt/gen lengths, share
    structure, arrival order); latency percentiles must agree within a
    multiplicative ``tolerance`` (host/noise dependent — a replay on
    the capture machine lands near 1x)."""
    problems: List[str] = []
    n = len(requests)
    if report["requests_submitted"] != n:
        problems.append(
            f"request count: {report['requests_submitted']} replayed "
            f"vs {n} recorded")
    for i, rec in enumerate(requests):
        if len(prompts[i]) != int(rec["prompt_len"]):
            problems.append(
                f"req {i}: prompt_len {len(prompts[i])} vs recorded "
                f"{rec['prompt_len']}")
        want = max(1, int(rec["gen_len"]))
        got = report["gen_lens"].get(i)
        if got != want:
            problems.append(
                f"req {i}: gen_len {got} vs recorded {want}")
    if (share_signature_prompts(prompts, page_size)
            != share_signature_recorded(requests)):
        problems.append("share structure: synthesized prompts do not "
                        "reproduce the recorded digest classes")
    arrival_order = sorted(
        range(n), key=lambda i: float(requests[i].get("arrival_s", 0.0)))
    if report["submit_order"] != arrival_order:
        problems.append("arrival order: replay submitted out of "
                        "recorded order")

    rec_pct = recorded_percentiles(requests)
    slo = {}
    for key in ("ttft_p50_ms", "ttft_p99_ms"):
        a, b = rec_pct.get(key), report.get(key)
        ratio = (round(b / a, 3) if a and b else None)
        slo[key] = {"recorded": a, "replayed": b, "ratio": ratio}
    within = all(
        v["ratio"] is None or 1.0 / tolerance <= v["ratio"] <= tolerance
        for v in slo.values())
    return {"structural_ok": not problems, "problems": problems,
            "slo": slo, "slo_within_tolerance": within,
            "tolerance": tolerance,
            "compile_on_path": report["compile_on_path"],
            "recorded_queue_wait_p50_ms": rec_pct["queue_wait_p50_ms"]}


def _reset_engine(engine) -> None:
    """Flush every tracked sequence and drop the prefix cache so the
    next replay pass starts from cold engine state."""
    for uid in list(engine.state_manager._seqs):
        engine.flush(uid)
    engine.reset_prefix_cache()


def run_replay(trace_path: str, limit: int = 0,
               include_errors: bool = False, speed: float = 0.0,
               model_size: str = "debug", seed: int = 0,
               warmup: bool = True,
               tolerance: float = 4.0,
               spec: bool = False,
               drafter: str = "ngram",
               tp: int = 1) -> Dict[str, Any]:
    """The one load → filter → build → synthesize → (shape-warmup) →
    measured-replay → diff sequence, shared by the CLI and the CI smoke
    — so the two can't drift on the warmup convention or the vocab clamp.  With ``spec`` the same
    workload is replayed a second time with speculative decoding
    enabled and the report gains a ``spec`` block: accept rate, tok/s
    on/off, and the spec pass's own structural-parity diff (ISSUE 10 —
    speculation must change throughput and metrics, nothing else).
    ``drafter`` selects the spec pass's draft source (ISSUE 17):
    ``ngram`` replays on the same engine; ``model``/``auto`` rebuild
    the spec engine WITH the draft head (draft params and the parallel
    draft-KV array are engine-level state), and the spec block gains a
    per-drafter accept-rate split.  ``tp`` shards the replay engine
    over a ``tp``-way simulated mesh (ISSUE 18) — the replay must stay
    tokenwise/structurally identical to the unsharded run, so the same
    ``--check`` verdict applies; the CLI prelude sets
    ``--xla_force_host_platform_device_count`` before jax loads."""
    trace = load_trace(trace_path)
    requests = trace["requests"]
    if not include_errors:
        requests = [r for r in requests if r.get("outcome") == "ok"]
    if limit:
        requests = requests[:limit]
    if not requests:
        raise ValueError(f"{trace_path}: no replayable requests")
    meta = trace["meta"]
    page = int(meta.get("page_size", 16))
    base_serving = None
    if tp > 1:
        from deepspeed_tpu.inference.v2 import ServingOptimizationConfig
        base_serving = ServingOptimizationConfig(tp_degree=tp)
    engine = build_replay_engine(meta, requests, model_size=model_size,
                                 serving=base_serving)
    vocab = min(int(meta.get("vocab_size", 0))
                or engine.model.cfg.vocab_size,
                engine.model.cfg.vocab_size)
    prompts = synthesize_prompts(requests, page, vocab, seed=seed)
    if warmup:
        # untimed shape warmup (the bench convention): the measured
        # replay then shows REAL on-path recompiles, not cold-start
        replay(engine, requests, prompts, speed=0.0)
        _reset_engine(engine)
    report = replay(engine, requests, prompts, speed=speed)
    verdict = diff_replay(requests, prompts, page, report,
                          tolerance=tolerance)
    out = {"trace": trace_path, "meta": meta,
           "requests": len(requests),
           "recorded_compiles": len(trace["compiles"]),
           "tp": int(max(tp, 1)),
           "replay": report, "diff": verdict}
    if spec:
        from deepspeed_tpu.inference.v2 import ServingOptimizationConfig
        spec_serving = ServingOptimizationConfig(speculative=True,
                                                 spec_drafter=drafter,
                                                 tp_degree=tp)
        if drafter == "ngram":
            # same engine: the n-gram drafter is host-side state only
            spec_engine = engine
        else:
            # model/auto need the draft head — draft params and the
            # parallel draft-KV array are ENGINE-level state, so the
            # spec pass gets its own engine built with the config
            spec_engine = build_replay_engine(
                meta, requests, model_size=model_size,
                serving=spec_serving)
        if warmup:
            _reset_engine(spec_engine)
            replay(spec_engine, requests, prompts, speed=0.0,
                   serving=spec_serving)
        _reset_engine(spec_engine)
        spec_report = replay(spec_engine, requests, prompts, speed=speed,
                             serving=spec_serving)
        spec_diff = diff_replay(requests, prompts, page, spec_report,
                                tolerance=tolerance)
        drafted = spec_report["spec_drafted"]
        off_tok_s = report["decode_tok_s"]

        def _rate(acc, dr):
            return round(acc / dr, 4) if dr else None

        d_model = spec_report["spec_draft_drafted"]
        a_model = spec_report["spec_draft_accepted"]
        d_ngram = drafted - d_model
        a_ngram = spec_report["spec_accepted"] - a_model
        out["spec"] = {
            "replay": spec_report, "diff": spec_diff,
            "drafter": drafter,
            "accept_rate": _rate(spec_report["spec_accepted"], drafted),
            "drafted": drafted,
            "accepted": spec_report["spec_accepted"],
            "per_drafter": {
                "ngram": {"drafted": d_ngram, "accepted": a_ngram,
                          "accept_rate": _rate(a_ngram, d_ngram)},
                "model": {"drafted": d_model, "accepted": a_model,
                          "accept_rate": _rate(a_model, d_model)},
            },
            "tok_s_off": off_tok_s,
            "tok_s_on": spec_report["decode_tok_s"],
            "tok_s_ratio": (round(spec_report["decode_tok_s"]
                                  / off_tok_s, 3)
                            if off_tok_s else None),
        }
    return out


# -- CLI ---------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace", required=True, help="workload JSONL path")
    ap.add_argument("--speed", type=float, default=0.0,
                    help="arrival pacing: 0 = full speed (default), "
                    "1 = recorded offsets, 2 = twice as fast, ...")
    ap.add_argument("--limit", type=int, default=0,
                    help="replay only the first N requests (0 = all)")
    ap.add_argument("--model-size", default="debug",
                    help="llama preset for the replay engine")
    ap.add_argument("--seed", type=int, default=0,
                    help="prompt-synthesis seed")
    ap.add_argument("--tolerance", type=float, default=4.0,
                    help="SLO percentile agreement factor")
    ap.add_argument("--include-errors", action="store_true",
                    help="also replay requests whose recorded outcome "
                    "was a structured error (default: ok only)")
    ap.add_argument("--spec", action="store_true",
                    help="replay a second pass with speculative "
                    "decoding enabled and report accept rate + tok/s "
                    "delta (ISSUE 10)")
    ap.add_argument("--drafter", default="ngram",
                    choices=("ngram", "model", "auto"),
                    help="draft source for the --spec pass (ISSUE 17): "
                    "model/auto rebuild the spec engine with the "
                    "in-program draft head and the report splits "
                    "accept rate per drafter")
    ap.add_argument("--tp", type=int, default=1,
                    help="shard the replay engine over an N-way "
                    "simulated tensor-parallel mesh (ISSUE 18); the "
                    "prelude forces N host devices before jax loads, "
                    "and --check additionally requires zero on-path "
                    "compiles and zero structured errors")
    ap.add_argument("--disagg", action="store_true",
                    help="replay through the two-pool disaggregated "
                    "prefill/decode scheduler (ISSUE 13): committed-"
                    "page KV streaming handoff, keyed sampling on "
                    "both pools; --check additionally requires zero "
                    "lost requests")
    ap.add_argument("--tier", action="store_true",
                    help="replay twice on one device-starved engine "
                    "backed by the host/disk prefix tier (ISSUE 16): "
                    "wave 1 demotes every parked page, wave 2 must "
                    "warm back through promotion; --check additionally "
                    "requires demotions, disk spills, promotions, "
                    "warm==cold tokens, and clean tier accounting")
    ap.add_argument("--tier-device-pages", type=int, default=4,
                    help="requested device pool size for --tier "
                    "(clamped up to the smallest schedulable pool: "
                    "one worst-case sequence + one page)")
    ap.add_argument("--tier-host-pages", type=int, default=8,
                    help="host DRAM ring capacity for --tier (kept "
                    "tiny so the smoke also exercises disk spill)")
    ap.add_argument("--tier-disk-pages", type=int, default=256,
                    help="disk tier capacity for --tier (0 disables "
                    "the disk tier and its spill check)")
    ap.add_argument("--journeys", action="store_true",
                    help="with --disagg: enable telemetry for the "
                    "measured run and verify request journeys (ISSUE "
                    "19) — every completed request must reconstruct a "
                    "gap-free segment chain summing to its measured "
                    "e2e latency, with zero orphaned handoff "
                    "fragments; --check fails on any finding")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the untimed shape-warmup pass (the "
                    "measured run then eats the XLA compiles)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless structural parity holds "
                    "(CI smoke mode)")
    ap.add_argument("--json", default="",
                    help="also write the full report to this path")
    args = ap.parse_args(argv)
    if args.tp > 1 and (args.tier or args.disagg):
        ap.error("--tp shards the base/--spec replay only; the tier "
                 "and disagg legs build their own engines")
    if args.journeys and not args.disagg:
        ap.error("--journeys rides the --disagg leg (the journey "
                 "smoke verifies the handoff segments)")

    try:
        if args.tier:
            out = run_tier_smoke(
                args.trace, limit=args.limit,
                include_errors=args.include_errors,
                device_pages=args.tier_device_pages,
                host_pages=args.tier_host_pages,
                disk_pages=args.tier_disk_pages,
                model_size=args.model_size, seed=args.seed,
                tolerance=args.tolerance)
        elif args.disagg:
            out = run_replay_disagg(
                args.trace, limit=args.limit,
                include_errors=args.include_errors,
                speed=args.speed, model_size=args.model_size,
                seed=args.seed, warmup=not args.no_warmup,
                tolerance=args.tolerance, journeys=args.journeys)
        else:
            out = run_replay(args.trace, limit=args.limit,
                             include_errors=args.include_errors,
                             speed=args.speed,
                             model_size=args.model_size,
                             seed=args.seed, warmup=not args.no_warmup,
                             tolerance=args.tolerance, spec=args.spec,
                             drafter=args.drafter, tp=args.tp)
    except ValueError as e:
        print(f"replay_trace: {e}", file=sys.stderr)
        return 1
    verdict = out["diff"]
    print(json.dumps(out, indent=1, default=str))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1, default=str)
    problems = list(verdict["problems"]) if not verdict["structural_ok"] \
        else []
    if args.disagg and out["replay"].get("lost"):
        problems.append(
            f"[disagg] {out['replay']['lost']} request(s) lost "
            "(neither completed nor structurally errored)")
    if args.journeys:
        jrep = out["replay"].get("journeys") or {}
        problems += [f"[journey] {p}" for p in jrep.get("problems", ())]
        if not jrep.get("completed_journeys"):
            problems.append("[journey] no journeys flushed during the "
                            "measured replay")
    if args.tp > 1 and not (args.tier or args.disagg):
        # the sharded leg is a STRONGER contract than base structural
        # parity: the one-program step must come entirely out of the
        # warmed shape set (tp in the compile-cache digest — a mesh
        # change is a MISS, never a wrong executable), and sharding may
        # not surface as per-request structured errors
        if out["replay"].get("compile_on_path"):
            problems.append(
                f"[tp] {out['replay']['compile_on_path']} on-path "
                "compile(s) during the sharded measured replay")
        if out["replay"].get("errors"):
            problems.append(
                f"[tp] {len(out['replay']['errors'])} structured "
                "error(s) during the sharded replay")
    if args.spec and not out["spec"]["diff"]["structural_ok"]:
        # the spec pass must reproduce the same structure — speculation
        # may only change throughput/metrics
        problems += [f"[spec] {p}"
                     for p in out["spec"]["diff"]["problems"]]
    if args.check and problems:
        print("replay_trace: STRUCTURAL PARITY FAILED", file=sys.stderr)
        for p in problems:
            print(f"replay_trace:   {p}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
