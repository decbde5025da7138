"""Workload observatory (ISSUE 9): capture -> replay -> analyze.

Covers the tentpole legs — the content-free rotating JSONL ledger
(schema, no-token-content rule, rotation bounds, <5µs disabled path,
config/env plumbing), digest-preserving anonymized replay (structural
parity: lengths, share structure, arrival order; SLO histogram
agreement on a deterministic warm workload), the trace analyzer
(occupancy mining, current-lattice coverage, quantile-fitted bucket
recommendation on a bimodal length distribution with zero uncovered
on-path compile keys) — plus the satellites: per-program cost/MFU
accounting from ``compiled.cost_analysis()``, instantaneous backlog
gauges, the postmortem bundle's sixth ``workload.jsonl`` artifact, and
the dead-metric pass of ``tools/check_metrics.py``.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from deepspeed_tpu.inference.v2 import (
    FastGenScheduler, InferenceEngineV2, KVCacheConfig,
    RaggedInferenceEngineConfig, RaggedInferenceModel, SamplingParams,
    ServingOptimizationConfig, StateManagerConfig)
from deepspeed_tpu.models.llama import LlamaForCausalLM
from deepspeed_tpu.telemetry import metrics as tm
from deepspeed_tpu.telemetry.workload_trace import (WorkloadTrace,
                                                    get_workload_trace)
from flax.core import meta

from tools.analyze_trace import analyze, fit_buckets
from tools.replay_trace import (diff_replay, load_trace, replay,
                                share_signature_prompts,
                                share_signature_recorded,
                                synthesize_prompts)

PAGE = 16
VOCAB = 128  # debug llama vocab


def _mk_engine(num_pages=256, max_seqs=16, max_batch=256):
    model_def = LlamaForCausalLM("debug", max_seq_len=256,
                                 dtype=jnp.float32)
    cfg = model_def.cfg
    params = meta.unbox(model_def.init_params(jax.random.key(0)))
    kv_cfg = KVCacheConfig(num_layers=cfg.num_layers,
                           kv_heads=cfg.kv_heads,
                           head_dim=cfg.dims_per_head, page_size=PAGE,
                           num_pages=num_pages, dtype=jnp.float32)
    model = RaggedInferenceModel(cfg, params, kv_config=kv_cfg)
    return InferenceEngineV2(model, RaggedInferenceEngineConfig(
        state_manager=StateManagerConfig(
            max_tracked_sequences=max_seqs,
            max_ragged_sequence_count=max_seqs,
            max_ragged_batch_size=max_batch)))


@pytest.fixture(scope="module")
def eng():
    return _mk_engine()


@pytest.fixture()
def wtrace(tmp_path):
    """The process singleton pointed at a per-test ledger, closed (and
    left inactive) afterwards regardless of outcome."""
    wt = get_workload_trace()
    path = str(tmp_path / "trace.jsonl")
    wt.configure(path)
    yield wt, path
    wt.close()


def _fresh(eng):
    """Return the shared engine to a cold, empty state."""
    for uid in list(eng.state_manager._seqs):
        eng.flush(uid)
    eng.reset_prefix_cache()


def _workload(eng, n=8, seed=0, max_new=6, shared_pages=2,
              serving=None, stagger=0):
    """A deterministic shared-prefix workload; returns the generations.
    ``stagger`` submits in waves with scheduler steps in between so
    arrival offsets / queue waits are non-degenerate."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, VOCAB, shared_pages * PAGE)
    prompts = [np.concatenate(
        [prefix, rng.integers(0, VOCAB, 3 + (i % 5))]).tolist()
        for i in range(n)]
    sched = FastGenScheduler(eng, serving=serving)
    sp = SamplingParams(max_new_tokens=max_new, temperature=0.0)
    if stagger:
        i = 0
        while i < n or sched.has_work:
            for _ in range(stagger):
                if i < n:
                    sched.submit(i, prompts[i], sp)
                    i += 1
            sched.step()
        return sched, prompts
    for i, p in enumerate(prompts):
        sched.submit(i, p, sp)
    sched.run_to_completion()
    return sched, prompts


# ---------------------------------------------------------------------------
# ledger: schema, content-free rule, rotation, disabled path, plumbing
# ---------------------------------------------------------------------------

REQUEST_KEYS = {"kind", "uid", "arrival_s", "prompt_len", "gen_len",
                "digests", "temperature", "top_k", "top_p",
                "max_new_tokens", "outcome", "ttft_ms", "itl_ms",
                "queue_wait_ms", "spec_drafted", "spec_accepted",
                "spec_drafter", "spec_ngram_drafted",
                "spec_ngram_accepted", "spec_model_drafted",
                "spec_model_accepted",
                "hit_device", "hit_host", "hit_disk", "hit_remote"}


class TestLedger:
    def test_schema_and_share_structure(self, eng, wtrace):
        wt, path = wtrace
        _fresh(eng)
        _workload(eng, n=6)
        wt.flush()
        lines = [json.loads(l) for l in open(path)]
        kinds = {l["kind"] for l in lines}
        assert {"meta", "request", "keys"} <= kinds
        meta_rec = next(l for l in lines if l["kind"] == "meta")
        assert meta_rec["page_size"] == PAGE
        assert meta_rec["vocab_size"] == VOCAB
        reqs = [l for l in lines if l["kind"] == "request"]
        assert len(reqs) == 6
        for r in reqs:
            assert set(r) == REQUEST_KEYS
            assert r["outcome"] == "ok"
            assert r["gen_len"] == 6
            assert r["ttft_ms"] > 0 and r["queue_wait_ms"] >= 0
            assert len(r["digests"]) == r["prompt_len"] // PAGE
        # all six share the 2-page prefix: identical digest chains
        assert len({tuple(r["digests"][:2]) for r in reqs}) == 1
        # key occupancy flushed at close/flush, every count positive
        keys_rec = next(l for l in lines if l["kind"] == "keys")
        assert keys_rec["counts"] and all(
            n > 0 for _, n in keys_rec["counts"])

    def test_content_free(self, eng, wtrace):
        """No token id ever reaches the ledger: prompts appear only as
        lengths and hex digest strings."""
        wt, path = wtrace
        _fresh(eng)
        _workload(eng, n=4)
        wt.flush()
        for line in open(path):
            rec = json.loads(line)
            if rec["kind"] != "request":
                continue
            for key, val in rec.items():
                if key == "digests":
                    assert all(isinstance(d, str) for d in val)
                else:
                    # nothing list-shaped besides the digest chain — a
                    # token array cannot hide in any other field
                    assert not isinstance(val, list), (key, val)

    def test_error_outcomes_recorded(self, eng, wtrace):
        """The error point of the ledger: a shed request lands with its
        structured code, not silently dropped."""
        wt, path = wtrace
        _fresh(eng)
        serving = ServingOptimizationConfig(max_queue_depth=2)
        sched = FastGenScheduler(eng, serving=serving)
        sp = SamplingParams(max_new_tokens=2, temperature=0.0)
        rng = np.random.default_rng(0)
        for i in range(4):  # 3rd+ submit sheds (depth 2)
            sched.submit(i, rng.integers(0, VOCAB, 8).tolist(), sp)
        sched.run_to_completion()
        wt.flush()
        outcomes = [json.loads(l)["outcome"] for l in open(path)
                    if json.loads(l)["kind"] == "request"]
        assert outcomes.count("shed") == 2
        assert outcomes.count("ok") == 2

    def test_rotation_bounds(self, tmp_path):
        wt = WorkloadTrace()
        path = str(tmp_path / "rot.jsonl")
        wt.configure(path, max_bytes=4096)
        for i in range(200):
            wt.record_request(
                uid=i, arrival_mono=time.monotonic(), prompt_len=32,
                gen_len=4, digests=["ab" * 16, "cd" * 16],
                page_size=16, vocab_size=128, temperature=0.0,
                top_k=0, top_p=1.0, max_new_tokens=4, outcome="ok",
                ttft_ms=1.0, itl_ms=1.0, queue_wait_ms=0.1)
        wt.close()
        import os
        assert os.path.exists(path + ".1")   # exactly one generation
        assert not os.path.exists(path + ".2")
        total = os.path.getsize(path) + os.path.getsize(path + ".1")
        assert total <= 2 * 4096 + 1024      # bounded at ~2x max
        # both generations stay parseable JSONL with their own header
        for p in (path, path + ".1"):
            lines = [json.loads(l) for l in open(p)]
            assert any(l["kind"] == "meta" for l in lines)

    def test_io_failure_degrades_never_raises(self, tmp_path):
        """A runtime ledger write failure (ENOSPC-style) deactivates
        capture instead of raising into the serving step, and the path
        unlatches so a retry can reopen it."""
        wt = WorkloadTrace()
        path = str(tmp_path / "enospc.jsonl")
        wt.configure(path)

        class _Boom:
            def write(self, *_a):
                raise OSError(28, "No space left on device")

            def flush(self):
                raise OSError(28, "No space left on device")

            def tell(self):
                return 0

            def close(self):
                pass

        wt._fh = _Boom()
        wt.record_request(
            uid=0, arrival_mono=time.monotonic(), prompt_len=8,
            gen_len=1, digests=[], page_size=16, vocab_size=128,
            temperature=0.0, top_k=0, top_p=1.0, max_new_tokens=1,
            outcome="ok", ttft_ms=1.0, itl_ms=None, queue_wait_ms=0.1)
        assert not wt.active and wt._path == ""
        wt.configure(path)           # same path reopens after the fault
        assert wt.active
        wt.close()

    def test_suspended_respects_inner_close(self, tmp_path):
        wt = WorkloadTrace()
        wt.configure(str(tmp_path / "s.jsonl"))
        with wt.suspended():
            assert not wt.active
            wt.close()               # e.g. a shutdown path mid-drive
        assert not wt.active         # close wins — never re-activated

    def test_tail_spans_rotation_boundary(self, tmp_path):
        """The postmortem tail reads across <path>.1 so a crash just
        after a rotation still ships history."""
        wt = WorkloadTrace()
        path = str(tmp_path / "t.jsonl")
        wt.configure(path, max_bytes=2048)
        for i in range(40):
            wt.record_request(
                uid=i, arrival_mono=time.monotonic(), prompt_len=32,
                gen_len=4, digests=["ab" * 16], page_size=16,
                vocab_size=128, temperature=0.0, top_k=0, top_p=1.0,
                max_new_tokens=4, outcome="ok", ttft_ms=1.0,
                itl_ms=1.0, queue_wait_ms=0.1)
        import os as _os
        assert _os.path.exists(path + ".1")
        in_current = sum(1 for l in open(path)
                         if json.loads(l)["kind"] == "request")
        tail = wt.tail_text(64 << 10)
        in_tail = sum(1 for l in tail.splitlines()
                      if l and json.loads(l)["kind"] == "request")
        assert in_tail > in_current   # history beyond the fresh file
        wt.close()

    def test_disabled_path_under_bound(self):
        """Inactive ledger: every entry point is one attribute read."""
        wt = WorkloadTrace()
        key = (8, 1, 8, False)
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            wt.note_step_key(key)
            wt.record_compile(key)
        per_call = (time.perf_counter() - t0) / (2 * n)
        assert per_call < 5e-6, f"{per_call * 1e6:.2f}us/call disabled"

    def test_config_and_env_plumbing(self, tmp_path, monkeypatch):
        """Both engine configs and the env reach the ledger through the
        shared apply_settings seam."""
        from deepspeed_tpu.inference.v2.config import TelemetryConfig
        from deepspeed_tpu.runtime.config import (
            TelemetryConfig as RuntimeTelemetryConfig)
        from deepspeed_tpu.telemetry import workload_trace as wtmod
        wt = get_workload_trace()
        p1 = str(tmp_path / "v2.jsonl")
        TelemetryConfig(workload_trace_path=p1).apply()
        assert wt.active and wt._path == p1
        p2 = str(tmp_path / "rt.jsonl")
        RuntimeTelemetryConfig(workload_trace_path=p2).apply()
        assert wt._path == p2
        RuntimeTelemetryConfig().apply()   # "" keeps current
        assert wt._path == p2
        p3 = str(tmp_path / "env.jsonl")
        monkeypatch.setenv("DS_WORKLOAD_TRACE", p3)
        monkeypatch.setenv("DS_WORKLOAD_TRACE_MAX_MB", "2")
        assert wtmod.maybe_configure_from_env()
        assert wt._path == p3 and wt._max_bytes == 2 << 20
        wt.close()


# ---------------------------------------------------------------------------
# replay: structural parity + SLO agreement
# ---------------------------------------------------------------------------

class TestReplay:
    def test_capture_replay_structural_parity(self, eng, wtrace):
        """A captured workload replays with the same request count,
        prompt/generated lengths, prefix-sharing structure, and
        arrival order — through anonymized synthesized prompts."""
        wt, path = wtrace
        _fresh(eng)
        _workload(eng, n=10, stagger=3)
        wt.flush()
        trace = load_trace(path)
        requests = trace["requests"]
        assert len(requests) == 10
        prompts = synthesize_prompts(requests, PAGE, VOCAB)
        # anonymized: synthesized prompts differ from the originals
        # (same lengths, same sharing classes, new content)
        assert (share_signature_prompts(prompts, PAGE)
                == share_signature_recorded(requests))
        _fresh(eng)
        report = replay(eng, requests, prompts, speed=0.0)
        verdict = diff_replay(requests, prompts, PAGE, report,
                              tolerance=1e9)
        assert verdict["structural_ok"], verdict["problems"]
        # arrival order held exactly
        order = sorted(range(len(requests)),
                       key=lambda i: requests[i]["arrival_s"])
        assert report["submit_order"] == order

    def test_synthesized_prompts_differ_but_share(self, eng, wtrace):
        """The anonymization rule: same digest -> same synthetic page,
        different digest -> different page; original tokens absent."""
        wt, path = wtrace
        _fresh(eng)
        _, originals = _workload(eng, n=4)
        wt.flush()
        requests = load_trace(path)["requests"]
        prompts = synthesize_prompts(requests, PAGE, VOCAB)
        by_uid = {r["uid"]: i for i, r in enumerate(requests)}
        for uid, orig in enumerate(originals):
            syn = prompts[by_uid[uid]]
            assert len(syn) == len(orig)
            assert not np.array_equal(syn[:PAGE],
                                      np.asarray(orig[:PAGE]))
        # shared recorded prefix -> shared synthesized prefix bytes
        a, b = prompts[by_uid[0]], prompts[by_uid[1]]
        np.testing.assert_array_equal(a[:2 * PAGE], b[:2 * PAGE])

    def test_recorded_vs_replayed_slo_agreement(self, eng, wtrace):
        """On a deterministic warm workload, the replayed TTFT
        percentiles agree with the recorded ones within tolerance (the
        replay engine is the capture engine, both windows warm)."""
        wt, path = wtrace
        _fresh(eng)
        _workload(eng, n=8)          # warm every bucket first
        wt.close()
        import os
        os.unlink(path)
        wt.configure(path)           # capture only the WARM run
        _fresh(eng)
        _workload(eng, n=8)
        wt.flush()
        requests = load_trace(path)["requests"]
        prompts = synthesize_prompts(requests, PAGE, VOCAB)
        _fresh(eng)
        report = replay(eng, requests, prompts, speed=0.0)
        verdict = diff_replay(requests, prompts, PAGE, report,
                              tolerance=8.0)
        assert verdict["structural_ok"], verdict["problems"]
        assert verdict["slo_within_tolerance"], verdict["slo"]
        # a warm replay of a warm capture recompiles nothing
        assert report["compile_on_path"] == 0

    def test_replay_paced_respects_arrival_offsets(self, eng, wtrace):
        wt, path = wtrace
        _fresh(eng)
        _workload(eng, n=6, stagger=2)
        wt.flush()
        requests = load_trace(path)["requests"]
        prompts = synthesize_prompts(requests, PAGE, VOCAB)
        spread = (max(r["arrival_s"] for r in requests)
                  - min(r["arrival_s"] for r in requests))
        _fresh(eng)
        t0 = time.perf_counter()
        report = replay(eng, requests, prompts, speed=1.0)
        wall = time.perf_counter() - t0
        assert report["requests_submitted"] == len(requests)
        # paced replay can't finish before the last recorded arrival
        assert wall >= spread


# ---------------------------------------------------------------------------
# analyzer: occupancy, coverage, fitted lattice
# ---------------------------------------------------------------------------

class TestAnalyzer:
    def test_fit_buckets_bimodal(self):
        """A bimodal length distribution gets bucket tops at the modes
        (bounded overshoot), not the enclosing powers of two."""
        rng = np.random.default_rng(0)
        lengths = np.concatenate([rng.integers(18, 23, 300),
                                  rng.integers(190, 211, 300)])
        buckets = fit_buckets(lengths, ratio=1.3)
        assert len(buckets) <= 4
        for l in lengths:
            top = min(b for b in buckets if b >= l)
            assert top <= l * 1.3, (l, top, buckets)
        # pow2 would overshoot the low mode by >= 32/22 ~ 1.45x
        assert any(b <= 23 for b in buckets)
        assert any(190 <= b <= 211 for b in buckets)
        assert 32 not in buckets and 256 not in buckets

    def test_analyze_trace_coverage_and_recommendation(self, eng,
                                                       wtrace):
        wt, path = wtrace
        _fresh(eng)
        _workload(eng, n=8, stagger=3)
        wt.flush()
        trace = load_trace(path)
        report = analyze(trace)
        assert report["requests"]["count"] == 8
        occ = report["occupancy"]
        assert occ["distinct_keys"] > 0
        assert occ["dispatches"] >= occ["distinct_keys"]
        rec = report["recommended_lattice"]
        # the acceptance bar: the recommended lattice leaves ZERO
        # observed on-path compile keys uncovered
        assert rec["uncovered_on_path_compile_keys"] == []
        assert rec["q_buckets"] and rec["p_buckets"] and rec["s_buckets"]
        # every observed key is in the recommended key set
        assert {tuple(k) for k, _ in occ["keys"]} <= {
            tuple(k) for k in rec["keys"]}

    def test_checked_in_sample_trace_loads(self):
        """The CI fixture stays parseable and structurally sound."""
        import os
        path = os.path.join(os.path.dirname(__file__), "..", "tools",
                            "traces", "sample_200.jsonl")
        trace = load_trace(path)
        assert len(trace["requests"]) == 200
        assert trace["meta"]["page_size"] == 16
        prompts = synthesize_prompts(trace["requests"], 16, 128)
        assert (share_signature_prompts(prompts, 16)
                == share_signature_recorded(trace["requests"]))


# ---------------------------------------------------------------------------
# satellites: cost/MFU accounting, backlog gauges, postmortem artifact,
# dead-metric lint
# ---------------------------------------------------------------------------

class TestCostAccounting:
    def test_program_costs_and_mfu_gauges(self, eng, monkeypatch):
        monkeypatch.delenv("DS_PEAK_FLOPS", raising=False)
        _fresh(eng)
        eng.model.reset_cost_window()
        _workload(eng, n=4)
        cs = eng.cost_summary()
        assert cs["programs"], "no program costs captured"
        assert all(c["flops"] > 0 and c["bytes"] > 0
                   for c in cs["programs"].values())
        assert cs["flops_dispatched"] > 0
        assert cs["bytes_per_s"] > 0
        assert tm.FASTGEN_PROGRAM_FLOPS.value > 0
        assert tm.FASTGEN_PROGRAM_BYTES.value > 0
        assert tm.FASTGEN_BYTES_PER_S.value > 0
        # the CPU has no published peak: utilization is not reported
        # (never a CPU rate over an assumed chip's peak) ...
        assert cs["peak_flops"] is None and cs["mfu"] == 0
        assert tm.FASTGEN_MFU.value == 0
        # ... until the operator states a denominator
        monkeypatch.setenv("DS_PEAK_FLOPS", "1e12")
        assert eng.cost_summary()["mfu"] > 0

    def test_precompiled_and_on_path_costs_agree(self):
        """The same key costed via precompile() and via an on-path
        compile reports the same flops (one accounting, two routes)."""
        e1, e2 = _mk_engine(max_seqs=4), _mk_engine(max_seqs=4)
        e1.precompile(max_prompt=8, max_new_tokens=2, sampling=False)
        prompt = np.arange(8, dtype=np.int32)
        e1.put([1], [prompt])
        e2.put([1], [prompt])          # compiles on path
        common = set(e1.model._program_costs) & set(
            e2.model._program_costs)
        assert common, "no shared step-cache key costed"
        for k in common:
            assert (e1.model._program_costs[k]["flops"]
                    == e2.model._program_costs[k]["flops"])


class TestBacklogGauges:
    def test_gauges_track_live_scheduler(self, eng):
        _fresh(eng)
        rng = np.random.default_rng(0)
        sched = FastGenScheduler(eng)
        sp = SamplingParams(max_new_tokens=3, temperature=0.0)
        for i in range(5):
            sched.submit(i, rng.integers(0, VOCAB, 8).tolist(), sp)
        assert tm.FASTGEN_QUEUE_DEPTH.value == 5
        assert tm.FASTGEN_RUNNING.value == 0
        sched.step()
        assert (tm.FASTGEN_QUEUE_DEPTH.value
                + tm.FASTGEN_RUNNING.value) == 5
        sched.run_to_completion()
        assert tm.FASTGEN_QUEUE_DEPTH.value == 0
        assert tm.FASTGEN_RUNNING.value == 0
        assert tm.FASTGEN_PREEMPTED.value == 0
        # a discarded scheduler must not pin state: gauges read 0, not
        # stale lengths (weakref binding)
        del sched
        import gc
        gc.collect()
        assert tm.FASTGEN_QUEUE_DEPTH.value == 0


class TestPostmortemArtifact:
    def test_bundle_ships_workload_tail(self, eng, wtrace, tmp_path,
                                        monkeypatch):
        from deepspeed_tpu import telemetry
        wt, path = wtrace
        _fresh(eng)
        monkeypatch.setattr(telemetry.state, "enabled", True)
        _workload(eng, n=4)
        out = tmp_path / "pm"
        paths = telemetry.dump_postmortem(str(out))
        assert "workload.jsonl" in paths
        lines = [json.loads(l)
                 for l in open(out / "workload.jsonl") if l.strip()]
        assert sum(1 for l in lines if l["kind"] == "request") == 4
        # the run flushed journeys too (telemetry was on at submit), so
        # the bundle ships them alongside the ledger tail (ISSUE 19)
        assert "journeys.json" in paths
        jdoc = json.loads(open(out / "journeys.json").read())
        assert len(jdoc["completed"]) >= 4

    def test_bundle_without_capture_stays_five_artifacts(self, tmp_path,
                                                         monkeypatch):
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.telemetry import journey
        assert not get_workload_trace().active
        # journeys.json follows the same skip-when-empty rule as the
        # ledger tail (ISSUE 19) — a journey-free process ships neither
        journey.get_journey_log().clear()
        # memory.json follows the same rule keyed on accountant
        # registration (ISSUE 20): simulate a process whose ledger
        # never armed, restoring the suite's accountants after
        from deepspeed_tpu.telemetry.memory import get_memory_ledger
        led = get_memory_ledger()
        saved_acct, saved_dev = dict(led._accountants), dict(led._device)
        led.reset()
        monkeypatch.setattr(telemetry.state, "enabled", True)
        try:
            paths = telemetry.dump_postmortem(str(tmp_path / "pm5"))
        finally:
            with led._lock:
                led._accountants.update(saved_acct)
                led._device.update(saved_dev)
        assert "workload.jsonl" not in paths
        assert "journeys.json" not in paths
        assert "memory.json" not in paths
        assert len(paths) == 5


class TestDeadMetricLint:
    def test_unrecorded_metric_is_flagged(self, tmp_path, monkeypatch):
        """A metric minted in the catalog but recorded nowhere in the
        production tree fails check_metrics; every LIVE metric passes.
        Simulated by pointing the lint at a catalog copy carrying one
        extra minted-but-dead metric (the real tree is still the one
        scanned for recordings)."""
        import os
        import tools.check_metrics as cm
        from deepspeed_tpu.telemetry import get_registry
        src = open(os.path.join(cm.REPO_ROOT, cm.CATALOG)).read()
        cat = tmp_path / "metrics.py"
        cat.write_text(src + '\nDEAD = registry.counter(\n'
                       '    "ds_fastgen_dead_series_total", "dead")\n')
        name = "ds_fastgen_dead_series_total"
        reg = get_registry()
        reg.counter(name, "dead")
        # CATALOG is joined onto REPO_ROOT; an absolute path wins the
        # join, so only the catalog moves — the scan stays on the tree
        monkeypatch.setattr(cm, "CATALOG", str(cat))
        try:
            errors = cm.check()
            assert any("dead metric" in e and name in e
                       for e in errors), errors
            assert not any("dead metric" in e for e in errors
                           if name not in e), errors
        finally:
            reg._metrics.pop(name, None)
