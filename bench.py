"""Benchmark: LLaMA training throughput + FastGen inference on the chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
auxiliary keys.  Primary metric: training tokens/sec/chip on the largest
LLaMA config that fits (BASELINE.json target family: ZeRO-3
tokens/sec/chip); vs_baseline is the achieved model FLOPs utilization
(MFU) fraction, since BASELINE.json has no published TPU number.
Auxiliary: FastGen continuous-batching req/s, p50 TTFT (ms) and decode
tokens/s through the SplitFuse scheduler (BASELINE.json FastGen metric
family, reference blogs/deepspeed-fastgen/README.md:139).
"""

import json
import os
import sys
import time

import numpy as np

MODEL_SIZE = os.environ.get("BENCH_MODEL", "1b")
SEQ_LEN = int(os.environ.get("BENCH_SEQ", "2048"))
MICRO_BS = int(os.environ.get("BENCH_BS", "4"))
STEPS = int(os.environ.get("BENCH_STEPS", "10"))
REMAT_POLICY = os.environ.get("BENCH_REMAT", "save_attn_out")

#: goodput ratio stamped by the training leg's telemetry-on coda at ITS
#: wall-clock moment (the gauge is wall-relative: reading it from the
#: later fastgen SLO leg would dilute the ratio with inference time)
_TRAIN_GOODPUT = None


def _require_chip():
    """The bench measures a chip: import JAX, fail unless its first
    device is a TPU with a published peak (an unknown ``device_kind``
    has no MFU denominator).  No probing child, no retry, no CPU
    continuation — one process owns the chip from here on."""
    import jax

    from deepspeed_tpu.profiling.flops_profiler import device_peak_flops
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py: JAX found no TPU ({devices[0].platform} "
            f"{devices[0].device_kind!r}) — a bench number is a chip "
            "number; there is no CPU fallback")
    return jax, len(devices), device_peak_flops()


def bench_fastgen(jax):
    """FastGen leg: continuous batching through FastGenScheduler.

    Random-init weights (throughput does not depend on values); compile
    cost is paid BEFORE the timed window (``engine.precompile`` with
    BENCH_PRECOMPILE, else a full warmup run) and reported separately as
    ``fastgen_compile_s``, so ``fastgen_ttft_p50_ms`` measures
    steady-state TTFT, not first-use XLA compile spikes.  The serving
    counters (programs per step, host<->device bytes) ride along so the
    fused step's "one program, token-sized transfer" claim is measured;
    BENCH_FASTGEN_COMPARE=1 (default) also times the split-path escape
    hatch on the same engine.  A leg that fails fails the run.
    """
    import numpy as np
    n_req = int(os.environ.get("BENCH_FASTGEN_REQS", "32"))
    max_new = int(os.environ.get("BENCH_FASTGEN_NEW_TOKENS", "64"))
    model_size = os.environ.get("BENCH_FASTGEN_MODEL", MODEL_SIZE)
    try:
        from deepspeed_tpu.inference.v2 import (FastGenScheduler,
                                                InferenceEngineV2,
                                                RaggedInferenceModel,
                                                SamplingParams,
                                                ServingOptimizationConfig)
        from deepspeed_tpu.models.llama import LlamaForCausalLM
        from deepspeed_tpu.utils.comms_logging import serving_counters
        from flax.core import meta

        model = LlamaForCausalLM(model_size)
        params = meta.unbox(model.init_params(jax.random.key(0)))
        eng_cfg = None
        quant = os.environ.get("BENCH_FASTGEN_QUANT")  # e.g. fp8_e4m3
        if quant:
            from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig
            eng_cfg = RaggedInferenceEngineConfig.from_dict(
                {"quantization": {"enabled": True, "fmt": quant}})
        eng = InferenceEngineV2(RaggedInferenceModel(model.cfg, params),
                                eng_cfg)
        rng = np.random.default_rng(0)
        max_prompt = max(8, min(512, model.cfg.max_seq_len - max_new - 1))
        lens = rng.integers(max(1, max_prompt // 4), max_prompt, size=n_req)
        prompts = [rng.integers(0, model.cfg.vocab_size,
                                size=int(l)).tolist() for l in lens]
        sp = SamplingParams(max_new_tokens=max_new, temperature=0.0)
        # headline + split legs measure COLD serving: prefix caching off
        # (the warmup replays the same prompts, which would otherwise
        # warm the cache and silently inflate fastgen_ttft_p50_ms vs
        # earlier commits; warm-vs-cold has its own leg below)
        main_serving = ServingOptimizationConfig(prefix_caching=False)
        split_serving = ServingOptimizationConfig(
            fused_step=False, on_device_sampling=False,
            async_scheduling=False, prefix_caching=False)

        def run(reqs, serving=None, prompt_set=None, engine=None, sp_=None):
            sched = FastGenScheduler(engine or eng, serving=serving)
            submit_t = {}
            first_t = {}
            count = [0]

            # token accounting rides the on_token callback: a
            # speculative step (BENCH_SPEC) commits a whole accepted
            # block per row per step, so counting step() return dict
            # entries (one per uid) would undercount
            def on_tok(uid, _tok):
                count[0] += 1
                if uid not in first_t:
                    first_t[uid] = time.perf_counter()

            t0 = time.perf_counter()
            for i in reqs:
                sched.submit(i, (prompt_set or prompts)[i], sp_ or sp)
                submit_t[i] = t0
            stalls = 0
            while sched.has_work:
                before = count[0]
                sched.step(on_token=on_tok)
                # prefill-only steps return no tokens but ARE progress;
                # a true stall scheduled zero tokens AND delivered none
                # (run_to_completion's predicate, token-count form)
                stalls = (stalls + 1 if sched.last_step_scheduled == 0
                          and count[0] == before else 0)
                if stalls > 32:
                    raise RuntimeError(
                        "scheduler stalled (requests unschedulable — "
                        "prompt exceeds KV capacity?)")
            total = time.perf_counter() - t0
            ttfts = [first_t[i] - submit_t[i] for i in reqs if i in first_t]
            return total, ttfts, count[0]

        # compile OUTSIDE the timed window, reported separately
        t_pre = time.perf_counter()
        if os.environ.get("BENCH_PRECOMPILE"):
            # full production lattice (every bucket the engine can ever
            # form, incl. the fused sample/chain variants) — thorough
            # but many compiles; the default warm run below compiles
            # exactly the buckets the measured run hits
            keys = eng.precompile(max_prompt=max_prompt,
                                  max_new_tokens=max_new, strict=True,
                                  sampling=True)
            sys.stderr.write(
                f"bench: precompiled {len(keys)} buckets in "
                f"{time.perf_counter() - t_pre:.1f}s\n")
        # warmup with the FULL request set: build_batch buckets (S, Q, P)
        # to powers of two, so an identical run precompiles every bucket
        # shape the measured run will hit
        run(range(n_req), serving=main_serving)
        compile_s = time.perf_counter() - t_pre

        serving_counters.reset()
        total, ttfts, done_tokens = run(range(n_req),
                                        serving=main_serving)
        counters = serving_counters.snapshot()
        ttfts.sort()
        result = {
            "fastgen_req_s": round(n_req / total, 2),
            "fastgen_ttft_p50_ms": round(
                1e3 * ttfts[len(ttfts) // 2], 1) if ttfts else None,
            "fastgen_decode_tok_s": round(done_tokens / total, 1),
            "fastgen_compile_s": round(compile_s, 1),
            "fastgen_programs_per_step": counters["programs_per_step"],
            "fastgen_h2d_bytes_per_step": counters["h2d_bytes_per_step"],
            "fastgen_d2h_bytes_per_step": counters["d2h_bytes_per_step"],
            "fastgen_logits_bytes_per_step":
                counters["logits_exposed_bytes_per_step"],
            "fastgen_model": model_size,
            **({"fastgen_quant": quant} if quant else {}),
        }
        if os.environ.get("BENCH_FASTGEN_COMPARE", "1") != "0":
            # escape-hatch comparison on the SAME engine (per-Q-bucket
            # programs + host sampling over [n, V] logits)
            run(range(n_req), serving=split_serving)   # warm split buckets
            serving_counters.reset()
            s_total, _, s_done = run(range(n_req), serving=split_serving)
            s_count = serving_counters.snapshot()
            result["fastgen_split_decode_tok_s"] = round(s_done / s_total, 1)
            result["fastgen_split_programs_per_step"] = \
                s_count["programs_per_step"]
            result["fastgen_split_logits_bytes_per_step"] = \
                s_count["logits_exposed_bytes_per_step"]
        if os.environ.get("BENCH_FASTGEN_PREFIX", "1") != "0":
            # warm/cold prefix-cache leg (ISSUE 3): every request shares
            # a >= 4-page prompt prefix; the same prompt set is replayed
            # against the warm cache, so the warm leg only prefills each
            # request's unique suffix.  Compile time stays outside the
            # timed windows (two untimed shape-warmup runs: the cold run
            # and the warm run hit DIFFERENT prefill chunk buckets).
            peng, pmodel = eng, model
            page = eng.model.kv_config.page_size
            sfx = max(page // 2, 8)
            if pmodel.cfg.max_seq_len < 4 * page + sfx + max_new + 1:
                # CPU-debug context (64 tokens, 64-token pages) can't
                # hold a 4-page prefix — dedicated small-page engine
                from deepspeed_tpu.inference.v2 import KVCacheConfig
                page, sfx = 16, 8
                pmodel = LlamaForCausalLM(model_size, max_seq_len=256)
                pcfg = pmodel.cfg
                kv_cfg = KVCacheConfig(
                    num_layers=pcfg.num_layers, kv_heads=pcfg.kv_heads,
                    head_dim=pcfg.dims_per_head, page_size=page,
                    num_pages=256)
                peng = InferenceEngineV2(RaggedInferenceModel(
                    pcfg, meta.unbox(pmodel.init_params(jax.random.key(0))),
                    kv_config=kv_cfg))
            pre_len = 4 * page
            max_new_pre = min(
                max_new, pmodel.cfg.max_seq_len - pre_len - sfx - 1)
            sp_pre = SamplingParams(max_new_tokens=max_new_pre,
                                    temperature=0.0)
            prefix = rng.integers(0, pmodel.cfg.vocab_size, size=pre_len)
            pre_prompts = [
                np.concatenate(
                    [prefix,
                     rng.integers(0, pmodel.cfg.vocab_size, size=sfx)]
                ).tolist() for _ in range(min(n_req, 8))]
            reqs = range(len(pre_prompts))

            def prun(): return run(reqs, prompt_set=pre_prompts,
                                   engine=peng, sp_=sp_pre)
            peng.reset_prefix_cache()
            prun()                           # cold-shape warmup
            prun()                           # warm-shape warmup
            peng.reset_prefix_cache()
            serving_counters.reset()
            _, cold_ttfts, _ = prun()
            cold_prefill = serving_counters.prefill_tokens
            serving_counters.reset()
            _, warm_ttfts, _ = prun()
            p_count = serving_counters.snapshot()
            cold_ttfts.sort(), warm_ttfts.sort()
            result["fastgen_ttft_cold_p50_ms"] = round(
                1e3 * cold_ttfts[len(cold_ttfts) // 2], 1)
            result["fastgen_ttft_warm_p50_ms"] = round(
                1e3 * warm_ttfts[len(warm_ttfts) // 2], 1)
            result["fastgen_prefix_hit_rate"] = p_count["prefix_hit_rate"]
            result["fastgen_prefix_prefill_tokens_cold"] = cold_prefill
            result["fastgen_prefix_prefill_tokens_warm"] = \
                p_count["prefill_tokens"]
        if os.environ.get("BENCH_SLO", "1") != "0":
            # SLO leg (ISSUE 4): replay the headline workload with the
            # telemetry spine enabled — the new tail-latency keys come
            # straight from the registry's log-bucketed histograms, not
            # hand-rolled percentile code.  A separate leg so the
            # headline timings above stay telemetry-off and comparable
            # across commits (the enabled overhead is ~us/span, but the
            # control must be exact).  Its own try: a failure here
            # (unwritable trace path, replay error) must not discard
            # the already-computed headline keys above.
            try:
                from deepspeed_tpu import telemetry
                from deepspeed_tpu.telemetry import metrics as tmet
                telemetry.get_tracer().clear()
                # the prefix leg may have bound the ds_kv_* gauges to
                # its dedicated engine — rebind to the measured one
                eng._bind_kv_gauges()
                # cost/MFU window (ISSUE 9): re-open at the measured
                # run so the warmups' dispatches don't dilute the rate
                eng.model.reset_cost_window()
                # measured-window reads come from the time-series ring
                # (ISSUE 11): bracketing samples make the run ITS OWN
                # delta window, so the cumulative SLO histograms and
                # miss counters need no reset-after-warmup dance — the
                # warmups' observations simply fall outside the window
                ts = telemetry.get_timeseries()
                # retention must outlast the slowest CI run of this
                # leg, or the bracketing s_before sample gets evicted
                # and the "measured window" silently becomes the tail
                ts.configure(interval_s=0.25, retention_s=1800)
                was_enabled = telemetry.enabled()
                telemetry.enable()
                s_before = ts.sample_now()
                try:
                    slo_total, _, slo_tokens = run(range(n_req),
                                                   serving=main_serving)
                finally:
                    telemetry.set_enabled(was_enabled)
                s_after = ts.sample_now()
                want_window = s_after["t"] - s_before["t"] + 1e-6
                win = ts.window_snapshot(want_window)
                if win["_window_covered_s"] < 0.98 * (want_window - 1e-6):
                    # ring evicted s_before: the values below cover
                    # only the tail — flag it instead of lying
                    result["fastgen_window_truncated_s"] = round(
                        want_window - win["_window_covered_s"], 1)
                result["fastgen_ttft_p99_ms"] = round(
                    win["ds_fastgen_ttft_ms_p99"], 1)
                result["fastgen_itl_p50_ms"] = round(
                    win["ds_fastgen_itl_ms_p50"], 2)
                result["fastgen_queue_wait_p50_ms"] = round(
                    win["ds_fastgen_queue_wait_ms_p50"], 1)
                result["fastgen_step_p99_ms"] = round(
                    win["ds_fastgen_step_ms_p99"], 2)
                # recompile accounting (ISSUE 5): the warmups above
                # compiled every bucket this workload hits, so misses
                # IN THE WINDOW are real on-request-path recompiles —
                # the bench trajectory should show 0 and flag drift
                result["fastgen_step_cache_miss_total"] = \
                    win["ds_fastgen_step_cache_miss_total"]
                result["fastgen_compile_on_path_total"] = \
                    win["ds_fastgen_compile_on_path_total"]
                # windowed-rate cross-check (ISSUE 11 acceptance): the
                # ring's tok/s over the measured window vs the
                # bench-computed throughput of the same run (~1.0)
                win_tok_s = win.get("ds_fastgen_tokens_total_per_s")
                if win_tok_s and slo_total:
                    bench_tok_s = slo_tokens / slo_total
                    result["fastgen_window_tok_s"] = round(win_tok_s, 1)
                    result["fastgen_window_rate_agreement"] = round(
                        win_tok_s / bench_tok_s, 4)
                # hardware denominator (ISSUE 9): dispatched-program
                # FLOPs / wall / peak over the measured window (read
                # IMMEDIATELY — the gauge is wall-relative and decays
                # once serving stops)
                cs = eng.cost_summary()
                result["fastgen_mfu"] = round(float(cs["mfu"]), 8)
                result["fastgen_hbm_gb_s"] = round(
                    cs["bytes_per_s"] / 1e9, 3)
                result["fastgen_program_flops_p50"] = float(np.median(
                    [c["flops"] for c in cs["programs"].values()]
                    or [0.0]))
                # goodput (ISSUE 5): stamped by the training leg's
                # telemetry-on coda at its own wall-clock moment.  When
                # no coda ran AND the gauge was never bound, OMIT the
                # key — an untouched gauge reads 0.0, which check_bench
                # would misread as a -100% goodput regression
                if _TRAIN_GOODPUT is not None:
                    result["train_goodput_ratio"] = _TRAIN_GOODPUT
                elif tmet.TRAIN_GOODPUT_RATIO.touched:
                    result["train_goodput_ratio"] = round(
                        float(tmet.TRAIN_GOODPUT_RATIO.value), 4)
                if os.environ.get("BENCH_TRACE", "") not in ("", "0"):
                    # Chrome-trace artifact of the SLO leg, loadable in
                    # Perfetto, written alongside the BENCH_*.json line
                    trace_path = os.environ.get("BENCH_TRACE_PATH",
                                                "BENCH_trace.json")
                    telemetry.dump_trace(trace_path)
                    result["fastgen_trace_path"] = trace_path
            except Exception as e:  # noqa: BLE001
                sys.stderr.write(f"bench: fastgen SLO leg failed: {e}\n")
                raise
        if os.environ.get("BENCH_SPEC", "0") != "0":
            # speculative-decoding leg (ISSUE 10): the same scheduler
            # drives a dedicated long-decode engine twice per workload —
            # speculation off, then on — on a HIGH-repetition workload
            # (long greedy decode: the model's own repetition loops are
            # exactly what the prompt-lookup drafter predicts) and a
            # LOW-repetition one (short decode: loops never develop, the
            # drafter backs off).  Shape warmup is untimed; the measured
            # windows report tok/s, accept rate, programs/token and
            # on-path recompiles.  Own try like the other legs.
            try:
                from deepspeed_tpu.inference.v2 import (
                    KVCacheConfig as _KVC)
                from deepspeed_tpu.telemetry import metrics as tmet
                page = 16
                smodel = LlamaForCausalLM(model_size, max_seq_len=256)
                scfg = smodel.cfg
                s_kv = _KVC(num_layers=scfg.num_layers,
                            kv_heads=scfg.kv_heads,
                            head_dim=scfg.dims_per_head, page_size=page,
                            num_pages=512)
                seng = InferenceEngineV2(RaggedInferenceModel(
                    scfg, meta.unbox(smodel.init_params(jax.random.key(0))),
                    kv_config=s_kv))
                spec_on = ServingOptimizationConfig(
                    prefix_caching=False, speculative=True)
                spec_off = ServingOptimizationConfig(prefix_caching=False)
                n_spec = min(n_req, 8)
                # HIGH-repetition: constant-token prompts + long greedy
                # decode — the model falls into its own repetition loop
                # almost immediately and the prompt-lookup drafter's
                # cyclic extrapolation predicts it (the bench analogue
                # of extraction/quote-heavy production traffic).
                # LOW-repetition: random prompts, short decode — loops
                # never develop, the drafter backs off.
                hi_prompts = [[7 % scfg.vocab_size] * 16
                              for _ in range(n_spec)]
                lo_prompts = [rng.integers(0, scfg.vocab_size,
                                           size=16).tolist()
                              for _ in range(n_spec)]
                sp_hi = SamplingParams(max_new_tokens=96, temperature=0.0)
                sp_lo = SamplingParams(max_new_tokens=8, temperature=0.0)

                def spec_leg(prompt_set, sp_leg, engine=None,
                             on_serving=None, n_leg=None):
                    leg_eng = engine or seng
                    leg_on = on_serving or spec_on
                    n_leg = n_leg or n_spec
                    # untimed shape warmup for BOTH serving variants
                    run(range(n_leg), serving=spec_off,
                        prompt_set=prompt_set, engine=leg_eng, sp_=sp_leg)
                    run(range(n_leg), serving=leg_on,
                        prompt_set=prompt_set, engine=leg_eng, sp_=sp_leg)
                    t_off, _, d_off = run(range(n_leg), serving=spec_off,
                                          prompt_set=prompt_set,
                                          engine=leg_eng, sp_=sp_leg)
                    serving_counters.reset()
                    dr0 = tmet.FASTGEN_SPEC_DRAFTED.value
                    ac0 = tmet.FASTGEN_SPEC_ACCEPTED.value
                    co0 = tmet.FASTGEN_COMPILE_ON_PATH.value
                    t_on, _, d_on = run(range(n_leg), serving=leg_on,
                                        prompt_set=prompt_set,
                                        engine=leg_eng, sp_=sp_leg)
                    drafted = tmet.FASTGEN_SPEC_DRAFTED.value - dr0
                    accepted = tmet.FASTGEN_SPEC_ACCEPTED.value - ac0
                    return {
                        "off_tok_s": round(d_off / t_off, 1),
                        "on_tok_s": round(d_on / t_on, 1),
                        "accept_rate": (round(accepted / drafted, 4)
                                        if drafted else 0.0),
                        "programs_per_token": round(
                            serving_counters.programs / max(d_on, 1), 4),
                        "compile_on_path":
                            tmet.FASTGEN_COMPILE_ON_PATH.value - co0,
                    }

                hi = spec_leg(hi_prompts, sp_hi)
                result["fastgen_spec_decode_tok_s"] = hi["on_tok_s"]
                result["fastgen_spec_off_decode_tok_s"] = hi["off_tok_s"]
                result["fastgen_spec_accept_rate"] = hi["accept_rate"]
                result["fastgen_spec_programs_per_token"] = \
                    hi["programs_per_token"]
                result["fastgen_spec_compile_on_path_total"] = \
                    hi["compile_on_path"]
                lo = spec_leg(lo_prompts, sp_lo)
                result["fastgen_spec_lowrep_decode_tok_s"] = lo["on_tok_s"]
                result["fastgen_spec_lowrep_off_decode_tok_s"] = \
                    lo["off_tok_s"]
                result["fastgen_spec_lowrep_accept_rate"] = \
                    lo["accept_rate"]
                # MODEL-drafted low-repetition leg (ISSUE 17): the same
                # random prompts the n-gram drafter backs off on, long
                # greedy decode, drafts from the in-program draft head.
                # Self-draft acceptance is repetition-INDEPENDENT, so
                # this is exactly the workload where the model drafter
                # must hold its >=1.5x over spec-off (dispatch
                # amortization: Q tokens committed per program launch).
                # Own engine: the draft head (params + the parallel
                # draft-KV array) is engine-level state.
                from deepspeed_tpu.inference.v2 import \
                    RaggedInferenceEngineConfig as _REC
                spec_model_on = ServingOptimizationConfig(
                    prefix_caching=False, speculative=True,
                    spec_drafter="model")
                m_econf = _REC()
                m_econf.serving = spec_model_on
                # pool sized to THIS leg's working set (2 rows x 7
                # pages, x2 for the parallel draft-KV array), not the
                # 512-page pool the 8-row legs need: paged attention
                # gathers over the whole pool, and on CPU that O(pages)
                # compute term buries the per-program dispatch overhead
                # speculation exists to amortize
                m_kv = _KVC(num_layers=scfg.num_layers,
                            kv_heads=scfg.kv_heads,
                            head_dim=scfg.dims_per_head, page_size=page,
                            num_pages=64)
                mdeng = InferenceEngineV2(
                    RaggedInferenceModel(
                        scfg,
                        meta.unbox(smodel.init_params(jax.random.key(0))),
                        kv_config=m_kv),
                    m_econf)
                sp_mo = SamplingParams(max_new_tokens=96, temperature=0.0)
                # batch 2, not n_spec: speculation is a SMALL-batch
                # latency play — per-program dispatch overhead is the
                # cost it amortizes, and at batch 8 the CPU-debug run
                # is compute-bound (self-draft pays ~2x per-token
                # FLOPs), burying the win it exists to measure
                n_model = min(n_spec, 2)
                mo = spec_leg(lo_prompts, sp_mo, engine=mdeng,
                              on_serving=spec_model_on, n_leg=n_model)
                result["fastgen_spec_model_decode_tok_s"] = mo["on_tok_s"]
                result["fastgen_spec_model_off_decode_tok_s"] = \
                    mo["off_tok_s"]
                result["fastgen_spec_model_accept_rate"] = \
                    mo["accept_rate"]
                result["fastgen_spec_model_compile_on_path_total"] = \
                    mo["compile_on_path"]
            except Exception as e:  # noqa: BLE001
                sys.stderr.write(f"bench: fastgen spec leg failed: {e}\n")
                raise
        if os.environ.get("BENCH_CHAOS", "0") != "0":
            # chaos leg (ISSUE 7): the same workload under a ~10%
            # injected-fault rate (poisoned requests + KV-allocator
            # OOM), with graceful degradation on — measures how much
            # decode throughput survives and what fraction of requests
            # the degradation ladder sheds.  Off by default so headline
            # legs stay comparable; its own try like the SLO leg.
            from deepspeed_tpu.runtime.fault_injection import \
                get_fault_injector
            try:
                from deepspeed_tpu.telemetry import metrics as tmet
                chaos_serving = ServingOptimizationConfig(
                    prefix_caching=False, shed_unservable=True)
                run(range(n_req), serving=chaos_serving)  # warm shapes
                fi = get_fault_injector()
                err0 = (tmet.FASTGEN_SHED.value
                        + tmet.FASTGEN_EXPIRED.value
                        + tmet.FASTGEN_REQUEST_ERROR.value)
                inj0 = tmet.CHAOS_INJECTED.value
                # the poison site is probed at EVERY per-step admission
                # of a request (and steady-state async decode chains
                # past admission entirely), so a bare probability both
                # compounds per token on host-path steps and misses on
                # chained ones.  Deterministic instead: poison ~10% of
                # requests at evenly-spaced admission ordinals of the
                # initial wave, plus a bounded dose of allocator OOMs.
                budget = max(1, round(0.1 * n_req))
                poison_at = [round((i + 0.5) * n_req / budget)
                             for i in range(budget)]
                fi.configure({
                    "fastgen.poison_request": {"at_calls": poison_at},
                    "kv.alloc_oom": {"p": 0.2, "max_fires": budget},
                }, seed=int(os.environ.get("BENCH_CHAOS_SEED", "0")))
                try:
                    c_total, _, c_done = run(range(n_req),
                                             serving=chaos_serving)
                finally:
                    fi.disarm()
                errs = (tmet.FASTGEN_SHED.value
                        + tmet.FASTGEN_EXPIRED.value
                        + tmet.FASTGEN_REQUEST_ERROR.value) - err0
                result["fastgen_chaos_decode_tok_s"] = round(
                    c_done / c_total, 1)
                result["fastgen_chaos_shed_rate"] = round(
                    errs / n_req, 3)
                result["fastgen_chaos_injected_total"] = \
                    tmet.CHAOS_INJECTED.value - inj0
                # preemption-tolerance sub-leg (ISSUE 8): snapshot a
                # live scheduler mid-workload, restore into a fresh
                # scheduler, and measure how much of the warm prefix
                # cache survives the restart.  A dedicated small-page
                # engine (the prefix leg's pattern: the CPU-debug
                # model's 64-token context can't hold full pages +
                # suffix on 64-token pages).
                import tempfile
                from deepspeed_tpu.inference.v2 import KVCacheConfig
                page = 16
                smodel = LlamaForCausalLM(model_size, max_seq_len=256)
                scfg = smodel.cfg
                s_kv = KVCacheConfig(
                    num_layers=scfg.num_layers, kv_heads=scfg.kv_heads,
                    head_dim=scfg.dims_per_head, page_size=page,
                    num_pages=256)
                s_params = meta.unbox(
                    smodel.init_params(jax.random.key(0)))
                s_rmodel = RaggedInferenceModel(scfg, s_params,
                                                kv_config=s_kv)
                seng = InferenceEngineV2(s_rmodel)
                prefix = rng.integers(0, scfg.vocab_size, size=4 * page)
                sp_s = SamplingParams(max_new_tokens=16, temperature=0.0)

                def s_prompts(n, seed):
                    r = np.random.default_rng(seed)
                    return [np.concatenate(
                        [prefix, r.integers(0, scfg.vocab_size, size=12)]
                    ).tolist() for _ in range(n)]

                def s_sched():
                    sched = FastGenScheduler(seng)
                    return sched

                # warm shapes + the prefix cache, like production
                sched = s_sched()
                for i, p in enumerate(s_prompts(8, 1)):
                    sched.submit(i, p, sp_s)
                sched.run_to_completion()
                # interrupt a fresh wave mid-flight
                sched = s_sched()
                for i, p in enumerate(s_prompts(8, 2)):
                    sched.submit(i, p, sp_s)
                for _ in range(4):
                    sched.step()
                snap_path = os.path.join(tempfile.gettempdir(),
                                         f"ds_snap_{os.getpid()}.bin")
                t0 = time.perf_counter()
                sched.snapshot(snap_path)
                result["fastgen_snapshot_ms"] = round(
                    (time.perf_counter() - t0) * 1e3, 2)
                result["fastgen_snapshot_bytes"] = \
                    os.path.getsize(snap_path)
                # a "fresh replica": same pool, emptied
                for uid in list(seng.state_manager._seqs):
                    seng.flush(uid)
                seng.reset_prefix_cache()
                sched2 = FastGenScheduler(seng)
                t0 = time.perf_counter()
                sched2.restore(snap_path)
                result["fastgen_restore_ms"] = round(
                    (time.perf_counter() - t0) * 1e3, 2)
                sched2.run_to_completion()
                # post-restore warm TTFT: new requests sharing the
                # prefix hit the RESTORED cache
                first_t = {}
                post = FastGenScheduler(seng)
                t0 = time.perf_counter()
                for i, p in enumerate(s_prompts(8, 3)):
                    post.submit(100 + i, p, sp_s)
                while post.has_work:
                    out = post.step()
                    now = time.perf_counter()
                    for uid in out:
                        first_t.setdefault(uid, now)
                ttfts = sorted(t - t0 for t in first_t.values())
                if ttfts:
                    result["fastgen_restore_warm_ttft_p50_ms"] = round(
                        1e3 * ttfts[len(ttfts) // 2], 1)
                os.unlink(snap_path)
            except Exception as e:  # noqa: BLE001
                get_fault_injector().disarm()
                sys.stderr.write(f"bench: fastgen chaos leg failed: "
                                 f"{e}\n")
                raise
        if os.environ.get("BENCH_REPLAY", "0") != "0":
            # replay leg (ISSUE 9): drive the checked-in 200-request
            # sample trace through tools/replay_trace.py — anonymized
            # prompts reproducing the recorded length / prefix-sharing
            # structure, untimed shape warmup, then a measured
            # full-speed replay.  replay_compile_on_path_total is the
            # ROADMAP item 5 success metric over a replayed trace (0 =
            # the warmed lattice covered everything the trace forms).
            # Off by default (headline legs stay comparable); own try.
            try:
                sys.path.insert(0, os.path.dirname(
                    os.path.abspath(__file__)))
                from tools.replay_trace import run_replay
                trace_path = os.environ.get(
                    "BENCH_REPLAY_TRACE",
                    os.path.join(os.path.dirname(os.path.abspath(
                        __file__)), "tools", "traces",
                        "sample_200.jsonl"))
                out = run_replay(trace_path)
                rep = out["replay"]
                result["replay_requests"] = rep["requests_submitted"]
                result["replay_ttft_p50_ms"] = rep["ttft_p50_ms"]
                result["replay_decode_tok_s"] = rep["decode_tok_s"]
                result["replay_compile_on_path_total"] = \
                    rep["compile_on_path"]
                result["replay_structural_ok"] = \
                    out["diff"]["structural_ok"]
            except Exception as e:  # noqa: BLE001
                sys.stderr.write(f"bench: fastgen replay leg failed: "
                                 f"{e}\n")
                raise
        if os.environ.get("BENCH_FLEET", "0") != "0":
            # fleet leg (ISSUE 11): two live replica subprocesses
            # replay a synthetic workload; one is killed mid-replay
            # through the serving.preempt chaos site while the parent
            # federates both /snapshot endpoints, samples a fleet
            # time-series ring, and runs the SLO burn-rate evaluator
            # over it.  Emits aggregate tok/s and merged p99 TTFT
            # ACROSS the kill event plus the page/advice facts — the
            # ROADMAP item 1 controller's input signals, measured.
            # Off by default (spawns two engines); own try.
            try:
                sys.path.insert(0, os.path.dirname(
                    os.path.abspath(__file__)))
                from tools.fleetctl import run_kill_demo
                result.update(run_kill_demo())
            except Exception as e:  # noqa: BLE001
                sys.stderr.write(f"bench: fastgen fleet leg failed: "
                                 f"{e}\n")
                raise
        if os.environ.get("BENCH_DISAGG", "0") != "0":
            # disaggregated prefill/decode leg (ISSUE 13): the
            # replayed mixed trace (decode-weighted via
            # BENCH_DISAGG_GEN_SCALE) through the fused single-pool
            # scheduler and the two-pool disagg scheduler, both with
            # keyed sampling so the output-identity check covers the
            # trace's SAMPLED requests.  Emits prefill-pool MFU and
            # decode-pool HBM GB/s vs the fused baseline's gauges
            # (both must be strictly above), per-pool compiled /
            # enumerated program counts vs the fused lattice's (below),
            # handoff count/bytes/p50 ms, aggregate tok/s ratio,
            # on-path compiles (0), lost requests (0), and
            # disagg_tokenwise_identical.  Off by default (builds
            # three engines); own try.
            try:
                sys.path.insert(0, os.path.dirname(
                    os.path.abspath(__file__)))
                from tools.replay_trace import run_disagg_bench
                result.update(run_disagg_bench())
            except Exception as e:  # noqa: BLE001
                sys.stderr.write(f"bench: fastgen disagg leg failed: "
                                 f"{e}\n")
                raise
        if os.environ.get("BENCH_POOL", "0") != "0":
            # replica-pool leg (ISSUE 12): the replayed shared-prefix
            # trace through one replica, two round-robin replicas, two
            # affinity-routed replicas, and the affinity pool with an
            # abrupt replica KILL + scale-up ADD mid-replay (threaded
            # replicas, per-step pacing as the simulated device
            # budget, every engine pre-warmed).  Emits aggregate tok/s
            # vs single, affinity-vs-round-robin prefix hit rate, p99
            # TTFT before/after the kill, and migrated/lost request
            # counts — the ROADMAP item 1 acceptance numbers.  Off by
            # default (builds three engines); own try.
            try:
                sys.path.insert(0, os.path.dirname(
                    os.path.abspath(__file__)))
                from tools.fleetctl import run_pool_demo
                result.update(run_pool_demo())
            except Exception as e:  # noqa: BLE001
                sys.stderr.write(f"bench: fastgen pool leg failed: "
                                 f"{e}\n")
                raise
        if os.environ.get("BENCH_TIER", "0") != "0":
            # tiered-KV leg (ISSUE 16): (1) int8 pages vs fp at an
            # EQUAL device byte budget on the replayed trace —
            # resident-sequence capacity from the allocator's own
            # bytes_per_page accounting plus measured TTFT p99
            # before/after; (2) a device-starved engine backed by the
            # host/disk prefix tier, warm-wave tier hit rates mined
            # from the replay's own workload ledger, promote-batch
            # p50; (3) cross-replica page fetch TTFT vs
            # recompute-prefill under an identical backlog shape.
            # check_bench gates: resident ratio >= 1.7x, TTFT p99 not
            # up >15%, tier actually warming, fetch beating recompute,
            # zero on-path compiles.  Off by default (builds five
            # engines); own try.
            try:
                sys.path.insert(0, os.path.dirname(
                    os.path.abspath(__file__)))
                from tools.replay_trace import run_tier_bench
                result.update(run_tier_bench())
            except Exception as e:  # noqa: BLE001
                sys.stderr.write(f"bench: fastgen tier leg failed: "
                                 f"{e}\n")
                raise
        if os.environ.get("BENCH_COLDSTART", "0") != "0":
            # cold-start leg (ISSUE 14): three-way restore-to-first-
            # token comparison across REAL process boundaries — cold
            # process with no compile cache (true compiles), cold
            # process against a warm persistent cache (disk loads),
            # and a warm in-process control — plus precompile walls,
            # compile-cache hit/true-compile counters, and the hard
            # recompile-proof facts (replay compile_on_path == 0, zero
            # true compiles, tokenwise parity).  Off by default
            # (spawns three engine subprocesses); own try.
            try:
                sys.path.insert(0, os.path.dirname(
                    os.path.abspath(__file__)))
                from tools.coldstart_smoke import run_coldstart_bench
                result.update(run_coldstart_bench())
            except Exception as e:  # noqa: BLE001
                sys.stderr.write(f"bench: fastgen coldstart leg "
                                 f"failed: {e}\n")
                raise
        return result
    except Exception as e:  # noqa: BLE001 — a failed leg fails the run
        sys.stderr.write(f"bench: fastgen leg failed: {e}\n")
        raise


def main():
    if os.environ.get("BENCH_SWEEP"):
        return _sweep()  # parent never touches the chip: children own it
    _train_and_report(*_require_chip())


def _sweep():
    """MFU sweep: try remat policy x micro-batch x model size with short
    runs, each in its own SUBPROCESS (a config that OOMs must not kill
    the sweep, and only one process may hold the chip at a time — the
    parent never initializes a backend), then rerun the winner fully and
    pass its JSON line through as THE artifact."""
    import subprocess

    def run_child(env_over, steps, fastgen, timeout):
        env = dict(os.environ)
        env.update(env_over)
        env.update(BENCH_STEPS=steps, BENCH_FASTGEN=fastgen, BENCH_SWEEP="")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, stdout=subprocess.PIPE, stderr=sys.stderr,
            text=True, timeout=timeout, start_new_session=True)
        lines = proc.stdout.strip().splitlines()
        return json.loads(lines[-1]) if lines else {}

    grid = []
    for model in os.environ.get("BENCH_SWEEP_MODELS", "1b,2b").split(","):
        for mbs in os.environ.get("BENCH_SWEEP_BS", "4,8,16").split(","):
            for remat in os.environ.get(
                    "BENCH_SWEEP_REMAT",
                    "save_attn_out,dots_with_no_batch_dims_saveable").split(","):
                grid.append((model.strip(), mbs.strip(), remat.strip()))
    results = []
    for model, mbs, remat in grid:
        try:
            r = run_child({"BENCH_MODEL": model, "BENCH_BS": mbs,
                           "BENCH_REMAT": remat}, steps="3", fastgen="0",
                          timeout=float(os.environ.get(
                              "BENCH_SWEEP_TIMEOUT", "420")))
            if r.get("unit") == "tokens/s/chip":
                results.append((r["vs_baseline"], model, mbs, remat))
                sys.stderr.write(
                    f"sweep: {model} bs={mbs} {remat}: "
                    f"{r['value']} tok/s MFU={r['vs_baseline']}\n")
            else:
                sys.stderr.write(
                    f"sweep: {model} bs={mbs} {remat}: {r}\n")
        except Exception as e:  # noqa: BLE001
            sys.stderr.write(f"sweep: {model} bs={mbs} {remat} failed: {e}\n")
    if not results:
        raise SystemExit("bench.py: sweep produced no successful configs")
    results.sort(reverse=True)
    _, model, mbs, remat = results[0]
    sys.stderr.write(f"sweep winner: {model} bs={mbs} {remat}; full run\n")
    final = run_child({"BENCH_MODEL": model, "BENCH_BS": mbs,
                       "BENCH_REMAT": remat},
                      steps=os.environ.get("BENCH_STEPS", "10"),
                      fastgen=os.environ.get("BENCH_FASTGEN", "1"),
                      timeout=1800)
    if "value" not in final:
        raise SystemExit(f"bench.py: sweep winner ({model} bs={mbs} "
                         f"{remat}) rerun returned no metric: {final}")
    final["swept_configs"] = len(grid)
    print(json.dumps(final), flush=True)


def _train_and_report(jax, n_chips, peak_flops):
    import deepspeed_tpu as dst
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    model = LlamaForCausalLM(MODEL_SIZE, max_seq_len=SEQ_LEN)
    config = {
        "train_micro_batch_size_per_gpu": MICRO_BS,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 3},
        "bf16": {"enabled": True, "master_weights": False},
        "steps_per_print": 10 ** 9,
        "tpu": {"remat_policy": REMAT_POLICY},
    }
    if os.environ.get("BENCH_COMM", "0") != "0":
        # quantized bucketed gradient wire (CollectiveScheduler); the
        # scheduler needs unrolled layers on tensor/seq meshes, but the
        # bench mesh is pure batch axes so scan_layers stays on
        config["comm_optimization"] = {"enabled": True}
    engine, _, _, _ = dst.initialize(model=model, config=config)
    bs = engine.train_batch_size()
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, model.cfg.vocab_size, size=(bs, SEQ_LEN)).astype(np.int32)}

    engine.train_batch(batch)  # compile + warmup
    engine.train_batch(batch)
    jax.block_until_ready(engine.state.params)

    t0 = time.perf_counter()
    for _ in range(STEPS):
        engine.train_batch(batch)
    jax.block_until_ready(engine.state.params)
    dt = time.perf_counter() - t0

    tokens_per_step = bs * SEQ_LEN
    tok_s = tokens_per_step * STEPS / dt
    tok_s_chip = tok_s / n_chips

    # MFU (PaLM-appendix convention): per-token fwd+bwd model FLOPs =
    # 6*N (matmuls) + 6*L*S*H (causal attention scores+values, the
    # 12*L*S*H full-attention term halved) — attention is real work the
    # MXU does and standard MFU accounting includes it
    n_params = model.cfg.n_params()
    attn_flops = 6.0 * model.cfg.num_layers * SEQ_LEN * model.cfg.hidden_size
    mfu = (6.0 * n_params + attn_flops) * tok_s / (peak_flops * n_chips)

    result = {
        "metric": f"llama-{MODEL_SIZE} bf16 train tokens/sec/chip (seq {SEQ_LEN})",
        "value": round(tok_s_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu, 4),
        "remat_policy": REMAT_POLICY,
        "micro_bs": MICRO_BS,
    }
    # comm accounting: lets the bench trajectory attribute future wins
    # to wire reduction vs compute.  Exact when the CollectiveScheduler
    # runs (static bucket plan); estimated for the compiler-psum path.
    comm = engine.comm_stats()
    gas = engine.gradient_accumulation_steps()
    if comm is not None:
        result["comm_bytes_per_step"] = comm["comm_bytes_per_step"]
        result["comm_quantized_fraction"] = comm["comm_quantized_fraction"]
        result["comm_buckets"] = comm["bucket_count"]
    else:
        batch_world = engine.topology.batch_shard_size
        result["comm_bytes_per_step"] = (
            8 * int(n_params) * gas if batch_world > 1 else 0)
        result["comm_quantized_fraction"] = 0.0
        result["comm_bytes_estimated"] = True
    device = jax.devices()[0]
    result["device"] = {"platform": device.platform,
                        "kind": device.device_kind, "count": n_chips}
    if os.environ.get("BENCH_SLO", "1") != "0":
        # goodput coda (ISSUE 5): a couple of telemetry-ON steps OUTSIDE
        # the timed window feed the watchdog's goodput phase
        # accumulators; the ratio is read back immediately (the gauge is
        # wall-clock-relative, so reading it later — e.g. from the
        # fastgen SLO leg — would dilute it with inference wall time).
        # Headline timings above stay telemetry-off and comparable.
        try:
            from deepspeed_tpu import telemetry
            from deepspeed_tpu.telemetry import metrics as tmet
            was_enabled = telemetry.enabled()
            telemetry.enable()
            try:
                for _ in range(2):
                    engine.train_batch(batch)
                jax.block_until_ready(engine.state.params)
            finally:
                telemetry.set_enabled(was_enabled)
            global _TRAIN_GOODPUT
            _TRAIN_GOODPUT = round(
                float(tmet.TRAIN_GOODPUT_RATIO.value), 4)
            result["train_goodput_ratio"] = _TRAIN_GOODPUT
        except Exception as e:  # noqa: BLE001 — a failed leg fails the run
            sys.stderr.write(f"bench: train goodput coda failed: {e}\n")
            raise
    del engine  # release training buffers before the inference leg
    if os.environ.get("BENCH_FASTGEN", "1") != "0":
        result.update(bench_fastgen(jax))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
