#!/usr/bin/env python
"""One serving replica as a process (ISSUE 11): the unit the fleet
federation scrapes.

Builds a CPU-debug engine, starts the telemetry endpoint on an
EPHEMERAL port (the ``DS_METRICS_PORT=0`` satellite — N replicas on a
host never collide), enables the time-series sampler, and drives a
deterministic synthetic workload in rounds.  The parent (a federation
test or ``tools/fleetctl.py --smoke`` / ``--kill-demo``) reads the
handshake line::

    FLEET_REPLICA ready label=<label> port=<port> pid=<pid>

then scrapes ``http://127.0.0.1:<port>/snapshot?raw=1`` like any other
replica.  Arm ``DS_CHAOS="serving.preempt:at=<N>"`` in the child's
environment to kill it mid-replay through the ISSUE 8 chaos site — the
injected preemption exits the process (status 17) exactly like a
preempted spot VM, server and all.

The workload is either synthetic (random prompts) or — with
``--trace <ledger.jsonl>`` — a CAPTURED workload trace replayed
through the ISSUE 9 machinery (``tools/replay_trace.py``): anonymized
prompts synthesized from the recorded page digests, recorded sampling
params, ``max_new_tokens = gen_len``, an engine sized to the trace.
The checked-in ``tools/traces/sample_200.jsonl`` is what the fleet
kill demo replays.

Progress lines (``FLEET_REPLICA round=<n> done``, ``... done``,
``... preempted``) are the parent's pacing signals; ``--step-sleep-s``
paces the step loop so the token rate is steady enough for burn-rate
windows to read.  After the workload the replica lingers
(``--linger-s``) so a controller can scrape final state.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

EXIT_PREEMPTED = 17


def build_engine(page_size: int, num_pages: int, max_seqs: int):
    import jax
    import jax.numpy as jnp
    from flax.core import meta
    from deepspeed_tpu.inference.v2 import (
        InferenceEngineV2, KVCacheConfig, RaggedInferenceEngineConfig,
        RaggedInferenceModel, StateManagerConfig)
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    # fp32 like the test engines: random-init bf16 argmax ties make
    # greedy decode path-dependent across compiled shapes
    model_def = LlamaForCausalLM("debug", max_seq_len=256,
                                 dtype=jnp.float32)
    params = meta.unbox(model_def.init_params(jax.random.key(0)))
    cfg = model_def.cfg
    kv_cfg = KVCacheConfig(num_layers=cfg.num_layers,
                           kv_heads=cfg.kv_heads,
                           head_dim=cfg.dims_per_head,
                           page_size=page_size, num_pages=num_pages,
                           dtype=jnp.float32)
    model = RaggedInferenceModel(cfg, params, kv_config=kv_cfg)
    econf = RaggedInferenceEngineConfig(
        state_manager=StateManagerConfig(
            max_tracked_sequences=max_seqs,
            max_ragged_sequence_count=max_seqs,
            max_ragged_batch_size=256))
    return InferenceEngineV2(model, econf)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--label", default="r0")
    ap.add_argument("--port", type=int, default=0,
                    help="metrics port (0 = ephemeral, the default)")
    ap.add_argument("--trace", default="",
                    help="replay this captured workload-trace JSONL "
                    "(anonymized prompt synthesis, recorded sampling "
                    "params) instead of the synthetic workload")
    ap.add_argument("--trace-limit", type=int, default=8,
                    help="replay only the first N trace requests per "
                    "round")
    ap.add_argument("--requests", type=int, default=4,
                    help="concurrent requests per round (synthetic "
                    "workload)")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=17)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--step-sleep-s", type=float, default=0.0,
                    help="pace the step loop (steady token rate for "
                    "burn-rate windows)")
    ap.add_argument("--ts-interval-s", type=float, default=0.1,
                    help="time-series sampler cadence")
    ap.add_argument("--linger-s", type=float, default=30.0,
                    help="keep serving /snapshot after the workload")
    args = ap.parse_args(argv)

    import numpy as np
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference.v2 import FastGenScheduler, SamplingParams
    from deepspeed_tpu.runtime.fault_injection import \
        InjectedPreemptionFault

    telemetry.enable()
    telemetry.get_timeseries().configure(interval_s=args.ts_interval_s,
                                         retention_s=300.0)
    if args.trace:
        # replay a CAPTURED workload: the ISSUE 9 synthesis (prompts
        # from recorded page digests, engine sized to the trace)
        from tools.replay_trace import (build_replay_engine, load_trace,
                                        synthesize_prompts)
        trace = load_trace(args.trace)
        requests = [r for r in trace["requests"]
                    if r.get("outcome") == "ok"][:args.trace_limit]
        meta = trace["meta"]
        engine = build_replay_engine(meta, requests,
                                     max_seqs=len(requests))
        vocab = min(int(meta.get("vocab_size", 0))
                    or engine.model.cfg.vocab_size,
                    engine.model.cfg.vocab_size)
        prompts = synthesize_prompts(
            requests, int(meta.get("page_size", 16)), vocab,
            seed=args.seed)
        workload = [(prompts[i].tolist(), SamplingParams(
            temperature=float(r.get("temperature", 0.0)),
            top_k=int(r.get("top_k", 0)),
            top_p=float(r.get("top_p", 1.0)),
            max_new_tokens=max(1, int(r["gen_len"]))))
            for i, r in enumerate(requests)]
    else:
        engine = build_engine(args.page_size, args.num_pages,
                              max_seqs=args.requests)
        rng = np.random.default_rng(args.seed)
        vocab = engine.model.cfg.vocab_size
        sp = SamplingParams(max_new_tokens=args.max_new,
                            temperature=0.0)
        workload = [(rng.integers(0, vocab, args.prompt_len).tolist(),
                     sp) for _ in range(args.requests)]
    srv = telemetry.start_http_server(args.port)
    port = srv.server_address[1]
    print(f"FLEET_REPLICA ready label={args.label} port={port} "
          f"pid={os.getpid()}", flush=True)

    try:
        for rnd in range(args.rounds):
            sched = FastGenScheduler(engine)
            for i, (prompt, params) in enumerate(workload):
                sched.submit(rnd * len(workload) + i, prompt, params)
            while sched.has_work:
                sched.step()
                if args.step_sleep_s:
                    time.sleep(args.step_sleep_s)
            print(f"FLEET_REPLICA round={rnd} done", flush=True)
    except InjectedPreemptionFault:
        # the serving.preempt chaos site fired: die like a preempted
        # spot VM — abruptly, endpoint and all (os._exit skips atexit;
        # the federation must observe a replica that just vanishes)
        print("FLEET_REPLICA preempted", flush=True)
        sys.stdout.flush()
        os._exit(EXIT_PREEMPTED)
    print("FLEET_REPLICA done", flush=True)
    deadline = time.monotonic() + args.linger_s
    while time.monotonic() < deadline:
        time.sleep(0.2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
