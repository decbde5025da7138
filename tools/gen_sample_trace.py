#!/usr/bin/env python
"""Generate the checked-in sample workload trace (ISSUE 9 CI satellite).

Runs a deterministic 200-request mixed workload — four shared system
prompts (2-4 full pages each), a bimodal suffix-length distribution,
mostly-greedy sampling, submissions in waves so arrival offsets are
non-trivial — through a small-page debug FastGen engine with workload
capture on, and writes the resulting content-free ledger to
``tools/traces/sample_200.jsonl``.  Regenerate after a ledger schema
change::

    python tools/gen_sample_trace.py [--out tools/traces/sample_200.jsonl]

The trace is the fixture for the ``tools/ci.sh`` replay smoke.
"""

from __future__ import annotations

import argparse
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

N_REQUESTS = 200
PAGE = 16


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(
        REPO_ROOT, "tools", "traces", "sample_200.jsonl"))
    ap.add_argument("--requests", type=int, default=N_REQUESTS)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta as flax_meta
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference.v2 import (
        FastGenScheduler, InferenceEngineV2, KVCacheConfig,
        RaggedInferenceEngineConfig, RaggedInferenceModel,
        SamplingParams, StateManagerConfig)
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    model_def = LlamaForCausalLM("debug", max_seq_len=256,
                                 dtype=jnp.float32)
    cfg = model_def.cfg
    params = flax_meta.unbox(model_def.init_params(jax.random.key(0)))
    kv_cfg = KVCacheConfig(num_layers=cfg.num_layers,
                           kv_heads=cfg.kv_heads,
                           head_dim=cfg.dims_per_head, page_size=PAGE,
                           num_pages=512, dtype=jnp.float32)
    model = RaggedInferenceModel(cfg, params, kv_config=kv_cfg)
    eng = InferenceEngineV2(model, RaggedInferenceEngineConfig(
        state_manager=StateManagerConfig(
            max_tracked_sequences=32, max_ragged_sequence_count=32,
            max_ragged_batch_size=256)))

    rng = np.random.default_rng(9)
    systems = [rng.integers(0, cfg.vocab_size, pages * PAGE)
               for pages in (2, 2, 3, 4)]

    def prompt(i):
        sys_p = systems[int(rng.integers(0, len(systems)))]
        # bimodal suffix: short chat turns vs long few-shot tails
        sfx = int(rng.integers(3, 9) if rng.random() < 0.6
                  else rng.integers(24, 40))
        return np.concatenate(
            [sys_p, rng.integers(0, cfg.vocab_size, sfx)]).tolist()

    tmp = args.out + ".gen"
    if os.path.exists(tmp):
        os.unlink(tmp)
    wt = telemetry.get_workload_trace()
    wt.configure(tmp)
    sched = FastGenScheduler(eng)
    uid = 0
    # waves of 20 with the scheduler stepping in between, so arrival
    # offsets (and queue waits) are non-degenerate
    while uid < args.requests or sched.has_work:
        for _ in range(20):
            if uid >= args.requests:
                break
            greedy = rng.random() < 0.8
            sp = SamplingParams(
                max_new_tokens=int(rng.integers(4, 11)),
                temperature=0.0 if greedy else 0.8,
                top_k=0 if greedy else 40)
            sched.submit(uid, prompt(uid), sp)
            uid += 1
        for _ in range(6):
            if sched.has_work:
                sched.step()
    wt.close()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    os.replace(tmp, args.out)

    from replay_trace import load_trace
    trace = load_trace(args.out)
    ok = sum(1 for r in trace["requests"]
             if r.get("outcome") == "ok")
    print(f"gen_sample_trace: {args.out}: "
          f"{len(trace['requests'])} requests ({ok} ok), "
          f"{len(trace['key_counts'])} distinct step keys, "
          f"{len(trace['compiles'])} on-path compiles, "
          f"{os.path.getsize(args.out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
