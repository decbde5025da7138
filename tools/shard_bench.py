#!/usr/bin/env python
"""Sharded-serving bench leg (ISSUE 18): tp=1 vs tp=N fp vs tp=N int8.

Three debug engines serve the same shared-prefix workload (greedy AND
keyed-sampled rows) on a simulated ``--xla_force_host_platform_device_
count`` mesh: the unsharded baseline, the tp-way sharded engine with
the GSPMD fp logits all-gather, and the tp-way engine with the int8
block-scaled in-program collective.  The leg emits, per arm, measured
decode tok/s over a warmed pass, tokenwise parity against the tp=1
baseline, the analytic collective wire bytes alongside what the same
dispatches would have moved at fp, and the on-path compile count of
the measured pass (must be 0 — warmup covers the key set).

check_bench's ``shard_findings`` gates on: the fp arm tokenwise
identical to tp=1 on EVERY row (sampled included), the int8 arm
tokenwise identical on the greedy rows (a keyed draw thresholds on
exact logit values, so the bounded int8 error may legitimately flip a
sampled token — the sampled-row agreement is reported as a rate), int8
wire bytes STRICTLY below fp wire bytes, and zero on-path compiles.
Numbers are CPU-debug-relative — the simulated
mesh times shard arithmetic on host cores, so tok/s across arms is a
sanity band, not a speedup claim; the wire-byte ratio is exact.

This is a CPU tool: the parent never imports JAX (a process that has
touched JAX holds the chip its child would need) and starts this file as
a ``--worker`` subprocess pinned to the CPU platform with the forced
device count in XLA_FLAGS; the worker labels its output
``"platform": "cpu"``.  It is not a leg of ``bench.py``, which holds the
chip.  The real tp path on chips is ``python chip_smoke.py --chips 4``.

Usage::

    python tools/shard_bench.py            # standalone (spawns worker)
    python tools/shard_bench.py --worker   # in a forced-mesh process
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def run_shard_bench() -> Dict[str, Any]:
    """Spawn the forced-mesh CPU worker and return its
    ``fastgen_shard_*`` facts (the host device count is read once at
    jax import, so the mesh needs a process of its own)."""
    tp = max(2, int(os.environ.get("BENCH_SHARD_TP", "2")))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={tp}")
    env["JAX_PLATFORMS"] = "cpu"     # a simulated mesh, never the chip
    budget = float(os.environ.get("BENCH_SHARD_TIMEOUT", "600"))
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker"],
        stdout=subprocess.PIPE, stderr=sys.stderr, env=env, text=True,
        timeout=budget)
    if proc.returncode != 0:
        raise RuntimeError(
            f"shard bench worker exited {proc.returncode}")
    # the worker prints exactly one JSON object as its last line
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _worker() -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta as flax_meta

    from deepspeed_tpu.inference.v2 import (
        InferenceEngineV2, KVCacheConfig, RaggedInferenceEngineConfig,
        RaggedInferenceModel, SamplingParams, ServingOptimizationConfig,
        StateManagerConfig, generate)
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.telemetry import metrics as tm
    from tools.replay_trace import _reset_engine

    tp = max(2, int(os.environ.get("BENCH_SHARD_TP", "2")))
    n_req = int(os.environ.get("BENCH_SHARD_REQS", "12"))
    max_new = int(os.environ.get("BENCH_SHARD_NEW_TOKENS", "24"))

    model_def = LlamaForCausalLM("debug", max_seq_len=256,
                                 dtype=jnp.float32)
    cfg = model_def.cfg
    params = flax_meta.unbox(model_def.init_params(jax.random.key(0)))

    # shared-prefix workload, greedy and keyed-sampled rows interleaved
    # — parity must hold on SAMPLED requests too (keyed sampling is
    # schedule- and shard-invariant by construction)
    rng = np.random.default_rng(0)
    prefix = [int(t) for t in rng.integers(0, cfg.vocab_size, 24)]
    prompts, sampling = [], []
    greedy = SamplingParams(max_new_tokens=max_new)
    keyed = SamplingParams(temperature=0.8, top_k=20,
                           max_new_tokens=max_new)
    for i in range(n_req):
        tail = [int(t)
                for t in rng.integers(0, cfg.vocab_size, 4 + (i % 13))]
        prompts.append(prefix + tail)
        sampling.append(keyed if i % 2 else greedy)

    def build(serving):
        kv_cfg = KVCacheConfig(num_layers=cfg.num_layers,
                               kv_heads=cfg.kv_heads,
                               head_dim=cfg.dims_per_head, page_size=16,
                               num_pages=128, dtype=jnp.float32)
        model = RaggedInferenceModel(cfg, params, kv_config=kv_cfg)
        econf = RaggedInferenceEngineConfig(
            state_manager=StateManagerConfig(
                max_tracked_sequences=8,
                max_ragged_sequence_count=8,
                max_ragged_batch_size=256))
        econf.serving = serving
        return InferenceEngineV2(model, econf)

    arms = [
        ("tp1", ServingOptimizationConfig(keyed_sampling=True)),
        ("fp", ServingOptimizationConfig(keyed_sampling=True,
                                         tp_degree=tp)),
        ("int8", ServingOptimizationConfig(
            keyed_sampling=True, tp_degree=tp,
            tp_collective_quantization="int8")),
    ]
    out: Dict[str, Any] = {
        "platform": jax.devices()[0].platform,   # cpu: a simulated mesh
        "fastgen_shard_tp": tp,
        "fastgen_shard_reqs": n_req,
        "fastgen_shard_new_tokens": max_new,
    }
    tokens_by_arm: Dict[str, Any] = {}
    compile_on_path = 0
    for name, serving in arms:
        engine = build(serving)
        generate(engine, prompts, sampling)      # untimed shape warmup
        _reset_engine(engine)    # measured pass starts from cold state
        b0 = tm.FASTGEN_SHARD_COLLECTIVE_BYTES.value
        f0 = tm.FASTGEN_SHARD_COLLECTIVE_FP_BYTES.value
        c0 = tm.FASTGEN_COMPILE_ON_PATH.value
        t0 = time.perf_counter()
        toks = generate(engine, prompts, sampling)
        wall = time.perf_counter() - t0
        tokens_by_arm[name] = toks
        gen = sum(len(t) for t in toks)
        out[f"fastgen_shard_{name}_decode_tok_s"] = round(
            gen / wall, 2) if wall > 0 else 0.0
        compile_on_path += int(tm.FASTGEN_COMPILE_ON_PATH.value - c0)
        if name != "tp1":
            out[f"fastgen_shard_{name}_wire_bytes"] = int(
                tm.FASTGEN_SHARD_COLLECTIVE_BYTES.value - b0)
            out[f"fastgen_shard_{name}_wire_fp_bytes"] = int(
                tm.FASTGEN_SHARD_COLLECTIVE_FP_BYTES.value - f0)
    # the fp all-gather is bit-identical — parity over EVERY row,
    # sampled included.  The int8 collective admits a bounded logit
    # error, and a keyed draw thresholds on exact values, so its
    # parity-grade bar is the greedy rows (argmax stable whenever the
    # top-1 margin exceeds the per-shard quantization step); sampled-
    # row agreement is reported as a rate, not gated
    out["fastgen_shard_parity_fp"] = int(
        tokens_by_arm["fp"] == tokens_by_arm["tp1"])
    g = [i for i in range(n_req) if not i % 2]
    out["fastgen_shard_parity_int8"] = int(
        [tokens_by_arm["int8"][i] for i in g]
        == [tokens_by_arm["tp1"][i] for i in g])
    s = [i for i in range(n_req) if i % 2]
    out["fastgen_shard_int8_sampled_agree_rate"] = round(
        sum(tokens_by_arm["int8"][i] == tokens_by_arm["tp1"][i]
            for i in s) / len(s), 4) if s else None
    fp_wire = out["fastgen_shard_fp_wire_bytes"]
    int8_wire = out["fastgen_shard_int8_wire_bytes"]
    out["fastgen_shard_wire_ratio"] = (
        round(int8_wire / fp_wire, 4) if fp_wire else None)
    out["fastgen_shard_compile_on_path_total"] = compile_on_path
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", action="store_true",
                    help="run the measurement in THIS process (the "
                    "forced-mesh subprocess mode)")
    args = ap.parse_args(argv)
    out = _worker() if args.worker else run_shard_bench()
    print(json.dumps(out, indent=None if args.worker else 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
