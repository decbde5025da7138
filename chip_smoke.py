"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives both hot paths once, through the entry points a user calls, at the
full published widths of Mistral-7B-v0.1 (hidden 4096, FFN 14336, 32 heads /
8 KV heads, head_dim 128, sliding window 4096, vocab 32000) with the depth
cut to what one 16 GB TPU v5e chip holds.  Weights and data come from
``--seed``; nothing is read from the network.

    python chip_smoke.py             # one chip: device, train, serve, cache
    python chip_smoke.py --chips 4   # four chips: sharded train + tp serve,
                                     # each against its one-chip run

Every phase prints one JSON line; the last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
No accelerator, a wrong device count, or any phase that raises: the last
line is ``{"ok": false, ...}`` and the exit code is non-zero — no phase
continues on CPU.  Wall times on the phase lines are smoke timings (compile
included), not metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

SEQ_LEN = 2048
#: depth that fits 16 GB.  Training: bf16 ZeRO-3 keeps fp32 master + two Adam
#: moments (12 B/param) beside the fp32 gradient accumulator and the bf16
#: weights and gradients (8 B/param) — 262M embedding/head + 218M per layer
#: is 9.6 GB at one layer and 14 GB at two, before activations and the
#: [micro, seq, vocab] fp32 logits.  Serving: bf16 weights + the page pool.
TRAIN_LAYERS = 1
SERVE_LAYERS = 8
SERVE_PAGES = 1024          # x 64 tokens: a 65k-token pool beside the weights
#: floor for the share of later tokens two greedy runs agree on before
#: their first divergence.  At seeded random weights the top-2 logit gap is
#: often under one bf16 ulp, so two correct attention paths part ways at a
#: few percent of the tokens (0.56 measured kernel vs dense gather); a wrong
#: path agrees on ~1/vocab of them.  The first token of every request, which
#: has no earlier divergence to inherit, must match, except at a near-tie.
MIN_REST_AGREEMENT = 0.3
#: two runs' first tokens may differ where they are the two largest logits
#: of the prompt's plain forward and closer than this: the margin under
#: which the benchmark's probe skips a prompt (``probe.margin`` of
#: benchmark/configs/mistral-7b-serve-8l.json), because bf16 rounding alone
#: flips it.  Since a mixed step runs one trunk pass over decode rows and
#: prefill tokens (PR 30), a prompt's rounding follows what shares its step;
#: the flips measured then had float32 gaps of 0.005 and 0.027.
NEAR_TIE = 0.1
#: largest step-loss gap between the {fsdp: 4} mesh and one chip (bf16)
FSDP_LOSS_TOLERANCE = 0.05
#: pallas_call names as they appear in a compiled program's text
KERNEL_NAMES = {"flash": "flash_attention_fwd", "paged": "paged_attention"}


def mistral_7b(num_layers: int, max_seq_len: int):
    """Mistral-7B-v0.1 at its published widths, depth cut to
    ``num_layers``."""
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    return LlamaForCausalLM("7b", intermediate_size=14336, num_kv_heads=8,
                            sliding_window=4096, num_layers=num_layers,
                            max_seq_len=max_seq_len)


def emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def custom_calls(hlo_text: str, kernel: str = "") -> int:
    """Mosaic custom calls in a compiled program's text (of the Pallas
    kernel named ``kernel``, when given)."""
    return sum(1 for line in hlo_text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and kernel in line)


def collectives(hlo_text: str) -> dict:
    return {op: hlo_text.count(f" {op}(") + hlo_text.count(f" {op}-start(")
            for op in ("all-gather", "all-reduce", "reduce-scatter",
                       "all-to-all", "collective-permute")}


def bytes_in_use(devices) -> list:
    return [int(d.memory_stats()["bytes_in_use"]) for d in devices]


def device_set_size(tree) -> int:
    """Smallest number of devices any array leaf of ``tree`` lives on."""
    import jax
    return min(len(x.sharding.device_set) for x in jax.tree.leaves(tree)
               if hasattr(x, "sharding") and getattr(x, "ndim", 0) > 0)


# ---------------------------------------------------------------------------
# kernels against their references, on the chip
# ---------------------------------------------------------------------------

def run_kernel_parity(seed: int, heads: int = 32, kv_heads: int = 8,
                      head_dim: int = 128, seq: int = 1024, page: int = 64,
                      interpret: bool = False, tol: float = 2e-2) -> dict:
    """The two attention kernels at the model's head geometry on a small
    seeded input, each against the repo's own plain reference computed on
    the same device: flash forward and gradients vs ``mha_reference``
    (full causal and banded), the paged kernel vs the dense-gather path
    and the cache-write kernel vs the XLA scatter (decode and chunk rows,
    bf16 and int8 pages; layer 1 of a two-layer pool).  Returns the
    largest error of each comparison relative to the reference's largest
    value."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.flash_attention import (flash_attention,
                                                   mha_reference)
    from deepspeed_tpu.ops.paged_attention import (KVPages, paged_attention,
                                                   quantize_kv_blocks,
                                                   write_kv)

    def rel_err(got, want):
        got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
        return float(jnp.max(jnp.abs(got - want))
                     / jnp.maximum(jnp.max(jnp.abs(want)), 1e-6))

    keys = jax.random.split(jax.random.key(seed), 8)
    errors = {}
    q, k, v, w = (jax.random.normal(kk, (1, heads, seq, head_dim),
                                    jnp.bfloat16) for kk in keys[:4])
    for name, window in (("causal", None), ("window", seq // 4)):
        def loss(fn, q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w), out
        kernel = jax.jit(jax.value_and_grad(lambda *a: loss(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, window=window, interpret=interpret),
            *a), argnums=(0, 1, 2), has_aux=True))
        plain = jax.jit(jax.value_and_grad(lambda *a: loss(
            lambda q, k, v: mha_reference(q, k, v, causal=True,
                                          window=window),
            *a), argnums=(0, 1, 2), has_aux=True))
        (_, out), grads = kernel(q, k, v)
        (_, ref), ref_grads = plain(q, k, v)
        errors[f"flash_{name}_out"] = rel_err(out, ref)
        for g, r, n in zip(grads, ref_grads, "qkv"):
            errors[f"flash_{name}_d{n}"] = rel_err(g, r)

    slots, pages_per_seq = 4, seq // page
    kv = jax.random.normal(keys[4], (2, slots * pages_per_seq + 1, 2,
                                     kv_heads, page, head_dim), jnp.bfloat16)
    codes, scale = quantize_kv_blocks(kv)
    table = (1 + jnp.arange(slots * pages_per_seq, dtype=jnp.int32)
             ).reshape(slots, pages_per_seq)
    for rows in (1, seq // 8):
        qp = jax.random.normal(keys[5], (slots, rows, heads, head_dim),
                               jnp.bfloat16)
        start = jnp.asarray([seq - rows, seq // 2, page + 3, 0], jnp.int32)
        lens = jnp.full((slots,), rows, jnp.int32)
        new = jax.random.normal(keys[6], (2, slots, rows, kv_heads, head_dim),
                                jnp.bfloat16)
        for fmt, pool in (("bf16", kv), ("int8", KVPages(codes, scale))):
            for name, window in (("", None), ("_window", seq // 4)):
                got, want = (jax.jit(lambda q, p, use=use: paged_attention(
                    q, p, 1, table, start, lens, use_kernel=use,
                    window=window, interpret=interpret and use))(qp, pool)
                    for use in (True, False))
                errors[f"paged_q{rows}_{fmt}{name}"] = rel_err(got, want)
            # a chunk's last token is padding: it must land nowhere real
            new_lens = lens - 1 if rows > 1 else lens
            got, want = (jax.jit(lambda p, k, v, use=use: write_kv(
                p, 1, k, v, table, start, new_lens, use_kernel=use,
                interpret=interpret and use))(pool, *new)
                for use in (True, False))
            # page 0 is the null page: the scatter parks padding there
            errors[f"kv_write_q{rows}_{fmt}"] = max(
                rel_err(g[:, 1:], w[:, 1:]) for g, w in zip(
                    jax.tree.leaves(got), jax.tree.leaves(want)))
    worst = max(errors, key=errors.get)
    if not errors[worst] <= tol:
        raise RuntimeError(f"kernel {worst} is {errors[worst]:.4f} off its "
                           f"reference (tolerance {tol}): {errors}")
    return {"tolerance": tol, "worst": worst,
            "relative_errors": {k: round(v, 5) for k, v in errors.items()}}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_config(micro_bs: int, gas: int, mesh=None) -> dict:
    """The README quick-start config (ZeRO-3, bf16, AdamW, clipping)."""
    cfg = {
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 3},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "checkpoint": {"async_save": False},
    }
    if mesh:
        cfg["tpu"] = {"mesh": mesh}
    return cfg


def run_train(model, *, micro_bs: int, gas: int, steps: int, seed: int,
              devices, mesh=None, checkpoint_dir: str | None = None):
    """``dst.initialize`` + ``steps`` x ``train_batch`` on one fixed seeded
    batch — on ``devices[0]`` alone, or with ``mesh`` over every device
    JAX reports; returns the facts of the run."""
    import jax
    import numpy as np

    import deepspeed_tpu as dst
    from deepspeed_tpu.parallel.topology import single_device_topology

    t0 = time.perf_counter()
    before = bytes_in_use(devices)
    seq_len = model.cfg.max_seq_len
    engine, _, _, _ = dst.initialize(
        model=model, config=train_config(micro_bs, gas, mesh),
        rng=jax.random.key(seed),
        topology=None if mesh else single_device_topology())
    rows = engine.train_batch_size()
    batch = {"input_ids": np.random.default_rng(seed).integers(
        0, model.cfg.vocab_size, (rows, seq_len), dtype=np.int32)}
    compiled = engine.lower_train_step(batch).compile()
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    facts = {
        "layers": model.cfg.num_layers, "params": model.cfg.n_params(),
        "step_gb": {"arguments": round(mem.argument_size_in_bytes / 1e9, 2),
                    "temporaries": round(mem.temp_size_in_bytes / 1e9, 2)},
        "seq_len": seq_len, "micro_bs": micro_bs, "gas": gas,
        "devices": len(devices), "tpu_custom_calls": custom_calls(hlo),
        "flash_custom_calls": custom_calls(hlo, KERNEL_NAMES["flash"]),
        "collectives": collectives(hlo),
    }
    if not facts["flash_custom_calls"]:
        raise RuntimeError("compiled train step holds no flash-attention "
                           f"custom call: {facts}")
    losses = [engine.train_batch(batch) for _ in range(steps)]
    facts["losses"] = losses
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite train loss: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"train loss did not fall: {losses}")
    state = engine.state
    facts["state_device_set"] = min(device_set_size(state.params),
                                    device_set_size(state.opt_state))
    facts["state_bytes_per_device"] = [
        a - b for a, b in zip(bytes_in_use(devices), before)]
    if checkpoint_dir is not None:
        # one save/load round trip must leave the next loss unchanged
        shutil.rmtree(checkpoint_dir, ignore_errors=True)
        engine.save_checkpoint(checkpoint_dir, tag="smoke")
        expect = engine.train_batch(batch)
        engine.load_checkpoint(checkpoint_dir, tag="smoke")
        got = engine.train_batch(batch)
        shutil.rmtree(checkpoint_dir, ignore_errors=True)
        facts["checkpoint"] = {"loss_after_save": expect,
                               "loss_after_load": got}
        if expect != got:
            raise RuntimeError("checkpoint round trip changed the next "
                               f"loss: {expect} != {got}")
    engine.destroy()
    facts["smoke_seconds"] = round(time.perf_counter() - t0, 1)
    return facts


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_requests(seed: int, vocab: int, n: int, min_len: int, max_len: int,
                  shared_prefix: int, new_tokens: tuple):
    """``n`` greedy requests, prompt lengths spread over
    [min_len, max_len]; the last two share a ``shared_prefix``-token
    (multi-page) prefix."""
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = np.linspace(min_len, max_len, n).astype(int)
    prompts = [rng.integers(0, vocab, int(l)).tolist() for l in lens]
    prefix = rng.integers(0, vocab, shared_prefix).tolist()
    for i in (n - 2, n - 1):
        prompts[i] = prefix + prompts[i][shared_prefix:]
    news = [int(x) for x in
            np.linspace(new_tokens[0], new_tokens[1], n).astype(int)]
    return prompts, news


def serve_params(model, seed: int):
    """Seeded weights in the serving dtype (bf16), boxed with their
    logical axes so a tp mesh can shard them."""
    import jax
    init = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(model.cfg.dtype), model.init_params(key)))
    return init(jax.random.key(seed))


def run_serve(cfg, params, prompts, news, *, num_pages: int, max_seqs: int,
              devices, attention_impl=None, tp_degree: int = 1):
    """One ``InferenceEngineV2`` + ``FastGenScheduler`` (default serving
    config: fused step, on-device sampling, async scheduling, prefix
    caching) over the requests; returns (tokens by request, facts)."""
    from deepspeed_tpu.inference.v2 import (
        FastGenScheduler, InferenceEngineV2, RaggedInferenceEngineConfig,
        SamplingParams, ServingOptimizationConfig, StateManagerConfig)
    from deepspeed_tpu.inference.v2.config import KVCacheUserConfig
    from deepspeed_tpu.inference.v2.model_implementations import (
        MistralInferenceModel)
    from deepspeed_tpu.telemetry import metrics as tm

    t0 = time.perf_counter()
    before = bytes_in_use(devices)
    prefix_hits0 = tm.SERVING_PREFIX_HIT_TOKENS.value
    model = MistralInferenceModel(cfg, params, attention_impl=attention_impl)
    engine = InferenceEngineV2(model, RaggedInferenceEngineConfig(
        state_manager=StateManagerConfig(max_tracked_sequences=max_seqs,
                                         max_ragged_sequence_count=max_seqs),
        kv_cache=KVCacheUserConfig(num_pages=num_pages, dtype=cfg.dtype),
        serving=ServingOptimizationConfig(tp_degree=tp_degree)))
    sched = FastGenScheduler(engine)
    for uid, (prompt, new) in enumerate(zip(prompts, news)):
        sched.submit(uid, prompt, SamplingParams(max_new_tokens=new))
    out = sched.run_to_completion()
    engine.state_manager.check_invariants()
    short = {u: len(t) for u, t in out.items() if len(t) != news[u]}
    if short:
        raise RuntimeError(f"requests did not complete: {short}")
    # one program text at a time: each carries its Mosaic bodies inline
    programs = with_call = with_paged = 0
    collective_counts: dict = {}
    for compiled in model.compiled_programs().values():
        text = compiled.as_text()
        programs += 1
        with_call += bool(custom_calls(text))
        with_paged += bool(custom_calls(text, KERNEL_NAMES["paged"]))
        for op, n in collectives(text).items():
            collective_counts[op] = collective_counts.get(op, 0) + n
    facts = {
        "attention": attention_impl or "auto", "tp_degree": tp_degree,
        "requests": len(prompts), "prompt_lens": [len(p) for p in prompts],
        "new_tokens": news, "kv_pages": num_pages, "programs": programs,
        "programs_with_tpu_custom_call": with_call,
        "programs_with_paged_kernel": with_paged,
        "collectives": collective_counts,
        "prefix_hit_tokens": int(tm.SERVING_PREFIX_HIT_TOKENS.value
                                 - prefix_hits0),
        "kv_device_set": device_set_size(
            engine.state_manager.kv_cache.data),
        "params_device_set": device_set_size(model.params),
        "bytes_per_device": [a - b for a, b in
                             zip(bytes_in_use(devices), before)],
        "smoke_seconds": round(time.perf_counter() - t0, 1),
    }
    return out, facts


def agreement(a: dict, b: dict) -> dict:
    """First-token exactness, and over the rest the share of tokens that
    agree before a request's first divergence (after it the two runs
    decode different texts; a bf16 near-tie at random weights is enough
    to start one) and the plain position-wise share."""
    first = all(a[u][0] == b[u][0] for u in a)
    total = sum(len(a[u]) - 1 for u in a)
    same = sum(x == y for u in a for x, y in zip(a[u][1:], b[u][1:]))
    prefix = 0
    for u in a:
        for x, y in zip(a[u][1:], b[u][1:]):
            if x != y:
                break
            prefix += 1
    return {"first_token_exact": first,
            "rest_agreement": round(prefix / max(total, 1), 4),
            "rest_positionwise": round(same / max(total, 1), 4)}


def first_token_ties(cfg, params, prompts, a: dict, b: dict, devices) -> dict:
    """For every request whose first tokens differ between two runs: the
    gap between the two tokens' logits in a plain forward of its prompt
    (``engine.put``: no cache behind it, nothing beside it) and whether
    they are that row's two largest.  {} where all first tokens agree."""
    differ = [u for u in a if a[u][0] != b[u][0]]
    if not differ:
        return {}
    import numpy as np
    from deepspeed_tpu.inference.v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig, StateManagerConfig)
    from deepspeed_tpu.inference.v2.config import KVCacheUserConfig
    from deepspeed_tpu.inference.v2.model_implementations import (
        MistralInferenceModel)
    engine = InferenceEngineV2(
        MistralInferenceModel(cfg, params), RaggedInferenceEngineConfig(
            state_manager=StateManagerConfig(
                max_tracked_sequences=len(differ),
                max_ragged_batch_size=max(len(p) for p in prompts)),
            kv_cache=KVCacheUserConfig(num_pages=SERVE_PAGES,
                                       dtype=cfg.dtype)))
    ties = {}
    for u in differ:
        row = np.asarray(engine.put([u], [np.asarray(prompts[u], np.int32)]),
                         np.float32)[0]
        pair = sorted((int(a[u][0]), int(b[u][0])))
        ties[u] = {"tokens": pair,
                   "gap": round(float(abs(row[pair[0]] - row[pair[1]])), 4),
                   "top2": sorted(np.argsort(-row)[:2].tolist()) == pair}
    return ties


def require_agreement(agree: dict, what: str, ties=None) -> None:
    """``ties``: :func:`first_token_ties` of the two runs; a first token
    may differ only at a near-tie (:data:`NEAR_TIE`)."""
    firsts = agree["first_token_exact"] or (ties and all(
        t["top2"] and t["gap"] < NEAR_TIE for t in ties.values()))
    if not firsts or agree["rest_agreement"] < MIN_REST_AGREEMENT:
        raise RuntimeError(f"{what} tokens disagree: {agree} {ties or ''}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_train_one_chip(args, devices):
    emit("train", **run_train(
        mistral_7b(TRAIN_LAYERS, SEQ_LEN), micro_bs=4, gas=4, steps=4,
        seed=args.seed, devices=devices[:1],
        checkpoint_dir=os.path.join(REPO, ".smoke_ckpt")))


def phase_serve_one_chip(args, devices):
    model = mistral_7b(SERVE_LAYERS, max_seq_len=4096)
    cfg, params = model.cfg, serve_params(model, args.seed)
    prompts, news = make_requests(args.seed, cfg.vocab_size, n=12,
                                  min_len=16, max_len=1500,
                                  shared_prefix=192, new_tokens=(32, 64))
    common = dict(num_pages=SERVE_PAGES, max_seqs=16, devices=devices[:1])
    out, facts = run_serve(cfg, params, prompts, news, **common)
    if not facts["programs_with_paged_kernel"]:
        raise RuntimeError("no compiled serving step holds the paged-"
                           f"attention custom call: {facts}")
    emit("serve", layers=cfg.num_layers, **facts)
    ref, ref_facts = run_serve(cfg, params, prompts, news,
                               attention_impl="dense_gather", **common)
    agree = agreement(out, ref)
    ties = first_token_ties(cfg, params, prompts, out, ref, devices[:1])
    emit("serve_reference", **ref_facts, **agree, first_token_ties=ties)
    require_agreement(agree, "kernel and dense-gather", ties)


def phase_cache():
    from deepspeed_tpu.utils.compile_cache import (active_cache_dir,
                                                   cache_counts)
    path = active_cache_dir()
    if path is None:
        raise RuntimeError("no persistent compile cache is active")
    emit("cache", dir=path,
         placed_by=("JAX_COMPILATION_CACHE_DIR"
                    if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                    else "in-checkout default"),
         entries=len(os.listdir(path)), **cache_counts())


def phase_four_chips(args, devices):
    """Only what exists across chips, each against its one-chip run."""
    import numpy as np
    model = mistral_7b(TRAIN_LAYERS, SEQ_LEN)
    common = dict(gas=1, steps=4, seed=args.seed)
    # same global batch (4 rows) on one chip and on the {fsdp: 4} mesh
    one = run_train(model, micro_bs=4, devices=devices[:1], **common)
    emit("train_one_chip", **one)
    four = run_train(model, micro_bs=1, devices=devices, mesh={"fsdp": 4},
                     **common)
    emit("train_fsdp4", **four)
    gap = float(np.max(np.abs(np.array(one["losses"])
                              - np.array(four["losses"]))))
    emit("train_compare", max_abs_loss_gap=gap,
         tolerance=FSDP_LOSS_TOLERANCE)
    if gap > FSDP_LOSS_TOLERANCE:
        raise RuntimeError(f"fsdp=4 losses left the bf16 band: {gap}")
    if four["state_device_set"] != 4 or min(
            four["state_bytes_per_device"]) <= 0:
        raise RuntimeError(f"train state is not on all four chips: {four}")
    coll = four["collectives"]
    if not (coll["all-gather"] and (coll["reduce-scatter"]
                                    or coll["all-reduce"])):
        raise RuntimeError(f"ZeRO-3 step without its collectives: {coll}")

    model = mistral_7b(SERVE_LAYERS, max_seq_len=4096)
    cfg, params = model.cfg, serve_params(model, args.seed)
    prompts, news = make_requests(args.seed, cfg.vocab_size, n=8,
                                  min_len=16, max_len=1500,
                                  shared_prefix=192, new_tokens=(32, 48))
    serve = dict(num_pages=SERVE_PAGES, max_seqs=8, devices=devices)
    tp1, facts1 = run_serve(cfg, params, prompts, news, **serve)
    emit("serve_tp1", **facts1)
    tp4, facts4 = run_serve(cfg, params, prompts, news, tp_degree=4, **serve)
    agree = agreement(tp1, tp4)
    ties = first_token_ties(cfg, params, prompts, tp1, tp4, devices[:1])
    emit("serve_tp4", **facts4, **agree, first_token_ties=ties)
    require_agreement(agree, "tp=4 and tp=1", ties)
    if (facts4["kv_device_set"] != 4 or facts4["params_device_set"] != 4
            or min(facts4["bytes_per_device"]) <= 0):
        raise RuntimeError(f"serving state is not on all four chips: "
                           f"{facts4}")
    if not (facts4["programs_with_paged_kernel"]
            and facts4["collectives"]["all-reduce"]):
        raise RuntimeError("tp=4 step lacks the paged kernel or its "
                           f"all-reduces: {facts4}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    phase = "device"
    try:
        import jax
        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        if device["platform"] != "tpu":
            raise RuntimeError(f"JAX found no TPU: {device}")
        if len(devices) < args.chips:
            raise RuntimeError(
                f"--chips {args.chips} needs {args.chips} TPU chips, "
                f"JAX reports {len(devices)}")
        # fails here if the repo is absent; its log lines go to stderr so
        # stdout carries the phase lines only
        from deepspeed_tpu.utils.logging import logger
        for handler in logger.handlers:
            handler.setStream(sys.stderr)
        emit("device", **device, train_layers=TRAIN_LAYERS,
             serve_layers=SERVE_LAYERS)
        if args.chips == 4:
            phase = "four_chips"
            phase_four_chips(args, devices[:4])
        else:
            phase = "kernels"
            emit("kernels", **run_kernel_parity(args.seed))
            phase = "train"
            phase_train_one_chip(args, devices)
            phase = "serve"
            phase_serve_one_chip(args, devices)
        phase = "cache"
        phase_cache()
    except BaseException as e:  # noqa: BLE001 — report, then fail
        import traceback
        traceback.print_exc()
        print(json.dumps({"ok": False, "phase": phase,
                          "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
