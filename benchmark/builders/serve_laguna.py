"""Builder ``serve_laguna``: one ``InferenceEngineV2`` + ``FastGenScheduler``
over seeded bf16 weights of Laguna (``laguna``), cut as its configuration
file says: one chip of an expert-parallel group.

The program is entered only through ``LagunaForCausalLM``,
``LagunaInferenceModel``, ``InferenceEngineV2`` and ``FastGenScheduler``.
``probe["ok"]`` comes from the two comparisons ``serve_pangu_moe`` makes
(its waves, its judge), here with the benchmark's reference of THIS family
(``benchmark/reference_laguna.py``: float32, every layer over the whole
sequence under its own mask, no cache), at the widths that are run:

(a) LOGITS of teacher-forced steps through both page groups
    (``engine.put``) against the reference's full forward over the same
    tokens: *short* (prompts, then 16 decode steps), *long* (rows decoded
    for 2,000 steps, every step compared: their contexts cross the
    512-token window after some 400 steps, from where every window layer
    attends through a table that has given pages back, and pages those rows
    released are reserved again by them and by the wide rows, under the
    comparison) and *wide* (copies of the short rows beside the long ones
    in the row bucket of the window's own steps);
(b) greedy FIRST TOKENS through the scheduler and the token-expert pairs
    the program counted for those prefills against the reference's router.
"""

from __future__ import annotations

import os

import numpy as np

from .serve_fastgen import ServeSystem, seeded_key, sized
from .serve_pangu_moe import probe_inputs, run_probe, sequences_of

SOURCE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "attention_bias", "rms_norm_eps", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "norm_topk_prob", "decoder_sparse_step", "mlp_only_layers",
    "tie_word_embeddings", "gating", "sliding_window", "rope_parameters",
    "layer_types", "moe_apply_router_weight_on_input", "mlp_layer_types",
    "gating_types", "moe_routed_scaling_factor",
    "num_attention_heads_per_layer", "moe_router_logit_softcapping")


def source_of(config: dict, rehearse: bool) -> dict:
    """The source's keys as the program's model class takes them; the
    router keeps the outputs the configuration says it scores."""
    c = sized(config, rehearse)
    assert c["router_scoring"] == "softmax" and not c["shared_expert_gate"]
    assert not c["qk_norm"] and c["rope_pairing"] == "interleaved"
    assert c["leading_dense_layers"] == len(c["mlp_only_layers"])
    return dict({k: c[k] for k in SOURCE_KEYS},
                num_experts_scored=c["routed_experts_scored"])


def reference_sizes(cfg, **controls) -> dict:
    """The reference's ``sizes`` from the program's configuration (plain
    attribute reads); ``controls`` plant a fault for the probe's controls
    (``window=``, ``gate=False``, ``rope_window=``)."""
    d = cfg.dims_per_head
    rotated = int(d * cfg.rope_pct)
    rotated -= rotated % 2
    return dict(dict(
        eps=cfg.norm_eps, head_dim=d, kinds=tuple(cfg.layer_kinds),
        window=cfg.sliding_window,
        rope_full=(cfg.rope_theta, rotated, tuple(cfg.rope_yarn)),
        rope_window=(cfg.window_rope_theta, d, ()),
        top_k=cfg.moe_top_k, scaling=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob,
        experts_first=cfg.experts_first), **controls)


def reference_side(params, cfg, sequences, precision=None,
                   weight_precision=None, **controls):
    """Per sequence the reference's (logits [T, V], held pairs a routed
    layer and token [layers, T]) as numpy.  Every sequence is padded to
    the longest one's length (the reference compiles once; under the
    causal mask the padding reaches no position that is read)."""
    import jax.numpy as jnp

    from .. import reference_laguna as reference
    sizes = reference_sizes(cfg, **controls)
    width = -(-max(len(s) for s in sequences) // 8) * 8
    out = []
    for seq in sequences:
        ids = np.zeros(width, np.int32)
        ids[:len(seq)] = seq
        logits, pairs = reference.forward(
            params, ids, sizes, precision or jnp.float32, weight_precision)
        out.append((np.asarray(logits[:len(seq)]),
                    np.asarray(pairs[:, :len(seq)])))
    return out


def make_model(config: dict, seed: int, rehearse: bool):
    """(configuration of the program's model class, seeded weights)."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    # a program without the family fails here, before anything is built
    from deepspeed_tpu.inference.v2.model_implementations import (  # noqa
        LagunaInferenceModel)
    from deepspeed_tpu.models.laguna import LagunaForCausalLM

    c = sized(config, rehearse)
    # a rehearsal runs float32: at its debug widths bfloat16 rounds by
    # more than the limits, which are set for the widths that are run
    dtype = jnp.float32 if rehearse else jnp.dtype(config["dtype"])
    model = LagunaForCausalLM(
        source_of(config, rehearse), experts_first=c["experts_first"],
        max_seq_len=config["engine"]["max_seq_len"], dtype=dtype)
    return model.cfg, meta.unbox(
        jax.jit(model.init_params)(seeded_key(seed)))


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def serving_of(eng: dict, rehearse: bool) -> dict:
    """The configuration's serving options as the engine takes them: the
    lattice of buckets is named by a path from the checkout's root.  A
    rehearsal keeps the default buckets: an artifact is bound to the
    page size and the vocabulary it was written for."""
    serving = dict(eng["serving"])
    spec = serving.pop("lattice", "")
    if spec and not rehearse:
        how, _, path = spec.partition(":")
        serving["lattice"] = f"{how}:{os.path.join(ROOT, path)}"
    return serving


def make_engine(cfg, params, eng: dict, rehearse: bool):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig,
        ServingOptimizationConfig, StateManagerConfig)
    from deepspeed_tpu.inference.v2.config import KVCacheUserConfig
    from deepspeed_tpu.inference.v2.model_implementations import (
        LagunaInferenceModel)
    return InferenceEngineV2(
        LagunaInferenceModel(cfg, params),
        RaggedInferenceEngineConfig(
            state_manager=StateManagerConfig(
                max_tracked_sequences=eng["max_sequences"],
                max_ragged_sequence_count=eng["max_sequences"],
                max_ragged_batch_size=eng["token_budget"]),
            kv_cache=KVCacheUserConfig(
                page_size=eng["page_size"], num_pages=eng["num_pages"],
                window_num_pages=eng["window_num_pages"],
                dtype=jnp.float32 if rehearse
                else jnp.dtype(eng["kv_dtype"])),
            serving=ServingOptimizationConfig(**serving_of(eng, rehearse))))


def build(config: dict, seed: int, devices, rehearse: bool) -> ServeSystem:
    from deepspeed_tpu.inference.v2 import FastGenScheduler
    cfg, params = make_model(config, seed, rehearse)
    # the probe's reference side, before the engine takes its memory
    pr = config["probe"]
    inputs = probe_inputs(pr, seed, cfg.vocab_size)
    want = reference_side(params, cfg, sequences_of(inputs))
    engine = make_engine(cfg, params, config["engine"], rehearse)
    sched = FastGenScheduler(engine)
    probe = run_probe(engine, sched, cfg, inputs, want, pr)
    return ServeSystem("serve", cfg, engine, sched, cfg.vocab_size,
                       config["engine"]["num_pages"], probe, list(devices))


def describe(system: ServeSystem) -> dict:
    cfg, model = system.cfg, system.engine.model
    return {"kind": system.kind, "layers": cfg.num_layers,
            "params": cfg.n_params(), "pages": system.num_pages,
            "bytes_per_page": model.kv_config.bytes_per_page,
            "window_pages": model.window_kv_config.num_pages,
            "window_bytes_per_page": model.window_kv_config.bytes_per_page,
            "experts_held": cfg.held_experts, "probe": system.probe}
