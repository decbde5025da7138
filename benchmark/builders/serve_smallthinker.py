"""Builder ``serve_smallthinker``: one ``InferenceEngineV2`` +
``FastGenScheduler`` over seeded bf16 weights of SmallThinker
(``smallthinker``), cut as its configuration file says: one stage of a
pipeline, two whole periods of the layer pattern with every expert of
every layer here.

The program is entered only through ``SmallThinkerForCausalLM``,
``SmallThinkerInferenceModel``, ``InferenceEngineV2`` and
``FastGenScheduler``.  ``probe["ok"]`` comes from the two comparisons
``serve_pangu_moe`` makes (its waves, its judge, imported), here with the
benchmark's reference of THIS family (``benchmark/
reference_smallthinker.py``: float32, every layer over the whole sequence
under its own mask, every expert over every token, no cache), at the
widths that are run:

(a) LOGITS of teacher-forced steps through both page groups
    (``engine.put``) against the reference's full forward over the same
    tokens: *short* (prompts, then 16 decode steps), *long* (rows decoded
    for 2,000 steps, every step compared, through both page buckets of the
    cell's lattice; their contexts end at 2,072-2,120 tokens, under the
    4,096-token window: the window group holds what the full group holds,
    as in the cell) and *wide* (copies of the short rows beside the long
    ones in the row bucket of the window's own steps);
(b) greedy FIRST TOKENS through the scheduler and the token-expert pairs
    the program counted for those prefills (every expert is held: 6 a
    token and layer) against the reference's router.

:data:`CONTROLS` plants one fault each in the REFERENCE side; a control
read against what the program served has to come out ``ok: false``
(``control_verdicts``; PERF.md has the chip's readings).  The window's
eviction at 4,096 tokens lies outside this mix's contexts: the CPU tests
hold it at a window of 128, ``tools/smallthinker_window.py`` once on the chip.
"""

from __future__ import annotations

import numpy as np

from .serve_fastgen import ServeSystem, seeded_key, sized
from .serve_jamba import _Recorder
from .serve_laguna import serving_of
from .serve_pangu_moe import (first_token_probe, judge, logits_probe,
                              probe_inputs, run_probe, sequences_of)

SOURCE_KEYS = (
    "vocab_size", "hidden_size", "head_dim", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "moe_ffn_hidden_size",
    "moe_num_primary_experts", "moe_num_active_primary_experts",
    "moe_primary_router_apply_softmax", "norm_topk_prob", "rms_norm_eps",
    "rope_theta", "rope_scaling", "rope_layout", "sliding_window_layout",
    "sliding_window_size", "tie_word_embeddings")

#: the probe's controls: arguments of :func:`reference_side` that plant
#: one fault each
CONTROLS = {
    "float8_weights": {"weight_precision": "float8_e4m3fn"},
    "silu_for_relu": {"act": "silu"},
    "router_reads_the_post_attention_norm": {"router_reads": "ffn"},
    "rope_on_the_global_layers": {"roped": ("full", "window")},
    "no_rope_on_the_window_layers": {"roped": ()},
    "top_k_weights_not_normalised": {"norm_topk_prob": False},
}


def source_of(config: dict, rehearse: bool) -> dict:
    """The source's keys as the program's model class takes them; the
    router keeps the outputs the configuration says it scores."""
    c = sized(config, rehearse)
    assert c["router_input"] == "attention_input_normed"
    assert c["expert_activation"] == "relu" and not c["attention_bias"]
    assert not c["qk_norm"] and c["rope_pairing"] == "interleaved"
    return dict({k: c[k] for k in SOURCE_KEYS},
                moe_num_primary_experts_scored=c["routed_experts_scored"])


def reference_sizes(cfg, **controls) -> dict:
    """The reference's ``sizes`` from the program's configuration (plain
    attribute reads); ``controls``: :data:`CONTROLS`."""
    kinds = tuple(cfg.layer_kinds)
    return dict(dict(
        eps=cfg.norm_eps, head_dim=cfg.dims_per_head, kinds=kinds,
        window=cfg.sliding_window, rope_theta=cfg.rope_theta,
        roped=tuple(k for k in dict.fromkeys(kinds)
                    if k not in cfg.nope_kinds),
        top_k=cfg.moe_top_k, norm_topk_prob=cfg.norm_topk_prob,
        act=cfg.expert_act, router_reads=cfg.router_reads,
        experts_first=cfg.experts_first), **controls)


def reference_side(params, cfg, sequences, precision=None,
                   weight_precision=None, **controls):
    """Per sequence the reference's (logits [T, V], held pairs a layer and
    token [layers, T]) as numpy.  The sequences are padded to two lengths,
    the short ones' longest and the long ones' (the reference compiles
    once a length; under the causal mask the padding reaches no position
    that is read): every expert runs over every position, so padding the
    short rows to 2,128 tokens would be most of the probe's time."""
    import jax.numpy as jnp

    from .. import reference_smallthinker as reference
    sizes = reference_sizes(cfg, **controls)
    cut = 4 * min(len(s) for s in sequences)    # past it: a long sequence
    width = {is_long: max((-(-len(s) // 8) * 8 for s in sequences
                           if (len(s) > cut) == is_long), default=0)
             for is_long in (False, True)}
    wp = jnp.dtype(weight_precision) if weight_precision else None
    out = []
    for seq in sequences:
        ids = np.zeros(width[len(seq) > cut], np.int32)
        ids[:len(seq)] = seq
        logits, pairs = reference.forward(
            params, ids, sizes, precision or jnp.float32, wp)
        out.append((np.asarray(logits[:len(seq)]),
                    np.asarray(pairs[:, :len(seq)])))
    return out


def _first(sched, cfg, inputs, want, pr, rows) -> dict:
    """(b) of the probe alone, given (a)'s rows."""
    prompts = [p for p, _ in inputs["short"] + inputs["long"]]
    first_row = {}
    for name, err in zip(rows.seq, rows.err):
        first_row.setdefault(name, err)
    outlier = [first_row[f"{kind}{i}"] > pr["outlier_rel_rms"]
               for kind, part in (("s", inputs["short"]),
                                  ("l", inputs["long"]))
               for i in range(len(part))]
    return first_token_probe(sched, prompts, want, pr,
                             cfg.moe_top_k * cfg.num_layers, outlier)


def control_verdicts(engine, sched, cfg, params, inputs, pr,
                     names=tuple(CONTROLS)) -> dict:
    """The sound verdict and each control's, all against ONE serving of
    the probe's waves: {name: judge's dict}.  For the chip's readings in
    PERF.md and for the tests; a run of the benchmark does not call it."""
    seqs = sequences_of(inputs)
    want = reference_side(params, cfg, seqs)
    rec = _Recorder(engine)
    rows = logits_probe(rec, inputs, want, pr)
    sound = _first(sched, cfg, inputs, want, pr, rows)
    out = {"sound": judge(rows, sound, pr)}
    for name in names:
        faulty = reference_side(params, cfg, seqs, **CONTROLS[name])
        rows = logits_probe(_Recorder(served=rec.served), inputs, faulty, pr)
        out[name] = judge(rows, sound, pr)
    return out


def make_model(config: dict, seed: int, rehearse: bool):
    """(configuration of the program's model class, seeded weights)."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    # a program without the family fails here, before anything is built
    from deepspeed_tpu.inference.v2.model_implementations import (  # noqa
        SmallThinkerInferenceModel)
    from deepspeed_tpu.models.smallthinker import SmallThinkerForCausalLM

    c = sized(config, rehearse)
    # a rehearsal runs float32: at its debug widths bfloat16 rounds by
    # more than the limits, which are set for the widths that are run
    dtype = jnp.float32 if rehearse else jnp.dtype(config["dtype"])
    model = SmallThinkerForCausalLM(
        source_of(config, rehearse), experts_first=c["experts_first"],
        max_seq_len=config["engine"]["max_seq_len"], dtype=dtype)
    return model.cfg, meta.unbox(
        jax.jit(model.init_params)(seeded_key(seed)))


def make_engine(cfg, params, eng: dict, rehearse: bool):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig,
        ServingOptimizationConfig, StateManagerConfig)
    from deepspeed_tpu.inference.v2.config import KVCacheUserConfig
    from deepspeed_tpu.inference.v2.model_implementations import (
        SmallThinkerInferenceModel)
    return InferenceEngineV2(
        SmallThinkerInferenceModel(cfg, params),
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
    # the probe's reference side, before the engine takes its memory (a
    # rehearsal decodes the long rows for ``rehearse.probe_cut``'s steps)
    pr = dict(config["probe"], **sized(config, rehearse).get("probe_cut", {}))
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
