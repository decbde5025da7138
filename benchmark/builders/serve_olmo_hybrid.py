"""Builder ``serve_olmo_hybrid``: one ``InferenceEngineV2`` +
``FastGenScheduler`` over seeded bf16 weights of Olmo-Hybrid
(``olmo_hybrid``), one period of its layer pattern, as its configuration
file says.

The program is entered only through ``OlmoHybridForCausalLM``,
``OlmoHybridInferenceModel``, ``InferenceEngineV2`` and
``FastGenScheduler``.  ``probe["ok"]`` comes from the comparisons
``serve_jamba`` makes (``serve_pangu_moe``'s waves and judge, and
``serve_jamba``'s state-drift term and recorder, all imported; there are no
token-expert pairs here, so that part counts 0 of 0), with the benchmark's
reference of THIS family (``benchmark/reference_olmo_hybrid.py``: float32,
every layer over the whole sequence from a zero state, the delta rule token
by token, no cache), at the widths that are run:

(a) LOGITS of teacher-forced steps through the state slots and the pages
    (``engine.put``) against the reference's full forward over the same
    tokens: *short* (prompts, then 16 decode steps: the chunked kernel,
    then the update kernel from its state and the convolution from the
    prompt's TRUE last tokens), *long* (rows decoded for 2,000 steps, every
    step compared: a matrix state integrated 2,000 times in place) and
    *wide* (copies of the short rows beside the long ones in the row bucket
    of the window's own steps, on slots the short wave gave back: a reused
    slot has to start from zeros);
(b) greedy FIRST TOKENS through the scheduler;
(c) STATE DRIFT (``serve_jamba.judge_state``): the long wave's median over
    the short wave's, under ``state_drift_limit``.

:data:`CONTROLS` plants one fault each in the REFERENCE side; a control
read against what the program served has to come out ``ok: false``
(``control_verdicts``; PERF.md has the chip's readings).
"""

from __future__ import annotations

import numpy as np

from .serve_fastgen import ServeSystem, seeded_key, sized
from .serve_jamba import _Recorder, judge_state, run_first
from .serve_laguna import serving_of
from .serve_pangu_moe import (judge, logits_probe, probe_inputs, run_probe,
                              sequences_of)

SOURCE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "hidden_act",
    "attention_bias", "rms_norm_eps", "tie_word_embeddings", "layer_types",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim",
    "linear_allow_neg_eigval", "rope_parameters", "head_dim")

#: the probe's controls: arguments of :func:`reference_side` that plant
#: one fault each
CONTROLS = {
    "beta_not_doubled": {"beta_doubled": False},
    "decay_dropped": {"decay": False},
    "l2norm_dropped": {"l2norm": False},
    "gate_dropped": {"gate": False},
    "qk_norm_dropped": {"qk_norm": False},
    "padded_conv_tail": {"tail_break": True},
    "slot_not_zeroed": {"stale_state": True},
    "bf16_state": {"state_precision": "bfloat16"},
}


def source_of(config: dict, rehearse: bool) -> dict:
    """The source's keys as the program's model class takes them;
    ``layer_types`` is read for its first ``num_hidden_layers`` entries."""
    c = sized(config, rehearse)
    assert c["position_encoding"] == "none"
    assert c["block_norm_order"] == "output_norm"
    assert c["qk_norm"] == "whole_width" and not c["linear_conv_bias"]
    assert c["delta_state_dtype"] == "float32"
    return {k: c[k] for k in SOURCE_KEYS}


def reference_sizes(cfg, **controls) -> dict:
    """The reference's ``sizes`` from the program's configuration (plain
    attribute reads); ``controls``: the reference's docstring lists them."""
    return dict(dict(
        eps=cfg.norm_eps, head_dim=cfg.dims_per_head,
        kinds=tuple(cfg.layer_kinds), conv=cfg.delta_conv,
        heads=cfg.delta_heads, dk=cfg.delta_key_dim,
        dv=cfg.delta_value_dim,
        beta_doubled=bool(cfg.delta_neg_eigval)), **controls)


def reference_side(params, cfg, sequences, precision=None,
                   weight_precision=None, state_precision=None,
                   tail_break=False, stale_state=False, prompt_lens=None,
                   **controls):
    """Per sequence the reference's (logits [T, V], None) as numpy (the
    second entry is where the held-experts families put their pairs).
    Every sequence is padded to the longest one's length (the reference
    compiles once; nothing after a position reaches it).  ``tail_break``:
    the convolution loses its inputs at each sequence's ``prompt_lens``
    entry; ``stale_state``: every sequence starts from the state the one
    before it in the list ended with (the last one's for the first)."""
    import jax.numpy as jnp

    from .. import reference_olmo_hybrid as reference
    sizes = reference_sizes(cfg, **controls)
    width = -(-max(len(s) for s in sequences) // 8) * 8
    sp = jnp.dtype(state_precision) if state_precision else None
    out = []
    for i, seq in enumerate(sequences):
        ids = np.zeros(width, np.int32)
        ids[:len(seq)] = seq
        carry = None
        if stale_state:
            carry = reference.forward(
                params, sequences[i - 1], sizes, precision or jnp.float32,
                weight_precision, sp)[1]
        logits, _ = reference.forward(
            params, ids, sizes, precision or jnp.float32, weight_precision,
            sp, carry_in=carry,
            tail_break=int(prompt_lens[i]) if tail_break else None)
        out.append((np.asarray(logits[:len(seq)]), None))
    return out


def control_verdicts(engine, sched, cfg, params, inputs, pr,
                     names=tuple(CONTROLS)) -> dict:
    """The sound verdict and each control's, all against ONE serving of
    the probe's waves: {name: judge's dict}.  For the chip's readings in
    PERF.md and for the tests; a run of the benchmark does not call it."""
    seqs = sequences_of(inputs)
    lens = [len(p) for p, _ in inputs["short"] + inputs["long"]]
    want = reference_side(params, cfg, seqs)
    rec = _Recorder(engine)
    rows = logits_probe(rec, inputs, want, pr)
    sound = run_first(sched, cfg, inputs, want, pr, rows)
    out = {"sound": judge_state(judge(rows, sound, pr), pr)}
    for name in names:
        faulty = reference_side(params, cfg, seqs, prompt_lens=lens,
                                **CONTROLS[name])
        rows = logits_probe(_Recorder(served=rec.served), inputs, faulty, pr)
        out[name] = judge_state(judge(rows, sound, pr), pr)
    return out


def make_model(config: dict, seed: int, rehearse: bool):
    """(configuration of the program's model class, seeded weights)."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    # a program without the family fails here, before anything is built
    from deepspeed_tpu.inference.v2.model_implementations import (  # noqa
        OlmoHybridInferenceModel)
    from deepspeed_tpu.models.olmo_hybrid import OlmoHybridForCausalLM

    # a rehearsal runs float32: at its debug widths bfloat16 rounds by
    # more than the limits, which are set for the widths that are run
    dtype = jnp.float32 if rehearse else jnp.dtype(config["dtype"])
    model = OlmoHybridForCausalLM(
        source_of(config, rehearse),
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
        OlmoHybridInferenceModel)
    return InferenceEngineV2(
        OlmoHybridInferenceModel(cfg, params),
        RaggedInferenceEngineConfig(
            state_manager=StateManagerConfig(
                max_tracked_sequences=eng["max_sequences"],
                max_ragged_sequence_count=eng["max_sequences"],
                max_ragged_batch_size=eng["token_budget"]),
            kv_cache=KVCacheUserConfig(
                page_size=eng["page_size"], num_pages=eng["num_pages"],
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
    probe = judge_state(run_probe(engine, sched, cfg, inputs, want, pr), pr)
    return ServeSystem("serve", cfg, engine, sched, cfg.vocab_size,
                       config["engine"]["num_pages"], probe, list(devices))


def describe(system: ServeSystem) -> dict:
    cfg, model = system.cfg, system.engine.model
    return {"kind": system.kind, "layers": cfg.num_layers,
            "params": cfg.n_params(), "pages": system.num_pages,
            "bytes_per_page": model.kv_config.bytes_per_page,
            "state_slots": model.state_config.num_slots,
            "bytes_per_slot": model.state_config.bytes_per_slot,
            "probe": system.probe}
