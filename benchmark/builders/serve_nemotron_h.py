"""Builder ``serve_nemotron_h``: one ``InferenceEngineV2`` +
``FastGenScheduler`` over seeded bf16 weights of Nemotron 3 Nano
(``nemotron_h``), cut as its configuration file says: one chip of an 8-chip
expert-parallel group, two whole blocks ``EMEMEM*`` of the layer pattern
(published layers 6-19).

The program is entered only through ``NemotronHForCausalLM``,
``NemotronHInferenceModel``, ``InferenceEngineV2`` and ``FastGenScheduler``.
``probe["ok"]`` comes from comparisons with the benchmark's reference of
THIS family (``benchmark/reference_nemotron_h.py``: float32, every layer
over the whole sequence from a zero state, Mamba-2's recurrence token by
token, the biased router, the held share of the two-matrix experts, no
cache), at the widths that are run, UNDER THE ROUTING THAT WAS SERVED (of
128 scores the 6th and 7th lie closer than bfloat16 rounds the router's
input on a share of the tokens; ``serve_bailing_hybrid``'s docstring has
the whole argument, and the serving of the waves, the record of the
routing and the verdict are ITS functions, imported):

(a) LOGITS of teacher-forced steps through the state slots AND the pages
    (``engine.put``) against the reference's full forward over the same
    tokens: *short* (prompts, then 16 decode steps: the chunked kernel, then
    the update kernel from its state and the convolution from the prompt's
    TRUE last tokens), *long* (rows decoded for 2,000 steps, every step
    compared, through both page buckets of the cell's lattice) and *wide*
    (further sequences of the short ones' tokens beside the long rows in
    the row bucket of the window's own steps, on slots the short wave gave
    back); ``serve_pangu_moe.judge``'s terms;
(b) greedy FIRST TOKENS through the scheduler, a prompt a step, against the
    reference's row under THAT step's routing, and the token-expert pairs
    the program counted for those prefills against the reference's own
    router;
(c) ``routing_off_share``: the share of (token, routed layer) at which the
    served experts are another set than the reference's router chooses.

:data:`CONTROLS` plants one fault each in the REFERENCE side; a control read
against what the program served has to come out ``ok: false``
(``control_verdicts``; PERF.md has the readings).
"""

from __future__ import annotations

import time

import numpy as np

from .serve_bailing_hybrid import (Served, serve_first_tokens, serve_waves,
                                   verdict, widths_of)
from .serve_fastgen import ServeSystem, seeded_key, sized
from .serve_laguna import serving_of
from .serve_pangu_moe import probe_inputs

SOURCE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "hybrid_override_pattern", "num_attention_heads", "num_key_value_heads",
    "head_dim", "norm_eps", "mamba_num_heads", "mamba_head_dim",
    "ssm_state_size", "n_groups", "conv_kernel", "chunk_size", "expand",
    "mamba_hidden_act", "mamba_proj_bias", "use_conv_bias", "use_bias",
    "mlp_bias", "attention_bias", "mlp_hidden_act", "n_routed_experts",
    "num_experts_per_tok", "n_shared_experts", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "n_group", "topk_group",
    "routed_scaling_factor", "norm_topk_prob", "tie_word_embeddings",
    "sliding_window", "time_step_min", "time_step_max", "time_step_floor")

#: the probe's controls: arguments of :func:`reference_side` that plant one
#: fault each
CONTROLS = {
    "float8_weights": {"weight_precision": "float8_e4m3fn"},
    "bf16_state": {"state_precision": "bfloat16"},
    "relu_for_relu2": {"act": "relu"},
    "one_bc_group_for_all_heads": {"bc_groups": False},
    "norm_over_the_whole_width": {"norm_groups": False},
    "norm_before_the_gate": {"gate_first": False},
    "router_without_bias": {"bias": False},
    "weights_from_the_biased_scores": {"weights_from": "c"},
    "rope_on_the_attention_layers": {"rope": 10000.0},
    "no_skip": {"skip": False},
}


def source_of(config: dict, rehearse: bool) -> dict:
    """The source's keys as the program's model class takes them; the
    router keeps the outputs the configuration says it scores."""
    c = sized(config, rehearse)
    assert c["attention_rope"] == "none" and c["dt_clamp"] == "none"
    assert c["mamba_gated_norm"] == "gate_then_rmsnorm_a_group_of_n_groups"
    assert c["ssm_state_dtype"] == "float32"
    return dict({k: c[k] for k in SOURCE_KEYS},
                n_routed_experts_scored=c["routed_experts_scored"])


def reference_sizes(cfg, **controls) -> dict:
    """The reference's ``sizes`` from the program's configuration (its own
    ``sizes_of``); ``controls``: the reference's docstring lists them."""
    from .. import reference_nemotron_h as reference
    return reference.sizes_of(cfg, **controls)


def reference_side(params, cfg, sequences, routing=None, widths=None,
                   precision=None, weight_precision=None,
                   state_precision=None, **controls):
    """Per sequence the reference's (logits [T, V], the router's held pairs
    a routed layer and token [layers, T], where ``routing`` is off the
    router's choice [layers, T]) as numpy; ``routing``: per sequence the
    served experts [T, layers, k] (``serve_bailing_hybrid.Served``), or
    None.  The sequences are padded to ``widths`` (``widths_of``; None:
    their own): nothing after a position reaches it."""
    import jax.numpy as jnp

    from .. import reference_nemotron_h as reference
    sizes = reference_sizes(cfg, **controls)
    cut, width = widths or widths_of(sequences)
    wp = jnp.dtype(weight_precision) if weight_precision else None
    sp = jnp.dtype(state_precision) if state_precision else None
    out = []
    for n, seq in enumerate(sequences):
        ids = np.zeros(width[len(seq) > cut], np.int32)
        ids[:len(seq)] = seq
        forced = None
        if routing is not None:
            forced = np.zeros((len(ids),) + routing[n].shape[1:], np.int32)
            forced[:len(seq)] = routing[n]
        logits, pairs, off = reference.forward(
            params, ids, sizes, precision or jnp.float32, wp, sp, forced)
        out.append((np.asarray(logits[:len(seq)]),
                    np.asarray(pairs[:, :len(seq)]),
                    np.asarray(off[:, :len(seq)])))
    return out


def first_tokens(first, params, cfg, pr, widths=None, **controls) -> dict:
    """(b)'s verdict: a served first token must have, in the reference's
    row UNDER ITS STEP'S ROUTING, a logit within ``margin`` of the largest;
    the pairs the program counted against the reference's own router."""
    prompts, got = first["prompts"], first["served"]
    want = reference_side(params, cfg, prompts, first["routing"], widths,
                          **controls)
    short_of = [float(w[0][-1].max() - w[0][-1][tok])
                for tok, w in zip(got, want)]
    tokens = sum(len(p) for p in prompts)
    pairs = cfg.moe_top_k * cfg.layer_kinds.count("ffn")
    return {"served": got,
            "reference": [int(np.argmax(w[0][-1])) for w in want],
            "served_short_of_max": [round(g, 4) for g in short_of],
            "compared": len(prompts),
            "matched": int(sum(g <= pr["margin"] for g in short_of)),
            "pairs_counted": first["pairs_counted"],
            "pairs_reference": sum(int(w[1].sum()) for w in want),
            "held_pair_share": round(
                100.0 * first["pairs_counted"] / (tokens * pairs), 3)}


def _ahead(engine, params, cfg, widths):
    """``serve_bailing_hybrid._ahead`` with this family's reference: the
    reference's layer functions compiled for the probe's two lengths beside
    the serving, and (through the function returned) the step programs of
    the engine's own lattice, formed once the sink is away.  Returns (that
    function, a function that waits for both)."""
    import concurrent.futures as cf

    from .. import reference_nemotron_h as reference
    pool = cf.ThreadPoolExecutor(4)
    jobs = [pool.submit(reference.compile_ahead, params,
                        reference_sizes(cfg), [n])
            for n in widths[1].values() if n]

    def programs():
        assert engine.model.routing_sink is None
        jobs.extend(pool.submit(engine.precompile_keys, [k])
                    for k in engine.model.lattice.keys)

    def wait():
        for job in jobs:
            job.result()
        pool.shutdown()

    return programs, wait


def run_probe(engine, sched, cfg, params, inputs, pr) -> dict:
    """(a)-(c) on a built engine: the waves and the first tokens served,
    the reference side under the served routing, the verdict; with the
    seconds each took (all of them set-up)."""
    t = [time.perf_counter()]
    short, long_ = inputs["short"], inputs["long"]
    widths = widths_of([np.concatenate(pf)[:len(pf[0]) + steps]
                        for part, steps in ((short, pr["decode_steps"]),
                                            (long_, pr["long_steps"]))
                        for pf in part])
    programs, wait = _ahead(engine, params, cfg, widths)
    served = serve_waves(engine, inputs, pr)
    first = serve_first_tokens(sched, served, inputs)
    t.append(time.perf_counter())
    programs()
    want = reference_side(params, cfg, served.sequences(), served.routing_of,
                          widths)
    first = first_tokens(first, params, cfg, pr, widths)
    t.append(time.perf_counter())
    wait()
    t.append(time.perf_counter())
    probe = verdict(served.compared(want), first, want, pr)
    return dict(probe, seconds={k: round(b - a, 1) for k, a, b in zip(
        ("serve", "reference", "programs_left"), t, t[1:])})


def control_verdicts(engine, sched, cfg, params, inputs, pr,
                     names=tuple(CONTROLS)) -> dict:
    """The sound verdict and each control's, all against ONE serving of
    the probe's waves: {name: judge's dict}.  For the readings in PERF.md
    and for the tests; a run of the benchmark does not call it."""
    served: Served = serve_waves(engine, inputs, pr)
    seqs, routing = served.sequences(), served.routing_of
    want = reference_side(params, cfg, seqs, routing)
    rows = served.compared(want)
    sound = first_tokens(serve_first_tokens(sched, served, inputs), params,
                         cfg, pr)

    def read(rows, want):
        # (with the quantiles, for PERF.md: where the rows lie)
        return dict(verdict(rows, sound, want, pr), quantiles=[
            round(float(q), 5) for q in np.quantile(
                np.asarray(rows.err), (0.1, 0.5, 0.9, 0.99))])

    out = {"sound": read(rows, want)}
    for name in names:
        faulty = reference_side(params, cfg, seqs, routing, **CONTROLS[name])
        out[name] = read(served.compared(faulty), faulty)
    return out


def make_model(config: dict, seed: int, rehearse: bool):
    """(configuration of the program's model class, seeded weights)."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    # a program without the family fails here, before anything is built
    from deepspeed_tpu.inference.v2.model_implementations import (  # noqa
        NemotronHInferenceModel)
    from deepspeed_tpu.models.nemotron_h import NemotronHForCausalLM

    c = sized(config, rehearse)
    # a rehearsal runs float32: at its debug widths bfloat16 rounds by
    # more than the limits, which are set for the widths that are run
    dtype = jnp.float32 if rehearse else jnp.dtype(config["dtype"])
    model = NemotronHForCausalLM(
        source_of(config, rehearse), experts_first=c["experts_first"],
        first_layer=c["first_layer"],
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
        NemotronHInferenceModel)
    return InferenceEngineV2(
        NemotronHInferenceModel(cfg, params),
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
    # (a rehearsal decodes the long rows for ``rehearse.probe_cut``'s steps)
    pr = dict(config["probe"], **sized(config, rehearse).get("probe_cut", {}))
    inputs = probe_inputs(pr, seed, cfg.vocab_size)
    engine = make_engine(cfg, params, config["engine"], rehearse)
    sched = FastGenScheduler(engine)
    probe = run_probe(engine, sched, cfg, params, inputs, pr)
    return ServeSystem("serve", cfg, engine, sched, cfg.vocab_size,
                       config["engine"]["num_pages"], probe, list(devices))


def describe(system: ServeSystem) -> dict:
    cfg, model = system.cfg, system.engine.model
    return {"kind": system.kind, "layers": cfg.num_layers,
            "params": cfg.n_params(), "pages": system.num_pages,
            "bytes_per_page": model.kv_config.bytes_per_page,
            "state_slots": model.state_config.num_slots,
            "bytes_per_slot": model.state_config.bytes_per_slot,
            "experts_held": cfg.held_experts, "probe": system.probe}
