"""Builder ``serve_bailing_hybrid``: one ``InferenceEngineV2`` +
``FastGenScheduler`` over seeded bf16 weights of Ling-3.0
(``bailing_hybrid``), cut as its configuration file says: one chip of a
16-chip expert-parallel group, the second dense layer and one whole period
of the layer pattern.

The program is entered only through ``BailingHybridForCausalLM``,
``BailingHybridInferenceModel``, ``InferenceEngineV2`` and
``FastGenScheduler``.  ``probe["ok"]`` comes from comparisons with the
benchmark's reference of THIS family (``benchmark/reference_bailing_hybrid
.py``: float32, every layer over the whole sequence from a zero state, the
Kimi-delta rule token by token, expanded latent attention, the grouped
router, the held share of the experts, no cache), at the widths that are
run, UNDER THE ROUTING THAT WAS SERVED: of 512 scores the 8th and 9th, or
the 4th and 5th group, lie closer than bfloat16 rounds the router's input
on a share of the tokens, such a token falls either way, and a token on
another expert differs by a whole expert, which says nothing of the
arithmetic.  So the probe is served with the model's ``routing_sink`` set
(every routed layer of the probe's own step programs hands out what its
router chose: the plain forwards of ``engine.put`` and the first prompts'
sampled step, none of which is a step of the window; every other program is
formed after the sink is taken away and holds no trace of it), the
reference multiplies the SAME experts, and what the reference's router
would have chosen is compared with the record on its own
(``routing_off_share``):

(a) LOGITS of teacher-forced steps through the state slots AND the latent
    pages (``engine.put``) against the reference's full forward over the
    same tokens: *short* (prompts, then 16 decode steps: the chunked
    kernel, then the update kernel from its state and the convolution from
    the prompt's TRUE last tokens), *long* (rows decoded for 2,000 steps,
    every step compared, through both page buckets of the cell's lattice)
    and *wide* (further sequences of the short ones' tokens beside the long
    rows in the row bucket of the window's own steps, on slots the short
    wave gave back); ``serve_pangu_moe.judge``'s terms: each wave's median,
    the share of outlier rows, no sequence with most of its rows outliers;
(b) greedy FIRST TOKENS through the scheduler, a prompt a step, against
    the reference's row under THAT step's routing, and the token-expert
    pairs the program counted for those prefills against the reference's
    own grouped router;
(c) ``routing_off_share``: the share of (token, routed layer) at which the
    served experts are another set than the reference's router chooses
    (the near-ties; a router of another rule moves most of them).

:data:`CONTROLS` plants one fault each in the REFERENCE side; a control
read against what the program served has to come out ``ok: false``
(``control_verdicts``; PERF.md has the readings).
"""

from __future__ import annotations

import time

import numpy as np

from .serve_fastgen import ServeSystem, seeded_key, sized
from .serve_laguna import serving_of
from .serve_pangu_moe import Rows, judge, probe_inputs

SOURCE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "hidden_act",
    "layer_group_size", "first_k_dense_replace", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "rope_theta", "rope_interleave", "rms_norm_eps", "tie_word_embeddings",
    "use_bias", "use_qkv_bias", "use_qk_norm", "num_experts",
    "num_experts_per_tok", "num_shared_experts", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "n_group", "topk_group",
    "topk_method", "score_function", "moe_router_enable_expert_bias",
    "routed_scaling_factor", "norm_topk_prob", "short_conv_kernel_size",
    "linear_silu", "kda_safe_gate", "kda_lower_bound", "no_kda_lora",
    "num_kv_heads_for_linear_attn", "group_norm_size",
    "gated_attention_proj_granularity_type", "expert_swiglu_limit_list",
    "share_expert_swiglu_limit_list")

#: the probe's controls: arguments of :func:`reference_side` that plant
#: one fault each
CONTROLS = {
    "float8_weights": {"weight_precision": "float8_e4m3fn"},
    "bf16_state": {"state_precision": "bfloat16"},
    "one_decay_a_head": {"decay": "head"},
    "router_without_groups": {"groups": False},
    "router_without_bias": {"bias": False},
    "weights_from_the_biased_scores": {"weights_from": "c"},
    "no_rope_on_the_latent_layer": {"latent_rope": False},
}


def source_of(config: dict, rehearse: bool) -> dict:
    """The source's keys as the program's model class takes them; the
    router keeps the outputs the configuration says it scores."""
    c = sized(config, rehearse)
    assert c["kda_gate_form"] == "lower_bound_times_sigmoid"
    assert c["kda_output_gate"] == "sigmoid_head_wise_kda_layers_only"
    assert c["qk_norm_form"] == "l2_on_kda_q_and_k_only"
    assert c["router_group_score"] == "sum_of_two_largest"
    assert c["kda_state_dtype"] == "float32" and not c["kda_conv_bias"]
    return dict({k: c[k] for k in SOURCE_KEYS},
                num_experts_scored=c["routed_experts_scored"])


def reference_sizes(cfg, **controls) -> dict:
    """The reference's ``sizes`` from the program's configuration (plain
    attribute reads); ``controls``: the reference's docstring lists them."""
    return dict(dict(
        eps=cfg.norm_eps, kinds=tuple(cfg.layer_kinds),
        first_k_dense=cfg.first_k_dense, conv=cfg.delta_conv,
        heads=cfg.delta_heads, dk=cfg.delta_key_dim,
        dv=cfg.delta_value_dim, lower=cfg.kda_lower_bound,
        rope_theta=cfg.rope_theta, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, top_k=cfg.moe_top_k,
        n_group=cfg.router_groups, topk_group=cfg.router_topk_groups,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob,
        experts_first=cfg.experts_first), **controls)


def widths_of(sequences):
    """(a length past which a sequence is a long one, the padded length of
    the short ones and of the long ones): the reference compiles once a
    length, and the held experts run over every position, so padding the
    short rows to 2,128 tokens would be most of the probe's time."""
    cut = 4 * min(len(s) for s in sequences)
    return cut, {is_long: max((-(-len(s) // 8) * 8 for s in sequences
                               if (len(s) > cut) == is_long), default=0)
                 for is_long in (False, True)}


def reference_side(params, cfg, sequences, routing=None, widths=None,
                   precision=None, weight_precision=None,
                   state_precision=None, **controls):
    """Per sequence the reference's (logits [T, V], the router's held pairs
    a routed layer and token [layers, T], where ``routing`` is off the
    router's choice [layers, T]) as numpy; ``routing``: per sequence the
    served experts [T, layers, k] (:class:`Served`), or None.  The sequences
    are padded to ``widths`` (:func:`widths_of`; None: their own): nothing
    after a position reaches it."""
    import jax.numpy as jnp

    from .. import reference_bailing_hybrid as reference
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


class Served:
    """What the probe's waves were served: each compared logits row with
    its wave, its sequence (an index into :meth:`sequences`: the short
    ones, the long ones, the wide ones) and its position there, and every
    sequence's served experts, position by position."""

    def __init__(self, engine, inputs):
        self.engine, self.model = engine, engine.model
        self.tokens = [np.concatenate(pf) for pf in inputs]
        self.rows = []          # (wave, sequence, position, logits [V])
        self.routing = [[] for _ in inputs]
        self.at = [0] * len(inputs)     # tokens served, a sequence
        self.heard = []         # a step's routed layers, in order
        self.open = True        # False: the probe is over

    def hear(self, chosen):
        """The model's ``routing_sink`` while the probe lasts (a program
        formed under it keeps calling it: afterwards into nothing)."""
        if self.open:
            self.heard.append(np.asarray(chosen))

    def take(self, rows: int, longest: int):
        """What the last step's routed layers chose, [T, layers, k], and
        the tokens a row of its bucket holds."""
        import jax
        jax.effects_barrier()
        chosen, self.heard = np.stack(self.heard, 1), []
        q = 1 if longest == 1 else \
            chosen.shape[0] // self.model.lattice.bucket_s(rows)
        return chosen, q

    def sequences(self):
        """The tokens that were served, a sequence (the forced tokens past
        the last served step cut off)."""
        return [t[:at] for t, at in zip(self.tokens, self.at)]

    def put(self, wave, seqs, uids, tokens):
        """One ``engine.put`` of ``tokens`` for sequences ``seqs``."""
        logits = np.asarray(self.engine.put(uids, tokens))
        chosen, q = self.take(len(seqs), max(len(t) for t in tokens))
        for row, (n, toks) in enumerate(zip(seqs, tokens)):
            self.routing[n].append(chosen[row * q:row * q + len(toks)])
            self.at[n] += len(toks)
            self.rows.append((wave, n, self.at[n] - 1, logits[row]))

    def close(self):
        """The probe is over: programs formed from here on hold no trace
        of the sink, and one that does talks into nothing."""
        self.open, self.model.routing_sink = False, None

    @property
    def routing_of(self):
        """The served experts a sequence, [T, routed layers, k]."""
        return [np.concatenate(got) for got in self.routing]

    def compared(self, want) -> Rows:
        """The rows against the reference side ``want``, named as
        ``serve_pangu_moe.judge`` reads them."""
        rows = Rows()
        for wave, n, at, got in self.rows:
            rows.add(wave, [f"q{n}"], got[None], [want[n][0][at]])
        return rows


def serve_waves(engine, inputs, pr) -> Served:
    """(a)'s serving: the short sequences in waves of ``wave`` (prompt,
    then ``decode_steps`` forced tokens), then the long ones for
    ``long_steps`` forced tokens; at the long steps ``wide_at`` the wide
    sequences (``wide_copies`` of every short one's tokens, their prompts
    put in waves first) decode ``wide_steps`` tokens beside the long
    rows.  Every routed layer of a put reports its experts to
    ``Served.heard`` through the model's ``routing_sink``."""
    short, long_ = inputs["short"], inputs["long"]
    wide = short * int(pr.get("wide_copies", 0)) if long_ else []
    served = Served(engine, short + long_ + wide)
    wave = int(pr.get("wave", len(short)))
    engine.model.routing_sink = served.hear
    try:
        _serve(engine, served, pr, short, long_, wide, wave)
    except BaseException:
        served.close()
        raise
    return served


def _serve(engine, served, pr, short, long_, wide, wave):
    import concurrent.futures as cf
    keys = pr.get("programs", [])
    if keys:
        # the probe's step programs, formed on a few threads (as the
        # hints are) instead of one after another on first use
        with cf.ThreadPoolExecutor(4) as pool:
            list(pool.map(lambda k: engine.precompile_keys([k]), keys))

    def prompts(name, part, base):
        for lo in range(0, len(part), wave):
            idx = range(lo, min(lo + wave, len(part)))
            served.put(name, [base + i for i in idx],
                       [-1000 - base - i for i in idx],
                       [part[i][0] for i in idx])

    def step(name, parts, at):
        """Forced token ``at[k]`` of every sequence of ``parts[k]``."""
        seqs, uids, toks = [], [], []
        for (part, base), t in zip(parts, at):
            seqs += [base + i for i in range(len(part))]
            uids += [-1000 - base - i for i in range(len(part))]
            toks += [p[1][t:t + 1] for p in part]
        served.put(name, seqs, uids, toks)

    for lo in range(0, len(short), wave):
        part = short[lo:lo + wave]
        prompts("short", part, lo)
        for t in range(pr["decode_steps"]):
            step("short", [(part, lo)], [t])
        for i in range(len(part)):
            engine.flush(-1000 - lo - i)
    if not long_:
        return
    l_base, w_base = len(short), len(short) + len(long_)
    prompts("long", long_, l_base)
    wide_at = pr.get("wide_at", []) if wide else []
    assert len(wide_at) * pr.get("wide_steps", 0) <= pr["decode_steps"]
    w_step = wide_left = 0
    for t in range(pr["long_steps"]):
        if t in wide_at:
            wide_left = int(pr["wide_steps"])
            if not w_step:      # the wide prompts, before the first such
                prompts("wide", wide, w_base)
        if wide_left:
            step("wide", [(long_, l_base), (wide, w_base)], [t, w_step])
            wide_left, w_step = wide_left - 1, w_step + 1
        else:
            step("long", [(long_, l_base)], [t])
    for n in range(l_base, w_base + (len(wide) if w_step else 0)):
        engine.flush(-1000 - n)


def serve_first_tokens(sched, served, inputs) -> dict:
    """(b)'s serving: each prompt's greedy first token through the
    scheduler, one prompt a step (the first prompts' own program: a sampled
    step of no decode row, which no step of the window is), its routing
    recorded like (a)'s and the pairs the program counted; then the sink is
    taken away."""
    from deepspeed_tpu.inference.v2 import SamplingParams
    prompts = [p for p, _ in inputs["short"] + inputs["long"]]
    got, routing, counted = [], [], 0
    for uid, prompt in enumerate(prompts):
        sched.submit(-1 - uid, [int(t) for t in prompt],
                     SamplingParams(max_new_tokens=1))
        got.append(sched.run_to_completion()[-1 - uid][0])
        routing.append(served.take(1, len(prompt))[0][:len(prompt)])
        counted += int(sched.last_moe_counts[0])
    served.close()
    return {"prompts": prompts, "served": got, "routing": routing,
            "pairs_counted": counted}


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
    pairs = cfg.moe_top_k * (cfg.num_layers - cfg.first_k_dense)
    return {"served": got,
            "reference": [int(np.argmax(w[0][-1])) for w in want],
            "served_short_of_max": [round(g, 4) for g in short_of],
            "compared": len(prompts),
            "matched": int(sum(g <= pr["margin"] for g in short_of)),
            "pairs_counted": first["pairs_counted"],
            "pairs_reference": sum(int(w[1].sum()) for w in want),
            "held_pair_share": round(
                100.0 * first["pairs_counted"] / (tokens * pairs), 3)}


def verdict(rows, first, want, pr) -> dict:
    """The probe's verdict: the waves' judge and (c)."""
    off = sum(int(w[2].sum()) for w in want) \
        / max(sum(w[2].size for w in want), 1)
    probe = judge(rows, first, pr)
    return dict(probe, routing_off_share=round(off, 5),
                ok=bool(probe["ok"] and off <= pr["routing_off_share"]))


def _ahead(engine, params, cfg, widths):
    """Two pieces of set-up that need no result of the probe, started
    beside it: the reference's layer functions compiled for the probe's two
    lengths (``reference.compile_ahead``: the calls that follow load them
    from the persistent cache), now; and, through the function returned,
    the step programs of the engine's own lattice (``engine.model.lattice
    .keys``: what ``InferenceEngineV2.precompile`` would form, and what the
    driver's hints ask for next), to be called once the sink is away.
    Returns (that function, a function that waits for both)."""
    import concurrent.futures as cf

    from .. import reference_bailing_hybrid as reference
    pool = cf.ThreadPoolExecutor(4)
    jobs = [pool.submit(reference.compile_ahead, params,
                        reference_sizes(cfg), [n], )
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
    served = serve_waves(engine, inputs, pr)
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
        BailingHybridInferenceModel)
    from deepspeed_tpu.models.bailing_hybrid import BailingHybridForCausalLM

    c = sized(config, rehearse)
    # a rehearsal runs float32: at its debug widths bfloat16 rounds by
    # more than the limits, which are set for the widths that are run
    dtype = jnp.float32 if rehearse else jnp.dtype(config["dtype"])
    model = BailingHybridForCausalLM(
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
        BailingHybridInferenceModel)
    return InferenceEngineV2(
        BailingHybridInferenceModel(cfg, params),
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
