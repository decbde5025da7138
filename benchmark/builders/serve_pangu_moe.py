"""Builder ``serve_pangu_moe``: one ``InferenceEngineV2`` + ``FastGenScheduler``
over seeded bf16 weights of openPangu-Ultra-MoE (``pangu_ultra_moe``), cut
as its configuration file says: one chip of an expert-parallel group.

The program is entered only through ``PanguUltraMoEForCausalLM``,
``PanguUltraMoEInferenceModel``, ``InferenceEngineV2`` and
``FastGenScheduler``.  ``probe["ok"]`` comes from two comparisons with the
benchmark's own reference (``benchmark/reference_pangu_moe.py``, float32,
expanded attention, no cache), both at the widths that are run:

(a) LOGITS of teacher-forced steps through the latent cache
    (``engine.put``) against the reference's full forward over the same
    tokens, in three waves.  *short*: ``prompts`` prompts in waves of
    ``wave``, the last prompt position and ``decode_steps`` decode steps
    (contexts inside one group of pages).  *long*: ``long_rows`` prompts
    decoded for ``long_steps`` steps, so that their contexts grow through
    every page bucket the window times (the decode kernel's running
    max / denominator merge over 2, 4 and 8 groups of pages).  *wide*: at
    the long rows' steps ``wide_at``, ``wide_steps`` steps in which
    ``wide_copies`` copies of every short prompt decode beside them: the
    row bucket of the window's own steps, long and short contexts in one
    program.  Every row's relative root-mean-square difference from the
    reference's row is judged by :func:`judge`;
(b) greedy FIRST TOKENS through the scheduler (the fused step programs the
    traffic uses): the served token's logit in the reference lies within
    ``probe.margin`` of the reference's largest, for every prompt whose
    own row in (a) is no outlier (there the program chose another expert
    than the reference: its arg-max may differ by more than a rounding);
    and the token-expert pairs the program counted for those prefills
    against the reference's router.
"""

from __future__ import annotations

import numpy as np

from .serve_fastgen import ServeSystem, seeded_key, sized

SOURCE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "q_lora_rank", "kv_lora_rank", "rms_norm_eps",
    "rope_theta", "sandwich_norm", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "moe_intermediate_size", "routed_scaling_factor",
    "norm_topk_prob", "first_k_dense_replace", "hidden_act",
    "tie_word_embeddings", "attention_bias")


def source_of(config: dict, rehearse: bool) -> dict:
    """The source's keys as the program's model class takes them; the
    router keeps the outputs the configuration says it scores."""
    c = sized(config, rehearse)
    assert c["scoring_func"] == "sigmoid" and c["topk_method"] == "plain"
    assert c["rope_pairing"] == "interleaved"
    return dict({k: c[k] for k in SOURCE_KEYS},
                n_routed_experts_scored=c["routed_experts_scored"])


def reference_sizes(cfg) -> dict:
    return dict(eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                kv_lora_rank=cfg.kv_lora_rank, top_k=cfg.moe_top_k,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob,
                experts_first=cfg.experts_first,
                sandwich_norm=cfg.sandwich_norm)


def rel_rms(got, want) -> np.ndarray:
    """Relative rms difference of each row of ``got`` from ``want``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.mean((got - want) ** 2, -1) / np.mean(want ** 2, -1))


def reference_side(params, cfg, sequences, precision=None,
                   weight_precision=None):
    """Per sequence the reference's (logits [T, V], held pairs a routed
    layer and token [layers, T]) as numpy.  Every sequence is padded to
    the longest one's length (the reference compiles once; under the
    causal mask the padding reaches no position that is read)."""
    import jax.numpy as jnp

    from .. import reference_pangu_moe as reference
    sizes = reference_sizes(cfg)
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


def probe_inputs(pr: dict, seed: int, vocab: int) -> dict:
    """The probe's token ids, from the seed: ``short`` and ``long`` lists
    of (prompt, forced tokens)."""
    rng = np.random.default_rng([int(seed) % (2 ** 63), 7])

    def draw(rows, steps):
        lens = np.linspace(pr["min_len"], pr["max_len"], rows).astype(int)
        return [(rng.integers(0, vocab, int(n)).astype(np.int32),
                 rng.integers(0, vocab, steps).astype(np.int32))
                for n in lens]

    return {"short": draw(pr["prompts"], pr["decode_steps"]),
            "long": draw(pr.get("long_rows", 0), pr.get("long_steps", 0))}


def sequences_of(inputs: dict) -> list:
    """What the reference runs over: prompt and forced tokens, the short
    sequences first."""
    return [np.concatenate(pf) for pf in inputs["short"] + inputs["long"]]


class Rows:
    """The compared logits rows: (wave, sequence, relative rms)."""

    def __init__(self):
        self.wave, self.seq, self.err = [], [], []

    def add(self, wave, seqs, got, want) -> None:
        self.wave += [wave] * len(seqs)
        self.seq += list(seqs)
        self.err += rel_rms(got, np.stack(want)).tolist()


def logits_probe(engine, inputs, want, pr) -> Rows:
    """(a): ``engine.put`` of prompts, then of one forced token a row and
    step; each logits row against the reference's row of that position.
    Sequences are named ``s<i>`` (short), ``l<i>`` (long) and
    ``w<copy>.<i>`` (a copy of short sequence ``i`` in the wide steps)."""
    rows = Rows()
    short, long_ = inputs["short"], inputs["long"]
    wave = int(pr.get("wave", len(short)))

    def put(name, seqs, uids, tokens, refs):
        rows.add(name, seqs, np.asarray(engine.put(uids, tokens)), refs)

    def at(ref, source, i, step):
        """The reference's row after ``step`` forced tokens (-1: after the
        prompt alone)."""
        return want[ref][0][len(source[i][0]) + step]

    # short: waves of a few, as (b) sends them
    for lo in range(0, len(short), wave):
        idx = list(range(lo, min(lo + wave, len(short))))
        uids, seqs = [-1000 - i for i in idx], [f"s{i}" for i in idx]
        put("short", seqs, uids, [short[i][0] for i in idx],
            [at(i, short, i, -1) for i in idx])
        for step in range(pr["decode_steps"]):
            put("short", seqs, uids, [short[i][1][step:step + 1] for i in idx],
                [at(i, short, i, step) for i in idx])
        for uid in uids:
            engine.flush(uid)
    if not long_:
        return rows

    # long, and at ``wide_at`` the wide steps beside it
    base, l_idx = len(short), list(range(len(long_)))
    l_uids, l_seqs = [-2000 - i for i in l_idx], [f"l{i}" for i in l_idx]
    put("long", l_seqs, l_uids, [long_[i][0] for i in l_idx],
        [at(base + i, long_, i, -1) for i in l_idx])
    copies = [(c, i) for c in range(int(pr.get("wide_copies", 0)))
              for i in range(len(short))]
    w_uids = [-3000 - n for n in range(len(copies))]
    w_seqs = [f"w{c}.{i}" for c, i in copies]
    wide_at = pr.get("wide_at", []) if copies else []
    assert len(wide_at) * pr.get("wide_steps", 0) <= pr["decode_steps"]
    w_step = wide_left = 0
    for step in range(pr["long_steps"]):
        if step in wide_at:
            wide_left = int(pr["wide_steps"])
            # the copies' prompts, before the first wide steps
            for lo in ([] if w_step else range(0, len(copies), wave)):
                part = range(lo, min(lo + wave, len(copies)))
                put("wide", [w_seqs[n] for n in part],
                    [w_uids[n] for n in part],
                    [short[copies[n][1]][0] for n in part],
                    [at(copies[n][1], short, copies[n][1], -1)
                     for n in part])
        seqs, uids = list(l_seqs), list(l_uids)
        toks = [long_[i][1][step:step + 1] for i in l_idx]
        refs = [at(base + i, long_, i, step) for i in l_idx]
        if wide_left:
            seqs, uids = seqs + w_seqs, uids + w_uids
            toks += [short[i][1][w_step:w_step + 1] for _, i in copies]
            refs += [at(i, short, i, w_step) for _, i in copies]
        put("wide" if wide_left else "long", seqs, uids, toks, refs)
        if wide_left:
            wide_left, w_step = wide_left - 1, w_step + 1
    for uid in l_uids + (w_uids if w_step else []):
        engine.flush(uid)
    return rows


def first_token_probe(sched, prompts, want, pr, pairs_per_token,
                      outlier) -> dict:
    """(b): greedy first tokens through the scheduler in waves, and the
    pairs the program counted for each wave's prefill step.
    ``outlier[i]``: prompt ``i``'s own logits row in (a) is one."""
    from deepspeed_tpu.inference.v2 import SamplingParams
    got, counted, expected = [], 0, 0
    wave = int(pr.get("wave", len(prompts)))
    for lo in range(0, len(prompts), wave):
        uids = list(range(lo, min(lo + wave, len(prompts))))
        for uid in uids:
            sched.submit(-1 - uid, [int(t) for t in prompts[uid]],
                         SamplingParams(max_new_tokens=1))
        out = sched.run_to_completion()
        got += [out[-1 - uid][0] for uid in uids]
        if sched.last_moe_counts is not None:
            counted += int(sched.last_moe_counts[0])
            expected += sum(int(want[u][1][:, :len(prompts[u])].sum())
                            for u in uids)
    ref_tok, short_of = [], []
    for p, tok, (logits, _) in zip(prompts, got, want):
        row = logits[len(p) - 1]
        ref_tok.append(int(np.argmax(row)))
        short_of.append(float(row.max() - row[tok]))
    compared = [gap <= pr["margin"]
                for gap, out in zip(short_of, outlier) if not out]
    tokens = sum(len(p) for p in prompts)
    return {"served": got, "reference": ref_tok,
            "served_short_of_max": [round(g, 4) for g in short_of],
            "compared": len(compared), "matched": int(sum(compared)),
            "pairs_counted": counted, "pairs_reference": expected,
            "held_pair_share": round(
                100.0 * counted / (tokens * pairs_per_token), 3)}


def judge(rows: Rows, first: dict, pr: dict) -> dict:
    """The probe's verdict, from the compared rows and (b)'s counts: each
    wave's MEDIAN relative rms under ``logit_rel_rms``; at most
    ``outlier_share`` of all rows over ``outlier_rel_rms`` (a row whose
    token's 8th and 9th expert scores all but tie lands on another expert
    than the reference's: a whole expert's difference, not a rounding, and
    it stays in that row); no SEQUENCE with more than
    ``sequence_outlier_share`` of its rows over it (a fault in one row's
    pages or slot spoils that sequence's rows and few others); the first
    tokens; the pairs."""
    err, wave = np.asarray(rows.err), np.asarray(rows.wave)
    out = err > pr["outlier_rel_rms"]
    stats = {"rows": int(err.size), "outlier_rows": int(out.sum()),
             "rel_rms_max": round(float(err.max()), 5)}
    for name in dict.fromkeys(rows.wave):
        part = err[wave == name]
        stats[name] = {"rows": int(part.size),
                       "rel_rms_median": round(float(np.median(part)), 5),
                       "rel_rms_p90": round(
                           float(np.quantile(part, 0.9)), 5),
                       "outlier_rows": int(out[wave == name].sum())}
    seqs = {}
    for name, bad in zip(rows.seq, out):
        n, k = seqs.get(name, (0, 0))
        seqs[name] = (n + 1, k + int(bad))
    worst, share = max(seqs.items(), key=lambda kv: kv[1][1] / kv[1][0])
    stats["rel_rms_median"] = max(
        stats[name]["rel_rms_median"] for name in dict.fromkeys(rows.wave))
    stats["sequence_outlier_worst"] = [worst, share[1], share[0]]
    pairs_off = abs(first["pairs_counted"] - first["pairs_reference"]) \
        / max(first["pairs_reference"], 1)
    stats["ok"] = bool(
        stats["rel_rms_median"] <= pr["logit_rel_rms"]
        and stats["outlier_rows"] <= pr["outlier_share"] * stats["rows"]
        and share[1] <= pr["sequence_outlier_share"] * share[0]
        and first["matched"] == first["compared"] >= pr["min_compared"]
        and pairs_off <= pr["pairs_tolerance"])
    return dict(stats, **first)


def run_probe(engine, sched, cfg, inputs, want, pr) -> dict:
    """(a) and (b) on a built engine, against the reference side ``want``
    (one entry a sequence of :func:`sequences_of`)."""
    import concurrent.futures as cf
    keys = pr.get("programs", [])
    if keys:
        # the probe's step programs, formed on a few threads (as the
        # hints are) instead of one after another on first use
        with cf.ThreadPoolExecutor(4) as pool:
            list(pool.map(lambda k: engine.precompile_keys([k]), keys))
    rows = logits_probe(engine, inputs, want, pr)
    prompts = [p for p, _ in inputs["short"] + inputs["long"]]
    first_row = {}
    for name, err in zip(rows.seq, rows.err):
        first_row.setdefault(name, err)     # a sequence's prompt row
    outlier = [first_row[f"{kind}{i}"] > pr["outlier_rel_rms"]
               for kind, part in (("s", inputs["short"]),
                                  ("l", inputs["long"]))
               for i in range(len(part))]
    routed_layers = cfg.num_layers - cfg.first_k_dense
    first = first_token_probe(sched, prompts, want, pr,
                              cfg.moe_top_k * routed_layers, outlier)
    return judge(rows, first, pr)


def make_model(config: dict, seed: int, rehearse: bool):
    """(configuration of the program's model class, seeded weights)."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    # a program without the family fails here, before anything is built
    from deepspeed_tpu.inference.v2.model_implementations import (  # noqa
        PanguUltraMoEInferenceModel)
    from deepspeed_tpu.models.pangu_moe import PanguUltraMoEForCausalLM

    c = sized(config, rehearse)
    # a rehearsal runs float32: at its debug widths bfloat16 rounds by
    # more than the limits, which are set for the widths that are run
    dtype = jnp.float32 if rehearse else jnp.dtype(config["dtype"])
    model = PanguUltraMoEForCausalLM(
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
        PanguUltraMoEInferenceModel)
    return InferenceEngineV2(
        PanguUltraMoEInferenceModel(cfg, params),
        RaggedInferenceEngineConfig(
            state_manager=StateManagerConfig(
                max_tracked_sequences=eng["max_sequences"],
                max_ragged_sequence_count=eng["max_sequences"],
                max_ragged_batch_size=eng["token_budget"]),
            kv_cache=KVCacheUserConfig(
                page_size=eng["page_size"], num_pages=eng["num_pages"],
                dtype=jnp.float32 if rehearse
                else jnp.dtype(eng["kv_dtype"])),
            serving=ServingOptimizationConfig(**eng["serving"])))


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
    cfg = system.cfg
    return {"kind": system.kind, "layers": cfg.num_layers,
            "params": cfg.n_params(), "pages": system.num_pages,
            "bytes_per_page": system.engine.model.kv_config.bytes_per_page,
            "experts_held": cfg.held_experts, "probe": system.probe}
