"""Laguna (``laguna``) as a served family, at a small size with seeded
weights: full and window attention layers in one model over two page
groups, a head count a kind, the per-head output gate, two ropes, a leading
dense layer and routed layers that hold some of the experts, on the FastGen
path, against the plain reference
(``deepspeed_tpu/models/laguna_reference.py``); and the two page groups on
the host's side (``inference/v2/ragged/manager.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from deepspeed_tpu.inference.v2 import (
    FastGenScheduler, InferenceEngineV2, RaggedInferenceEngineConfig,
    SamplingParams, ServingOptimizationConfig, StateManagerConfig)
from deepspeed_tpu.inference.v2.config import KVCacheUserConfig
from deepspeed_tpu.inference.v2.model_implementations import (
    LagunaInferenceModel, implementation_for, supported_model_types)
from deepspeed_tpu.inference.v2.ragged.blocked_allocator import (
    KVAllocationError)
from deepspeed_tpu.inference.v2.step_key import window_slots
from deepspeed_tpu.models import laguna, laguna_reference as reference
from deepspeed_tpu.models.laguna import LagunaForCausalLM
from deepspeed_tpu.models.transformer import layer_runs
from deepspeed_tpu.moe import held

WINDOW, PAGE = 32, 8
SOURCE = dict(
    model_type="laguna", vocab_size=160, hidden_size=64,
    intermediate_size=96, num_hidden_layers=5, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, attention_bias=False,
    rms_norm_eps=1e-6, num_experts=4, num_experts_scored=16,
    num_experts_per_tok=3, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[0], tie_word_embeddings=False,
    gating="per-head", sliding_window=WINDOW,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 64, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    # the published lists stay whole: 48 entries, the first
    # num_hidden_layers are read
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + (["full_attention"] + ["sliding_attention"] * 3) * 11,
    mlp_layer_types=["dense"] + ["sparse"] * 47,
    gating_types=["per_head"] * 48, moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[4, 6, 6, 6] * 12,
    moe_router_logit_softcapping=0, moe_apply_router_weight_on_input=False)


def family(first=4, held_experts=4, seed=3, **over):
    model = LagunaForCausalLM(
        dict(SOURCE, num_experts=held_experts, **over),
        experts_first=first, dtype=jnp.float32)
    return model.cfg, meta.unbox(model.init_params(jax.random.key(seed)))


def engine_of(cfg, params, pages=64, window_pages=40, seqs=8, serving=None,
              budget=256):
    return InferenceEngineV2(
        LagunaInferenceModel(cfg, params),
        RaggedInferenceEngineConfig(
            state_manager=StateManagerConfig(
                max_tracked_sequences=seqs, max_ragged_sequence_count=seqs,
                max_ragged_batch_size=budget),
            kv_cache=KVCacheUserConfig(
                page_size=PAGE, num_pages=pages, dtype=jnp.float32,
                window_num_pages=window_pages),
            serving=serving or ServingOptimizationConfig()))


def sequences_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SOURCE["vocab_size"], n).astype(np.int32)
            for n in lengths]


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def served_logit_error(cfg, params, sizes=None, lengths=(62, 53),
                       prompts=(11, 20)):
    """Largest relative rms difference of a served logits row (the last
    prompt position, then every teacher-forced decode step through both
    page groups, to the sequence's end) against the plain reference's full
    forward under ``sizes`` (default: the configuration's own)."""
    sizes = sizes or reference.sizes_of(cfg)
    engine = engine_of(cfg, params)
    seqs = sequences_of(lengths)
    want = [np.asarray(reference.forward(params, s, sizes)[0]) for s in seqs]
    uids = list(range(len(seqs)))
    got = np.asarray(engine.put(uids, [s[:p] for s, p in zip(seqs, prompts)]))
    worst = max(rel_rms(got[i], want[i][p - 1])
                for i, p in enumerate(prompts))
    at = list(prompts)
    while uids:
        got = np.asarray(engine.put(uids, [seqs[u][at[u]:at[u] + 1]
                                           for u in uids]))
        worst = max([worst] + [rel_rms(got[n], want[u][at[u]])
                               for n, u in enumerate(uids)])
        engine.state_manager.check_invariants()
        for u in uids:
            at[u] += 1
        uids = [u for u in uids if at[u] < len(seqs[u])]
    return worst, engine


@pytest.mark.parametrize("layers,lengths,prompts", [
    (5, (62, 53), (11, 20)),        # a dense layer and one period
    (8, (45,), (9,)),               # ... and a tail of three layers
    (10, (41, 60, 37), (33, 8, 16)),  # two periods (a scan of 2) and a tail
], ids=["one_period", "period_and_tail", "two_periods_and_tail"])
def test_served_logits_match_the_plain_reference(layers, lengths, prompts):
    """Prefill, then decode through both page groups, equals the
    reference's full forward: contexts cross the window (32) and page
    boundaries (8), and window pages are released on the way."""
    cfg, params = family(num_hidden_layers=layers)
    period = [("window", 3), ("full", 1)]
    assert layer_runs(cfg) == {5: (1, period, 1, 0), 8: (1, period, 1, 3),
                               10: (1, period, 2, 1)}[layers]
    worst, engine = served_logit_error(cfg, params, None, lengths, prompts)
    assert worst < 2e-5
    state = engine.state_manager
    assert state.window_pages_released > 0
    sd = state.get_sequence(0)
    assert sd.window_base > 0 and len(sd.window_pages) < len(sd.pages)


def test_a_pattern_of_one_kind_behind_leading_layers_of_the_same_kind():
    """Two leading dense layers (full, window) and then window layers
    alone: a pattern of period 1 whose scan starts at the window group's
    SECOND layer and at the held experts' FIRST."""
    cfg, params = family(num_hidden_layers=4, mlp_only_layers=[0, 1],
                         mlp_layer_types=["dense"] * 2 + ["sparse"] * 46)
    assert layer_runs(cfg) == (2, [("window", 1)], 2, 0)
    worst, _ = served_logit_error(cfg, params, None, (45,), (9,))
    assert worst < 2e-5


def planted(cfg, fault):
    """The reference's sizes with one fault planted (comparing the sound
    program with a faulty reference is comparing a faulty program with
    the sound reference)."""
    sizes = reference.sizes_of(cfg)
    if fault == "dropped_gate":
        return dict(sizes, gate=False)
    if fault == "full_rope_on_window_layers":
        return dict(sizes, rope=dict(sizes["rope"],
                                     window=sizes["rope"]["full"]))
    if fault == "window_less_a_page":
        return dict(sizes, window=WINDOW - PAGE)
    if fault == "window_plus_a_page":
        return dict(sizes, window=WINDOW + PAGE)
    raise KeyError(fault)


@pytest.mark.parametrize("fault", [
    "dropped_gate", "full_rope_on_window_layers", "window_less_a_page",
    "window_plus_a_page"])
def test_a_planted_fault_fails_the_probes_tolerance(fault):
    """Each of the faults the probe has to catch moves the logits by far
    more than the probe's limit on the median row (0.014 at the published
    widths in bfloat16; here float32 reads 1e-5 when sound)."""
    cfg, params = family()
    worst, _ = served_logit_error(cfg, params, planted(cfg, fault))
    assert worst > 0.014


def test_greedy_through_the_scheduler_matches_the_reference():
    """The fused step programs (sample, chain, mixed): greedy tokens of
    five requests of unequal lengths equal the reference's arg-max of
    every position, and all pages of both groups come back."""
    cfg, params = family()
    engine = engine_of(cfg, params, seqs=8, budget=64)   # prompts in turns
    sched = FastGenScheduler(engine)
    prompts = sequences_of((9, 17, 30, 12, 21), seed=1)
    news = (40, 25, 50, 33, 45)
    for uid, (p, n) in enumerate(zip(prompts, news)):
        sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=n))
    out = sched.run_to_completion()
    forward = jax.jit(lambda ids: reference.forward(
        params, ids, reference.sizes_of(cfg))[0])
    for uid, (p, n) in enumerate(zip(prompts, news)):
        ids = np.zeros(96, np.int32)
        ids[:len(p) + n] = np.concatenate([p, out[uid][:n]])
        want = np.asarray(forward(ids))[len(p) - 1:len(p) + n - 1].argmax(-1)
        np.testing.assert_array_equal(np.asarray(out[uid][:n]), want)
    kinds = {k.kind for k in engine.model._dispatched_keys}
    assert {"sample", "chain", "mixed"} <= kinds
    engine.state_manager.check_invariants()
    assert engine.free_blocks == 64 and engine.free_window_blocks == 40
    assert engine.state_manager.window_pages_released > 0


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One routed layer: the partial results of all shares (experts 0-3,
    4-7, ...), the shared expert counted once, add up to the layer with
    every expert held; an expert's weights come from its layer and its
    global index; the served share equals the reference's."""
    scored, each = SOURCE["num_experts_scored"], 4
    whole_cfg, whole = family(first=0, held_experts=scored)
    sizes = reference.sizes_of(whole_cfg)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(12, 64)),
                    jnp.float32)

    def layer1(params):
        moe = jax.tree.map(lambda a: a[0], params["periods"]["l0"])["moe"]
        return moe, {n: params["experts"][n][0] for n in ("wg", "wu", "wd")}

    moe0, experts0 = layer1(whole)
    want, counts = reference.routed_ffn(x, moe0, experts0, sizes)
    assert int(counts.sum()) == 12 * SOURCE["num_experts_per_tok"]
    shared = reference.swiglu(x, moe0["shared"])
    total = jnp.zeros_like(x)
    for share in range(scored // each):
        cfg, params = family(first=share * each, held_experts=each)
        moe, experts = layer1(params)
        for n in ("wg", "wu", "wd"):      # the uncut model's own experts
            np.testing.assert_array_equal(
                np.asarray(experts[n]),
                np.asarray(experts0[n][share * each:(share + 1) * each]))
        part, _ = reference.routed_ffn(x, moe, experts,
                                       reference.sizes_of(cfg))
        total = total + part - shared     # the shared expert once
        served, _ = held.held_experts_ffn(
            x, *held.route_softmax_topk(x, moe["router"], 3, 2.5),
            experts, share * each)
        np.testing.assert_allclose(np.asarray(served),
                                   np.asarray(part - shared), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=5e-5)


def test_softmax_router_against_numbers_worked_by_hand():
    """Four experts, top 2: logits (0, ln 2, ln 4, ln 1) give softmax
    scores 1/8, 2/8, 4/8, 1/8; the two largest are experts 2 and 1, their
    weights 2.5 x (4/6, 2/6)."""
    x = jnp.ones((1, 1), jnp.float32)
    w = jnp.log(jnp.asarray([[1.0, 2.0, 4.0, 1.0]], jnp.float32))
    experts, weights = held.route_softmax_topk(x, w, 2, 2.5)
    assert experts.tolist() == [[2, 1]]
    np.testing.assert_allclose(np.asarray(weights),
                               [[2.5 * 4 / 6, 2.5 * 2 / 6]], rtol=1e-6)
    assert held.ROUTERS["softmax"] is held.route_softmax_topk


def test_yarn_frequencies_against_numbers_worked_by_hand():
    """The published full-attention rope: 64 rotated dims, theta 5e5,
    factor 128, original 8192, beta 32 / 1.  correction_dim(32) = 64 ln(8192
    / (64 pi)) / (2 ln 5e5) = 9.03 -> low 9; correction_dim(1) = 64 ln(8192
    / (2 pi)) / (2 ln 5e5) = 17.49 -> high 18.  Pairs 0-9 keep their
    frequency, pairs 18-31 are divided by 128, between them a ramp."""
    cfg = laguna.laguna_config(dict(
        SOURCE, head_dim=128, rope_parameters=dict(
            SOURCE["rope_parameters"], full_attention=dict(
                SOURCE["rope_parameters"]["full_attention"],
                original_max_position_embeddings=8192))))
    freqs, scale = laguna.rope_frequencies(cfg, "full")
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    assert freqs.shape == (32,) and scale == 1.4852030263919618
    np.testing.assert_allclose(np.asarray(freqs[:10]), plain[:10], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(freqs[18:]), plain[18:] / 128,
                               rtol=1e-6)
    ramp = (12 - 9) / (18 - 9)
    np.testing.assert_allclose(
        float(freqs[12]), plain[12] / 128 * ramp + plain[12] * (1 - ramp),
        rtol=1e-6)
    window, one = laguna.rope_frequencies(cfg, "window")
    np.testing.assert_allclose(
        np.asarray(window), 10000.0 ** (-np.arange(0, 128, 2) / 128),
        rtol=1e-6)
    assert one == 1.0
    # the reference computes the same, on its own
    np.testing.assert_allclose(
        np.asarray(reference.inverse_frequencies(500000.0, 64,
                                                 cfg.rope_yarn)),
        np.asarray(freqs), rtol=1e-6)


def test_config_reads_the_first_layers_of_the_published_lists():
    cfg, params = family()
    assert cfg.layer_kinds == ("full", "window", "window", "window", "full")
    assert dict(cfg.heads_by_kind) == {"full": 4, "window": 6}
    assert (cfg.first_k_dense, cfg.n_routed_experts, cfg.held_experts,
            cfg.moe_top_k, cfg.router_scoring) == (1, 16, 4, 3, "softmax")
    assert laguna.group_layers(cfg) == {"full": 2, "window": 3}
    period = params["periods"]
    assert period["l0"]["attn"]["wq"].shape == (1, 64, 6 * 16)
    assert period["l3"]["attn"]["wq"].shape == (1, 64, 4 * 16)
    assert period["l3"]["attn"]["wo"].shape == (1, 4 * 16, 64)
    assert period["l0"]["attn"]["wgate"].shape == (1, 64, 6)
    assert params["experts"]["wg"].shape == (4, 4, 32, 64)
    assert "mlp" in params["dense_layers"]["l0"]
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    gains = 64 * (2 * 5 + 1)
    assert cfg.n_params() == leaves - gains
    with pytest.raises(AssertionError, match="disagree"):
        laguna.laguna_config(dict(SOURCE, mlp_only_layers=[0, 1]))


def test_implementation_for_laguna_and_what_it_refuses():
    assert implementation_for("laguna") is LagunaInferenceModel
    assert supported_model_types()["laguna"] == "LagunaInferenceModel"
    cfg, params = family()
    model = LagunaInferenceModel(cfg, params)
    assert model.kv_config.num_layers == 2
    assert model.window_kv_config.num_layers == 3
    assert model.step_tail == 3
    assert model.table.window_slots(1) == window_slots(WINDOW, 64, 1) == 8
    with pytest.raises(ValueError, match="int8"):
        engine_of(cfg, params, serving=ServingOptimizationConfig(
            kv_quantization="int8"))
    with pytest.raises(ValueError, match="tp_degree"):
        engine_of(cfg, params, serving=ServingOptimizationConfig(
            tp_degree=2))
    with pytest.raises(ValueError, match="speculation"):
        engine_of(cfg, params, serving=ServingOptimizationConfig(
            speculative=True, spec_drafter="model"))
    with pytest.raises(ValueError, match="quantization"):
        model.quantize_weights("fp8_e4m3")
    from deepspeed_tpu.models.transformer import forward
    with pytest.raises(NotImplementedError, match="laguna_reference"):
        forward(cfg, params, jnp.zeros((1, 4), jnp.int32))


@pytest.mark.parametrize("window,page,q,slots", [
    (512, 64, 1, 16), (512, 64, 128, 16), (512, 64, 512, 24),
    (32, 8, 1, 8), (32, 8, 32, 16)])
def test_window_slots_hold_what_a_row_can_hold_live(window, page, q, slots):
    """From the page of position ``seen - window + 1`` to the page of
    ``seen + q - 1``, whatever ``seen``; whole groups of 8."""
    assert window_slots(window, page, q) == slots
    most = max((seen + q - 1) // page - max(seen - window + 1, 0) // page + 1
               for seen in range(4 * window))
    assert slots - 8 < most <= slots


# -- the two page groups on the host's side -----------------------------------

def prefill(engine, uid, n, seed=0):
    tokens = np.random.default_rng(seed).integers(0, 160, n).astype(np.int32)
    engine.put([uid], [tokens])
    return tokens


def test_a_window_table_releases_exactly_the_pages_under_the_window():
    """After every commit the window table starts at the page of position
    ``seen - window + 1`` and ends at the page of the last token; the full
    table keeps every page."""
    cfg, params = family()
    engine = engine_of(cfg, params)
    state = engine.state_manager
    prefill(engine, 7, 13)
    sd = state.get_sequence(7)
    assert (sd.window_base, len(sd.window_pages), len(sd.pages)) == (0, 2, 2)
    released = 0
    for step in range(60):
        before = list(sd.window_pages)
        engine.put([7], [np.asarray([step], np.int32)])
        seen = sd.seen_tokens
        first = max(seen - WINDOW + 1, 0) // PAGE
        assert sd.window_base == first
        assert sd.window_base + len(sd.window_pages) == -(-seen // PAGE)
        assert len(sd.pages) == -(-seen // PAGE)
        gone = [p for p in before if p not in sd.window_pages]
        released += len(gone)
        assert state.window_pages_released == released
        state.check_invariants()
    assert released == (73 - WINDOW + 1) // PAGE
    assert state.window_occupancy() == (len(sd.window_pages),
                                        73 - sd.window_base * PAGE)
    assert state.kv_occupancy() == (10, 73)


@pytest.mark.parametrize("codec", ["flush", "preempt", "snapshot",
                                   "handoff"])
def test_invariants_hold_across_the_codecs_with_two_groups(codec):
    """Admit, decode past the window, then flush / preempt and restore /
    snapshot into a second engine / hand one sequence over: both groups'
    accounts hold at every point, and decoding goes on to the same
    logits."""
    cfg, params = family()
    engine = engine_of(cfg, params)
    state = engine.state_manager
    seqs = sequences_of((64, 50), seed=4)
    uids = [0, 1]
    engine.put(uids, [s[:20] for s in seqs])
    for at in range(20, 45):
        engine.put(uids, [s[at:at + 1] for s in seqs])
    state.check_invariants()
    held_w = state.window_occupancy()[0]
    assert held_w and engine.free_window_blocks == 40 - held_w
    if codec == "flush":
        engine.flush(0)
        state.check_invariants()
        engine.flush(1)
        state.check_invariants()
        assert (engine.free_blocks, engine.free_window_blocks) == (64, 40)
        return
    other = engine_of(cfg, params)
    if codec == "preempt":
        engine.offload_sequence(0)
        sd = state.get_sequence(0)
        assert sd.window_pages == [] and sd.window_blob is not None
        assert state.offloaded_blobs == 2       # one a group
        state.check_invariants()
        engine.restore_sequence(0)
        assert sd.window_blob is None and state.offloaded_blobs == 0
        target = engine
    elif codec == "snapshot":
        meta_, arrays = state.export_state()
        other.state_manager.import_state(meta_, arrays)
        target = other
    else:
        meta_, arrays = state.export_state(seq_ids=[0])
        got = other.state_manager.import_state(meta_, arrays)
        assert got["pages_streamed"] == len(state.get_sequence(0).pages)
        engine.flush(0)
        target, uids = other, [0]
    state.check_invariants()
    target.state_manager.check_invariants()
    want = [np.asarray(reference.forward(
        params, s, reference.sizes_of(cfg))[0]) for s in seqs]
    for at in range(45, 50):
        got = np.asarray(target.put(uids, [seqs[u][at:at + 1] for u in uids]))
        for n, u in enumerate(uids):
            assert rel_rms(got[n], want[u][at]) < 2e-5
        target.state_manager.check_invariants()


def test_admission_reserves_in_both_groups_or_in_neither():
    """A window pool with room for one sequence's pages: the second
    sequence fits the full group and not the window group, and reserves
    nothing in either."""
    cfg, params = family()
    engine = engine_of(cfg, params, pages=64, window_pages=3)
    state = engine.state_manager
    prefill(engine, 0, 17)                      # 3 pages of each group
    assert (engine.free_blocks, engine.free_window_blocks) == (61, 0)
    from deepspeed_tpu.inference.v2.engine import (SchedulingError,
                                                   SchedulingResult)
    assert engine.can_schedule([1], [9]) \
        == SchedulingResult.KVCacheLimitExceeded
    assert engine.window_blocks_needed(1, 9) == 2
    with pytest.raises(SchedulingError):
        engine.put([1], [np.zeros(9, np.int32)])
    with pytest.raises(KVAllocationError, match="window group"):
        state.allocate_for(state.get_or_create_sequence(2), 9)
    assert state.get_sequence(2).pages == []
    assert (engine.free_blocks, engine.free_window_blocks) == (61, 0)
    engine.flush(2)
    state.check_invariants()
    # the scheduler's admission holds the same account
    sched = FastGenScheduler(engine)
    sched.submit(5, list(range(9)), SamplingParams(max_new_tokens=2))
    sched.step()
    assert state.get_sequence(5) is None or not state.get_sequence(5).pages
    engine.flush(0)
    out = sched.run_to_completion()
    assert len(out[5]) == 2
    state.check_invariants()


def test_a_model_of_one_group_has_no_window_pool():
    """One window or none: ONE group, the table as it was, the prefix
    cache on."""
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    model = LlamaForCausalLM("debug", sliding_window=32)
    params = meta.unbox(model.init_params(jax.random.key(0)))
    engine = InferenceEngineV2(
        implementation_for("mistral")(model.cfg, params),
        RaggedInferenceEngineConfig(kv_cache=KVCacheUserConfig(
            page_size=PAGE, num_pages=32, dtype=jnp.float32)))
    state = engine.state_manager
    assert state.window_cache is None and state.prefix_cache is not None
    assert engine.free_window_blocks == 0 and engine.take_attended() == (0, 0)
    engine.put([0], [np.arange(50, dtype=np.int32)])
    sd = state.get_sequence(0)
    assert sd.window_pages == [] and sd.pages[0] == 0   # evicted in place
    assert engine.model.table.window_slots(1) == 0
    assert model.cfg.layer_kinds == ()


def test_step_spans_carry_the_window_groups_counts():
    """Under telemetry ``fastgen.step`` carries the window group's pages,
    tokens and releases and what the decode rows attend, ``kv.evict_window``
    is a span under ``engine.commit``, and the held-experts counts ride the
    token vector's tail as for the other held-experts family."""
    import deepspeed_tpu.telemetry as telemetry
    from deepspeed_tpu.telemetry import get_tracer
    cfg, params = family()
    sched = FastGenScheduler(engine_of(cfg, params))
    prompts = sequences_of((21, 30), seed=2)
    telemetry.set_enabled(True)
    try:
        mark = len(get_tracer().records())
        for uid, p in enumerate(prompts):
            sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=24))
        sched.run_to_completion()
        recs = get_tracer().records()[mark:]
    finally:
        telemetry.set_enabled(False)
    steps = [r[5] for r in recs if r[0] == "fastgen.step" and r[5]]
    assert steps and all(
        {"kv_pages_reserved_window", "kv_tokens_held_window",
         "kv_pages_released_window", "attn_tokens_full",
         "attn_tokens_window"} <= set(s) for s in steps)
    assert sum(s["kv_pages_released_window"] for s in steps) > 0
    for s in steps:
        assert s["kv_tokens_held_window"] <= s["kv_tokens_held"]
        assert s["kv_tokens_held_window"] \
            <= s["kv_pages_reserved_window"] * PAGE
        assert s["attn_tokens_window"] <= s["attn_tokens_full"]
    assert any(s["attn_tokens_window"] == 2 * WINDOW < s["attn_tokens_full"]
               for s in steps)
    first = next(s for s in steps if "moe_pairs_here" in s)
    sizes = reference.sizes_of(cfg)
    assert first["moe_tokens"] == 51
    assert first["moe_pairs_here"] == sum(
        int(reference.forward(params, jnp.asarray(p), sizes)[1].sum())
        for p in prompts)
    ids = {r[6]: r for r in recs}
    evicts = [r for r in recs if r[0] == "kv.evict_window"]
    assert evicts and all(ids[r[7]][0] == "engine.commit" for r in evicts)
