"""SmallThinker (``smallthinker``) as a served family, at a small size with
seeded weights: global layers without rope and window layers under rope in
one model over two page groups at one head count (7 query heads a KV head),
every layer routed from its attention block's input over ReLU-gated
experts that are all held here, on the FastGen path, against the plain
reference (``deepspeed_tpu/models/smallthinker_reference.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from deepspeed_tpu.inference.v2 import (
    FastGenScheduler, InferenceEngineV2, RaggedInferenceEngineConfig,
    SamplingParams, ServingOptimizationConfig, StateManagerConfig)
from deepspeed_tpu.inference.v2.config import KVCacheUserConfig
from deepspeed_tpu.inference.v2.model import RaggedInferenceModel
from deepspeed_tpu.inference.v2.model_implementations import (
    SmallThinkerInferenceModel, implementation_for, supported_model_types)
from deepspeed_tpu.inference.v2.step_key import window_slots
from deepspeed_tpu.models import smallthinker
from deepspeed_tpu.models import smallthinker_reference as reference
from deepspeed_tpu.models.smallthinker import SmallThinkerForCausalLM
from deepspeed_tpu.models.transformer import layer_runs
from deepspeed_tpu.moe import held

WINDOW, PAGE = 128, 8
SOURCE = dict(
    model_name="smallthinker_debug", vocab_size=160, hidden_size=64,
    head_dim=16, num_attention_heads=14, num_key_value_heads=2,
    num_hidden_layers=8, max_position_embeddings=16384,
    moe_ffn_hidden_size=32, moe_num_primary_experts=8,
    moe_num_active_primary_experts=3,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=1500000, rope_scaling=None,
    sliding_window_size=WINDOW, tie_word_embeddings=False,
    # the published lists stay whole: 52 entries, the first
    # num_hidden_layers are read
    rope_layout=[0, 1, 1, 1] * 13, sliding_window_layout=[0, 1, 1, 1] * 13)


def family(seed=3, first=0, **over):
    model = SmallThinkerForCausalLM(dict(SOURCE, **over),
                                    experts_first=first, dtype=jnp.float32)
    return model.cfg, meta.unbox(model.init_params(jax.random.key(seed)))


def engine_of(cfg, params, pages=96, window_pages=64, seqs=8, serving=None,
              budget=256, impl=SmallThinkerInferenceModel):
    return InferenceEngineV2(
        impl(cfg, params),
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


def test_served_logits_match_the_plain_reference_and_a_fault_does_not():
    """Prefill, then every teacher-forced decode step through both page
    groups to the sequence's end (150 tokens), equals the
    reference's full forward: contexts cross the window (128) and page
    boundaries (8), and window pages are released on the way.  Read
    against a reference with a fault planted (comparing the sound program
    with a faulty reference is comparing a faulty program with the sound
    reference) the same rows are far over the probe's limit: a window a
    page off either way, and the router fed the post-attention norm's
    output.  (The benchmark builder's six controls, each against one
    serving: ``tests/benchmark/test_benchmark_smallthinker.py``.)"""
    cfg, params = family()
    assert layer_runs(cfg) == (0, [("full", 1), ("window", 3)], 2, 0)
    engine = engine_of(cfg, params)
    seqs, prompts = sequences_of((150,)), (21,)
    rows = [[None] * len(s) for s in seqs]
    uids = list(range(len(seqs)))
    got = np.asarray(engine.put(uids, [s[:p] for s, p in zip(seqs, prompts)]))
    for u, p in enumerate(prompts):
        rows[u][p - 1] = got[u]
    at = list(prompts)
    while uids:
        got = np.asarray(engine.put(uids, [seqs[u][at[u]:at[u] + 1]
                                           for u in uids]))
        for n, u in enumerate(uids):
            rows[u][at[u]] = got[n]
            at[u] += 1
        uids = [u for u in uids if at[u] < len(seqs[u])]
    state = engine.state_manager
    state.check_invariants()
    assert state.window_pages_released > 0
    sd = state.get_sequence(0)
    assert sd.window_base > 0 and len(sd.window_pages) < len(sd.pages)

    def worst(sizes, which):
        forward = jax.jit(lambda ids: reference.forward(params, ids,
                                                        sizes)[0])
        return max(rel_rms(rows[u][t], want[t]) for u in which
                   for want in [np.asarray(forward(seqs[u]))]
                   for t in range(prompts[u] - 1, len(seqs[u])))

    sizes = reference.sizes_of(cfg)
    assert worst(sizes, (0,)) < 2e-5
    for fault in (dict(window=WINDOW - PAGE), dict(window=WINDOW + PAGE),
                  dict(router_reads="ffn")):
        assert worst(dict(sizes, **fault), (0,)) > 0.03, fault


def test_a_cut_with_a_tail_matches_the_plain_reference():
    """Five layers: one period and a tail of one global layer."""
    cfg, params = family(num_hidden_layers=5)
    assert layer_runs(cfg) == (0, [("full", 1), ("window", 3)], 1, 1)
    engine = engine_of(cfg, params)
    seq = sequences_of((142,), seed=4)[0]
    want = np.asarray(jax.jit(lambda ids: reference.forward(
        params, ids, reference.sizes_of(cfg))[0])(seq))
    got = np.asarray(engine.put([0], [seq[:136]]))
    assert rel_rms(got[0], want[135]) < 2e-5
    for t in range(136, 142):
        got = np.asarray(engine.put([0], [seq[t:t + 1]]))
        assert rel_rms(got[0], want[t]) < 2e-5


class RoutedLate(RaggedInferenceModel):
    """The family's ropes without its class's invariants: the control
    whose router reads the post-attention norm's output."""
    rope_table = SmallThinkerInferenceModel.rope_table


def pairs_here(cfg, params, prompt, impl=SmallThinkerInferenceModel):
    """Token-expert pairs that fell to the experts held here in one
    prompt's prefill, as the step program counted them."""
    sched = FastGenScheduler(engine_of(cfg, params, impl=impl))
    sched.submit(0, prompt.tolist(), SamplingParams(max_new_tokens=1))
    sched.run_to_completion()
    return int(sched.last_moe_counts[0])


def test_the_routing_is_made_before_the_mixer():
    """A share that holds 3 of 8 experts counts the pairs that fall to
    them, which tells one routing from another.  Two layers; the LAST
    layer's attention output projection is replaced: a router that reads
    the attention block's input routes the same (and as the reference), a
    router that reads the post-attention norm's output does not."""
    over = dict(num_hidden_layers=2, moe_num_primary_experts=3,
                moe_num_primary_experts_scored=8)
    cfg, params = family(first=2, **over)
    assert (cfg.held_experts, cfg.n_routed_experts) == (3, 8)
    prompt = sequences_of((40,), seed=9)[0]
    perturbed = jax.tree.map(lambda a: a, params)
    wo = perturbed["periods"]["l1"]["attn"]["wo"]
    perturbed["periods"]["l1"]["attn"]["wo"] = jnp.flip(wo, axis=1) * 3.0
    sizes = reference.sizes_of(cfg)
    want = int(reference.forward(params, prompt, sizes)[1].sum())
    assert 0 < want < 40 * 3 * 2
    assert pairs_here(cfg, params, prompt) == want
    assert pairs_here(cfg, perturbed, prompt) == want
    assert int(reference.forward(perturbed, prompt, sizes)[1].sum()) == want
    # the control: the same weights routed behind attention
    late = dataclasses.replace(cfg, router_reads="ffn")
    late_sizes = dict(sizes, router_reads="ffn")
    a = pairs_here(late, params, prompt, impl=RoutedLate)
    b = pairs_here(late, perturbed, prompt, impl=RoutedLate)
    assert a == int(reference.forward(params, prompt, late_sizes)[1].sum())
    assert b == int(reference.forward(perturbed, prompt,
                                      late_sizes)[1].sum())
    assert a != b


def test_route_brings_the_row_layout_where_it_is_asked_to():
    """``_route`` is the reference's routing; with ``layout`` it brings the
    held experts' row layout, which ``_layer_body`` asks for where the
    configuration's router reads the mixer's input."""
    cfg, params = family(num_hidden_layers=2)
    model = SmallThinkerInferenceModel(cfg, params)
    lp = jax.tree.map(lambda a: a[0], params["periods"]["l0"])
    h = jnp.asarray(np.random.default_rng(2).normal(size=(2, 4, 64)),
                    jnp.float32)
    ctx = type("Ctx", (), {"cfg": cfg, "valid": jnp.ones(8, bool)})
    chosen, weights, rows = model._route(lp, h, ctx, layout=True)
    want_e, want_w = reference.route(h.reshape(8, 64), lp["moe"]["router"],
                                     reference.sizes_of(cfg))
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want_e))
    np.testing.assert_allclose(np.asarray(weights), np.asarray(want_w),
                               rtol=1e-6)
    for a, b in zip(rows, held.plan_rows(chosen, None, 0, 8)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    again = model._route(lp, h.reshape(8, 64), ctx)
    assert again[2] is None
    np.testing.assert_array_equal(np.asarray(again[0]), np.asarray(chosen))


@pytest.mark.parametrize("act", ["relu", "silu"])
def test_held_experts_under_a_gate_against_the_dense_reference(act):
    """The Pallas kernel (interpret mode) and the ``jnp`` path of the
    grouped matmul, with the row layout made ahead or inside, against
    every expert over every token; the two gates differ."""
    rng = np.random.default_rng(7)
    T, e, F, E, k = 48, 128, 64, 8, 3
    x = jnp.asarray(rng.normal(size=(T, e)), jnp.float32)
    params = {n: jnp.asarray(rng.normal(size=(E, F, e)) * e ** -0.5,
                             jnp.float32) for n in ("wg", "wu", "wd")}
    router = jnp.asarray(rng.normal(size=(e, E)), jnp.float32)
    chosen, weights = held.route_softmax_topk(x, router, k, 1.0)
    want = held.dense_held_reference(x, chosen, weights, params, 0, act)
    other = held.dense_held_reference(
        x, chosen, weights, params, 0, "silu" if act == "relu" else "relu")
    assert rel_rms(other, want) > 0.1
    plan = held.plan_rows(chosen, None, 0, E)
    # told how many experts were scored, the layout sizes its tile by the
    # pairs an expert sees (18 here: over half a tile of 32), and the
    # kernel takes the tile from the plan
    wide = held.plan_rows(chosen, None, 0, E, E)
    assert [p[0].shape[0] // p[2].shape[0] for p in (plan, wide)] == [32, 64]
    assert held.row_tile(256, 256 * 6 / 64) == 64      # the cell's decode step
    assert held.row_tile(256, 256 * 8 / 256) == 32     # 8 of 256 experts
    for kw in (dict(interpret=True), dict(use_kernel=False),
               dict(interpret=True, plan=plan), dict(interpret=True, plan=wide),
               dict(use_kernel=False, plan=wide)):
        got, counts = held.held_experts_ffn(x, chosen, weights, params, 0,
                                            act=act, **kw)
        assert rel_rms(got, want) < 1e-5
        assert int(counts.sum()) == T * k


def test_greedy_through_the_scheduler_matches_the_reference():
    """The fused step programs (sample, chain, mixed): greedy tokens of
    four requests of unequal lengths equal the reference's arg-max of
    every position, and all pages of both groups come back."""
    cfg, params = family(num_hidden_layers=4)
    engine = engine_of(cfg, params, seqs=8, budget=64)   # prompts in turns
    sched = FastGenScheduler(engine)
    prompts = sequences_of((9, 17, 30, 12), seed=1)
    news = (16, 10, 20, 12)
    for uid, (p, n) in enumerate(zip(prompts, news)):
        sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=n))
    out = sched.run_to_completion()
    forward = jax.jit(lambda ids: reference.forward(
        params, ids, reference.sizes_of(cfg))[0])
    for uid, (p, n) in enumerate(zip(prompts, news)):
        ids = np.zeros(64, np.int32)
        ids[:len(p) + n] = np.concatenate([p, out[uid][:n]])
        want = np.asarray(forward(ids))[len(p) - 1:len(p) + n - 1].argmax(-1)
        np.testing.assert_array_equal(np.asarray(out[uid][:n]), want)
    kinds = {k.kind for k in engine.model._dispatched_keys}
    assert {"sample", "chain", "mixed"} <= kinds
    # every expert is held: a token's every pair is here
    assert int(sched.last_moe_counts[0]) > 0
    engine.state_manager.check_invariants()
    assert engine.free_blocks == 96 and engine.free_window_blocks == 64


def test_config_reads_the_first_layers_of_the_published_lists():
    cfg, params = family()
    assert cfg.layer_kinds == ("full", "window", "window", "window") * 2
    assert dict(cfg.heads_by_kind) == {"full": 14, "window": 14}
    assert (cfg.first_k_dense, cfg.n_routed_experts, cfg.held_experts,
            cfg.moe_top_k, cfg.router_scoring, cfg.router_reads,
            cfg.expert_act, cfg.nope_kinds) == (
        0, 8, 8, 3, "softmax", "mixer", "relu", ("full",))
    period = params["periods"]
    assert period["l0"]["attn"]["wq"].shape == (2, 64, 14 * 16)
    assert period["l3"]["attn"]["wk"].shape == (2, 64, 2 * 16)
    assert period["l3"]["attn"]["wo"].shape == (2, 14 * 16, 64)
    assert period["l1"]["moe"]["router"].shape == (2, 64, 8)
    assert params["experts"]["wg"].shape == (8, 8, 32, 64)
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    gains = 64 * (2 * 8 + 1)
    assert cfg.n_params() == leaves - gains
    # the published widths: a layer is 398,627,840 parameters, the
    # embedding and the head 388,956,160 each (ISSUE 47's arithmetic)
    published = smallthinker.smallthinker_config(dict(
        SOURCE, vocab_size=151936, hidden_size=2560, head_dim=128,
        num_attention_heads=28, num_key_value_heads=4,
        moe_ffn_hidden_size=768, moe_num_primary_experts=64,
        moe_num_active_primary_experts=6, sliding_window_size=4096))
    assert published.n_params() == 8 * (398_627_840 - 2 * 2560) \
        + 2 * 388_956_160
    for other in ([1, 1, 1, 1] * 13, [0, 1, 1, 0] * 13):
        with pytest.raises(ValueError, match="agree layer by layer"):
            smallthinker.smallthinker_config(dict(SOURCE,
                                                  rope_layout=other))


def test_implementation_for_smallthinker_and_what_it_refuses():
    assert implementation_for("smallthinker") is SmallThinkerInferenceModel
    assert supported_model_types()["smallthinker"] \
        == "SmallThinkerInferenceModel"
    cfg, params = family(num_hidden_layers=4)
    model = SmallThinkerInferenceModel(cfg, params)
    assert model.kv_config.num_layers == 1
    assert model.window_kv_config.num_layers == 3
    assert model.step_tail == 3
    pos = jnp.arange(6)[None]
    assert model.rope_table(cfg, "full", pos) is None
    sin, cos = model.rope_table(cfg, "window", pos)
    assert sin.shape == (1, 6, 8)
    # a 4,096-token window over pages of 64: 65 live pages and the one
    # being filled, in whole groups of 8 slots
    assert window_slots(4096, 64, 1) == window_slots(4096, 64, 128) == 72
    assert model.table.window_slots(1) == window_slots(WINDOW, 64, 1) == 8
    engine = engine_of(cfg, params)
    assert engine.state_manager.prefix_cache is None
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
    with pytest.raises(NotImplementedError, match="smallthinker_reference"):
        SmallThinkerForCausalLM(SOURCE).logits(params, {})
