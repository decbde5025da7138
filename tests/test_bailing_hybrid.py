"""Ling-3.0 (``models/bailing_hybrid.py``) through the serving path at a
small size: the delta rule's kernels under a decay a KEY CHANNEL against the
token-by-token recurrence (near the gate's lower bound over a chunk of 16),
the grouped biased router, the model class against its plain reference
through latent pages AND state slots in one table row, a ``kda`` slot
through the codecs of ``StateManager``, the 16 shares of a routed layer,
what the configuration refuses, and the step's spans."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from deepspeed_tpu.inference.v2 import (FastGenScheduler, InferenceEngineV2,
                                        RaggedInferenceEngineConfig,
                                        SamplingParams,
                                        ServingOptimizationConfig,
                                        StateManagerConfig)
from deepspeed_tpu.inference.v2.config import KVCacheUserConfig
from deepspeed_tpu.inference.v2.model import MIXERS
from deepspeed_tpu.inference.v2.model_implementations import (
    BailingHybridInferenceModel, implementation_for)
from deepspeed_tpu.inference.v2.ragged.cache_kinds import (CACHE_KINDS,
                                                           TableLayout,
                                                           slot_kind)
from deepspeed_tpu.models import bailing_hybrid_reference as reference
from deepspeed_tpu.models.bailing_hybrid import (BailingHybridForCausalLM,
                                                 bailing_hybrid_config)
from deepspeed_tpu.models.transformer import kind_runs, layer_runs
from deepspeed_tpu.moe import held
from deepspeed_tpu.ops.delta_rule import (MAX_CHANNEL_CHUNK, chunk_len,
                                          delta_chunk_reference, delta_rule,
                                          delta_rule_reference)
from deepspeed_tpu.ops.ssm import conv_slot_shape

PAGE = 8
SOURCE = dict(
    model_type="bailing_hybrid", vocab_size=160, hidden_size=64,
    intermediate_size=96, num_hidden_layers=7, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, hidden_act="silu",
    layer_group_size=6, first_k_dense_replace=1, q_lora_rank=None,
    kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=6e6, rms_norm_eps=1e-6, num_experts=16, num_experts_per_tok=3,
    num_shared_experts=1, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, norm_topk_prob=True,
    short_conv_kernel_size=4, kda_lower_bound=-5,
    expert_swiglu_limit_list=[0] * 8 + [4],
    share_expert_swiglu_limit_list=[0] * 8 + [5])

#: served float32 against the float32 reference: orders of sums differ (the
#: pool's state ``[dk, H dv]`` against the reference's ``[H, dk, dv]``, the
#: absorbed against the expanded latent attention, the grouped matmul
#: against one expert at a time), a few float32 ulps a layer.  The worst
#: row reads ~5e-6; the mildest control (no rope on the one latent layer)
#: reads 0.02 and more (``test_a_control_is_told``)
TOLERANCE = 2e-4


def family(seed=3, first_layer=1, **over):
    model = BailingHybridForCausalLM(dict(SOURCE, **over),
                                     first_layer=first_layer,
                                     dtype=jnp.float32)
    return model.cfg, meta.unbox(model.init_params(jax.random.key(seed)))


def engine_of(cfg, params, pages=64, seqs=8, serving=None, budget=256):
    return InferenceEngineV2(
        BailingHybridInferenceModel(cfg, params),
        RaggedInferenceEngineConfig(
            state_manager=StateManagerConfig(
                max_tracked_sequences=seqs, max_ragged_sequence_count=seqs,
                max_ragged_batch_size=budget),
            kv_cache=KVCacheUserConfig(page_size=PAGE, num_pages=pages,
                                       dtype=jnp.float32),
            serving=serving or ServingOptimizationConfig()))


def sequences_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SOURCE["vocab_size"], n).astype(np.int32)
            for n in lengths]


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def reference_logits(cfg, params, seqs, **kw):
    controls = {k: kw.pop(k) for k in list(kw)
                if k not in ("weight_precision", "state_precision")}
    return [np.asarray(reference.forward(
        params, s, reference.sizes_of(cfg, **controls), **kw)[0])
        for s in seqs]


def served_rows(cfg, params, seqs, prompts, chunk=None, preempt=None):
    """The served logits rows as (sequence, position, row): the last
    prompt position (the prompt in pieces of ``chunk`` tokens where given:
    a continued prefill from a carried state), then every teacher-forced
    decode step through the slots and the pages; ``preempt``: a decode step
    after which sequence 0 is offloaded, its slot taken by another, and
    restored."""
    engine = engine_of(cfg, params)
    uids = list(range(len(seqs)))
    at = [0] * len(seqs)
    rows, step = [], 0
    while any(a < p for a, p in zip(at, prompts)):
        part = [u for u in uids if at[u] < prompts[u]]
        n = [min(chunk or prompts[u], prompts[u] - at[u]) for u in part]
        got = np.asarray(engine.put(
            part, [seqs[u][at[u]:at[u] + k] for u, k in zip(part, n)]))
        for i, (u, k) in enumerate(zip(part, n)):
            at[u] += k
            if at[u] == prompts[u]:
                rows.append((u, at[u] - 1, got[i]))
    while uids:
        got = np.asarray(engine.put(uids, [seqs[u][at[u]:at[u] + 1]
                                           for u in uids]))
        rows += [(u, at[u], got[n]) for n, u in enumerate(uids)]
        engine.state_manager.check_invariants()
        for u in uids:
            at[u] += 1
        for u in [u for u in uids if at[u] == len(seqs[u])]:
            engine.flush(u)
            uids.remove(u)
        step += 1
        if step == preempt and 0 in uids:
            engine.offload_sequence(0)
            engine.put([77], [seqs[0][:5]])     # takes the slot given back
            engine.restore_sequence(0)
            engine.state_manager.check_invariants()
    return rows


def worst_error(rows, want):
    return max(rel_rms(got, want[u][pos]) for u, pos, got in rows)


# -- the recurrence under a decay a key channel ------------------------------

def rule_args(S, Q, H=4, dk=16, dv=16, L=2, slots=5, seed=0, lower=-5.0,
              near_bound=True):
    ks = jax.random.split(jax.random.key(seed), 8)
    K, ch = 4, H * (2 * dk + dv)

    def l2(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    state = jax.random.normal(ks[0], (L, slots + 1, dk, H * dv), jnp.float32)
    conv = jnp.zeros((L, slots + 1) + conv_slot_shape((K - 1) * ch),
                     jnp.float32)
    q = l2(jax.random.normal(ks[1], (S, Q, H, dk))) * dk ** -0.5
    k = l2(jax.random.normal(ks[2], (S, Q, H, dk)))
    v = jax.random.normal(ks[3], (S, Q, H * dv))
    # near the bound: most channels decay by e^-5 a token, 16 in a row
    g = lower * jax.nn.sigmoid(
        jax.random.normal(ks[4], (S, Q, H, dk)) * 3 + (4 if near_bound else -2))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (S, Q, H)))
    # a decode row's tail is the convolution's to write (``conv_step``)
    tail = jax.random.normal(ks[6], (S, K - 1, ch)) if Q > 1 else None
    return (state, conv, 1, jnp.arange(S, dtype=jnp.int32) % slots,
            jnp.arange(S) % 2 == 0, q, k, v, g, beta, tail)


def close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


@pytest.mark.parametrize("S,Q,near", [(4, 1, True), (3, 16, True),
                                      (2, 64, True), (2, 48, False)],
                         ids=["update", "one-chunk", "four-chunks",
                              "three-chunks-mild"])
def test_the_kda_kernels_against_the_plain_scan(S, Q, near):
    """Both Pallas kernels (interpret mode) and the chunked ``jnp`` form
    against the token-by-token scan, at a decay near the gate's lower bound
    over whole chunks of 16: ``exp(-G)`` reaches e^80 there and the result
    holds to float32."""
    args = rule_args(S, Q, near_bound=near)
    assert float(args[8].min()) > -5.0 and (
        not near or float(jnp.median(args[8])) < -4.5)
    want = delta_rule_reference(*args)
    got = delta_rule(*args, interpret=True)
    for a, b in zip(got, want):
        close(a, b)
    if Q > 1:
        assert chunk_len(Q, MAX_CHANNEL_CHUNK) == 16
        for a, b in zip(delta_chunk_reference(*args), want):
            close(a, b)


def test_one_decay_a_head_is_the_broadcast_case():
    """A decay a key channel whose channels agree is the head-scalar rule:
    the same reference, the same kernels' arithmetic."""
    args = rule_args(3, 16, near_bound=False)
    g_head = args[8][..., 0]
    same = args[:8] + (jnp.broadcast_to(g_head[..., None], args[8].shape),) \
        + args[9:]
    head = args[:8] + (g_head,) + args[9:]
    for a, b in zip(delta_rule_reference(*same), delta_rule_reference(*head)):
        close(a, b, 1e-6)
    for a, b in zip(delta_rule(*same, interpret=True),
                    delta_rule(*head, interpret=True)):
        close(a, b)


# -- the router ----------------------------------------------------------------

def test_the_grouped_router_against_the_plain_one():
    """``route_sigmoid_grouped`` (arg-max passes) against the reference's
    ``top_k`` form; the chosen experts lie in the kept groups, and the bias
    moves the choice and not the weights."""
    x = jax.random.normal(jax.random.key(0), (64, 32))
    w = jax.random.normal(jax.random.key(1), (32, 64)) * 0.3
    b = jax.random.normal(jax.random.key(2), (64,)) * 0.1
    sizes = dict(top_k=4, n_group=8, topk_group=3, routed_scaling_factor=2.5,
                 norm_topk_prob=True)
    got_e, got_w = held.ROUTERS["sigmoid_grouped"](
        x, w, 4, 2.5, True, bias=b, groups=8, keep=3)
    want_e, want_w = reference.route(x, {"router": w, "router_bias": b},
                                     sizes)
    assert np.array_equal(np.sort(got_e, -1), np.sort(want_e, -1))
    assert np.allclose(np.sort(got_w, -1), np.sort(want_w, -1), atol=1e-6)
    assert np.allclose(np.sum(got_w, -1), 2.5, atol=1e-5)
    assert all(len({int(e) // 8 for e in row}) <= 3 for row in got_e)
    plain_e, _ = held.ROUTERS["sigmoid"](x, w, 4, 2.5, True)
    unbiased_e, _ = held.ROUTERS["sigmoid_grouped"](
        x, w, 4, 2.5, True, bias=jnp.zeros(64), groups=8, keep=3)
    assert not np.array_equal(np.sort(plain_e, -1), np.sort(unbiased_e, -1))
    assert not np.array_equal(np.sort(got_e, -1), np.sort(unbiased_e, -1))


def test_the_routing_sink_hears_every_routed_layer_in_order():
    """A program traced while ``routing_sink`` is set hands it each routed
    layer's experts, in layer order, the tokens row by row (what a
    comparison that must follow the served routing reads); the logits are
    what they are without it, and a model that never had one set forms
    programs with no callback."""
    cfg, params = family()
    seqs = sequences_of([9, 6])
    heard = []
    engine = engine_of(cfg, params)
    engine.model.routing_sink = lambda chosen: heard.append(
        np.asarray(chosen))
    got = np.asarray(engine.put([0, 1], seqs))
    jax.effects_barrier()
    engine.model.routing_sink = None
    routed = cfg.num_layers - cfg.first_k_dense
    assert len(heard) == routed
    S, Q = engine.model.lattice.shape(2, 9, 1)[:2]
    assert {h.shape for h in heard} == {(S * Q, cfg.moe_top_k)}
    plain = engine_of(cfg, params)
    np.testing.assert_array_equal(got, np.asarray(plain.put([0, 1], seqs)))
    assert all(0 <= h.min() and h.max() < cfg.n_routed_experts
               and len(set(row)) == cfg.moe_top_k for h in heard for row in h)
    # (that a row's record IS the plain router's choice for that token,
    # layer by layer, is held where the record is used: the benchmark's
    # probe reads ``routing_off_share`` 0 in float32)
    text = next(iter(plain.model.compiled_programs().values())).as_text()
    assert "callback" not in text.lower()
    told = next(iter(engine.model.compiled_programs().values())).as_text()
    assert "callback" in told.lower()


# -- the configuration ---------------------------------------------------------

def test_layer_pattern_and_sizes_from_the_sources_keys():
    cfg, _ = family()
    assert cfg.layer_kinds == ("kda",) * 4 + ("latent", "kda", "kda")
    zeros = dict(expert_swiglu_limit_list=[0] * 20,
                 share_expert_swiglu_limit_list=[0] * 20)
    # the shortest repeat of ONE published period behind the dense layer is
    # (kda x 3, latent) + a tail of two; two periods repeat with all six
    assert layer_runs(cfg) == (1, [("kda", 3), ("latent", 1)], 1, 2)
    # the parameters follow the RUNS of like kinds, each run one stack
    assert kind_runs(cfg.layer_kinds[1:]) == [("kda", 3), ("latent", 1),
                                              ("kda", 2)]
    two = bailing_hybrid_config(dict(SOURCE, num_hidden_layers=13, **zeros),
                                first_layer=1)
    assert layer_runs(two) == (1, [("kda", 3), ("latent", 1), ("kda", 2)],
                               2, 0)
    # the published pattern from layer 0: the latent layer closes a period
    whole = bailing_hybrid_config(dict(SOURCE, num_hidden_layers=12,
                                       **zeros), first_layer=0)
    assert [i for i, k in enumerate(whole.layer_kinds) if k == "latent"] \
        == [5, 11]
    assert (cfg.router_scoring, cfg.router_groups, cfg.router_topk_groups) \
        == ("sigmoid_grouped", 4, 2)
    assert (cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim,
            cfg.delta_conv, cfg.kda_lower_bound) == (4, 16, 16, 4, -5.0)
    assert cfg.q_lora_rank == 0 and cfg.latent_dim == 32
    # the matmul parameters n_params counts are the tree's
    model = BailingHybridForCausalLM(SOURCE, first_layer=1)
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    matrices = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(
        meta.unbox(shapes)) if len(a.shape) >= 2)
    # (a run's stack makes its layers' gains and biases 2-D too)
    assert abs(cfg.n_params() - matrices) < 0.002 * matrices


@pytest.mark.parametrize("key", ["expert_swiglu_limit_list",
                                 "share_expert_swiglu_limit_list"])
def test_a_non_zero_swiglu_limit_raises(key):
    """The lists are held whole and read for the held layers alone: layer 8
    carries a limit, so seven layers from 1 build and seven from 2 raise."""
    family()
    other = ({"expert_swiglu_limit_list", "share_expert_swiglu_limit_list"}
             - {key}).pop()
    with pytest.raises(ValueError, match="models/bailing_hybrid.py: " + key):
        bailing_hybrid_config(dict(SOURCE, **{other: [0] * 9}),
                              first_layer=2)
    with pytest.raises(ValueError, match="q_lora_rank"):
        bailing_hybrid_config(dict(SOURCE, q_lora_rank=32))
    with pytest.raises(ValueError, match="kda_safe_gate"):
        bailing_hybrid_config(dict(SOURCE, kda_safe_gate=False))


# -- served logits against the plain reference --------------------------------

@pytest.mark.parametrize("lengths,prompts,chunk,preempt", [
    ((30, 21, 13), (24, 15, 7), None, None),
    ((38, 24), (33, 20), 16, None),
    ((28, 18), (19, 11), None, 3)],
    ids=["prefill-then-decode", "chunked-and-continued-prefill",
         "a-preempted-and-resumed-row"])
def test_served_logits_match_the_plain_reference(lengths, prompts, chunk,
                                                 preempt):
    """Prefill, then decode through the latent pages AND the slots, against
    the reference's full forward; a prompt in pieces of 16 (a continued
    prefill from the slot's state, the latent layer from its pages); a row
    offloaded, its slot reused, and restored."""
    cfg, params = family()
    seqs = sequences_of(lengths, seed=1)
    want = reference_logits(cfg, params, seqs)
    rows = served_rows(cfg, params, seqs, prompts, chunk, preempt)
    assert len(rows) == sum(n - p + 1 for n, p in zip(lengths, prompts))
    assert worst_error(rows, want) < TOLERANCE


@pytest.mark.parametrize("layers,first,dense", [(13, 1, 1), (8, 3, 0)],
                         ids=["two-periods",
                              "no-leading-layer-and-a-shifted-period"])
def test_served_logits_at_the_corners_of_the_layer_pattern(layers, first,
                                                           dense):
    cfg, params = family(num_hidden_layers=layers, first_layer=first,
                         first_k_dense_replace=dense,
                         expert_swiglu_limit_list=[0] * 20,
                         share_expert_swiglu_limit_list=[0] * 20)
    assert "latent" in cfg.layer_kinds
    seqs = sequences_of((20, 13), seed=2)
    want = reference_logits(cfg, params, seqs)
    rows = served_rows(cfg, params, seqs, (15, 9))
    assert worst_error(rows, want) < TOLERANCE


@pytest.fixture(scope="module")
def control_rows():
    cfg, params = family()
    seqs = sequences_of((40, 29), seed=6)
    return cfg, params, seqs, served_rows(cfg, params, seqs, (30, 18))


@pytest.mark.parametrize("name,control", [
    ("float8_weights", {"weight_precision": jnp.float8_e4m3fn}),
    ("bf16_state", {"state_precision": jnp.bfloat16}),
    ("one_decay_a_head", {"decay": "head"}),
    ("router_without_groups", {"groups": False}),
    ("router_without_bias", {"bias": False}),
    ("weights_from_the_biased_scores", {"weights_from": "c"}),
    ("no_rope_on_the_latent_layer", {"latent_rope": False})])
def test_a_control_is_told(control_rows, name, control):
    """Each control the probe must tell, planted in the reference and read
    against what the program served: a hundred times the tolerance."""
    cfg, params, seqs, rows = control_rows
    faulty = reference_logits(cfg, params, seqs, **control)
    assert worst_error(rows, faulty) > 100 * TOLERANCE, name


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """A routed layer's partial sums over the 4 shares of 4 experts each,
    the shared expert counted once, are the layer with all 16 experts held;
    the program's held share is the reference's."""
    cfg, params = family()
    sizes = reference.sizes_of(cfg)
    x = jax.random.normal(jax.random.key(5), (24, 64))
    lp = jax.tree.map(lambda a: a[1], params["runs"]["r0"])["moe"]
    full = jax.tree.map(lambda a: a[1], params["experts"])
    whole, _ = reference.routed_ffn(x, lp, full, sizes)
    shared = reference.swiglu(x, lp["shared"])
    total = shared
    for first in range(0, 16, 4):
        part = jax.tree.map(lambda a: a[first:first + 4], full)
        y, pairs = reference.routed_ffn(
            x, lp, part, dict(sizes, experts_first=first))
        total = total + (y - shared)
        # the program's share: the same router, the grouped matmul
        chosen, weights = held.ROUTERS["sigmoid_grouped"](
            x, lp["router"], 3, 2.5, True, bias=lp["router_bias"],
            groups=4, keep=2)
        got, counts = held.held_experts_ffn(x, chosen, weights, part, first)
        assert np.allclose(got, y - shared, atol=2e-5)
        assert int(jnp.sum(counts)) == int(jnp.sum(pairs))
    assert np.allclose(total, whole, atol=5e-5)
    with jax.default_matmul_precision("highest"):
        assert float(jnp.max(jnp.abs(whole))) > 0.1


# -- through the scheduler -----------------------------------------------------

def test_greedy_through_the_scheduler_matches_the_reference():
    cfg, params = family(num_experts=4, num_experts_scored=16)
    assert (cfg.held_experts, cfg.n_routed_experts) == (4, 16)
    prompts = sequences_of((21, 30, 9), seed=5)
    sched = FastGenScheduler(engine_of(cfg, params))
    for uid, p in enumerate(prompts):
        sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=6))
    out = sched.run_to_completion()
    assert {k.kind for k in sched._engine.compiled_keys()} >= {"chain"}
    for uid, p in enumerate(prompts):
        seq = np.concatenate([p, np.asarray(out[uid][:-1], np.int32)])
        want = reference_logits(cfg, params, [seq])[0]
        assert out[uid] == [int(t) for t in
                            np.argmax(want[len(p) - 1:], axis=-1)]
    sched._engine.state_manager.check_invariants()
    assert sched._engine.free_state_slots == 8
    assert sched._engine.free_blocks == 64


# -- what the kind caches, and what the engine refuses ------------------------

def test_what_the_kda_kind_caches_is_declared_in_one_place():
    assert CACHE_KINDS["kda"].slot and not CACHE_KINDS["kda"].group
    assert CACHE_KINDS["latent"].group == "full"
    assert slot_kind(("kda", "latent")) == "kda"
    with pytest.raises(AssertionError, match="one slot kind"):
        slot_kind(("kda", "delta", "latent"))
    assert set(MIXERS) == set(CACHE_KINDS)
    assert MIXERS["kda"].pools == ("state", "conv") == MIXERS["delta"].pools
    assert MIXERS["latent"].pools == ("pages",) == MIXERS["full"].pools
    cfg, params = family()
    model = BailingHybridInferenceModel(cfg, params)
    assert model.table == TableLayout(window=0, page_size=64, state=True)
    assert model.pool_names == ("pages", "state", "conv")
    sc, kv = model.state_config, model.kv_config
    assert (sc.kind, sc.num_layers) == ("kda", 6)
    assert sc.state == (16, 4 * 16) and sc.tail == (3, 4 * 3 * 16)
    # ONE latent layer's planes in the page pool, beside the slots
    assert (kv.num_layers, kv.planes, kv.kv_heads, kv.head_dim) \
        == (1, 1, 1, 128)
    assert implementation_for("bailing_hybrid") is BailingHybridInferenceModel
    engine = engine_of(cfg, params)
    state = engine.state_manager
    assert state.prefix_cache is None and state.state_pool.cfg.num_slots == 8
    assert [a.shape for a in state.state_pool.data] \
        == [(6, 9, 16, 64), (6, 9, 8, 3 * 192 // 8)]
    assert state.kv_cache.data.shape == (1, 65, 1, 1, PAGE, 128)
    for serving, names in [(dict(tp_degree=2), "tp_degree"),
                           (dict(speculative=True), "spec.py"),
                           (dict(kv_quantization="int8"), "int8"),
                           (dict(kv_tier_host_pages=4), "kv_tiers")]:
        with pytest.raises(ValueError, match=names):
            engine_of(cfg, params,
                      serving=ServingOptimizationConfig(**serving))
    with pytest.raises(AssertionError):
        BailingHybridInferenceModel(dataclasses.replace(
            cfg, layer_kinds=("kda",) * 7), params)


@pytest.mark.parametrize("codec", ["flush", "snapshot"])
def test_a_row_of_latent_pages_and_a_slot_rides_the_codecs(codec):
    """Admit, decode, then flush / snapshot into a second engine: the
    account of pages AND slots holds, the slot's matrix state arrives bit
    for bit, and decoding goes on to the reference's logits (the preempt
    codec: ``served_rows(preempt=...)``)."""
    cfg, params = family()
    engine = engine_of(cfg, params)
    state = engine.state_manager
    seqs = sequences_of((40, 33), seed=4)
    uids = [0, 1]
    engine.put(uids, [s[:20] for s in seqs])
    for at in range(20, 27):
        engine.put(uids, [s[at:at + 1] for s in seqs])
    state.check_invariants()
    assert engine.free_state_slots == 6 and engine.free_blocks == 64 - 8
    if codec == "flush":
        for u in uids:
            engine.flush(u)
            state.check_invariants()
        assert (engine.free_blocks, engine.free_state_slots) == (64, 8)
        return
    before = state.state_pool.read_slot(state.get_sequence(0).state_slot)
    assert before.h.shape == (6, 16, 64) and np.abs(before.h).max() > 0
    other = engine_of(cfg, params)
    other.put([9], [seqs[0][:5]])       # so that slot 0 is taken over there
    other.flush(9)
    meta_, arrays = state.export_state()
    assert meta_["kv"]["state"][:2] == ["kda", 6]
    assert meta_["kv"]["planes"] == 1
    other.state_manager.import_state(meta_, arrays)
    state.check_invariants()
    other.state_manager.check_invariants()
    after = other.state_manager.state_pool.read_slot(
        other.state_manager.get_sequence(0).state_slot)
    assert np.array_equal(before.h, after.h) \
        and np.array_equal(before.conv, after.conv)
    want = reference_logits(cfg, params, seqs)
    for at in range(27, 31):
        got = np.asarray(other.put(uids, [seqs[u][at:at + 1] for u in uids]))
        for n, u in enumerate(uids):
            assert rel_rms(got[n], want[u][at]) < TOLERANCE


def test_step_spans_carry_the_kda_kinds_counts():
    """Under telemetry ``fastgen.step`` carries the slots held and their
    bytes (under the pool's names), the rows the update kernel stepped and
    the true tokens the chunked form consumed under the KIND's name, and the
    held experts' counts; ``engine.program`` keys are the step's."""
    import deepspeed_tpu.telemetry as telemetry
    from deepspeed_tpu.telemetry import get_tracer
    cfg, params = family()
    sched = FastGenScheduler(engine_of(cfg, params))
    prompts = sequences_of((21, 30), seed=2)
    telemetry.set_enabled(True)
    try:
        mark = len(get_tracer().records())
        for uid, p in enumerate(prompts):
            sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=8))
        sched.run_to_completion()
        recs = get_tracer().records()[mark:]
    finally:
        telemetry.set_enabled(False)
    steps = [r[5] for r in recs if r[0] == "fastgen.step" and r[5]]
    assert steps and all(
        {"ssm_slots_held", "kda_rows_decode", "kda_tokens_prefill",
         "ssm_state_bytes"} <= set(s) for s in steps)
    assert not any("delta_rows_decode" in s or "ssm_rows_decode" in s
                   or "kv_slots_held" in s for s in steps)
    assert any("moe_pairs_here" in s for s in steps)
    slot = sched._engine.state_manager.state_pool.cfg.bytes_per_slot
    assert slot == 6 * (16 * 64 * 4 + 3 * 192 * 4)
    assert sum(s["kda_tokens_prefill"] for s in steps) == 51
    assert max(s["ssm_slots_held"] for s in steps) == 2
    assert all(s["ssm_state_bytes"] == s["ssm_slots_held"] * slot
               for s in steps)
    assert sum(s["kda_rows_decode"] for s in steps) == 2 * 7
    # every expert is held: 3 pairs a token and routed layer
    assert sum(s["moe_pairs_here"] for s in steps if "moe_pairs_here" in s) \
        == (51 + 14) * 3 * 6
