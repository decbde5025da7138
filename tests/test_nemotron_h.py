"""Nemotron-H (``models/nemotron_h.py``) through the serving path at a small
size: Mamba-2's kernels against the token-by-token recurrence, the
two-matrix expert kernel, the model class against its plain reference
through pages AND state slots with layers that cache nothing between them,
a prompt continued from its slot, the eight shares of a routed layer, what
a kind that caches nothing reserves, and the step's spans."""

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
    NemotronHInferenceModel, implementation_for)
from deepspeed_tpu.inference.v2.ragged.cache_kinds import (CACHE_KINDS,
                                                           TableLayout,
                                                           slot_kind)
from deepspeed_tpu.models import nemotron_h_reference as reference
from deepspeed_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                             nemotron_h_config)
from deepspeed_tpu.models.transformer import layer_runs
from deepspeed_tpu.moe import held
from deepspeed_tpu.ops.ssm import (conv_slot_shape, ssd_chunk_len, ssd_scan,
                                   ssd_scan_reference)

PAGE = 8
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
SOURCE = dict(
    model_type="nemotron_h", vocab_size=160, hidden_size=64,
    intermediate_size=32, num_hidden_layers=14,
    hybrid_override_pattern=PATTERN, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, norm_eps=1e-5, mamba_num_heads=8,
    mamba_head_dim=16, ssm_state_size=16, n_groups=2, conv_kernel=4,
    chunk_size=128, expand=2, n_routed_experts=16, num_experts_per_tok=3,
    n_shared_experts=1, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=64, n_group=1, topk_group=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, mlp_hidden_act="relu2",
    mamba_hidden_act="silu", use_conv_bias=True, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4)

#: served float32 against the float32 reference: orders of sums differ (the
#: pool's state ``[N, H P]`` against the reference's ``[H, P, N]``, the
#: chunk's matrix form against the token-by-token scan, the grouped matmul
#: against one expert at a time), a few float32 ulps a layer.  The worst
#: row reads ~1e-5; the mildest control reads 0.02 and more
#: (``test_a_control_is_told``)
TOLERANCE = 2e-4


def family(seed=3, first_layer=6, **over):
    """Published layers 6-12, ONE block ``EMEMEM*``, unless ``over`` says
    otherwise (a program of two periods takes twice as long to form, and
    the suite is near its limit: the first served case runs both)."""
    over.setdefault("num_hidden_layers", 7)
    model = NemotronHForCausalLM(dict(SOURCE, **over),
                                 first_layer=first_layer, dtype=jnp.float32)
    return model.cfg, meta.unbox(model.init_params(jax.random.key(seed)))


def engine_of(cfg, params, pages=64, seqs=8, serving=None, budget=256):
    return InferenceEngineV2(
        NemotronHInferenceModel(cfg, params),
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
    """The served logits rows as (sequence, position, row): the last prompt
    position (the prompt in pieces of ``chunk`` tokens where given: a
    continued prefill from the slot's state), then every teacher-forced
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


# -- Mamba-2's recurrence ------------------------------------------------------

def scan_args(S, Q, H=4, P=64, G=2, N=16, L=2, slots=5, seed=0):
    ks = jax.random.split(jax.random.key(seed), 8)
    K, W = 4, H * P
    ch = W + 2 * G * N
    state = jax.random.normal(ks[0], (L, slots + 1, N, W), jnp.float32)
    conv = jnp.zeros((L, slots + 1) + conv_slot_shape((K - 1) * ch),
                     jnp.float32)
    # steps from a thousandth to a few: a head's decay over a chunk runs
    # from nothing to e^-100 and less
    dt = jax.nn.softplus(jax.random.normal(ks[1], (S, Q, H)) * 2 - 2)
    if Q > 4:
        dt = dt.at[:, -3:].set(0.0)                        # padded positions
    x = jax.random.normal(ks[2], (S, Q, W))
    B = jax.random.normal(ks[3], (S, Q, G * N))
    C = jax.random.normal(ks[4], (S, Q, G * N))
    A = -jnp.exp(jax.random.uniform(ks[5], (H,), minval=0.0, maxval=2.77))
    # a decode row's tail is the convolution's to write (``conv_step``)
    tail = jax.random.normal(ks[6], (S, K - 1, ch)) if Q > 1 else None
    return (state, conv, 1, jnp.arange(S, dtype=jnp.int32) % slots,
            jnp.arange(S) % 2 == 0, dt, x, B, C, A, jnp.ones((H,)), tail)


def close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


@pytest.mark.parametrize("S,Q,chunk", [(8, 1, 1), (3, 16, 16), (2, 256, 128),
                                       (2, 192, 64), (1, 72, 8)],
                         ids=["update", "one-short-chunk", "two-chunks",
                              "not-a-multiple-of-the-chunk", "chunks-of-8"])
def test_the_ssd_kernels_against_the_plain_scan(S, Q, chunk):
    """Both Pallas kernels (interpret mode) against the token-by-token scan:
    the update kernel; the chunked form at a row bucket that is a multiple
    of the published chunk and at ones that are not (a shorter chunk that
    divides them); rows of a REUSED slot that start ``fresh`` (every other
    row: the slot's state is noise) beside rows that continue from it;
    padded positions (``dt = 0``) move nothing."""
    args = scan_args(S, Q)
    assert ssd_chunk_len(Q) == chunk
    want = ssd_scan_reference(*args)
    got = ssd_scan(*args, interpret=True)
    for a, b in zip(got, want):
        close(a, b)
    # a continued row read its slot, a fresh one did not
    zeroed = ssd_scan_reference(args[0] * 0.0, *args[1:])
    fresh = np.asarray(args[4])
    assert np.allclose(zeroed[0][fresh], want[0][fresh])
    assert not np.allclose(zeroed[0][~fresh], want[0][~fresh]) or S == 1


def test_a_row_bucket_shorter_than_a_chunk_is_walked_by_the_scan():
    args = scan_args(2, 4)
    for a, b in zip(ssd_scan(*args, interpret=True),
                    ssd_scan_reference(*args)):
        assert np.array_equal(a, b)


# -- the two-matrix expert ------------------------------------------------------

@pytest.mark.parametrize("tokens", [256])
def test_the_two_matrix_expert_kernel_against_the_dense_reference(tokens):
    """``down(relu(up x)^2)``: the kernel (interpret mode) and the ``jnp``
    form against every held expert over every token; a work item copies two
    slices, and a stack that brings a gate is refused."""
    ks = jax.random.split(jax.random.key(tokens), 5)
    E, F, e, k, first = 4, 128, 128, 3, 4
    x = jax.random.normal(ks[0], (tokens, e))
    chosen = jnp.stack([jax.random.permutation(kk, 16)[:k] for kk in
                        jax.random.split(ks[1], tokens)]).astype(jnp.int32)
    weights = jax.random.uniform(ks[2], (tokens, k))
    stack = {"wu": jax.random.normal(ks[3], (E, F, e)) * e ** -0.5,
             "wd": jax.random.normal(ks[4], (E, F, e)) * F ** -0.5}
    want = held.dense_held_reference(x, chosen, weights, stack, first,
                                     act="relu2")
    assert float(jnp.max(jnp.abs(want))) > 0.1
    for kw in ({"use_kernel": False}, {"interpret": True}):
        got, counts = held.held_experts_ffn(x, chosen, weights, stack, first,
                                            act="relu2", **kw)
        assert np.allclose(got, want, atol=1e-4), kw
        assert int(counts.sum()) == int(np.sum(
            (np.asarray(chosen) >= first) & (np.asarray(chosen) < first + E)))
    assert held.ring_sets(64, 2688, 2, 2) == 3 == held.ring_sets(64, 2688, 2)
    assert held.ring_sets(64, 7680, 2, 2) == 3 > held.ring_sets(64, 7680, 2)
    with pytest.raises(AssertionError):
        held.grouped_expert_ffn(x, jnp.zeros(1, jnp.int32),
                                jnp.ones(1, jnp.int32), 0, stack["wu"][None],
                                stack["wu"][None], stack["wd"][None], tm=32,
                                act="relu2", interpret=True)


@pytest.mark.parametrize("tile", [32, 64])
def test_rows_that_route_alike_under_the_tile_the_family_states(tile):
    """Every row sends its pairs to the same three experts, two of them
    held (a seeded router under greedy rows): the tile a caller states
    (``cfg.moe_row_tile`` through ``held_experts_ffn(tile=)`` and
    ``plan_rows(tile=)``) decides how often a hot expert's weights are
    walked, and nothing of the result."""
    tokens, E, F, e, first = 96, 4, 128, 128, 4
    ks = jax.random.split(jax.random.key(tile), 4)
    x = jax.random.normal(ks[0], (tokens, e))
    chosen = jnp.tile(jnp.asarray([[5, 6, 12]], jnp.int32), (tokens, 1))
    weights = jax.random.uniform(ks[1], (tokens, 3))
    stack = {"wu": jax.random.normal(ks[2], (E, F, e)) * e ** -0.5,
             "wd": jax.random.normal(ks[3], (E, F, e)) * F ** -0.5}
    want = held.dense_held_reference(x, chosen, weights, stack, first,
                                     act="relu2")
    plan = held.plan_rows(chosen, None, first, E, 16, tile)
    assert plan[0].shape[0] // plan[2].shape[0] == tile
    # two hot experts of 96 rows each: their tiles, and no other
    assert int(plan[3][0]) == 2 * -(-tokens // tile)
    for kw in ({"tile": tile, "interpret": True}, {"plan": plan},
               {"tile": tile, "use_kernel": False}):
        got, counts = held.held_experts_ffn(x, chosen, weights, stack, first,
                                            act="relu2", **kw)
        assert np.allclose(got, want, atol=1e-4), kw
        assert counts.tolist() == [0, tokens, tokens, 0]
    assert held.row_tile(tokens) == 32 and family()[0].moe_row_tile == 64


def test_the_one_group_router_against_the_plain_one():
    """``route_sigmoid_grouped`` with ONE group is this family's router: the
    top-k of ``s + bias``, the weights from ``s``."""
    x = jax.random.normal(jax.random.key(0), (64, 32))
    w = jax.random.normal(jax.random.key(1), (32, 16)) * 0.3
    b = jax.random.normal(jax.random.key(2), (16,)) * 0.1
    sizes = dict(top_k=3, routed_scaling_factor=2.5, norm_topk_prob=True)
    got_e, got_w = held.ROUTERS["sigmoid_grouped"](
        x, w, 3, 2.5, True, bias=b, groups=1, keep=1)
    want_e, want_w, _ = reference.route(x, {"router": w, "router_bias": b},
                                        sizes)
    assert np.array_equal(np.sort(got_e, -1), np.sort(want_e, -1))
    assert np.allclose(np.sort(got_w, -1), np.sort(want_w, -1), atol=1e-6)
    plain_e, _ = held.ROUTERS["sigmoid"](x, w, 3, 2.5, True)
    assert not np.array_equal(np.sort(plain_e, -1), np.sort(got_e, -1))


# -- the configuration ---------------------------------------------------------

def test_layer_pattern_and_sizes_from_the_sources_keys():
    cfg, params = family(num_hidden_layers=14)
    block = ("ffn", "ssd", "ffn", "ssd", "ffn", "ssd", "full")
    assert cfg.layer_kinds == block * 2 and cfg.half_blocks
    # published layers 6-19 are two whole blocks: ONE period of seven
    assert layer_runs(cfg) == (0, [(k, 1) for k in block], 2, 0)
    assert sorted(params["periods"]) == [f"l{j}" for j in range(7)]
    assert "tail" not in params and set(params["experts"]) == {"wu", "wd"}
    assert params["experts"]["wu"].shape == (6, 16, 32, 64)
    # from layer 0 the same period of seven (MEMEM*E), a tail of four
    whole = nemotron_h_config(dict(SOURCE, num_hidden_layers=18))
    assert layer_runs(whole)[2:] == (2, 4)
    assert whole.layer_kinds[:6] == ("ssd", "ffn", "ssd", "ffn", "ssd",
                                     "full")
    assert (cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state_dim) == (128, 8, 16, 2, 16)
    assert (cfg.router_scoring, cfg.router_groups, cfg.expert_act,
            cfg.activation, cfg.pos_emb) \
        == ("sigmoid_grouped", 1, "relu2", "relu2", "none")
    # the shared expert is two expert widths
    assert cfg.n_shared_experts == 2
    mixer = jax.tree.map(lambda a: a[0], params["periods"]["l1"])["mixer"]
    assert mixer["w_in"].shape == (64, 128 + (128 + 2 * 2 * 16) + 8)
    assert float(jnp.exp(mixer["A_log"]).min()) >= 1.0
    dt = jax.nn.softplus(mixer["dt_bias"])
    assert 0.001 <= float(dt.min()) and float(dt.max()) <= 0.1001
    # the matmul parameters n_params counts are the tree's
    matrices = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)
                   if a.ndim >= 2 + (a.shape[0] == 2))
    assert abs(cfg.n_params() - matrices) < 0.01 * matrices
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nemotron_h_config(dict(SOURCE, hybrid_override_pattern="ME-M*"
                               + PATTERN))
    with pytest.raises(ValueError, match="mlp_hidden_act"):
        nemotron_h_config(dict(SOURCE, mlp_hidden_act="silu"))


# -- served logits against the plain reference --------------------------------

@pytest.mark.parametrize("lengths,prompts,chunk,preempt", [
    ((30, 21, 13), (24, 15, 7), None, None),
    ((38, 24), (33, 20), 16, 3)],
    ids=["prefill-then-decode",
         "a-prompt-in-segments-and-a-preempted-row"])
def test_served_logits_match_the_plain_reference(lengths, prompts, chunk,
                                                 preempt):
    """Prefill, then decode through the pages AND the slots, against the
    reference's full forward; a prompt in pieces of 16 (a continued prefill
    from the slot's state and tail, the attention layers from their pages);
    a row offloaded, its slot reused, and restored."""
    cfg, params = family(num_hidden_layers=14 if chunk is None
                         and preempt is None else 7)
    seqs = sequences_of(lengths, seed=1)
    want = reference_logits(cfg, params, seqs)
    rows = served_rows(cfg, params, seqs, prompts, chunk, preempt)
    assert len(rows) == sum(n - p + 1 for n, p in zip(lengths, prompts))
    assert worst_error(rows, want) < TOLERANCE


def test_served_logits_where_the_pattern_does_not_repeat():
    """Published layers 0-8: one period and a tail of two behind it, the
    routed stack indexed by a layer's place among the ``E`` layers."""
    cfg, params = family(num_hidden_layers=9, first_layer=0)
    assert cfg.layer_kinds.count("ffn") == 4
    assert layer_runs(cfg)[2:] == (1, 2) and sorted(params["tail"]) \
        == ["l0", "l1"]
    seqs = sequences_of((20, 13), seed=2)
    want = reference_logits(cfg, params, seqs)
    assert worst_error(served_rows(cfg, params, seqs, (15, 9)), want) \
        < TOLERANCE


@pytest.fixture(scope="module")
def control_rows():
    cfg, params = family()
    seqs = sequences_of((40, 29), seed=6)
    return cfg, params, seqs, served_rows(cfg, params, seqs, (30, 18))


@pytest.mark.parametrize("name,control", [
    ("float8_weights", {"weight_precision": jnp.float8_e4m3fn}),
    ("bf16_state", {"state_precision": jnp.bfloat16}),
    ("relu_for_relu2", {"act": "relu"}),
    ("one_bc_group_for_all_heads", {"bc_groups": False}),
    ("norm_over_the_whole_width", {"norm_groups": False}),
    ("norm_before_the_gate", {"gate_first": False}),
    ("router_without_bias", {"bias": False}),
    ("weights_from_the_biased_scores", {"weights_from": "c"}),
    ("rope_on_the_attention_layers", {"rope": 10000.0}),
    ("no_skip", {"skip": False})])
def test_a_control_is_told(control_rows, name, control):
    """Each control the probe must tell, planted in the reference and read
    against what the program served: tens of times the tolerance (a state
    rounded to bfloat16 over 40 tokens: three times; it is the chip's 2,000
    steps that integrate it, ``PERF.md``)."""
    cfg, params, seqs, rows = control_rows
    faulty = reference_logits(cfg, params, seqs, **control)
    assert worst_error(rows, faulty) > (
        3 if name == "bf16_state" else 20) * TOLERANCE, name


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """A routed layer's partial sums over the 8 shares of 2 experts each,
    the shared expert counted once, are the layer with all 16 experts held;
    the program's held share is the reference's."""
    cfg, params = family()
    sizes = reference.sizes_of(cfg)
    x = jax.random.normal(jax.random.key(5), (24, 64))
    lp = jax.tree.map(lambda a: a[0], params["periods"]["l2"])["moe"]
    full = jax.tree.map(lambda a: a[1], params["experts"])
    whole, _, _ = reference.routed_ffn(x, lp, full, sizes)
    shared = reference.relu2_mlp(x, lp["shared"]["wi"], lp["shared"]["wo"],
                                 sizes)
    total = shared
    for first in range(0, 16, 2):
        part = jax.tree.map(lambda a: a[first:first + 2], full)
        y, pairs, _ = reference.routed_ffn(
            x, lp, part, dict(sizes, experts_first=first))
        total = total + (y - shared)
        # the program's share: the same router, the grouped matmul
        chosen, weights = held.ROUTERS["sigmoid_grouped"](
            x, lp["router"], 3, 2.5, True, bias=lp["router_bias"],
            groups=1, keep=1)
        got, counts = held.held_experts_ffn(x, chosen, weights, part, first,
                                            act="relu2")
        assert np.allclose(got, y - shared, atol=2e-5)
        assert int(jnp.sum(counts)) == int(jnp.sum(pairs))
    assert np.allclose(total, whole, atol=5e-5)
    with jax.default_matmul_precision("highest"):
        assert float(jnp.max(jnp.abs(whole))) > 0.1


# -- through the scheduler -----------------------------------------------------

def test_greedy_through_the_scheduler_matches_the_reference():
    cfg, params = family(n_routed_experts=4, n_routed_experts_scored=16)
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


def test_the_routing_sink_hears_the_routed_layers_alone():
    cfg, params = family()
    heard = []
    engine = engine_of(cfg, params)
    engine.model.routing_sink = lambda chosen: heard.append(
        np.asarray(chosen))
    got = np.asarray(engine.put([0, 1], sequences_of([9, 6])))
    jax.effects_barrier()
    engine.model.routing_sink = None
    assert len(heard) == cfg.layer_kinds.count("ffn") == 3
    plain = engine_of(cfg, params)
    np.testing.assert_array_equal(
        got, np.asarray(plain.put([0, 1], sequences_of([9, 6]))))
    text = next(iter(plain.model.compiled_programs().values())).as_text()
    assert "callback" not in text.lower()


# -- what the kinds cache, and what the engine refuses ------------------------

def test_a_kind_that_caches_nothing_reserves_no_page_and_no_slot():
    assert CACHE_KINDS["ssd"].slot and not CACHE_KINDS["ssd"].group
    ffn = CACHE_KINDS["ffn"]
    assert not ffn.slot and not ffn.group and not ffn.windowed
    assert slot_kind(("ffn", "ssd", "full")) == "ssd"
    assert set(MIXERS) == set(CACHE_KINDS)
    assert MIXERS["ffn"].run is None and MIXERS["ffn"].pools == ()
    assert MIXERS["ssd"].pools == ("state", "conv")
    cfg, params = family()
    model = NemotronHInferenceModel(cfg, params)
    # the table has the attention layers' pages and the slot, no column of
    # the routed layers'
    assert model.table == TableLayout(window=0, page_size=64, state=True)
    assert model.pool_names == ("pages", "state", "conv")
    sc, kv = model.state_config, model.kv_config
    assert (sc.kind, sc.num_layers) == ("ssd", 3)
    assert sc.state == (16, 128) and sc.tail == (3, 128 + 2 * 2 * 16)
    assert (kv.num_layers, kv.kv_heads, kv.head_dim) == (1, 2, 16)
    assert implementation_for("nemotron_h") is NemotronHInferenceModel
    engine = engine_of(cfg, params)
    state = engine.state_manager
    assert [a.shape for a in state.state_pool.data] \
        == [(3, 9, 16, 128), (3, 9, 8, 3 * 192 // 8)]
    assert state.kv_cache.data.shape == (1, 65, 2, 2, PAGE, 16)
    # 20 tokens: 3 pages of the attention layer's one group and one slot;
    # the three routed layers reserve nothing
    engine.put([0], sequences_of([20]))
    state.check_invariants()
    assert (engine.free_blocks, engine.free_state_slots) == (64 - 3, 7)
    engine.flush(0)
    assert (engine.free_blocks, engine.free_state_slots) == (64, 8)
    for serving, names in [(dict(tp_degree=2), "tp_degree"),
                           (dict(speculative=True), "spec.py"),
                           (dict(kv_quantization="int8"), "int8"),
                           (dict(kv_tier_host_pages=4), "kv_tiers")]:
        with pytest.raises(ValueError, match=names):
            engine_of(cfg, params,
                      serving=ServingOptimizationConfig(**serving))
    with pytest.raises(AssertionError):
        NemotronHInferenceModel(dataclasses.replace(
            cfg, layer_kinds=("ssd", "ffn", "ssd", "ffn", "ssd", "ffn",
                              "ssd")), params)


def test_step_spans_carry_the_ssd_kinds_counts():
    """Under telemetry ``fastgen.step`` carries the slots held and their
    bytes (under the pool's names), the rows the update kernel stepped and
    the true tokens the chunked form consumed under the KIND's name, the
    Mamba-2 layers a step ran, and the held experts' counts."""
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
        {"ssm_slots_held", "ssd_rows_decode", "ssd_tokens_prefill",
         "ssm_state_bytes", "ssd_layers"} <= set(s) for s in steps)
    assert all(s["ssd_layers"] == 3 for s in steps)
    slot = sched._engine.state_manager.state_pool.cfg.bytes_per_slot
    assert slot == 3 * (16 * 128 * 4 + 3 * 192 * 4)
    assert sum(s["ssd_tokens_prefill"] for s in steps) == 51
    assert max(s["ssm_slots_held"] for s in steps) == 2
    assert all(s["ssm_state_bytes"] == s["ssm_slots_held"] * slot
               for s in steps)
    assert sum(s["ssd_rows_decode"] for s in steps) == 2 * 7
    # every expert is held: 3 pairs a token and routed layer, three of them
    assert sum(s["moe_pairs_here"] for s in steps if "moe_pairs_here" in s) \
        == (51 + 14) * 3 * 3
