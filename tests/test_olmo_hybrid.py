"""Olmo-Hybrid (``models/olmo_hybrid.py``) through the serving path at a
small size: the chunked matrix form and the two delta-rule kernels against
the token-by-token recurrence, the model class against its plain reference
through slots and pages, a ``delta`` slot through every codec of
``StateManager``, what the kind declares of its slot, and the step's
spans."""

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
from deepspeed_tpu.inference.v2.model_implementations import (
    OlmoHybridInferenceModel, implementation_for)
from deepspeed_tpu.inference.v2.ragged.cache_kinds import (CACHE_KINDS,
                                                           TableLayout,
                                                           slot_kind)
from deepspeed_tpu.inference.v2.ragged.kv_cache import StatePoolConfig
from deepspeed_tpu.models import olmo_hybrid_reference as reference
from deepspeed_tpu.models.olmo_hybrid import (OlmoHybridForCausalLM,
                                              olmo_hybrid_config)
from deepspeed_tpu.models.transformer import layer_runs
from deepspeed_tpu.ops.delta_rule import (chunk_len, delta_chunk_prefill,
                                          delta_chunk_reference, delta_rule,
                                          delta_rule_reference,
                                          delta_state_update_decode)
from deepspeed_tpu.ops.ssm import conv_step

PAGE = 8
SOURCE = dict(
    model_type="olmo_hybrid", vocab_size=160, hidden_size=64,
    intermediate_size=96, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=4, hidden_act="silu", attention_bias=False,
    rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=["linear_attention", "linear_attention",
                 "linear_attention", "full_attention"] * 3,
    linear_num_key_heads=4, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=32,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rope_parameters={"rope_theta": None})

#: served float32 against the float32 reference: the two differ in the
#: order of their sums (a batched einsum against a matrix product, the
#: pool's state ``[dk, H dv]`` against the reference's ``[H, dk, dv]``),
#: a few float32 ulps a layer, which the output norms pass on at unit
#: scale.  The worst row reads 4.3e-5 relative rms over 8 layers and
#: 1.1e-5 over 10; the nearest planted fault, a state rounded to bfloat16
#: every step, reads 0.14, every other one 0.88 and more
#: (``test_a_planted_fault_is_seen``).  2e-4 is five times the first and
#: a seven-hundredth of the second
TOLERANCE = 2e-4


def family(seed=3, **over):
    model = OlmoHybridForCausalLM(dict(SOURCE, **over), dtype=jnp.float32)
    return model.cfg, meta.unbox(model.init_params(jax.random.key(seed)))


def engine_of(cfg, params, pages=64, seqs=8, serving=None, budget=256):
    return InferenceEngineV2(
        OlmoHybridInferenceModel(cfg, params),
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
    return [np.asarray(reference.forward(
        params, s, reference.sizes_of(cfg), **kw)[0]) for s in seqs]


def served_rows(cfg, params, seqs, prompts, chunk=None):
    """The served logits rows as (sequence, position, row): the last
    prompt position (the prompt in pieces of ``chunk`` tokens where given:
    a continued prefill from a carried state), then every teacher-forced
    decode step through the slots and the pages."""
    engine = engine_of(cfg, params)
    uids = list(range(len(seqs)))
    at = [0] * len(seqs)
    rows = []
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
    return rows


def worst_error(rows, want):
    """Largest relative rms difference of a served row from ``want``'s."""
    return max(rel_rms(got, want[u][pos]) for u, pos, got in rows)


def served_logit_error(cfg, params, want, seqs, prompts, chunk=None):
    return worst_error(served_rows(cfg, params, seqs, prompts, chunk), want)


# -- the recurrence: chunked matrix form and kernels against the plain scan --

def rule_args(S, Q, H=4, dk=16, dv=32, L=3, slots=6, seed=0, q_lens=None,
              fresh=None):
    """Arguments of ``delta_rule``: l2-normed q and k, log-decays down to
    -1.6 a step, ``beta`` over the whole of (0, 2), padded positions with
    ``g = 0`` and ``beta = 0``."""
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    q_lens = np.asarray(q_lens if q_lens is not None
                        else rng.integers(1, Q + 1, S))
    valid = (np.arange(Q)[None, :] < q_lens[:, None])[..., None]

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    chan = H * (2 * dk + dv)
    beta = 2.0 / (1.0 + np.exp(-2.0 * rng.normal(size=(S, Q, H))))
    return dict(
        state_pool=jnp.asarray(
            rng.normal(size=(L, slots + 1, dk, H * dv)), f32),
        conv_pool=jnp.asarray(
            rng.normal(size=(L, slots + 1, 8, 3 * chan // 8)), f32),
        # a decode row's tail is the convolution's to write (``conv_step``)
        new_tail=jnp.asarray(rng.normal(size=(S, 3, chan)), f32) if Q > 1
        else None,
        layer=jnp.int32(1),
        slots=jnp.asarray(rng.permutation(slots)[:S], jnp.int32),
        fresh=jnp.asarray(fresh if fresh is not None
                          else rng.integers(0, 2, S).astype(bool)),
        q=jnp.asarray(unit(rng.normal(size=(S, Q, H, dk))) * dk ** -0.5,
                      f32),
        k=jnp.asarray(unit(rng.normal(size=(S, Q, H, dk))), f32),
        v=jnp.asarray(rng.normal(size=(S, Q, H * dv)), f32),
        g=jnp.asarray(-np.exp(rng.uniform(-6, 0.5, (S, Q, H))) * valid,
                      f32),
        beta=jnp.asarray(beta * valid, f32)), q_lens


def close(got, want, tol=2e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("S,Q", [(2, 8), (3, 64), (2, 128), (2, 192)],
                         ids=["one-short-chunk", "one-chunk",
                              "two-chunks", "three-chunks"])
def test_the_chunked_form_equals_the_token_by_token_recurrence(S, Q):
    """The matrix form (``K K^T``, ``Q K^T``, the triangular solve by
    squarings, the products into and out of the state) chunk by chunk
    against the plain scan: outputs and end state, rows continued from a
    non-zero state and rows from zeros, rows padded inside a chunk (a
    ragged last chunk) and rows that end chunks before the bucket does."""
    args, q_lens = rule_args(S, Q, seed=Q)
    assert float(args["beta"].max()) > 1.5 and chunk_len(Q) == min(Q, 64)
    want = delta_rule_reference(**args)
    for impl in (delta_chunk_reference,
                 lambda **a: delta_chunk_prefill(**a, interpret=True)):
        got = impl(**args)
        close(got[0], want[0])
        close(got[1], want[1])
        assert np.array_equal(got[2], want[2])


@pytest.mark.parametrize("S,H,dk,dv", [(5, 4, 16, 32), (8, 2, 16, 64),
                                       (16, 3, 8, 128), (4, 3, 8, 32)],
                         ids=["groups-of-4", "pairs", "whole-tiles",
                              "no-tile-group"])
def test_the_update_kernel_against_the_plain_scan(S, H, dk, dv):
    """Interpret mode, Q = 1: the row's whole state walked in lane groups
    of heads (4 x 32, 2 x 64 and 1 x 128 lanes are each one tile; 3 x 32
    is none, and the whole row is one group); only the rows' own slots of
    the one layer change, and the conv pool is no operand (a decode row's
    tail is ``conv_step``'s to write: ``tests/test_jamba.py``)."""
    args, _ = rule_args(S, 1, H=H, dk=dk, dv=dv, slots=max(S, 6), seed=S)
    want = delta_rule_reference(**args)
    got = delta_state_update_decode(**args, interpret=True)
    close(got[0], want[0], 1e-5)
    close(got[1], want[1], 1e-5)
    assert got[2] is args["conv_pool"] and want[2] is args["conv_pool"]
    touched = np.zeros(args["state_pool"].shape[:2], bool)
    touched[1, np.asarray(args["slots"])] = True
    assert np.array_equal(np.asarray(got[1])[~touched],
                          np.asarray(args["state_pool"])[~touched])


def test_a_prefill_continued_from_a_slots_state_equals_one_pass():
    """24 tokens in one call, and in calls of 16 and 8 with the state
    carried in the slot between them: the same outputs and end state, by
    the scan and by the chunked kernel."""
    whole, _ = rule_args(2, 24, q_lens=[24, 24], fresh=[True, False],
                         seed=5)
    for impl in (delta_rule_reference,
                 lambda **a: delta_chunk_prefill(**a, interpret=True)):
        # the kernel takes 24 as three chunks of 8, 16 as one, 8 as one
        o, pool, _ = impl(**whole)

        def part(lo, hi, pool_in, fresh):
            return impl(**dict(
                whole, state_pool=pool_in, fresh=jnp.asarray(fresh),
                **{n: whole[n][:, lo:hi] for n in "qkvg"},
                beta=whole["beta"][:, lo:hi]))

        o1, mid, _ = part(0, 16, whole["state_pool"], [True, False])
        o2, end, _ = part(16, 24, mid, [False, False])
        close(jnp.concatenate([o1, o2], 1), o)
        close(end, pool)


def test_padding_moves_neither_state_nor_tail():
    """A row of 5 true tokens in a block of 8 and in a block of 64, garbage
    in the padded positions of q, k and v (``g = 0`` and ``beta = 0``
    there): the same state and the same outputs; and the convolution's
    tail is that of the row's TRUE last three inputs over q, k and v."""
    short, _ = rule_args(2, 8, q_lens=[5, 3], seed=1)

    def pad(a, value=0.0):
        return jnp.pad(a, ((0, 0), (0, 56)) + ((0, 0),) * (a.ndim - 2),
                       constant_values=value)

    long_ = dict(short, q=pad(short["q"], 3.0), k=pad(short["k"], -2.0),
                 v=pad(short["v"], 5.0), g=pad(short["g"]),
                 beta=pad(short["beta"]))
    for impl in (delta_rule_reference,
                 lambda **a: delta_rule(**a, interpret=True)):
        o_s, pool_s, _ = impl(**short)
        o_l, pool_l, _ = impl(**long_)
        close(pool_l, pool_s, 1e-5)
        close(o_l[:, :5], o_s[:, :5], 1e-5)
    # a row of 5 true tokens changes its state: the test is not vacuous
    assert np.abs(np.asarray(pool_s) - np.asarray(
        short["state_pool"])).max() > 0.1
    rng = np.random.default_rng(2)
    chan, K = 4 * (2 * 16 + 32), 4
    pool = jnp.asarray(rng.normal(size=(2, 5, 8, (K - 1) * chan // 8)),
                       jnp.float32)
    slots, fresh = jnp.asarray([3, 0], jnp.int32), jnp.asarray([False, True])
    x = jnp.asarray(rng.normal(size=(2, 8, chan)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, chan)), jnp.float32)
    _, _, tails = conv_step(pool, 1, slots, fresh, jnp.asarray([5, 2]), x, w)
    np.testing.assert_allclose(tails[0], x[0, 2:5])
    np.testing.assert_allclose(tails[1][0], 0.0)
    np.testing.assert_allclose(tails[1][1:], x[1, :2])


def test_the_kernel_is_chosen_by_the_platform_and_the_bucket():
    args, _ = rule_args(2, 1)
    o, _, _ = delta_rule(**args)               # the CPU: the plain scan
    np.testing.assert_allclose(o, delta_rule_reference(**args)[0])
    # a row bucket under a sublane tile is walked token by token
    short, _ = rule_args(2, 4, seed=3)
    o, _, _ = delta_rule(**short, interpret=True)
    np.testing.assert_allclose(o, delta_rule_reference(**short)[0])


# -- the model against the plain reference ----------------------------------

def test_layer_pattern_and_sizes_from_the_sources_keys():
    """``layer_types`` read for the layers that are there; three linear
    layers and a full one a period; the published widths give the slot the
    issue reckons (2,211,840 B of state and 69,120 B of tail a layer)."""
    cfg = olmo_hybrid_config(SOURCE)
    assert cfg.layer_kinds == ("delta", "delta", "delta", "full") * 2
    assert layer_runs(cfg) == (0, [("delta", 3), ("full", 1)], 2, 0)
    assert cfg.pos_emb == "none" and not cfg.tie_embeddings
    assert cfg.post_norm and cfg.qk_norm and cfg.delta_neg_eigval
    assert cfg.dims_per_head == 16 and cfg.kv_heads == 4
    published = olmo_hybrid_config(dict(
        SOURCE, hidden_size=3840, intermediate_size=11008,
        num_attention_heads=30, num_key_value_heads=30,
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        num_hidden_layers=4, vocab_size=100352))
    state, tail = CACHE_KINDS["delta"].slot_shape(published)
    assert state == (96, 5760) and tail == (3, 11520)
    pool = StatePoolConfig(num_layers=3, state=state, tail=tail,
                           kind="delta", num_slots=256)
    # 270 rows of one lane tile, the count rounded up to a sublane tile
    assert pool.shapes() == ((3, 257, 96, 5760), (3, 257, 272, 128))
    assert pool.bytes_per_slot == 3 * (2211840 + 69120)
    # 88.7M a mixer, 59.0M an attention layer, 126.8M an MLP, 770.7M of
    # embedding and head: one period
    assert published.n_params() == 3 * 88750140 + 58982400 \
        + 4 * 126812160 + 2 * 100352 * 3840
    with pytest.raises(ValueError, match="rope_theta"):
        olmo_hybrid_config(dict(SOURCE,
                                rope_parameters={"rope_theta": 5e5}))


@pytest.mark.parametrize("layers,lengths,prompts,chunk", [
    (8, (40, 33), (11, 20), None),
    # a tail after the whole periods, a prompt of one token, a prompt
    # continued in pieces of 7 tokens from the carried state
    (10, (30, 21, 26), (17, 1, 20), 7)],
    ids=["two-periods", "tail-and-continued-prefill"])
def test_served_logits_match_the_plain_reference(layers, lengths, prompts,
                                                 chunk):
    cfg, params = family(num_hidden_layers=layers)
    seqs = sequences_of(lengths)
    want = reference_logits(cfg, params, seqs)
    assert served_logit_error(cfg, params, want, seqs, prompts,
                              chunk) < TOLERANCE


def test_beta_reaches_the_negative_eigenvalue_branch():
    """In the draw the served model is held to the reference on, ``beta``
    of the later layers lies on both sides of 1 (an eigenvalue ``1 -
    beta`` of either sign)."""
    cfg, params = family()
    seq = sequences_of((40,))[0]
    x = np.asarray(params["embed"]["tokens"])[seq]
    beta = []
    for kind, lp in zip(cfg.layer_kinds, reference.layers_of(
            params, cfg.layer_kinds)):
        if kind == "delta":
            ab = x @ np.asarray(lp["mixer"]["w_ab"]).T
            beta.append(2 / (1 + np.exp(-ab[:, cfg.delta_heads:])))
        # the residual stream grows by a normed output a sub-layer
        x = x + np.random.default_rng(0).normal(size=x.shape)
    beta = np.concatenate(beta)
    assert beta.min() < 0.7 and beta.max() > 1.3


@pytest.fixture(scope="module")
def fault_rows():
    """One serving of two sequences, which every planted fault is read
    against."""
    cfg, params = family()
    seqs, prompts = sequences_of((40, 33)), (11, 20)
    return cfg, params, seqs, prompts, served_rows(cfg, params, seqs,
                                                   prompts)


@pytest.mark.parametrize("fault", [
    {"beta_doubled": False}, {"decay": False}, {"l2norm": False},
    {"gate": False}, {"qk_norm": False}, "bf16_state", "tail_break",
    "stale_state"])
def test_a_planted_fault_is_seen(fault_rows, fault):
    """Each of the probe's controls, planted in the reference, reads far
    outside the tolerance: the comparison can tell each of them, a
    bfloat16 state among them."""
    cfg, params, seqs, prompts, rows = fault_rows
    sizes = reference.sizes_of(cfg, **(fault if isinstance(fault, dict)
                                       else {}))
    want = []
    for i, (s, p) in enumerate(zip(seqs, prompts)):
        kw = {}
        if fault == "bf16_state":
            kw["state_precision"] = jnp.bfloat16
        elif fault == "tail_break":
            kw["tail_break"] = p
        elif fault == "stale_state":
            kw["carry_in"] = reference.forward(
                params, seqs[i - 1], sizes)[1]
        want.append(np.asarray(reference.forward(params, s, sizes,
                                                 **kw)[0]))
    # (keys that are not l2-normed make the transition expansive under
    # beta up to 2: that control's reference overflows, which no limit
    # passes either)
    assert not worst_error(rows, want) <= 20 * TOLERANCE


def test_greedy_through_the_scheduler_matches_the_reference():
    cfg, params = family()
    prompts = sequences_of((21, 30, 9), seed=5)
    sched = FastGenScheduler(engine_of(cfg, params))
    for uid, p in enumerate(prompts):
        sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=6))
    out = sched.run_to_completion()
    for uid, p in enumerate(prompts):
        seq = np.concatenate([p, np.asarray(out[uid][:-1], np.int32)])
        want = reference_logits(cfg, params, [seq])[0]
        assert out[uid] == [int(t) for t in
                            np.argmax(want[len(p) - 1:], axis=-1)]
    sched._engine.state_manager.check_invariants()
    assert sched._engine.free_state_slots == 8


def test_a_chained_a_drained_and_a_mixed_run_give_the_same_tokens():
    """The same requests served with the chain (the default), drained
    every step, and without the one-pass mixed step come to the same
    tokens: a step dispatched ahead reads the state the step in flight is
    writing in stream order, and a mixed step's two segments step their
    own rows' slots."""
    cfg, params = family()
    old, new = sequences_of((20, 26), seed=7), sequences_of((13, 9), seed=8)

    def serve(**serving):
        sched = FastGenScheduler(engine_of(
            cfg, params, serving=ServingOptimizationConfig(**serving)))
        for uid, p in enumerate(old):
            sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=10))
        for _ in range(3):
            sched.step()
        for uid, p in enumerate(new):
            sched.submit(10 + uid, p.tolist(),
                         SamplingParams(max_new_tokens=6))
        out = sched.run_to_completion()
        return out, {k.kind for k in sched._engine.compiled_keys()}

    chained, kinds = serve()
    assert "mixed" in kinds
    assert chained == serve(async_scheduling=False)[0]
    apart, kinds = serve(fused_step=False)
    assert "mixed" not in kinds and apart == chained


# -- a delta slot through StateManager ---------------------------------------

def prefill(engine, uid, n, seed=0):
    toks = np.random.default_rng(seed).integers(0, 160, n).astype(np.int32)
    engine.put([uid], [toks])
    return toks


def test_what_the_delta_kind_caches_is_declared_in_one_place():
    assert CACHE_KINDS["delta"].slot and not CACHE_KINDS["delta"].group
    assert slot_kind(("delta", "full")) == "delta"
    assert slot_kind(("full", "window")) is None
    with pytest.raises(AssertionError, match="one slot kind"):
        slot_kind(("ssm", "delta", "full"))
    cfg, params = family()
    model = OlmoHybridInferenceModel(cfg, params)
    assert model.table == TableLayout(window=0, page_size=64, state=True)
    sc = model.state_config
    # a matrix [dk, dv] a head side by side; the tail of q, k AND v
    assert (sc.kind, sc.num_layers) == ("delta", 6)
    assert sc.state == (16, 4 * 32) and sc.tail == (3, 4 * (2 * 16 + 32))
    assert model.kv_config.num_layers == 2 and model.kv_config.kv_heads == 4
    assert implementation_for("olmo_hybrid") is OlmoHybridInferenceModel
    engine = engine_of(cfg, params)
    state = engine.state_manager
    assert state.prefix_cache is None and state.state_pool.cfg.num_slots == 8
    assert [a.shape for a in state.state_pool.data] \
        == [(6, 9, 16, 128), (6, 9, 8, 128)]   # 6 rows of a tile, held as 8
    assert state.state_pool.data[0].dtype == jnp.float32
    for serving, names in [(dict(tp_degree=2), "tp_degree"),
                           (dict(speculative=True), "spec.py")]:
        with pytest.raises(ValueError, match=names) as err:
            engine_of(cfg, params,
                      serving=ServingOptimizationConfig(**serving))
        assert "state pool" in str(err.value)
    with pytest.raises(AssertionError):
        OlmoHybridInferenceModel(dataclasses.replace(
            cfg, layer_kinds=("full",) * 8), params)


@pytest.mark.parametrize("codec", ["flush", "preempt", "snapshot",
                                   "handoff"])
def test_a_delta_slot_rides_every_codec_bit_exact(codec):
    """Admit, decode, then flush / preempt and restore / snapshot into a
    second engine / hand one sequence over: the slots' account holds at
    every point, the slot's matrix state and tail arrive bit for bit (on
    another slot), and decoding goes on to the reference's logits."""
    cfg, params = family()
    engine = engine_of(cfg, params)
    state = engine.state_manager
    seqs = sequences_of((40, 33), seed=4)
    uids = [0, 1]
    engine.put(uids, [s[:20] for s in seqs])
    for at in range(20, 27):
        engine.put(uids, [s[at:at + 1] for s in seqs])
    state.check_invariants()
    assert engine.free_state_slots == 6
    if codec == "flush":
        engine.flush(0)
        state.check_invariants()
        engine.flush(1)
        state.check_invariants()
        assert (engine.free_blocks, engine.free_state_slots) == (64, 8)
        return
    before = state.state_pool.read_slot(state.get_sequence(0).state_slot)
    assert before.h.shape == (6, 16, 128) and np.abs(before.h).max() > 0
    other = engine_of(cfg, params)
    prefill(other, 9, 5)            # so that slot 0 is taken over there
    if codec == "preempt":
        engine.offload_sequence(0)
        sd = state.get_sequence(0)
        assert sd.state_slot == -1 and sd.state_blob is not None
        assert state.offloaded_blobs == 2 and engine.free_state_slots == 7
        state.check_invariants()
        prefill(engine, 5, 5)       # takes the slot that was given back
        engine.restore_sequence(0)
        assert sd.state_blob is None and state.offloaded_blobs == 0
        target = engine
    elif codec == "snapshot":
        other.flush(9)
        meta_, arrays = state.export_state()
        assert meta_["kv"]["state"][:2] == ["delta", 6]
        other.state_manager.import_state(meta_, arrays)
        target = other
    else:
        meta_, arrays = state.export_state(seq_ids=[0])
        other.state_manager.import_state(meta_, arrays)
        engine.flush(0)
        target, uids = other, [0]
    state.check_invariants()
    target.state_manager.check_invariants()
    after = target.state_manager.state_pool.read_slot(
        target.state_manager.get_sequence(0).state_slot)
    assert np.array_equal(before.h, after.h) \
        and np.array_equal(before.conv, after.conv)
    want = reference_logits(cfg, params, seqs)
    for at in range(27, 32):
        got = np.asarray(target.put(uids, [seqs[u][at:at + 1]
                                           for u in uids]))
        for n, u in enumerate(uids):
            assert rel_rms(got[n], want[u][at]) < TOLERANCE
        target.state_manager.check_invariants()


def test_a_reused_slot_starts_from_zero():
    """The program zeroes a row at position 0, not the host: a sequence on
    a slot that another just left reads the reference's logits."""
    cfg, params = family()
    engine = engine_of(cfg, params, seqs=1)
    first, second = sequences_of((30, 24), seed=9)
    engine.put([0], [first])
    slot = engine.state_manager.get_sequence(0).state_slot
    engine.flush(0)
    left = engine.state_manager.state_pool.read_slot(slot)
    assert np.abs(left.h).max() > 0        # the host cleared nothing
    got = np.asarray(engine.put([1], [second[:10]]))
    assert engine.state_manager.get_sequence(1).state_slot == slot
    want = reference_logits(cfg, params, [second])[0]
    assert rel_rms(got[0], want[9]) < TOLERANCE
    for at in range(10, 14):
        got = np.asarray(engine.put([1], [second[at:at + 1]]))
        assert rel_rms(got[0], want[at]) < TOLERANCE


def test_step_spans_carry_the_delta_kinds_counts():
    """Under telemetry ``fastgen.step`` carries the slots held and their
    bytes (under the pool's names), the rows the update kernel stepped and
    the true tokens the chunked form consumed (under the kind's), and the
    context the decode rows attend in the full layers."""
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
        {"ssm_slots_held", "delta_rows_decode", "delta_tokens_prefill",
         "ssm_state_bytes", "attn_tokens_full"} <= set(s) for s in steps)
    assert not any("ssm_rows_decode" in s or "attn_tokens_window" in s
                   for s in steps)
    slot = sched._engine.state_manager.state_pool.cfg.bytes_per_slot
    assert slot == 6 * (16 * 128 * 4 + 3 * 256 * 4)
    assert sum(s["delta_tokens_prefill"] for s in steps) == 51
    assert max(s["ssm_slots_held"] for s in steps) == 2
    assert all(s["ssm_state_bytes"] == s["ssm_slots_held"] * slot
               for s in steps)
    assert sum(s["delta_rows_decode"] for s in steps) == 2 * 7
    # decode step n of a prompt of p tokens attends p + n tokens
    assert sum(s["attn_tokens_full"] for s in steps) \
        == sum(p + n for p in (21, 30) for n in range(1, 8))


def test_the_state_space_kinds_pool_is_what_it_was():
    """The pool takes its shapes from the kind, and the ``ssm`` kind
    declares what ``StatePoolConfig`` held before it did: at the published
    Jamba widths the two arrays, a slot's bytes and the pool's bytes are
    the numbers of PR 34 (9,318,400 B a slot; the cell's
    ``memory_peak_bytes`` rests on them)."""
    from deepspeed_tpu.models.jamba import jamba_config
    cfg = jamba_config(dict(
        num_hidden_layers=28, attn_layer_period=14, attn_layer_offset=7,
        num_attention_heads=20, num_key_value_heads=1, hidden_size=2560,
        intermediate_size=8192, vocab_size=65536, rms_norm_eps=1e-6,
        mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=160,
        mamba_expand=2))
    state, tail = CACHE_KINDS["ssm"].slot_shape(cfg)
    assert (state, tail) == ((16, 5120), (3, 5120))
    pool = StatePoolConfig(num_layers=26, state=state, tail=tail,
                           num_slots=256)
    assert pool.kind == "ssm"
    assert pool.shapes() == ((26, 257, 16, 5120), (26, 257, 120, 128))
    assert pool.bytes_per_slot == 26 * 358_400 == 9_318_400
    assert pool.total_bytes() == 257 * 9_318_400
