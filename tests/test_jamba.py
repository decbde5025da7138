"""Jamba (``models/jamba.py``) through the serving path at a small size:
the model class against its plain reference through slots and pages, the
two state-space kernels against a plain ``lax.scan``, the state pool's
slots through every codec of ``StateManager``, what is refused at build,
and the step's spans."""

import dataclasses
import functools

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
    JambaInferenceModel, implementation_for)
from deepspeed_tpu.inference.v2.ragged.blocked_allocator import (
    KVAllocationError)
from deepspeed_tpu.inference.v2.ragged.cache_kinds import (CACHE_KINDS,
                                                           TableLayout)
from deepspeed_tpu.models import jamba_reference as reference
from deepspeed_tpu.models.jamba import JambaForCausalLM, jamba_config
from deepspeed_tpu.models.transformer import layer_runs
from deepspeed_tpu.ops.ssm import (conv_slot_shape, conv_step, slot_tails,
                                   ssm_scan, ssm_scan_kernel,
                                   ssm_scan_reference, write_tails)

PAGE = 8
SOURCE = dict(
    model_type="jamba", attn_layer_offset=1, attn_layer_period=4,
    expert_layer_offset=1, expert_layer_period=2, hidden_act="silu",
    hidden_size=64, intermediate_size=96, mamba_conv_bias=True,
    mamba_d_conv=4, mamba_d_state=8, mamba_dt_rank=8, mamba_expand=2,
    mamba_proj_bias=False, num_attention_heads=4, num_experts=1,
    num_experts_per_tok=1, num_hidden_layers=8, num_key_value_heads=1,
    rms_norm_eps=1e-6, sliding_window=None, tie_word_embeddings=True,
    vocab_size=160)

#: served float32 against the float32 reference: the two differ in the
#: order of their sums alone (a batched einsum against a matrix product,
#: the pool's scan against a whole-sequence scan), a few float32 ulps a
#: layer; 2e-5 relative rms is ten times what 8 layers read here
TOLERANCE = 2e-5


def family(seed=3, **over):
    model = JambaForCausalLM(dict(SOURCE, **over), dtype=jnp.float32)
    return model.cfg, meta.unbox(model.init_params(jax.random.key(seed)))


def engine_of(cfg, params, pages=64, seqs=8, serving=None, budget=256):
    return InferenceEngineV2(
        JambaInferenceModel(cfg, params),
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


def served_logit_error(cfg, params, want, seqs, prompts, chunk=None):
    """Largest relative rms difference of a served logits row from
    ``want``'s: the last prompt position (the prompt in pieces of
    ``chunk`` tokens where given: a continued prefill from a carried
    state), then every teacher-forced decode step through the slots and
    the pages."""
    engine = engine_of(cfg, params)
    uids = list(range(len(seqs)))
    at = [0] * len(seqs)
    while any(a < p for a, p in zip(at, prompts)):
        part = [u for u in uids if at[u] < prompts[u]]
        n = [min(chunk or prompts[u], prompts[u] - at[u]) for u in part]
        got = np.asarray(engine.put(
            part, [seqs[u][at[u]:at[u] + k] for u, k in zip(part, n)]))
        for u, k in zip(part, n):
            at[u] += k
    worst = max(rel_rms(got[i], want[u][prompts[u] - 1])
                for i, u in enumerate(part) if at[u] == prompts[u])
    while uids:
        got = np.asarray(engine.put(uids, [seqs[u][at[u]:at[u] + 1]
                                           for u in uids]))
        worst = max([worst] + [rel_rms(got[n], want[u][at[u]])
                               for n, u in enumerate(uids)])
        engine.state_manager.check_invariants()
        for u in uids:
            at[u] += 1
        for u in [u for u in uids if at[u] == len(seqs[u])]:
            engine.flush(u)
            uids.remove(u)
    return worst


# -- the model against the plain reference ----------------------------------

def test_layer_pattern_from_period_and_offset():
    """Attention at 7 and 21 of 28 at the published period and offset; one
    period is 7 Mamba, the attention layer, 6 Mamba."""
    cfg = jamba_config(dict(SOURCE, num_hidden_layers=28,
                            attn_layer_period=14, attn_layer_offset=7))
    assert [i for i, k in enumerate(cfg.layer_kinds) if k == "full"] \
        == [7, 21]
    assert cfg.layer_kinds.count("ssm") == 26
    assert layer_runs(cfg) == (0, [("ssm", 7), ("full", 1), ("ssm", 6)], 2, 0)
    assert layer_runs(jamba_config(dict(SOURCE, num_hidden_layers=10))) \
        == (0, [("ssm", 1), ("full", 1), ("ssm", 2)], 2, 2)
    assert cfg.pos_emb == "none" and cfg.tie_embeddings
    assert cfg.dims_per_head == 16 and cfg.ssm_inner == 128


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


@pytest.mark.parametrize("fault", [
    {"norms": False}, {"skip": False}, "bf16_state", "tail_break",
    "stale_state"])
def test_a_planted_fault_is_seen(fault):
    """Each of the probe's controls, planted in the reference, reads far
    outside the tolerance: the comparison can tell each of them."""
    cfg, params = family()
    seqs, prompts = sequences_of((40, 33)), (11, 20)
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
    assert served_logit_error(cfg, params, want, seqs, prompts) \
        > 50 * TOLERANCE


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


def test_a_chained_run_and_a_drained_run_give_the_same_tokens():
    """A step dispatched ahead of the drain reads the state the step in
    flight is still writing, in stream order through the donated carry:
    the same requests served with the chain (the default) and drained
    every step come to the same tokens."""
    cfg, params = family()
    prompts = sequences_of((21, 30, 9, 17), seed=6)

    def serve(**serving):
        sched = FastGenScheduler(engine_of(
            cfg, params, serving=ServingOptimizationConfig(**serving)))
        for uid, p in enumerate(prompts):
            sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=12))
        return sched.run_to_completion()

    chained = serve()
    assert chained == serve(async_scheduling=False)


def test_a_mixed_step_against_its_two_segments_run_apart():
    """Decode rows and prompt rows in ONE program (the mixer's projections
    over all tokens at once, the convolution and the recurrence a
    segment) sample what the two segments' own programs sample."""
    cfg, params = family()
    old, new = sequences_of((20, 26), seed=7), sequences_of((13, 9), seed=8)

    def run(mixed):
        sched = FastGenScheduler(engine_of(
            cfg, params, serving=ServingOptimizationConfig(
                fused_step=mixed)))
        for uid, p in enumerate(old):
            sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=10))
        for _ in range(3):
            sched.step()
        for uid, p in enumerate(new):
            sched.submit(10 + uid, p.tolist(),
                         SamplingParams(max_new_tokens=6))
        out = sched.run_to_completion()
        kinds = {k.kind for k in sched._engine.compiled_keys()}
        return out, kinds

    fused, kinds = run(True)
    assert "mixed" in kinds
    apart, kinds = run(False)
    assert "mixed" not in kinds
    assert fused == apart


# -- the kernels against the plain scan --------------------------------------

def scan_args(S, Q, d=256, N=8, L=3, slots=6, seed=0, q_lens=None):
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    q_lens = np.asarray(q_lens if q_lens is not None
                        else rng.integers(1, Q + 1, S))
    dt = np.abs(rng.normal(size=(S, Q, d))) * 0.1 \
        * (np.arange(Q)[None, :, None] < q_lens[:, None, None])
    return dict(
        h_pool=jnp.asarray(rng.normal(size=(L, slots + 1, N, d)), f32),
        conv_pool=jnp.asarray(
            rng.normal(size=(L, slots + 1, 8, 3 * d // 8)), f32),
        # a decode row's tail is the convolution's to write (``conv_step``)
        new_tail=jnp.asarray(rng.normal(size=(S, 3, d)), f32) if Q > 1
        else None,
        layer=jnp.int32(1),
        slots=jnp.asarray(rng.permutation(slots)[:S], jnp.int32),
        fresh=jnp.asarray(rng.integers(0, 2, S).astype(bool)),
        dt=jnp.asarray(dt, f32),
        x=jnp.asarray(rng.normal(size=(S, Q, d)), f32),
        B=jnp.asarray(rng.normal(size=(S, Q, N)), f32),
        C=jnp.asarray(rng.normal(size=(S, Q, N)), f32),
        A_t=-jnp.exp(jnp.asarray(rng.normal(size=(N, d)), f32)),
        D=jnp.asarray(rng.normal(size=(d,)), f32)), q_lens


@pytest.mark.parametrize("S,Q,d", [
    (5, 1, 256), (3, 7, 256), (2, 20, 384), (2, 128, 1280)],
    ids=["update", "scan-short", "scan-q-no-tile", "scan-blocked"])
def test_the_state_space_kernel_against_the_plain_scan(S, Q, d):
    """Interpret mode: the update kernel (Q = 1) and the scan kernel, with
    padded rows, rows continued from a non-zero state and rows that start
    from zeros, ``Q`` no multiple of a tile, ``d_inner`` in several
    blocks; only the rows' own slots of the one layer change."""
    args, _ = scan_args(S, Q, d)
    want_y, want_pool, want_conv = ssm_scan_reference(**args)
    got_y, got_pool, got_conv = ssm_scan_kernel(**args, interpret=True)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_pool, want_pool, rtol=1e-5, atol=1e-5)
    assert np.array_equal(got_conv, want_conv)
    touched = np.zeros(args["h_pool"].shape[:2], bool)
    touched[1, np.asarray(args["slots"])] = True
    for got, was in ((got_pool, args["h_pool"]),
                     (got_conv, args["conv_pool"])):
        assert np.array_equal(np.asarray(got)[~touched],
                              np.asarray(was)[~touched])
    if Q == 1:
        # the update kernel carries no tail: the conv pool is no operand
        assert got_conv is args["conv_pool"]
        return
    # the rows' new tails, laid end to end, are what their slots hold
    assert np.array_equal(
        np.asarray(got_conv)[1, np.asarray(args["slots"])].reshape(S, 3, d),
        args["new_tail"])


def test_padding_a_row_does_not_move_its_state():
    """A row of 5 true tokens in a block of 5 and in a block of 16 (dt = 0
    past the true tokens) leaves the same state, and steps from it alike."""
    short, _ = scan_args(2, 5, q_lens=[5, 3], seed=1)
    def pad(a, value=0.0):
        return jnp.pad(a, ((0, 0), (0, 11), (0, 0)), constant_values=value)

    # garbage in the padded positions of everything but dt
    long_ = dict(short, dt=pad(short["dt"]), x=pad(short["x"], 3.0),
                 B=pad(short["B"], -2.0), C=pad(short["C"], 5.0))
    for impl in (ssm_scan_reference,
                 lambda **a: ssm_scan_kernel(**a, interpret=True)):
        y_s, pool_s, _ = impl(**short)
        y_l, pool_l, _ = impl(**long_)
        np.testing.assert_allclose(pool_l, pool_s, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(y_l[:, :5], y_s, rtol=1e-6, atol=1e-6)


def test_the_conv_tail_kept_is_that_of_the_true_last_tokens():
    """``conv_step`` over a padded block gives the tail of the row's TRUE
    last three inputs (older ones where the row has fewer), and convolves
    behind the slot's tail unless the row is fresh."""
    rng = np.random.default_rng(2)
    S, Q, d, K = 3, 8, 16, 4
    pool = jnp.asarray(rng.normal(size=(2, 5, 8, (K - 1) * d // 8)),
                       jnp.float32)
    slots = jnp.asarray([3, 0, 2], jnp.int32)
    fresh = jnp.asarray([False, True, False])
    q_lens = jnp.asarray([8, 5, 2], jnp.int32)
    x = jnp.asarray(rng.normal(size=(S, Q, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, d)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    out, same, new = conv_step(pool, 1, slots, fresh, q_lens, x, w, b)
    assert same is pool                 # the scan writes a prompt's tails
    for i in range(S):
        old = np.zeros((K - 1, d)) if fresh[i] else \
            np.asarray(pool[1, slots[i]]).reshape(K - 1, d)
        seq = np.concatenate([old, np.asarray(x[i])])
        n = int(q_lens[i])
        for t in range(n):
            want = b + sum(w[k] * seq[t + k] for k in range(K))
            np.testing.assert_allclose(out[i, t], want, rtol=1e-5,
                                       atol=1e-5)
        np.testing.assert_allclose(new[i], seq[n:n + K - 1])


# -- a decode row's tail: convolved and shifted where it lies ----------------

def tail_args(S, c, dtype=jnp.bfloat16, K=4, seed=0, idle=(),
              x_dtype=jnp.float32):
    """A conv pool of ``S`` + 2 slots and a scratch slot in the pool's own
    layout, ``S`` rows at distinct slots but the ``idle`` ones (``q_lens ==
    0``), which SHARE the scratch slot; every fourth row ``fresh``."""
    rng = np.random.default_rng(seed)
    scratch = S + 2
    pool = jnp.asarray(rng.normal(size=(2, scratch + 1)
                                  + conv_slot_shape((K - 1) * c)), dtype)
    slots = rng.permutation(scratch)[:S]
    q_lens = np.ones(S, np.int32)
    q_lens[list(idle)] = 0
    slots[list(idle)] = scratch
    return dict(
        conv_pool=pool, layer=jnp.int32(1),
        slots=jnp.asarray(slots, jnp.int32),
        fresh=jnp.asarray(np.arange(S) % 4 == 1),
        q_lens=jnp.asarray(q_lens),
        x=jnp.asarray(rng.normal(size=(S, 1, c)), x_dtype),
        w=jnp.asarray(rng.normal(size=(K, c)), jnp.float32),
        b=jnp.asarray(rng.normal(size=(c,)), jnp.float32))


#: the jnp form as a step program holds it: one jitted computation (taken
#: operation by operation the CPU rounds each product before its sum)
conv_step_jnp = jax.jit(functools.partial(conv_step, use_kernel=False))


@pytest.mark.parametrize("S", [1, 8, 256])
@pytest.mark.parametrize("c", [5120, 6144, 11520, 12288])
def test_the_decode_convolution_is_the_jnp_form_bit_for_bit(c, S):
    """``conv_step`` at one token a row through ``conv_tail_decode``
    (interpret mode) at the four families' channel counts: the output and
    every slot but the scratch slot equal the jnp form's, with fresh rows
    (a zero tail whatever the slot held) and rows with nothing to write that
    share the scratch slot; no other slot and no other layer moves.  At
    5,120 channels the inputs come in the pool's dtype, as that family's
    projection gives them (sixteen sequences at a time, where a grid step
    has as many)."""
    args = tail_args(S, c, idle=(0, 3, 5) if S > 1 else (), seed=c + S,
                     x_dtype=jnp.bfloat16 if c == 5120 else jnp.float32)
    if c == 11520:              # 270 rows of one lane tile, held as 272
        assert args["conv_pool"].shape[2:] == (272, 128)
    want_out, want_pool, _ = conv_step_jnp(**args)
    out, pool, tail = conv_step(**args, interpret=True)
    assert tail is None and np.array_equal(out, want_out)
    true_slots = np.ones(pool.shape[1], bool)
    true_slots[-1] = False
    assert np.array_equal(np.asarray(pool)[:, true_slots],
                          np.asarray(want_pool)[:, true_slots])
    written = np.zeros(pool.shape[:2], bool)
    written[1, np.asarray(args["slots"])] = True
    assert np.array_equal(np.asarray(pool)[~written],
                          np.asarray(args["conv_pool"])[~written])
    # a true row's slot holds its old taps but the oldest, then the input
    K, live = 4, np.flatnonzero(np.asarray(args["q_lens"]))
    got = np.asarray(slot_tails(pool[1, args["slots"][live]], K - 1, c))
    was = np.array(slot_tails(
        args["conv_pool"][1, args["slots"][live]], K - 1, c))
    was[np.asarray(args["fresh"])[live]] = 0
    assert np.array_equal(got[:, :-1], was[:, 1:])
    assert np.array_equal(got[:, -1], np.asarray(
        args["x"][live, 0].astype(pool.dtype)))


@pytest.mark.parametrize("dtype,kernel", [
    (jnp.float32, True), (jnp.bfloat16, False)], ids=["kernel-f32", "jnp"])
def test_decode_steps_continue_a_prompts_tail(dtype, kernel):
    """A prompt through the ``Q > 1`` branch (its tails written as the scan
    writes them), then four decode steps: the outputs and the tails left in
    the slots are those of ONE call over all the tokens, so the order of
    the taps survives the hand-over between the two branches."""
    S, c, K, steps = 2, 512, 4, 4
    args = tail_args(S, c, dtype=dtype, seed=7)
    rng = np.random.default_rng(8)
    n = np.asarray([8, 3])                              # the prompts' tokens
    tokens = jnp.asarray(rng.normal(size=(S, 16, c)), dtype)
    shared = {k: args[k] for k in ("layer", "slots", "fresh", "w", "b")}

    def prompt(q_lens, Q):
        out, pool, tails = conv_step(args["conv_pool"], x=tokens[:, :Q],
                                     q_lens=jnp.asarray(q_lens), **shared)
        return out, write_tails(pool, 1, args["slots"], tails)

    whole, want_pool = prompt(n + steps, 16)
    _, pool = prompt(n, 8)
    for t in range(steps):
        x = jnp.stack([tokens[i, n[i] + t] for i in range(S)])[:, None]
        out, pool, _ = conv_step(
            pool, x=x, q_lens=jnp.ones(S, jnp.int32),
            **dict(shared, fresh=jnp.zeros(S, bool)),
            use_kernel=kernel, interpret=kernel)
        for i in range(S):
            np.testing.assert_allclose(out[i, 0], whole[i, n[i] + t],
                                       rtol=2e-6, atol=2e-6)
    assert np.array_equal(pool, want_pool)


def test_a_tail_moved_to_another_slot_continues_bit_for_bit():
    """``read_slot`` -> ``write_slot`` into ANOTHER slot (an offload and a
    restore, a hand-over): the blob carries the taps as they lie, and the
    decode steps behind it read what the steps at the old slot read."""
    from deepspeed_tpu.inference.v2.ragged.kv_cache import (StatePool,
                                                            StatePoolConfig)
    c, K = 256, 4
    store = StatePool(StatePoolConfig(num_layers=2, state=(8, 128),
                                      tail=(K - 1, c), num_slots=4))
    assert store.data[1].shape == (2, 5, 8, 128)
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(K, c)), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(6, 1, 1, c)), jnp.float32)
    old, new = store.reserve(), store.reserve()

    def step(t, slot, fresh=False):
        h, conv = store.data
        out, conv, _ = conv_step(
            conv, 1, jnp.asarray([slot], jnp.int32), jnp.asarray([fresh]),
            jnp.ones(1, jnp.int32), xs[t], w, interpret=True)
        store.data = (h, conv)
        return np.asarray(out)

    for t in range(3):
        step(t, old, fresh=t == 0)
    store.write_slot(new, store.read_slot(old))
    for t in range(3, 6):
        assert np.array_equal(step(t, old), step(t, new))
        assert np.array_equal(store.read_slot(old).conv,
                              store.read_slot(new).conv)


def test_the_kernel_is_chosen_by_the_platform_alone():
    args, _ = scan_args(2, 1)
    y, _, _ = ssm_scan(**args)                 # the CPU: the plain scan
    np.testing.assert_allclose(y, ssm_scan_reference(**args)[0])


# -- the state pool's slots through StateManager -----------------------------

def prefill(engine, uid, n, seed=0):
    toks = np.random.default_rng(seed).integers(0, 160, n).astype(np.int32)
    engine.put([uid], [toks])
    return toks


def test_what_a_kind_caches_is_declared_in_one_place():
    assert CACHE_KINDS["ssm"].slot and not CACHE_KINDS["ssm"].group
    assert CACHE_KINDS["window"].windowed and CACHE_KINDS["full"].group
    cfg, params = family()
    model = JambaInferenceModel(cfg, params)
    table = model.table
    assert table == TableLayout(window=0, page_size=64, state=True)
    assert table.extra(1) == table.extra(128) == 1
    assert model.state_config.num_layers == 6
    assert model.kv_config.num_layers == 2 and model.window_kv_config is None
    parts = table.split(np.arange(18).reshape(2, 9), 1)
    assert parts["full"].shape == (2, 8) and list(parts["slot"]) == [8, 17]
    # a model of two page groups and a model of one keep their tables
    assert TableLayout.of(("full", "window"), 32, 8).extra(1) == 8 + 1
    assert TableLayout.of(("full",), 4096, 64).extra(128) == 0


def test_a_slot_and_pages_are_reserved_together_or_not_at_all():
    cfg, params = family()
    engine = engine_of(cfg, params, pages=64, seqs=2)
    state = engine.state_manager
    prefill(engine, 0, 17)
    prefill(engine, 1, 9)
    assert engine.free_state_slots == 0 and engine.free_blocks == 64 - 3 - 2
    from deepspeed_tpu.inference.v2.engine import SchedulingResult
    assert engine.state_slots_needed(0) == 0
    assert engine.state_slots_needed(7) == 1
    state.max_tracked_sequences = 3          # the slots are what is short
    assert engine.can_schedule([7], [9]) \
        == SchedulingResult.KVCacheLimitExceeded
    with pytest.raises(KVAllocationError, match="state pool"):
        state.allocate_for(state.get_or_create_sequence(7), 9)
    assert state.get_sequence(7).pages == [] \
        and state.get_sequence(7).state_slot == -1
    assert engine.free_blocks == 64 - 5
    engine.flush(7)
    state.check_invariants()
    # pages short, slots free: neither is taken either
    small = engine_of(cfg, params, pages=2, seqs=4)
    with pytest.raises(KVAllocationError):
        small.state_manager.allocate_for(
            small.state_manager.get_or_create_sequence(0), 40)
    assert small.free_state_slots == 4 and small.free_blocks == 2
    # the scheduler's admission holds the same account
    sched = FastGenScheduler(engine)
    sched.submit(5, list(range(9)), SamplingParams(max_new_tokens=2))
    sched.step()
    assert state.get_sequence(5) is None \
        or state.get_sequence(5).state_slot == -1
    engine.flush(0)
    assert len(sched.run_to_completion()[5]) == 2
    state.check_invariants()


@pytest.mark.parametrize("codec", ["flush", "preempt", "snapshot",
                                   "handoff"])
def test_a_slot_rides_every_codec_bit_exact(codec):
    """Admit, decode, then flush / preempt and restore / snapshot into a
    second engine / hand one sequence over: the slots' account holds at
    every point, the slot's rows arrive bit for bit (on another slot), and
    decoding goes on to the reference's logits."""
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


def test_a_restore_or_an_import_without_a_free_slot_fails_whole():
    cfg, params = family()
    engine = engine_of(cfg, params, seqs=2)
    state = engine.state_manager
    # one more sequence may be tracked than there are slots (a built
    # engine has a slot a tracked sequence, so this cannot arise there)
    engine._config.state_manager.max_tracked_sequences = 3
    state.max_tracked_sequences = 3
    prefill(engine, 0, 12)
    prefill(engine, 1, 12)
    engine.offload_sequence(0)
    prefill(engine, 2, 5)
    with pytest.raises(KVAllocationError, match="state slot"):
        engine.restore_sequence(0)
    sd = state.get_sequence(0)
    assert sd.host_blob is not None and sd.state_blob is not None
    state.check_invariants()
    meta_, arrays = state.export_state(seq_ids=[1])
    full = engine_of(cfg, params, seqs=2)
    prefill(full, 7, 5)
    prefill(full, 8, 5)
    full.state_manager.max_tracked_sequences = 3
    with pytest.raises(KVAllocationError, match="state slots"):
        full.state_manager.import_state(meta_, arrays)
    full.state_manager.check_invariants()
    assert full.state_manager.get_sequence(1) is None


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


def test_check_invariants_catches_a_slot_held_twice_or_lost():
    cfg, params = family()
    engine = engine_of(cfg, params)
    state = engine.state_manager
    prefill(engine, 0, 9)
    prefill(engine, 1, 9)
    state.check_invariants()
    a, b = state.get_sequence(0), state.get_sequence(1)
    keep = b.state_slot
    b.state_slot = a.state_slot
    with pytest.raises(RuntimeError, match="state slot"):
        state.check_invariants()
    b.state_slot = -1
    with pytest.raises(RuntimeError, match="state"):
        state.check_invariants()
    b.state_slot = keep
    state.check_invariants()
    with pytest.raises(ValueError, match="not held"):
        state.state_pool.release(7)


# -- what is refused at build, and what is built off --------------------------

def test_implementation_for_jamba_and_what_it_refuses():
    cfg, params = family()
    assert implementation_for("jamba") is JambaInferenceModel
    engine = engine_of(cfg, params)
    state = engine.state_manager
    # the prefix cache is built off, as for a window page group
    assert state.prefix_cache is None and state.tiers is None
    assert state.state_pool.cfg.num_slots == 8
    assert [a.shape for a in state.state_pool.data] \
        == [(6, 9, 8, 128), (6, 9, 8, 128)]    # 3 rows of a tile, held as 8
    assert state.state_pool.data[0].dtype == jnp.float32

    def build(**serving):
        return engine_of(cfg, params,
                         serving=ServingOptimizationConfig(**serving))

    for serving, names in [
            (dict(kv_tier_host_pages=4), "kv_tiers"),
            (dict(tp_degree=2), "tp_degree"),
            (dict(kv_quantization="int8"), "int8"),
            (dict(speculative=True), "spec.py"),
            (dict(speculative=True, spec_drafter="model"), "spec.py")]:
        with pytest.raises(ValueError, match=names) as err:
            build(**serving)
        assert "state pool" in str(err.value)
    with pytest.raises(ValueError, match="num_experts > 1"):
        JambaForCausalLM(dict(SOURCE, num_experts=16,
                              num_experts_per_tok=2))
    with pytest.raises(ValueError, match="state-space"):
        JambaInferenceModel(cfg, params).quantize_weights()
    with pytest.raises(AssertionError):
        JambaInferenceModel(dataclasses.replace(
            cfg, layer_kinds=("full",) * 8), params)


def test_step_spans_carry_the_state_pools_counts():
    """Under telemetry ``fastgen.step`` carries the slots held, the rows
    the update kernel stepped, the true tokens the scan consumed and the
    bytes the held slots hold."""
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
        {"ssm_slots_held", "ssm_rows_decode", "ssm_tokens_prefill",
         "ssm_state_bytes"} <= set(s) for s in steps)
    slot = sched._engine.state_manager.state_pool.cfg.bytes_per_slot
    assert slot == 6 * 128 * (8 * 4 + 3 * 4)
    assert sum(s["ssm_tokens_prefill"] for s in steps) == 51
    assert max(s["ssm_slots_held"] for s in steps) == 2
    assert all(s["ssm_state_bytes"] == s["ssm_slots_held"] * slot
               for s in steps)
    assert sum(s["ssm_rows_decode"] for s in steps) == 2 * 7
    assert not any("kv_pages_reserved_window" in s for s in steps)
