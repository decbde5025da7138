"""openPangu-Ultra-MoE (``pangu_ultra_moe``) as a served family, at a small
size with seeded weights: the latent (MLA) page pool, the routed layer that
holds some of the experts, sandwich norms and a leading dense layer on the
FastGen path, against the plain reference
(``deepspeed_tpu/models/pangu_moe_reference.py``)."""

import dataclasses
import json
import os

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
    PanguUltraMoEInferenceModel, implementation_for, supported_model_types)
from deepspeed_tpu.inference.v2.ragged import KVCacheConfig
from deepspeed_tpu.inference.v2.ragged.kv_cache import (BlockedKVCache,
                                                        pages_for_memory)
from deepspeed_tpu.models import pangu_moe_reference as reference
from deepspeed_tpu.models.pangu_moe import (PanguUltraMoEForCausalLM,
                                            pangu_moe_config)
from deepspeed_tpu.moe import held
from deepspeed_tpu.ops import mla_attention as mla

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SOURCE = dict(
    vocab_size=160, hidden_size=64, intermediate_size=96,
    num_hidden_layers=3, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=32, kv_lora_rank=24,
    rms_norm_eps=1e-5, rope_theta=25600000, sandwich_norm=True,
    n_routed_experts=4, n_routed_experts_scored=16, n_shared_experts=1,
    num_experts_per_tok=3, moe_intermediate_size=32,
    routed_scaling_factor=2.5, norm_topk_prob=True, first_k_dense_replace=1)
PAGE = 16


def family(first=4, held_experts=4, seed=3, **over):
    model = PanguUltraMoEForCausalLM(
        dict(SOURCE, n_routed_experts=held_experts, **over),
        experts_first=first, dtype=jnp.float32)
    return model.cfg, meta.unbox(model.init_params(jax.random.key(seed)))


def engine_of(cfg, params, serving=None, pages=64):
    return InferenceEngineV2(
        implementation_for("pangu_ultra_moe")(cfg, params),
        RaggedInferenceEngineConfig(
            state_manager=StateManagerConfig(
                max_tracked_sequences=8, max_ragged_sequence_count=8,
                max_ragged_batch_size=256),
            kv_cache=KVCacheUserConfig(page_size=PAGE, num_pages=pages,
                                       dtype=jnp.float32),
            serving=serving or ServingOptimizationConfig()))


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SOURCE["vocab_size"], n).tolist()
            for n in lengths]


def reference_logits(params, token_ids, sizes):
    """``reference.forward``'s logits; of a model without a routed layer
    (whose pairs ``forward`` cannot stack) from the reference's own layers
    in ``forward``'s order."""
    if "layers" in params:
        return reference.forward(params, token_ids, sizes)[0]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"].astype(jnp.float32)[token_ids]
        for lp in reference.layers_of(params):
            x, _ = reference.layer(x, lp, sizes)
        x = reference.rms_norm(x, params["final_norm"]["scale"],
                               sizes["eps"])
        return x @ params["lm_head"].astype(jnp.float32)


def served_logit_error(cfg, params, ref_cfg=None, ref_params=None,
                       lengths=(21, 40), steps=3):
    """Largest relative rms difference of a served logits row (the last
    prompt position, then ``steps`` teacher-forced decode steps through
    the latent cache) against the plain reference's forward.  The
    reference runs ONCE a sequence, over the prompt and the forced tokens
    (it is causal: row ``p - 1`` is the forward of the first ``p`` tokens;
    run eagerly, every new length compiles each of its operations anew)."""
    sizes = reference.sizes_of(ref_cfg or cfg)
    ref_params = params if ref_params is None else ref_params
    engine = engine_of(cfg, params)
    seqs = prompts_of(lengths)
    rng = np.random.default_rng(1)
    uids = list(range(len(seqs)))
    forced = [[int(rng.integers(0, SOURCE["vocab_size"])) for _ in seqs]
              for _ in range(steps)]
    want = [np.asarray(reference_logits(
        ref_params, jnp.asarray(list(seq) + [nxt[i] for nxt in forced]),
        sizes)) for i, seq in enumerate(seqs)]

    def worst(logits, step):
        out = 0.0
        for i, seq in enumerate(seqs):
            got, row = np.asarray(logits[i]), want[i][len(seq) - 1 + step]
            out = max(out, float(np.sqrt(np.mean((got - row) ** 2)
                                         / np.mean(row ** 2))))
        return out

    err = worst(engine.put(uids, seqs), 0)
    for step, nxt in enumerate(forced):
        err = max(err, worst(engine.put(uids, [[t] for t in nxt]), step + 1))
    return err


# -- the served path against the plain reference ------------------------------

@pytest.mark.parametrize("lengths", [(21, 40), (16,), (7, 33, 64)],
                         ids=["two", "page-edge", "three"])
def test_served_logits_match_the_plain_reference(lengths):
    """Prefill (expanded attention) and decode through the latent cache
    (absorbed attention over pages) give the reference's logits."""
    cfg, params = family()
    assert served_logit_error(cfg, params, lengths=lengths) < 2e-5


@pytest.mark.parametrize("dense", [0, 2, 3],
                         ids=["none", "two", "every_layer"])
def test_served_logits_with_any_number_of_leading_dense_layers(dense):
    """The leading dense layers are a stack of their own, the routed ones
    another: either may be missing (``first_k_dense_replace`` 0, or every
    layer of a 3-layer cut under the published 3), and the pool's layer
    index and the held experts' run on through both."""
    cfg, params = family(first_k_dense_replace=dense)
    assert ("dense_layers" in params, "layers" in params) \
        == (dense > 0, dense < 3)
    assert served_logit_error(cfg, params, lengths=(16,), steps=1) < 2e-5


def test_greedy_through_the_scheduler_matches_the_reference():
    """The fused sample / chain step programs (whose token vector carries
    the held-experts counts past its rows) decode what the reference's
    arg-max says."""
    cfg, params = family()
    sched = FastGenScheduler(engine_of(cfg, params))
    prompts = prompts_of((21, 40))
    for uid, p in enumerate(prompts):
        sched.submit(uid, p, SamplingParams(max_new_tokens=5))
    out = sched.run_to_completion()
    sizes = reference.sizes_of(cfg)
    for uid, p in enumerate(prompts):
        # the reference once a sequence (it is causal: the row before a
        # token is the forward of what precedes it)
        logits = reference.forward(
            params, jnp.asarray(list(p) + out[uid][:-1]), sizes)[0]
        for n, tok in enumerate(out[uid]):
            assert int(jnp.argmax(logits[len(p) - 1 + n])) == tok
    assert sched.last_moe_counts is not None \
        and len(sched.last_moe_counts) == 3


@pytest.mark.parametrize("wrong", ["dropped_post_norm", "no_rope_term"])
def test_a_wrong_program_fails_the_probes_tolerance(wrong):
    """The configuration's logits tolerance refuses a program that drops
    the post-sub-layer norms or leaves the rope term out of the score
    (and the right program, in float32, is far inside it)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "pangu-ultra-moe-serve-5l-ep16.json")) as f:
        limit = json.load(f)["probe"]["logit_rel_rms"]
    cfg, params = family()
    assert served_logit_error(cfg, params) < limit / 100
    bad_cfg, bad_params = cfg, params
    if wrong == "dropped_post_norm":
        bad_cfg = dataclasses.replace(cfg, sandwich_norm=False)
    else:
        dn = cfg.qk_nope_head_dim

        def no_rope(stack):
            attn = dict(stack["attn"])
            attn["wq_b"] = attn["wq_b"].at[..., dn:].set(0.0)
            return dict(stack, attn=attn)
        bad_params = dict(params,
                          dense_layers=no_rope(params["dense_layers"]),
                          layers=no_rope(params["layers"]))
    assert served_logit_error(bad_cfg, bad_params, ref_cfg=cfg,
                              ref_params=params) > limit


# -- latent attention ---------------------------------------------------------

def _latent_case(seed=0, S=3, Q=1, H=4, rank=24, rope=8, page=PAGE, P=8):
    rng = np.random.default_rng(seed)
    W = mla.plane_width(rank + rope)
    pool = np.zeros((2, 40, 1, 1, page, W), np.float32)
    pool[..., :rank + rope] = rng.normal(size=pool.shape[:-1] + (rank + rope,))
    pt = (rng.permutation(39)[:S * P].reshape(S, P) + 1).astype(np.int32)
    q = np.zeros((S, Q, H, W), np.float32)
    q[..., :rank + rope] = rng.normal(size=(S, Q, H, rank + rope))
    return jnp.asarray(pool), jnp.asarray(pt), jnp.asarray(q), W


def test_absorbed_attention_equals_expanded_attention():
    """The absorbed form over the cached planes equals the expanded form
    (k_n = c W_kb^K, v = c W_kb^V per head) on the same tokens."""
    rng = np.random.default_rng(2)
    T, H, rank, rope, dn, dv = 20, 4, 24, 8, 16, 16
    c = rng.normal(size=(T, rank)).astype(np.float32)
    k_r = rng.normal(size=(T, rope)).astype(np.float32)
    q_n = rng.normal(size=(T, H, dn)).astype(np.float32)
    q_r = rng.normal(size=(T, H, rope)).astype(np.float32)
    w_k = rng.normal(size=(rank, H, dn)).astype(np.float32)
    w_v = rng.normal(size=(rank, H, dv)).astype(np.float32)
    scale = (dn + rope) ** -0.5
    k = np.concatenate([np.einsum("tr,rhd->thd", c, w_k),
                        np.broadcast_to(k_r[:, None], (T, H, rope))], -1)
    expanded = mla.mla_fresh_attention(
        jnp.asarray(np.concatenate([q_n, q_r], -1))[None], jnp.asarray(k)[None],
        jnp.asarray(np.einsum("tr,rhd->thd", c, w_v))[None],
        sm_scale=scale, use_kernel=False)[0]
    # the same tokens as one row's cached planes, absorbed
    W = mla.plane_width(rank + rope)
    pool = np.zeros((1, 3, 1, 1, PAGE, W), np.float32)
    planes = np.concatenate([c, k_r], -1)
    pool[0, 1, 0, 0, :, :rank + rope] = planes[:PAGE]
    pool[0, 2, 0, 0, :T - PAGE, :rank + rope] = planes[PAGE:]
    q_abs = np.zeros((1, T, H, W), np.float32)
    q_abs[0, ..., :rank] = np.einsum("thd,rhd->thr", q_n, w_k)
    q_abs[0, ..., rank:rank + rope] = q_r
    ctx = mla.mla_paged_attention(
        jnp.asarray(q_abs), jnp.asarray(pool), 0,
        jnp.asarray([[1, 2, 0, 0, 0, 0, 0, 0]], jnp.int32),
        jnp.asarray([0], jnp.int32), jnp.asarray([T], jnp.int32),
        rank=rank, sm_scale=scale, use_kernel=False)[0]
    absorbed = np.einsum("thr,rhd->thd", np.asarray(ctx), w_v)
    np.testing.assert_allclose(absorbed, np.asarray(expanded), atol=2e-5)


def test_mla_decode_kernel_interpreted_matches_jnp():
    pool, pt, q, _ = _latent_case()
    sp = jnp.asarray([5, 77, 127], jnp.int32)
    ql = jnp.ones(3, jnp.int32)
    kw = dict(rank=24, sm_scale=0.2)
    want = mla.mla_paged_attention(q, pool, 1, pt, sp, ql, use_kernel=False,
                                   **kw)
    got = mla.mla_paged_attention(q, pool, 1, pt, sp, ql, interpret=True,
                                  **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("P,contexts", [
    (16, (5, 128, 129, 255)), (32, (127, 256, 300, 511)),
    (64, (40, 512, 700, 1023))], ids=["2-groups", "4-groups", "8-groups"])
def test_mla_decode_kernel_merges_groups_of_pages(P, contexts):
    """Page buckets of 16, 32 and 64 slots are 2, 4 and 8 grid steps of 8
    pages a row: the running max / denominator / sum across them gives
    what one softmax over the whole context gives, for a context inside
    the first group (the others skipped), one that ends on a group's
    edge, one a token past it and one in the last group."""
    rng = np.random.default_rng(P)
    S, H, rank, rope, page = len(contexts), 4, 24, 8, PAGE
    W = mla.plane_width(rank + rope)
    pool = np.zeros((2, S * P + 1, 1, 1, page, W), np.float32)
    pool[..., :rank + rope] = rng.normal(size=pool.shape[:-1] + (rank + rope,))
    pt = jnp.asarray(rng.permutation(S * P).reshape(S, P) + 1, jnp.int32)
    q = np.zeros((S, 1, H, W), np.float32)
    q[..., :rank + rope] = rng.normal(size=(S, 1, H, rank + rope))
    # the new token's position: a context of that many tokens and itself
    sp = jnp.asarray(contexts, jnp.int32)
    assert max(contexts) < P * page and P // mla.PAGES_PER_STEP >= 2
    args = (jnp.asarray(q), jnp.asarray(pool), 1, pt, sp,
            jnp.ones(S, jnp.int32))
    kw = dict(rank=rank, sm_scale=0.2)
    want = mla.mla_paged_attention(*args, use_kernel=False, **kw)
    got = mla.mla_paged_attention(*args, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _walk_case(contexts, P, page, table="exact", padding=(), seed=0):
    """Rows of one new token each whose contexts (the new token's
    positions) are ``contexts``, over a pool of ``page``-token pages:
    ``(args, kw)`` of ``mla_paged_attention``.  A row's table holds its own
    pages and the null page 0 past them, as ``ragged/batch.py`` fills it;
    the null page holds NaN, so a fetch of it that reaches the output shows.
    ``table="full"`` fills every slot with a live page instead (stale ids
    past the context must not be read either); the rows ``padding`` are a
    batch's padding rows: position 0 over a table of null pages."""
    rng = np.random.default_rng(seed)
    S, H, rank, rope = len(contexts), 4, 24, 8
    W = mla.plane_width(rank + rope)
    pool = np.zeros((2, S * P + 1, 1, 1, page, W), np.float32)
    pool[..., :rank + rope] = rng.normal(size=pool.shape[:-1] + (rank + rope,))
    pt = (rng.permutation(S * P).reshape(S, P) + 1).astype(np.int32)
    if table == "exact":
        for s, c in enumerate(contexts):
            pt[s, c // page + 1:] = 0
        pool[:, 0] = np.nan
    for s in padding:
        assert contexts[s] == 0
        pt[s] = 0
    q = np.zeros((S, 1, H, W), np.float32)
    q[..., :rank + rope] = rng.normal(size=(S, 1, H, rank + rope))
    assert max(contexts) < P * page
    return ((jnp.asarray(q), jnp.asarray(pool), 1, jnp.asarray(pt),
             jnp.asarray(contexts, jnp.int32), jnp.ones(S, jnp.int32)),
            dict(rank=rank, sm_scale=0.2))


@pytest.mark.parametrize("contexts,P,page,table,padding", [
    ((0,), 8, 64, "exact", ()),
    ((0, 0, 0, 0, 0), 16, 64, "exact", ()),
    ((63, 64, 127, 128), 8, 64, "exact", ()),
    ((511, 512, 1023, 1024), 24, 64, "exact", ()),
    ((5, 2047, 70, 1500, 0, 1100), 32, 64, "exact", ()),
    ((2047, 3, 2047, 3), 32, 64, "full", ()),
    ((700, 0, 0, 130, 0), 16, 64, "exact", (1, 2, 4)),
    ((100, 767, 384, 500), 12, 64, "exact", ()),
    ((40, 79, 5), 5, 16, "full", ()),
    ((9, 150, 447), 7, 64, "full", ()),
    ((0, 200, 255, 256), 4, 128, "exact", ()),
], ids=["one-token", "one-token-rows", "page-edges", "tile-edges",
        "short-beside-bucket-filling", "alternating-long-short",
        "padding-rows", "bucket-of-12", "bucket-of-5-pages-of-16",
        "bucket-of-7", "pages-of-128"])
def test_mla_decode_kernel_walks_each_rows_own_pages(contexts, P, page, table,
                                                     padding):
    """The kernel's work follows a row's own context (``ops/
    mla_attention.py::_decode_kernel``), so what can go wrong is the
    walk: a context of one token; contexts ending on a page's or a tile's
    edge and one token past it; a row of a page or two beside rows that
    fill the page bucket (the copies run ahead across rows, three tiles
    in flight); padding rows (``start_pos`` 0 over the null page), whose
    own output is garbage by contract and which must leave their
    neighbours' alone; page buckets that are no multiple of the 8 pages
    a tile holds; and both widths a tile is multiplied to (half its
    columns where no more of its pages are live, else all)."""
    args, kw = _walk_case(contexts, P, page, table, padding)
    want = mla.mla_paged_attention(*args[:1], jnp.nan_to_num(args[1]),
                                   *args[2:], use_kernel=False, **kw)
    got = mla.mla_paged_attention(*args, interpret=True, **kw)
    read = [s for s in range(len(contexts)) if s not in padding]
    assert np.isfinite(np.asarray(got)[read]).all()
    np.testing.assert_allclose(np.asarray(got)[read], np.asarray(want)[read],
                               atol=2e-5)


def test_mla_decode_kernel_takes_a_row_for_what_its_context_costs():
    """The page bucket is not in the work: the same rows under a table of
    8, 32 and 64 slots give the same numbers, bit for bit (no tile exists
    for a slot past the context)."""
    args, kw = _walk_case((5, 300, 511), 64, 64)
    outs = [np.asarray(mla.mla_paged_attention(
        *args[:3], args[3][:, :P], *args[4:], interpret=True, **kw))
        for P in (8, 32, 64)]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_mla_prefill_kernel_interpreted_matches_jnp():
    rng = np.random.default_rng(3)
    q, k = (jnp.asarray(rng.normal(size=(2, 16, 8, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 16, 8, 16)), jnp.float32)
    want = mla.mla_fresh_attention(q, k, v, sm_scale=0.2, use_kernel=False)
    got = mla.mla_fresh_attention(q, k, v, sm_scale=0.2, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("Q,q_lens,start", [
    (1, [1, 1, 1], [5, 77, 127]), (24, [24, 9, 17], [0, 30, 3])],
    ids=["decode", "prefill"])
def test_latent_write_kernel_interpreted_matches_the_scatter(Q, q_lens, start):
    pool, pt, _, W = _latent_case()
    plane = jnp.asarray(np.random.default_rng(4).normal(size=(3, Q, W)),
                        jnp.float32)
    args = (pt, jnp.asarray(start, jnp.int32), jnp.asarray(q_lens, jnp.int32))
    want = mla.latent_write(pool, 1, plane, *args, use_kernel=False)
    got = mla.latent_write(pool, 1, plane, *args, interpret=True)
    # the null page (0) holds garbage by contract
    np.testing.assert_array_equal(np.asarray(got)[:, 1:],
                                  np.asarray(want)[:, 1:])
    assert not np.array_equal(np.asarray(want)[1], np.asarray(pool)[1])
    np.testing.assert_array_equal(np.asarray(want)[0], np.asarray(pool)[0])


# -- the routed layer ---------------------------------------------------------

def test_router_against_numbers_worked_by_hand():
    """sigmoid, the 2 largest of all 4 experts, normalised over the 2
    chosen (before any cut to the held ones), times 2.5."""
    x = jnp.asarray([[1.0, 0.0], [0.0, 2.0]])
    w = jnp.asarray([[0.0, 1.0, -1.0, 2.0], [1.0, 0.0, 0.5, -1.0]])
    experts, weights = held.route_sigmoid_topk(x, w, 2, 2.5)
    # token 0: logits [0, 1, -1, 2] -> experts 3, 1
    s3, s1 = 1 / (1 + np.exp(-2.0)), 1 / (1 + np.exp(-1.0))
    np.testing.assert_array_equal(np.asarray(experts[0]), [3, 1])
    np.testing.assert_allclose(np.asarray(weights[0]),
                               [2.5 * s3 / (s3 + s1), 2.5 * s1 / (s3 + s1)],
                               rtol=1e-6)
    # token 1: logits [2, 0, 1, -2] -> experts 0, 2
    s0, s2 = 1 / (1 + np.exp(-2.0)), 1 / (1 + np.exp(-1.0))
    np.testing.assert_array_equal(np.asarray(experts[1]), [0, 2])
    np.testing.assert_allclose(np.asarray(weights[1]),
                               [2.5 * s0 / (s0 + s2), 2.5 * s2 / (s0 + s2)],
                               rtol=1e-6)
    # the same two tokens through the plain reference's router
    ref_e, ref_w = reference.route(x, w, dict(
        top_k=2, routed_scaling_factor=2.5, norm_topk_prob=True))
    np.testing.assert_array_equal(np.asarray(ref_e), np.asarray(experts))
    np.testing.assert_allclose(np.asarray(ref_w), np.asarray(weights),
                               rtol=1e-6)


def _routed_case(T=48, e=64, F=32, E=32, k=4, held_n=4, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(T, e)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(e, E)) / 8, jnp.float32)
    experts, weights = held.route_sigmoid_topk(x, router, k, 2.5)
    params = {n: jnp.asarray(rng.normal(size=(held_n, F, e)) / 8, jnp.float32)
              for n in ("wg", "wu", "wd")}
    return x, experts, weights, params


@pytest.mark.parametrize("first,interpret", [(0, False), (8, False),
                                             (8, True), (28, True)])
def test_held_experts_ffn_matches_the_dense_form(first, interpret):
    """Sorted pairs through the grouped matmul (``jnp`` form, and the
    Pallas kernel interpreted) against every held expert over every
    token; the counts are the pairs that fell to each held expert."""
    x, experts, weights, params = _routed_case()
    want = held.dense_held_reference(x, experts, weights, params, first)
    got, counts = held.held_experts_ffn(x, experts, weights, params, first,
                                        interpret=interpret)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    here = (np.asarray(experts) >= first) & (np.asarray(experts) < first + 4)
    assert int(counts.sum()) == int(here.sum())
    np.testing.assert_array_equal(
        np.asarray(counts),
        [(np.asarray(experts) == first + i).sum() for i in range(4)])


def test_padding_rows_route_nowhere():
    x, experts, weights, params = _routed_case()
    valid = jnp.arange(x.shape[0]) < 30
    got, counts = held.held_experts_ffn(x, experts, weights, params, 8,
                                        valid=valid)
    here = (np.asarray(experts)[:30] >= 8) & (np.asarray(experts)[:30] < 12)
    assert int(counts.sum()) == int(here.sum())
    assert float(jnp.abs(got[30:]).max()) == 0.0


def test_stacked_experts_are_addressed_by_layer():
    x, experts, weights, params = _routed_case()
    stack = {n: jnp.stack([p * 0.0, p, p * 2.0]) for n, p in params.items()}
    want, _ = held.held_experts_ffn(x, experts, weights, params, 8)
    for interpret in (False, True):
        got, _ = held.held_experts_ffn(x, experts, weights, stack, 8,
                                       layer=jnp.int32(1),
                                       interpret=interpret)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One routed layer: the partial results of all shares (experts 0-3,
    4-7, ...), the shared expert counted once, add up to the layer with
    every expert held; an expert's weights come from its global index."""
    scored, each = SOURCE["n_routed_experts_scored"], 4
    whole_cfg, whole = family(first=0, held_experts=scored)
    sizes = reference.sizes_of(whole_cfg)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(12, 64)),
                    jnp.float32)
    layer0 = jax.tree.map(lambda a: a[0], whole["layers"])["moe"]
    want, counts = reference.routed_ffn(x, layer0, sizes)
    assert int(counts.sum()) == 12 * SOURCE["num_experts_per_tok"]
    shared = reference.swiglu(x, layer0["shared"])
    total = jnp.zeros_like(x)
    for share in range(scored // each):
        cfg, params = family(first=share * each, held_experts=each)
        moe = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
        for n in ("wg", "wu", "wd"):      # the uncut model's own experts
            np.testing.assert_array_equal(
                np.asarray(moe["experts"][n]),
                np.asarray(layer0["experts"][n][share * each:
                                                (share + 1) * each]))
        part, _ = reference.routed_ffn(x, moe, reference.sizes_of(cfg))
        total = total + part - shared     # the shared expert once
        served, _ = held.held_experts_ffn(
            x, *held.route_sigmoid_topk(x, moe["router"], 3, 2.5),
            moe["experts"], share * each)
        np.testing.assert_allclose(np.asarray(served),
                                   np.asarray(part - shared), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=5e-5)


# -- the latent page pool on the host's side ----------------------------------

def test_latent_pool_layout_and_bytes():
    cfg, params = family()
    model = PanguUltraMoEInferenceModel(cfg, params)
    kv = model.kv_config
    assert kv.latent and kv.planes == 1 and kv.kv_heads == 1
    assert kv.head_dim == mla.plane_width(24 + 8) == 128
    assert kv.cache_shape() == (3, kv.num_pages + 1, 1, 1, kv.page_size, 128)
    # what is really held: the padded plane, one a token a layer
    assert kv.bytes_per_page == 3 * kv.page_size * 128 * 4
    assert pages_for_memory(kv, 10 * kv.bytes_per_page + 5) == 10
    engine = engine_of(cfg, params)
    assert engine.model.kv_config.latent
    assert engine.state_manager.kv_cache.data.shape == \
        (3, 65, 1, 1, PAGE, 128)
    # (tokens schedulable, pages needed) is by tokens a page, any layout
    assert engine.model.get_kv_requirements(10, 1, 30, 4) == (30, 2)
    assert engine.model.get_kv_requirements(0, 0, 200, 2) == (2 * PAGE, 2)


def test_latent_pool_refuses_int8_pages_and_tp():
    cfg, params = family()
    with pytest.raises(ValueError, match="latent page pool has no int8"):
        engine_of(cfg, params,
                  serving=ServingOptimizationConfig(kv_quantization="int8"))
    with pytest.raises(ValueError, match="latent page pool cannot be served "
                                         "under tp_degree"):
        engine_of(cfg, params, serving=ServingOptimizationConfig(tp_degree=2))


def test_latent_pages_offload_and_restore_round_trip():
    kv = KVCacheConfig(num_layers=2, kv_heads=1, head_dim=128, planes=1,
                       page_size=4, num_pages=8, dtype=jnp.float32)
    cache = BlockedKVCache(kv)
    cache.data = jnp.asarray(np.random.default_rng(6).normal(
        size=kv.cache_shape()), jnp.float32)
    before = np.asarray(cache.data)
    pages = cache.reserve(3)
    blob = cache.offload_pages(pages)
    assert blob.shape == (2, 3, 1, 1, 4, 128)
    new_pages = cache.restore_pages(blob)
    np.testing.assert_array_equal(np.asarray(cache.data)[:, new_pages],
                                  before[:, np.asarray(pages)])


def test_prefix_match_round_trip_on_latent_pages():
    """A second request with the same long prompt attaches the first one's
    latent pages and decodes the same tokens as a cold engine."""
    cfg, params = family()
    prompt = prompts_of((3 * PAGE + 5,), seed=9)[0]
    outs, hits = [], []
    for caching in (True, False):
        sched = FastGenScheduler(engine_of(
            cfg, params,
            serving=ServingOptimizationConfig(prefix_caching=caching)))
        got = []
        for uid in (0, 1):
            sched.submit(uid, prompt, SamplingParams(max_new_tokens=4))
            got.append(sched.run_to_completion()[uid])
        outs.append(got)
        # a hit leaves only the unmatched suffix (5 tokens: Q bucket 8)
        # to prefill, over history: the paged form with Q > 1
        hits.append(any(k[1] == 8 and not k[3] for k in
                        sched._engine.compiled_keys(dispatched_only=True)))
        sched._engine.state_manager.check_invariants()
    assert outs[0][0] == outs[0][1] == outs[1][0] == outs[1][1]
    assert hits == [True, False]


# -- the family on the program's surfaces -------------------------------------

def test_implementation_for_pangu_ultra_moe():
    assert implementation_for("pangu_ultra_moe") is PanguUltraMoEInferenceModel
    assert supported_model_types()["pangu_ultra_moe"] == \
        "PanguUltraMoEInferenceModel"
    cfg, params = family()
    llama_like = dataclasses.replace(cfg, kv_lora_rank=0)
    with pytest.raises(AssertionError, match="latent-attention family"):
        PanguUltraMoEInferenceModel(llama_like, params)
    with pytest.raises(AssertionError, match="outside the router"):
        PanguUltraMoEInferenceModel(
            dataclasses.replace(cfg, experts_first=14), params)


def test_n_params_counts_the_share_held_here():
    cfg, params = family()
    gains = sum(int(np.prod(a.shape)) for path, a in
                jax.tree_util.tree_flatten_with_path(params)[0]
                if "scale" in jax.tree_util.keystr(path))
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert cfg.n_params() == total - gains
    # the published share: 4.92B parameters (ISSUE 27's arithmetic)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "pangu-ultra-moe-serve-5l-ep16.json")) as f:
        config = json.load(f)
    from benchmark import flops_pangu_moe
    from benchmark.builders.serve_pangu_moe import source_of
    big = pangu_moe_config(source_of(config, False))
    assert big.n_params() == flops_pangu_moe.total_params(config) \
        == 4_918_968_320
    assert big.latent_dim == 576 and mla.plane_width(576) == 640


def test_training_forward_refuses_the_block():
    from deepspeed_tpu.models.transformer import forward
    cfg, params = family()
    with pytest.raises(NotImplementedError, match="latent-attention"):
        forward(cfg, params, jnp.zeros((1, 8), jnp.int32))


def test_step_spans_carry_the_held_experts_counts():
    """Under telemetry the ``fastgen.step`` span of the step that drains a
    dispatch carries its counts and its tokens."""
    import deepspeed_tpu.telemetry as telemetry
    from deepspeed_tpu.telemetry import get_tracer
    cfg, params = family()
    sched = FastGenScheduler(engine_of(cfg, params))
    prompts = prompts_of((21, 40))
    telemetry.set_enabled(True)
    try:
        mark = len(get_tracer().records())
        for uid, p in enumerate(prompts):
            sched.submit(uid, p, SamplingParams(max_new_tokens=4))
        sched.run_to_completion()
        spans = [r[5] for r in get_tracer().records()[mark:]
                 if r[0] == "fastgen.step" and r[5]
                 and "moe_pairs_here" in r[5]]
    finally:
        telemetry.set_enabled(False)
    assert spans
    first = spans[0]                      # the prefill of both prompts
    assert first["moe_tokens"] == 61
    sizes = reference.sizes_of(cfg)
    want = sum(int(reference.forward(params, jnp.asarray(p), sizes)[1].sum())
               for p in prompts)
    assert first["moe_pairs_here"] == want
    assert 0 < first["moe_expert_load_max"] <= want
    assert 0 < first["moe_experts_touched"] <= 2 * 4


def test_every_choice_held_here_fits_the_bound():
    """Every token's every choice held here (the most the layer can be
    sent, many times the even share): the padded row layout holds every
    pair, and the layer gives the dense form's result."""
    rng = np.random.default_rng(7)
    T, e, F, k = 40, 64, 32, 4
    x = jnp.asarray(rng.normal(size=(T, e)), jnp.float32)
    experts = jnp.asarray(np.stack([rng.permutation(4) for _ in range(T)]),
                          jnp.int32) + 8
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, k)), jnp.float32)
    params = {n: jnp.asarray(rng.normal(size=(4, F, e)) / 8, jnp.float32)
              for n in ("wg", "wu", "wd")}
    want = held.dense_held_reference(x, experts, weights, params, 8)
    for interpret in (False, True):
        got, counts = held.held_experts_ffn(x, experts, weights, params, 8,
                                            interpret=interpret)
        assert int(counts.sum()) == T * k
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
