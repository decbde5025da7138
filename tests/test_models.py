"""Model tests: forward shape/dtype, training convergence with ZeRO+TP+SP
shardings over the 8-device mesh."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as dst
from deepspeed_tpu.models.llama import LlamaForCausalLM, llama_config
from deepspeed_tpu.models.gpt import GPTForCausalLM
from deepspeed_tpu.models.bert import BertForMaskedLM
from deepspeed_tpu.models.transformer import forward, init_params


def lm_batch(bs, seq, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, size=(bs, seq)).astype(np.int32)}


class TestForward:
    def test_llama_logits_shape(self, rng):
        model = LlamaForCausalLM("debug")
        params = model.init_params(rng)
        batch = lm_batch(2, 16, model.cfg.vocab_size)
        logits = model.logits(params, batch)
        assert logits.shape == (2, 16, model.cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_causal_masking(self, rng):
        """Changing a future token must not change past logits."""
        model = LlamaForCausalLM("debug")
        params = model.init_params(rng)
        b1 = lm_batch(1, 16, model.cfg.vocab_size, seed=1)
        b2 = {"input_ids": b1["input_ids"].copy()}
        b2["input_ids"][0, -1] = (b2["input_ids"][0, -1] + 1) % model.cfg.vocab_size
        l1 = np.asarray(model.logits(params, b1))
        l2 = np.asarray(model.logits(params, b2))
        np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=1e-5)
        assert not np.allclose(l1[0, -1], l2[0, -1])

    def test_bert_not_causal(self, rng):
        model = BertForMaskedLM("debug")
        params = model.init_params(rng)
        b1 = lm_batch(1, 16, model.cfg.vocab_size, seed=1)
        b2 = {"input_ids": b1["input_ids"].copy()}
        b2["input_ids"][0, -1] = (b2["input_ids"][0, -1] + 1) % model.cfg.vocab_size
        l1 = np.asarray(model.logits(params, b1))
        l2 = np.asarray(model.logits(params, b2))
        # bidirectional: early positions DO see the change
        assert not np.allclose(l1[0, 0], l2[0, 0])

    def test_scan_matches_unrolled(self, rng):
        cfg_scan = llama_config("debug", scan_layers=True)
        cfg_loop = llama_config("debug", scan_layers=False)
        p_scan = init_params(cfg_scan, rng)
        # restack scanned params into per-layer for the loop variant
        from flax.core import meta
        p_loop = jax.tree.map(lambda x: x, p_scan,
                              is_leaf=lambda x: isinstance(x, meta.Partitioned))
        unboxed = meta.unbox(p_scan)
        loop_layers = {
            f"layer_{i}": jax.tree.map(lambda x: x[i], unboxed["layers"])
            for i in range(cfg_loop.num_layers)}
        p2 = dict(unboxed)
        p2["layers"] = loop_layers
        ids = lm_batch(2, 8, cfg_scan.vocab_size)["input_ids"]
        out_scan = forward(cfg_scan, unboxed, ids)
        out_loop = forward(cfg_loop, p2, ids)
        # bf16 compute: scan vs unrolled layer order changes rounding; a
        # handful of logits can land just past 2e-2 (r3 shipped 0.0215).
        np.testing.assert_allclose(np.asarray(out_scan), np.asarray(out_loop),
                                   atol=4e-2, rtol=1e-2)


def _train(model, config, steps=6, seq=16, seed0=0):
    engine, _, _, _ = dst.initialize(model=model, config=config)
    bs = engine.train_batch_size()
    losses = []
    for s in range(steps):
        rng = np.random.default_rng(42)  # same data every step -> memorization
        batch = {"input_ids": rng.integers(
            0, model.cfg.vocab_size, size=(bs, seq)).astype(np.int32)}
        losses.append(engine.train_batch(batch))
    return engine, losses


TRAIN_CFG = {
    "train_micro_batch_size_per_gpu": 1,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
    "gradient_clipping": 1.0,
    "steps_per_print": 1000,
}


class TestTraining:
    @pytest.mark.parametrize("stage", [0, 3])
    def test_llama_zero_trains(self, stage):
        cfg = dict(TRAIN_CFG, zero_optimization={
            "stage": stage, "stage3_param_persistence_threshold": 4096})
        engine, losses = _train(LlamaForCausalLM("debug"), cfg)
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]

    def test_llama_tp_sp_mesh(self):
        """TP=2 x SP=2 x fsdp=2: full 3D sharding trains and matches the
        data-parallel-only loss trajectory."""
        cfg = dict(TRAIN_CFG, zero_optimization={"stage": 3},
                   tensor_parallel={"enabled": True, "tp_size": 2},
                   sequence_parallel={"enabled": True, "sp_size": 2},
                   tpu={"mesh": {"tensor": 2, "seq": 2, "fsdp": 2}})
        engine, losses = _train(LlamaForCausalLM("debug"), cfg)
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]

        # reference: pure DP on 2 devices -> same global batch of 2
        from deepspeed_tpu.parallel.topology import MeshTopology, TopologyConfig
        topo2 = MeshTopology(TopologyConfig(data=2), devices=jax.devices()[:2])
        engine0, _, _, _ = dst.initialize(
            model=LlamaForCausalLM("debug"),
            config=dict(TRAIN_CFG, zero_optimization={"stage": 0}),
            topology=topo2)
        losses0 = []
        for s in range(6):
            rng2 = np.random.default_rng(42)
            batch = {"input_ids": rng2.integers(
                0, 128, size=(engine0.train_batch_size(), 16)).astype(np.int32)}
            losses0.append(engine0.train_batch(batch))
        np.testing.assert_allclose(losses, losses0, rtol=5e-2)

    def test_gpt_trains(self):
        engine, losses = _train(GPTForCausalLM("debug"), dict(TRAIN_CFG))
        assert losses[-1] < losses[0]

    def test_bert_mlm_trains(self):
        model = BertForMaskedLM("debug")
        engine, _, _, _ = dst.initialize(model=model, config=dict(TRAIN_CFG))
        bs = engine.train_batch_size()
        rng = np.random.default_rng(0)
        ids = rng.integers(0, model.cfg.vocab_size, size=(bs, 16)).astype(np.int32)
        mask_pos = rng.random((bs, 16)) < 0.15
        labels = np.where(mask_pos, ids, -100).astype(np.int32)
        masked = np.where(mask_pos, 103, ids).astype(np.int32)
        batch = {"input_ids": masked, "labels": labels}
        losses = [engine.train_batch(batch) for _ in range(6)]
        assert losses[-1] < losses[0]


# what a layer's checkpoint keeps must not change the mathematics: every
# policy that saves NAMED values (and "auto" under a budget that buys the
# richest rung) against nothing_saveable, on every attention path that
# names them its own way
ATTENTION_PATHS = {
    "flash": dict(),                               # the jnp reference on CPU
    "flash_kernel": dict(),                        # the Pallas kernel, interpreted
    "einsum": dict(attention_impl="einsum"),
    "ring": dict(sp_mode="ring"),
    "unrolled": dict(scan_layers=False),
    "parallel_residual": dict(parallel_residual=True),
}


@functools.lru_cache(maxsize=None)
def _tiny_llama(scan_layers=True):
    from flax.core import meta
    m = LlamaForCausalLM("tiny", num_layers=2, scan_layers=scan_layers)
    return m.cfg, meta.unbox(m.init_params(jax.random.key(0)))


def _loss_and_grads(path, policy, monkeypatch):
    import contextlib
    import dataclasses
    import importlib
    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.models import transformer as T
    fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")
    base, params = _tiny_llama(
        ATTENTION_PATHS[path].get("scan_layers", True))
    cfg = dataclasses.replace(base, dtype=jnp.float32, remat_policy=policy,
                              **ATTENTION_PATHS[path])
    ids = np.arange(2 * 16, dtype=np.int32).reshape(2, 16) % cfg.vocab_size
    mesh = contextlib.nullcontext()
    if path == "ring":
        from deepspeed_tpu.parallel.topology import (MeshTopology,
                                                     TopologyConfig)
        mesh = MeshTopology(TopologyConfig(data=1, seq=2),
                            devices=jax.devices()[:2]).mesh
    with monkeypatch.context() as patch:
        if path == "flash_kernel":
            # trace what the chip traces: the kernel (interpreted here) and
            # the names of its own outputs
            patch.setattr(real_accelerator, "device_platform", lambda: "tpu")
            patch.setattr(fa, "flash_attention", functools.partial(
                fa.flash_attention, interpret=True))
        # a budget that buys every rung: what "auto" makes of it
        budget = T.RematBudget(budget_bytes=1 << 40)
        with mesh, (T.remat_budget(budget) if policy == "auto"
                    else contextlib.nullcontext()):
            l, g = jax.jit(jax.value_and_grad(
                lambda p: jnp.mean(forward(cfg, p, ids) ** 2)))(params)
    if policy == "auto":
        assert budget.policy == T.REMAT_RUNGS[-1] and budget.layer_bytes > 0
    return float(l), g


_REFERENCE = {}


@pytest.mark.parametrize("policy", ["save_attn_out", "save_attn",
                                    "save_attn_residual", "auto"])
@pytest.mark.parametrize("path", sorted(ATTENTION_PATHS))
def test_save_attn_out_remat_policy(path, policy, monkeypatch):
    """The policies that keep named values must trace on every attention
    path and match nothing_saveable's loss and gradients (remat changes
    scheduling, not math)."""
    if path not in _REFERENCE:
        _REFERENCE[path] = _loss_and_grads(path, "nothing_saveable",
                                           monkeypatch)
    l_ref, g_ref = _REFERENCE[path]
    l_new, g_new = _loss_and_grads(path, policy, monkeypatch)
    assert abs(l_ref - l_new) < 1e-5
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), g_ref, g_new)


MISTRAL_7B = dict(hidden_size=4096, intermediate_size=14336, num_heads=32,
                  num_kv_heads=8, num_layers=12, vocab_size=32000)


def test_remat_rule_is_a_pure_function_of_shapes_and_memory():
    """Mistral-7B widths at 8,192 tokens a device (the train cell): the
    rungs' bytes a layer, the choice by budget, and what overrides it."""
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.llama import llama_config
    cfg = llama_config("tiny", **MISTRAL_7B)
    assert cfg.remat_policy == "auto"
    rungs = T.remat_rung_bytes(cfg, 8192)
    assert [round(rungs[r] / 1e6) for r in T.REMAT_RUNGS] == [0, 169, 236]
    # heads shard over 'tensor', the residual after attention does not
    halves = T.remat_rung_bytes(cfg, 8192, tensor_shards=2)
    assert halves["save_attn"] * 2 == rungs["save_attn"]
    assert round(halves["save_attn_residual"] / 1e6) == 169 // 2 + 67 + 1

    # a budget just under a rung's bytes over all layers buys the rung
    # below, just over buys the rung; none buys today's
    for below, rung in zip(T.REMAT_RUNGS, T.REMAT_RUNGS[1:]):
        need = cfg.num_layers * rungs[rung]
        assert T.choose_remat_policy(cfg, 8192, need - 1) \
            == (below, rungs[below])
        assert T.choose_remat_policy(cfg, 8192, need) == (rung, rungs[rung])
    for none in (0, -5_000_000_000):
        assert T.choose_remat_policy(cfg, 8192, none) \
            == ("nothing_saveable", 0)

    # the budget is the limit less what the engine holds, what a step makes
    # of every parameter, the working set and the margin ...
    seen = dict(limit_bytes=16_900_000_000, state_bytes=8_640_000_000,
                params_bytes=1_440_000_000, grads_bytes=1_440_000_000)
    work = T.remat_working_set(cfg, 8192, seen["grads_bytes"])
    budget = T.RematBudget(**seen)
    assert budget.choose(cfg, 8192) in T.REMAT_RUNGS
    assert budget.budget_bytes == 16_900_000_000 - 8_640_000_000 \
        - 1_440_000_000 - work - T.REMAT_MARGIN_BYTES
    assert (budget.policy, budget.layer_bytes) == T.choose_remat_policy(
        cfg, 8192, budget.budget_bytes)
    # ... which holds the layers' inputs and grows with the tokens
    assert work > cfg.num_layers * 8192 * 4096 * 2 + seen["grads_bytes"]
    assert T.remat_working_set(cfg, 16384, seen["grads_bytes"]) > work
    # no limit (the CPU): nothing is reckoned, today's policy
    blind = T.RematBudget(state_bytes=1)
    assert blind.choose(cfg, 8192) == "nothing_saveable"
    assert (blind.layer_bytes, blind.budget_bytes) == (0, 0)


@pytest.mark.parametrize("configured", ["nothing_saveable", "save_attn_out",
                                        "dots_saveable"])
def test_a_configured_remat_policy_is_never_overridden(configured):
    """Under a budget that buys every rung, a policy somebody wrote down
    stays: the budget is not even asked."""
    import dataclasses
    from deepspeed_tpu.models import transformer as T
    base, params = _tiny_llama()
    cfg = dataclasses.replace(base, remat_policy=configured)
    ids = np.zeros((2, 16), np.int32)
    budget = T.RematBudget(budget_bytes=1 << 40)
    with T.remat_budget(budget):
        jax.eval_shape(jax.grad(lambda p: jnp.mean(forward(cfg, p, ids))),
                       params)
        assert budget.policy is None
        jax.eval_shape(jax.grad(lambda p: jnp.mean(forward(
            dataclasses.replace(cfg, remat_policy="auto"), p, ids))), params)
    assert budget.policy == "save_attn_residual"


def test_learned_positions_ignore_padding():
    """Right-padded batch + attention_mask must produce the same logits
    on real tokens as the unpadded run: learned positions are derived
    from the mask (HF OPTLearnedPositionalEmbedding cumsum semantics),
    not raw sequence offsets.  Also covers left padding, where arange
    positions would be maximally wrong."""
    model = GPTForCausalLM("debug", max_seq_len=32)
    from flax.core import meta
    params = meta.unbox(model.init_params(jax.random.key(0)))
    rng = np.random.default_rng(3)
    real = rng.integers(0, model.cfg.vocab_size, size=(1, 8)).astype(np.int32)

    ref = np.asarray(forward(model.cfg, params, jnp.asarray(real)))

    pad = np.zeros((1, 4), np.int32)
    right = {"ids": np.concatenate([real, pad], 1),
             "mask": np.concatenate([np.ones((1, 8)), np.zeros((1, 4))], 1),
             "sel": slice(0, 8)}
    left = {"ids": np.concatenate([pad, real], 1),
            "mask": np.concatenate([np.zeros((1, 4)), np.ones((1, 8))], 1),
            "sel": slice(4, 12)}
    for case in (right, left):
        out = np.asarray(forward(
            model.cfg, params, jnp.asarray(case["ids"]),
            attention_mask=jnp.asarray(case["mask"].astype(np.int32))))
        np.testing.assert_allclose(out[0, case["sel"]], ref[0], atol=2e-2,
                                   rtol=2e-2)


def test_ring_sp_mode_matches_ulysses():
    """sequence_parallel.mode='ring' trains with context parallelism
    (K/V on the ppermute ring) and must match the Ulysses mode loss for
    loss on the same mesh/model/data."""
    from deepspeed_tpu.parallel.topology import MeshTopology, TopologyConfig

    def run(mode):
        model = LlamaForCausalLM("debug", num_heads=4, num_kv_heads=2,
                                 max_seq_len=32)
        topo = MeshTopology(TopologyConfig(data=2, seq=4))
        cfg = {
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "sequence_parallel": {"enabled": True, "sp_size": 4,
                                  "mode": mode},
            "steps_per_print": 1000,
        }
        engine, _, _, _ = dst.initialize(model=model, config=cfg,
                                         topology=topo)
        if mode == "ring":
            assert model.cfg.sp_mode == "ring"
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(
            0, model.cfg.vocab_size,
            size=(engine.train_batch_size(), 32)).astype(np.int32)}
        return [float(engine.train_batch(batch)) for _ in range(3)]

    ring = run("ring")
    uly = run("ulysses")
    np.testing.assert_allclose(ring, uly, rtol=2e-3)
