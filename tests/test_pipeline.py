"""Pipeline parallelism tests (reference tests/unit/runtime/pipe/).

Schedule unit tests mirror the reference topology/schedule tests; the
engine tests check the XLA pipelined executor computes the SAME loss and
gradients as a non-pipelined run of the identical model — the property the
reference asserts via pipeline-vs-dense convergence tests
(tests/unit/runtime/pipe/test_pipe.py)."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.runtime.pipe import (BackwardPass, ForwardPass,
                                        InferenceSchedule, LoadMicroBatch,
                                        OptimizerStep, PipelineEngine,
                                        PipelineModule, LayerSpec,
                                        RecvActivation, RecvGrad, ReduceGrads,
                                        SendActivation, SendGrad,
                                        TrainSchedule, gpipe_spmd,
                                        stack_stages)
from deepspeed_tpu.models.llama import LlamaForCausalLM


# ---------------------------------------------------------------------------
# schedule ISA
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("micro,stages", [(4, 2), (8, 4), (2, 4), (1, 3)])
def test_train_schedule_completeness(micro, stages):
    """Every stage forwards and backwards each micro-batch exactly once,
    backward i never precedes forward i, and the tail reduces + steps."""
    for sid in range(stages):
        sched = TrainSchedule(micro, stages, sid)
        fwd, bwd = [], []
        saw_step = False
        for cmds in sched:
            for c in cmds:
                if isinstance(c, ForwardPass):
                    fwd.append(c.micro_batch_id)
                elif isinstance(c, BackwardPass):
                    assert c.micro_batch_id in fwd
                    bwd.append(c.micro_batch_id)
                elif isinstance(c, OptimizerStep):
                    saw_step = True
        assert sorted(fwd) == list(range(micro))
        assert sorted(bwd) == list(range(micro))
        assert saw_step


@pytest.mark.parametrize("micro,stages", [(8, 4), (4, 2)])
def test_train_schedule_1f1b_memory_bound(micro, stages):
    """In-flight forwards (fwd issued - bwd retired) never exceed the 1F1B
    bound S - stage_id (reference TrainSchedule property)."""
    for sid in range(stages):
        in_flight = 0
        peak = 0
        for cmds in TrainSchedule(micro, stages, sid):
            for c in cmds:
                if isinstance(c, ForwardPass):
                    in_flight += 1
                elif isinstance(c, BackwardPass):
                    in_flight -= 1
                peak = max(peak, in_flight)
        assert peak <= stages - sid, f"stage {sid}: peak {peak}"


def test_train_schedule_p2p_matching():
    """Stage s's SendActivation count equals stage s+1's RecvActivation
    count (and grads in reverse)."""
    micro, stages = 6, 3
    counts = []
    for sid in range(stages):
        c = collections.Counter()
        for cmds in TrainSchedule(micro, stages, sid):
            for cmd in cmds:
                c[type(cmd).__name__] += 1
        counts.append(c)
    for s in range(stages - 1):
        assert counts[s]["SendActivation"] == counts[s + 1]["RecvActivation"] == micro
        assert counts[s]["RecvGrad"] == counts[s + 1]["SendGrad"] == micro
    assert counts[0]["LoadMicroBatch"] == micro
    assert counts[stages - 1]["SendActivation"] == 0


def test_inference_schedule():
    micro, stages = 4, 3
    for sid in range(stages):
        fwd = [c.micro_batch_id
               for cmds in InferenceSchedule(micro, stages, sid)
               for c in cmds if isinstance(c, ForwardPass)]
        assert fwd == list(range(micro))


# ---------------------------------------------------------------------------
# gpipe_spmd numerics
# ---------------------------------------------------------------------------

def _mk_mesh(pipe, data=1):
    from deepspeed_tpu.parallel.topology import MeshTopology, TopologyConfig
    topo = MeshTopology(TopologyConfig(pipe=pipe, data=data, fsdp=1),
                        devices=jax.devices()[:pipe * data])
    return topo.mesh


@pytest.mark.parametrize("pipe", [2, 4])
def test_gpipe_matches_sequential(pipe):
    """Pipelined linear-stack forward == sequential application, and the
    gradients agree with plain jax.grad of the sequential model."""
    L, M, mb, d = 8, 4, 2, 16
    key = jax.random.key(0)
    ws = jax.random.normal(key, (L, d, d)) * 0.3
    x = jax.random.normal(jax.random.key(1), (M, mb, d))

    def stage_fn(sp, act, consts, mb_id):
        def layer(c, w):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(layer, act, sp)
        return out

    def seq_loss(ws, x):
        def layer(c, w):
            return jnp.tanh(c @ w), None
        flat = x.reshape(M * mb, d)
        out, _ = jax.lax.scan(layer, flat, ws)
        return (out ** 2).mean()

    mesh = _mk_mesh(pipe)
    stages_ws = ws.reshape(pipe, L // pipe, d, d)

    def pipe_loss(stages_ws, x):
        out = gpipe_spmd(mesh, pipe, stage_fn, stages_ws, x)
        return (out ** 2).mean()

    with jax.set_mesh(mesh):
        pl, pg = jax.jit(jax.value_and_grad(pipe_loss))(stages_ws, x)
    sl, sg = jax.value_and_grad(seq_loss)(ws, x)
    np.testing.assert_allclose(float(pl), float(sl), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(pg).reshape(L, d, d),
                               np.asarray(sg), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# PipelineEngine end-to-end
# ---------------------------------------------------------------------------

CFG = {
    "train_batch_size": 8,
    "train_micro_batch_size_per_gpu": 1,
    "gradient_accumulation_steps": 4,
    "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
    "bf16": {"enabled": True},
    "zero_optimization": {"stage": 0},
}


def _tiny_llama():
    m = LlamaForCausalLM("tiny")
    import dataclasses
    # 4 layers so it splits into 2 stages x 2 layers
    m.cfg = dataclasses.replace(m.cfg, num_layers=4, dtype=jnp.float32,
                                remat=False)
    return m


def _batch(M=4, b=2, s=16, vocab=256):
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, size=(M, b, s)).astype(np.int32)
    return {"input_ids": ids}


def test_pipeline_engine_matches_dense():
    """PipelineEngine (pipe=2) loss == plain forward loss on the same
    params, and one train step moves the loss down."""
    model = _tiny_llama()
    cfg = dict(CFG)
    cfg["train_batch_size"] = 16
    cfg["tpu"] = {"mesh": {"pipe": 2, "data": 4}}
    eng = PipelineEngine(model=model, config=cfg)

    batch = _batch(M=4, b=4, s=16, vocab=model.cfg.vocab_size)
    flat_ids = batch["input_ids"].reshape(16, 16)

    # reference loss with unstacked params on a single device
    stages_params = jax.device_get(eng.state.params)
    params = jax.tree.map(lambda x: np.asarray(x), stages_params)
    # merge [S, L/S, ...] back to [L, ...] for the dense forward
    merged = dict(params)
    merged["layers"] = jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), params["layers"])
    dense_loss = float(model.loss(merged, {"input_ids": flat_ids}))

    pipe_loss = eng.train_batch(
        batch={"input_ids": flat_ids})
    np.testing.assert_allclose(pipe_loss, dense_loss, rtol=2e-3)

    for _ in range(3):
        last = eng.train_batch(batch={"input_ids": flat_ids})
    assert last < dense_loss


def test_pipeline_engine_with_zero_and_data():
    """PP=2 x data=2 x fsdp=2 composes; loss decreases."""
    model = _tiny_llama()
    cfg = dict(CFG)
    cfg["train_batch_size"] = 16
    cfg["zero_optimization"] = {"stage": 1}
    cfg["tpu"] = {"mesh": {"pipe": 2, "data": 2, "fsdp": 2}}
    eng = PipelineEngine(model=model, config=cfg)
    ids = _batch(M=4, b=4, s=16, vocab=model.cfg.vocab_size)["input_ids"]
    flat = ids.reshape(16, 16)
    first = eng.train_batch(batch={"input_ids": flat})
    for _ in range(3):
        last = eng.train_batch(batch={"input_ids": flat})
    assert last < first


def test_pipelined_module_generic():
    """Homogeneous PipelineModule path (LayerSpec API parity)."""
    d = 16

    class Tanh:
        def __init__(self, dim):
            self.dim = dim

        def init_params(self, rng):
            return {"w": jax.random.normal(rng, (self.dim, self.dim)) * 0.3}

        def __call__(self, p, x):
            return jnp.tanh(x @ p["w"])

    mod = PipelineModule(
        layers=[LayerSpec(Tanh, d) for _ in range(4)],
        loss_fn=lambda out, y: ((out - y) ** 2).mean(),
        partition_method="uniform")
    cfg = dict(CFG)
    cfg["gradient_accumulation_steps"] = 2
    cfg["tpu"] = {"mesh": {"pipe": 2, "data": 4}}
    eng = PipelineEngine(model=mod, config=cfg)
    rng = np.random.RandomState(0)
    batch = {"x": rng.randn(8, d).astype(np.float32),
             "y": rng.randn(8, d).astype(np.float32)}
    first = eng.train_batch(batch=batch)
    for _ in range(10):
        last = eng.train_batch(batch=batch)
    assert last < first


def test_pipeline_respects_per_microbatch_mask():
    """Padding that differs across micro-batches must give the same loss as
    the dense model (regression: mask/positions were taken from mb 0)."""
    model = _tiny_llama()
    cfg = dict(CFG)
    cfg["train_batch_size"] = 16
    cfg["tpu"] = {"mesh": {"pipe": 2, "data": 4}}
    eng = PipelineEngine(model=model, config=cfg)

    rng = np.random.RandomState(1)
    ids = rng.randint(0, model.cfg.vocab_size, size=(16, 16)).astype(np.int32)
    attn = np.ones((16, 16), np.int32)
    # ragged padding: row i keeps 6 + (i % 10) tokens — differs per micro-batch
    for i in range(16):
        attn[i, 6 + (i % 10):] = 0
    dense_params = jax.tree.map(np.asarray, jax.device_get(eng.state.params))
    merged = dict(dense_params)
    merged["layers"] = jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), dense_params["layers"])
    dense = float(model.loss(merged, {"input_ids": ids, "attention_mask": attn}))
    pipe = eng.train_batch(batch={"input_ids": ids, "attention_mask": attn})
    np.testing.assert_allclose(pipe, dense, rtol=2e-3)


def test_stack_stages_shapes():
    model = _tiny_llama()
    boxed = model.init_params(jax.random.key(0))
    stacked = stack_stages(boxed, 2)
    leaf = stacked["layers"]["attn"]["wq"]
    assert leaf.names[0] == "stages"
    assert leaf.value.shape[0] == 2
    assert leaf.value.shape[1] == 2  # 4 layers / 2 stages


def test_1f1b_schedule_uses_less_memory_than_gpipe():
    """The memory claim, MEASURED: compiled temp-buffer size of the 1f1b
    (loss-fused, no [M] output buffer) schedule must be below the gpipe
    (stack-all-outputs) schedule for the same model/config."""
    import deepspeed_tpu as dst
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    def peak_temp(schedule):
        model = LlamaForCausalLM("debug", num_heads=4, num_kv_heads=2,
                                 max_seq_len=64)
        cfg = {
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "pipeline": {"schedule": schedule},
            "tpu": {"mesh": {"pipe": 2, "data": 4}},
            "steps_per_print": 1000,
        }
        from deepspeed_tpu.runtime.pipe import PipelineEngine
        eng = PipelineEngine(model=model, config=cfg)
        bs = eng.train_batch_size()
        batch = {"input_ids": np.zeros((bs, 64), np.int32)}
        shaped = eng._shape_batch(batch)
        placed = jax.tree.map(jnp.asarray, shaped)
        with eng.topology.mesh:
            lowered = eng._train_step.lower(
                eng.state, placed, jax.random.key(0))
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        return float(mem.temp_size_in_bytes)

    t_1f1b = peak_temp("1f1b")
    t_gpipe = peak_temp("gpipe")
    assert t_1f1b < t_gpipe, (t_1f1b, t_gpipe)


def test_pipeline_1f1b_matches_gpipe_loss():
    """Both schedules compute the same loss (weighted per-micro-batch CE
    accumulation == flat mean)."""
    import deepspeed_tpu as dst
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.runtime.pipe import PipelineEngine

    losses = {}
    for schedule in ("1f1b", "gpipe"):
        model = LlamaForCausalLM("debug", num_heads=4, num_kv_heads=2,
                                 max_seq_len=32)
        cfg = {
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 4,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "pipeline": {"schedule": schedule},
            "tpu": {"mesh": {"pipe": 2, "data": 2, "fsdp": 2}},
            "steps_per_print": 1000,
        }
        eng = PipelineEngine(model=model, config=cfg)
        rng = np.random.default_rng(3)
        batch = {"input_ids": rng.integers(
            0, 128, size=(eng.train_batch_size(), 32)).astype(np.int32)}
        losses[schedule] = [eng.train_batch(batch) for _ in range(3)]
    np.testing.assert_allclose(losses["1f1b"], losses["gpipe"], rtol=2e-3)


def test_pipeline_engine_matches_dense_alibi():
    """BLOOM-style features (ALiBi + post-embedding norm) through the
    pipeline == dense forward loss on the same params (regression: the
    pipeline embed/stage path silently ignored both)."""
    import dataclasses
    model = _tiny_llama()
    model.cfg = dataclasses.replace(model.cfg, pos_emb="alibi",
                                    embed_layernorm=True)
    cfg = dict(CFG)
    cfg["train_batch_size"] = 16
    cfg["tpu"] = {"mesh": {"pipe": 2, "data": 4}}
    eng = PipelineEngine(model=model, config=cfg)

    batch = _batch(M=4, b=4, s=16, vocab=model.cfg.vocab_size)
    flat_ids = batch["input_ids"].reshape(16, 16)

    stages_params = jax.device_get(eng.state.params)
    params = jax.tree.map(lambda x: np.asarray(x), stages_params)
    merged = dict(params)
    merged["layers"] = jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), params["layers"])
    dense_loss = float(model.loss(merged, {"input_ids": flat_ids}))

    pipe_loss = eng.train_batch(batch={"input_ids": flat_ids})
    np.testing.assert_allclose(pipe_loss, dense_loss, rtol=2e-3)


def test_pipeline_moe_matches_dense():
    """Mixtral (MoE) through the pipeline: the gating aux loss threads
    the carry, and the pipeline loss equals the dense per-micro-batch
    mean (regression: MoE under PipelineEngine raised
    NotImplementedError)."""
    import dataclasses
    from deepspeed_tpu.models.mixtral import MixtralForCausalLM
    model = MixtralForCausalLM("debug", num_experts=2, top_k=1)
    model.cfg = dataclasses.replace(model.cfg, dtype=jnp.float32,
                                    remat=False)
    cfg = dict(CFG)
    cfg["train_batch_size"] = 16
    cfg["tpu"] = {"mesh": {"pipe": 2, "data": 4}}
    eng = PipelineEngine(model=model, config=cfg)

    M, b, s = 4, 4, 16
    batch = _batch(M=M, b=b, s=s, vocab=model.cfg.vocab_size)
    flat_ids = batch["input_ids"].reshape(M * b, s)

    stages_params = jax.device_get(eng.state.params)
    params = jax.tree.map(lambda x: np.asarray(x), stages_params)
    merged = dict(params)
    merged["layers"] = jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), params["layers"])
    # dense reference with the PIPELINE's loss convention: mean of
    # per-micro-batch losses (each = ce + aux for that forward)
    per_mb = [float(model.loss(merged,
                               {"input_ids": batch["input_ids"][m]}))
              for m in range(M)]
    dense_loss = float(np.mean(per_mb))

    pipe_loss = eng.train_batch(batch={"input_ids": flat_ids})
    np.testing.assert_allclose(pipe_loss, dense_loss, rtol=2e-3)

    for _ in range(3):
        last = eng.train_batch(batch={"input_ids": flat_ids})
    assert last < pipe_loss
