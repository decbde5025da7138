"""The train step seen from inside (ISSUE 37): the named scopes of the one
step program, the table the program exports from its own compiled text,
and the host spans of ``train_batch``.  CPU, a debug Llama (2 layers under
a scan), ZeRO-3 in bf16 over 4 virtual devices."""

import collections

import jax
import numpy as np
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu import telemetry
from deepspeed_tpu.models.llama import LlamaForCausalLM
from deepspeed_tpu.models.transformer import MODULE_SCOPES
from deepspeed_tpu.parallel.topology import MeshTopology, TopologyConfig
from deepspeed_tpu.runtime.engine import TRAIN_SCOPES
from deepspeed_tpu.telemetry import get_tracer, program_scopes, tracer
from deepspeed_tpu.telemetry.tracer import _NULL_SPAN


@pytest.fixture(autouse=True)
def _telemetry_hygiene():
    telemetry.disable()
    get_tracer().clear()
    tracer._PROGRAMS.clear()
    yield
    telemetry.disable()
    get_tracer().clear()
    tracer._PROGRAMS.clear()


def classify(op_name):
    return program_scopes.classify(op_name, TRAIN_SCOPES, MODULE_SCOPES)


def scope_table(text):
    return program_scopes.scope_table(text, TRAIN_SCOPES, MODULE_SCOPES)


def engine_of(**model):
    engine, _, _, _ = dst.initialize(
        model=LlamaForCausalLM("debug", **model),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3},
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "checkpoint": {"async_save": False}},
        topology=MeshTopology(TopologyConfig(data=1, fsdp=4),
                              devices=jax.devices()[:4]))
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 128, (engine.train_batch_size(), 64), dtype=np.int32)}
    return engine, batch


def instructions_of(engine, batch):
    """``{instruction: (opcode, phase, module)}`` of what can leave an
    event, and the table itself."""
    text = engine.lower_train_step(batch).compile().as_text()
    comps, _ = program_scopes._computations(text)
    opcodes = {name: opcode for body in comps.values()
               for name, opcode, *_ in body}
    table = scope_table(text)
    return {name: (opcodes[name], *scope)
            for name, scope in table["instructions"].items()}, table


# ---------------------------------------------------------------------------
# (a), (b): the scopes are in the compiled program
# ---------------------------------------------------------------------------

def recomputed_matmuls(ins):
    """{module: matmuls of the recomputed forward}"""
    return collections.Counter(m for op, p, m in ins.values()
                               if p == "recompute"
                               and op in ("dot", "convolution"))


@pytest.mark.parametrize("policy, recomputes", [
    ("nothing_saveable", True), ("everything_saveable", False),
    # the rungs of "auto" (PR 38): on the CPU attention is the jnp
    # reference, which has no log-sum-exp to keep, so the scores are
    # recomputed; the compile for the described chip
    # (tests/test_chip_compile.py) holds the kernel's side
    ("save_attn", True), ("save_attn_residual", True)])
def test_every_phase_and_module_has_instructions(policy, recomputes):
    ins, table = instructions_of(*engine_of(remat_policy=policy))
    assert not table["stale"]
    if policy.startswith("save_attn"):
        # q k v and the output are kept: of attention's eight matmuls (the
        # four projections, the scores' two, and since PR 49 the rope's
        # pair swap of q and of k, a product with a 0/1 permutation) the
        # recomputed forward runs the scores and, on rung 1, the output
        # projection: q and k are kept AFTER the rope and are not swapped
        # again; the MLP's gate and up as under nothing_saveable
        base = recomputed_matmuls(instructions_of(
            *engine_of(remat_policy="nothing_saveable"))[0])
        kept = recomputed_matmuls(ins)
        assert base["attn"] == 8 and base["mlp"] == kept["mlp"] == 2
        assert kept["attn"] == {"save_attn": 2, "save_attn_residual": 1}[
            policy]
    phases = collections.Counter(p for _, p, _ in ins.values())
    modules = collections.Counter(m for _, _, m in ins.values())
    want = {"params", "forward", "backward", "grad_norm_clip", "optimizer"}
    assert want | ({"recompute"} if recomputes else set()) \
        <= {p for p, n in phases.items() if n}
    assert (phases["recompute"] > 0) == recomputes
    assert {"embed", "attn", "mlp", "head", "loss"} <= set(modules)
    # the scheduler is off: nothing of its phase
    assert phases["grad_reduce"] == 0
    # every matmul is somebody's
    dots = {n: v for n, v in ins.items() if v[0] in ("dot", "convolution")}
    assert dots and all(p in ("forward", "recompute", "backward")
                        and m != "none" for _, p, m in dots.values())
    # a recomputed matmul is told from the forward one it repeats
    if recomputes:
        assert {m for _, p, m in dots.values() if p == "recompute"} \
            == {"attn", "mlp"}
    # under a tenth of what can leave an event is nobody's
    loud = [v for v in ins.values()
            if v[0] not in program_scopes._SILENT
            and v[0] not in program_scopes.CONTAINERS]
    assert sum(p == "other" for _, p, _ in loud) < len(loud) / 10
    # the layer scan's two loops are listed as containers, and the step
    # opens with an instruction that can leave an event
    assert sum(ins[c][0] == "while" for c in table["containers"]) == 2
    assert table["entry_order"] and ins[table["entry_order"][0]][0] \
        not in program_scopes._SILENT


def test_remat_off_recomputes_nothing():
    ins, _ = instructions_of(*engine_of(remat=False))
    assert not any(p == "recompute" for _, p, _ in ins.values())


@pytest.mark.parametrize("configured, limit, chosen", [
    (None, 1 << 30, "save_attn_residual"),  # "auto" with room for a rung
    (None, 1 << 20, "nothing_saveable"),    # ... under a limit with none
    (None, 0, "nothing_saveable"),          # ... no limit reported: the CPU
    ("nothing_saveable", 1 << 30, "nothing_saveable"),
    ("dots_saveable", 1 << 30, "dots_saveable")])
def test_the_table_says_what_the_layers_checkpoint_keeps(
        monkeypatch, configured, limit, chosen):
    """The engine hands the model what it sees of a device's memory, the
    model picks a rung when the step is traced, and the step's table says
    which, with the bytes a layer and the budget; a configured policy is
    never overridden and nothing is reckoned for it."""
    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.models import transformer as T
    monkeypatch.setattr(type(get_accelerator()), "total_memory",
                        lambda self, index=None: limit)
    engine, batch = engine_of(
        **({"remat_policy": configured} if configured else {}))
    telemetry.enable()
    first = engine.train_batch(batch)
    table = engine.step_scope_table()
    assert table["remat_policy"] == chosen
    auto_with_room = configured is None and limit == 1 << 30
    if auto_with_room:
        # 2 rows x 64 tokens a device of the debug Llama
        cfg = engine.module.cfg
        assert cfg.remat_policy == "auto"
        assert table["remat_layer_bytes"] == T.remat_rung_bytes(
            cfg, 2 * 64)["save_attn_residual"]
        assert cfg.num_layers * table["remat_layer_bytes"] \
            <= table["remat_budget_bytes"] < limit
    else:
        assert table["remat_layer_bytes"] == 0
        assert (table["remat_budget_bytes"] == 0) == (
            configured is not None or not limit)
    assert any(p == "recompute" for p, _ in table["instructions"].values())
    # the same step, whatever is kept of it (to bf16's rounding: a kept
    # value is rounded where a fused one need not be)
    monkeypatch.setattr(type(get_accelerator()), "total_memory",
                        lambda self, index=None: 0)
    telemetry.disable()
    plain, _ = engine_of(remat_policy="nothing_saveable")
    assert first == pytest.approx(plain.train_batch(batch), rel=1e-3)


# ---------------------------------------------------------------------------
# (c): JAX's literal markers, pinned
# ---------------------------------------------------------------------------

STEP = "jit(step_fn)/train.fwd_bwd/"
SCAN = "while/body/closed_call/"

@pytest.mark.parametrize("op_name, want", [
    (STEP + "jvp()/" + SCAN + "mlp/bse,ef->bsf/dot_general",
     ("forward", "mlp")),
    (STEP + "transpose(jvp())/" + SCAN
     + "checkpoint/rematted_computation/mlp/dot_general",
     ("recompute", "mlp")),
    (STEP + "transpose(jvp())/" + SCAN + "checkpoint/attn/dot_general",
     ("backward", "attn")),
    # a scope entered outside the scan is folded into the marker
    (STEP + "jvp(loss)/reduce_sum", ("forward", "loss")),
    (STEP + "transpose(jvp(loss))/mul", ("backward", "loss")),
    (STEP + "transpose(jvp(head))/bse,ev->bsv/dot_general",
     ("backward", "head")),
    (STEP + "transpose(jvp(embed))/scatter-add", ("backward", "embed")),
    # the forward pass's own transpose is no marker
    (STEP + "jvp()/" + SCAN + "attn/transpose", ("forward", "attn")),
    # no marker under train.fwd_bwd: the gradients' cast and accumulation
    (STEP + "convert_element_type", ("backward", "none")),
    ("jit(step_fn)/train.params/convert_element_type", ("params", "none")),
    ("jit(step_fn)/train.grad_norm_clip/reduce_sum",
     ("grad_norm_clip", "none")),
    ("jit(step_fn)/train.optimizer/sub", ("optimizer", "none")),
    ("jit(step_fn)/train.optimizer/cond/branch_1_fun/mul",
     ("optimizer", "none")),
    ("jit(step_fn)/train.grad_reduce/shard_map/psum",
     ("grad_reduce", "none")),
    # a fused instruction's paths, joined: the scope most of them carry
    (STEP + "transpose(jvp(loss))/mul;" + STEP
     + "transpose(jvp(loss))/broadcast_in_dim;" + STEP + "jvp(loss)/sub",
     ("backward", "loss")),
    # a text without the program's scopes keeps JAX's markers
    ("jit(step_fn)/transpose(jvp())/" + SCAN
     + "checkpoint/rematted_computation/dot_general", ("recompute", "none")),
    ("jit(step_fn)/jvp()/" + SCAN + "dot_general", ("forward", "none")),
    ("jit(step_fn)/sub", ("other", "none")),
    ("state.params['layers']['mlp']['wo']", ("other", "none")),
    ("", ("other", "none")),
])
def test_classify_by_hand(op_name, want):
    assert classify(op_name) == want


# ---------------------------------------------------------------------------
# the table from a text worked by hand
# ---------------------------------------------------------------------------

HLO = """HloModule jit_step_fn, is_scheduled=true

%fused.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %neg.1 = f32[4]{0} negate(%p), metadata={op_name="jit(step_fn)/train.fwd_bwd/jvp()/while/body/closed_call/mlp/neg"}
  ROOT %exp.1 = f32[4]{0} exponential(%neg.1), metadata={op_name="jit(step_fn)/train.fwd_bwd/jvp()/while/body/closed_call/mlp/exp"}
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%body (c: (s32[], f32[4])) -> (s32[], f32[4]) {
  %c = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%c), index=1
  %copy.7 = f32[4]{0:T(8,128)S(1)} copy(%x)
  %fusion.1 = f32[4]{0} fusion(%copy.7), kind=kLoop, calls=%fused.1
  %dot.2 = f32[4]{0} dot(%fusion.1, %fusion.1), metadata={op_name="jit(step_fn)/train.fwd_bwd/jvp()/while/body/closed_call/attn/dot_general"}
  ROOT %t = (s32[], f32[4]{0}) tuple(%x, %dot.2)
}

%cond (c: (s32[], f32[4])) -> pred[] {
  %c.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0), metadata={op_name="state.params"}
  %zero = s32[] constant(0)
  %cast.1 = f32[4]{0} convert(%p0), metadata={op_name="jit(step_fn)/train.params/convert_element_type"}
  %init = (s32[], f32[4]{0}) tuple(%zero, %cast.1)
  %while.3 = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(step_fn)/train.fwd_bwd/jvp()/while"}
  %y = f32[4]{0} get-tuple-element(%while.3), index=1
  %custom-call.5 = f32[4]{0} custom-call(), custom_call_target="Mystery"
  ROOT %sub.4 = f32[4]{0} subtract(%p0, %y), metadata={op_name="jit(step_fn)/train.optimizer/sub"}
}
"""


def test_scope_table_by_hand():
    table = scope_table(HLO)
    ins = table["instructions"]
    assert not table["stale"]
    # a fusion with no name of its own takes its computation's; a bare
    # copy that of what it feeds; a reducer's body leaves no event
    assert ins["fusion.1"] == ["forward", "mlp"]
    assert ins["copy.7"] == ["forward", "mlp"]
    # ... and is marked: its scope is a neighbour's, no metadata's
    assert table["inherited"] == ["copy.7"]
    assert ins["dot.2"] == ["forward", "attn"]
    assert "add.9" not in ins and "exp.1" not in ins
    assert ins["cast.1"] == ["params", "none"]
    assert ins["sub.4"] == ["optimizer", "none"]
    # nothing names it, it reads nothing and nothing uses it
    assert ins["custom-call.5"] == ["other", "none"]
    assert table["containers"] == ["while.3"]
    assert ins["while.3"] == ["forward", "none"]
    # parameters, constants and tuples leave no event: the step opens
    # with the cast
    assert table["entry_order"] == ["cast.1", "while.3", "custom-call.5",
                                    "sub.4"]


def test_the_names_are_the_callers():
    """The module knows JAX's markers and nothing of this program: another
    program's scopes read the same text another way."""
    table = program_scopes.scope_table(
        HLO, {"train.params": "load", "train.optimizer": "store"}, ["attn"])
    ins = table["instructions"]
    assert not table["stale"]
    assert ins["cast.1"] == ["load", "none"]
    assert ins["sub.4"] == ["store", "none"]
    assert ins["dot.2"] == ["forward", "attn"]
    assert ins["fusion.1"] == ["forward", "none"]
    assert program_scopes.scope_table(HLO, {"eval.step": None}, [])["stale"]


def test_a_text_without_the_programs_scopes_is_stale():
    """(f) JAX's persistent cache leaves metadata out of its key, so a tree
    with the scopes can be handed an executable a tree without them
    cached: the table says so, and keeps what JAX's own markers tell."""
    text = HLO
    for scope in ("train.params/", "train.fwd_bwd/", "train.optimizer/",
                  "mlp/", "attn/"):
        text = text.replace(scope, "")
    table = scope_table(text)
    assert table["stale"]
    assert table["instructions"]["dot.2"] == ["forward", "none"]
    assert table["instructions"]["sub.4"] == ["other", "none"]


# ---------------------------------------------------------------------------
# (d), (e): train_batch's spans and what it publishes
# ---------------------------------------------------------------------------

def test_with_telemetry_off_nothing_is_registered_or_recorded(monkeypatch):
    engine, batch = engine_of()
    spans = []
    real = tracer.trace_span
    monkeypatch.setattr(
        "deepspeed_tpu.runtime.engine.trace_span",
        lambda *a: spans.append(real(*a)) or spans[-1])
    engine.train_batch(batch)
    engine.train_batch(batch)
    # every span site took the shared null span: nothing allocated
    assert spans and all(s is _NULL_SPAN for s in spans)
    assert get_tracer().records() == []
    assert telemetry.program_table("train.step") is None
    assert engine.step_scope_table() is None
    assert tracer._PROGRAMS == {}


def test_with_telemetry_on_the_step_has_its_tree_and_its_table(monkeypatch):
    engine, batch = engine_of()
    engine.train_batch(batch)                    # the compile, unrecorded
    lowered = []
    real = program_scopes.scope_table
    monkeypatch.setattr("deepspeed_tpu.runtime.engine.scope_table",
                        lambda *a: lowered.append(1) or real(*a))
    telemetry.enable()
    engine.train_batch(batch)
    engine.train_batch(batch)
    # nothing was compiled or parsed inside train_batch
    assert lowered == [] and list(tracer._PROGRAMS) == ["train.step"]

    recs = get_tracer().records()
    ids = {r[6]: r for r in recs}
    batches = [r for r in recs if r[0] == "train.batch"]
    assert len(batches) == 2
    for root in batches:
        assert root[7] is None
        kids = sorted((r for r in recs if r[7] == root[6]),
                      key=lambda r: r[1])
        assert [k[0] for k in kids] == ["train.place_batch", "train.step",
                                        "train.after_step"]
        step = kids[1]
        assert [k[0] for k in sorted((r for r in recs if r[7] == step[6]),
                                     key=lambda r: r[1])] \
            == ["train.step.dispatch", "train.step.wait"]
        # no attribute of its own (nothing would read one): only what the
        # tracer gives every span
        assert step[5] == root[5]
        # properly nested, and the root ends with the bookkeeping
        for r in recs:
            if r[7] in (root[6], step[6]):
                p = ids[r[7]]
                assert p[1] <= r[1] and r[1] + r[2] <= p[1] + p[2]
        assert kids[2][1] + kids[2][2] == pytest.approx(
            root[1] + root[2], abs=2e-3)

    # the table is evaluated by whoever reads, once per built step
    table = telemetry.program_table("train.step")
    assert lowered == [1] and not table["stale"]
    assert telemetry.program_table("train.step") is table
    assert lowered == [1]
    phases = {p for p, _ in table["instructions"].values()}
    assert {"params", "forward", "recompute", "backward", "grad_norm_clip",
            "optimizer"} <= phases
    # ... and what the layers' checkpoint keeps in this program (PR 38):
    # "auto" where the backend reports no memory limit is today's
    assert (table["remat_policy"], table["remat_layer_bytes"],
            table["remat_budget_bytes"]) == ("nothing_saveable", 0, 0)
    # reading it consumed none of the training's randomness and left the
    # state alone: the next step is the step it would have been
    assert np.isfinite(engine.train_batch(batch))
    # a rebuilt step is a new program: registered again, read again
    engine.set_lr(1e-4)
    engine.train_batch(batch)
    assert telemetry.program_table("train.step") is not table
    assert lowered == [1, 1]
