"""A step takes prompts only up to the ridge (ISSUE 53).

``FastGenScheduler._plan_step`` admits a pending request into a step that
streams its weights for fewer decode rows than the device's ridge only
while the step's padded tokens stay at or under the ridge; what it leaves
out rides the next step, which admits it whatever it costs.  The CPU has
no published peaks and so no ridge: the tests state one through the two
tables of ``profiling/flops_profiler.py``.

Held here: (a) the order of admission, (b) that a step past the ridge, a
step without a decode row and a device without an entry plan what the
parent commit planned (``data/ridge_parent_steps.json``, recorded there),
(c) that a held request holds nothing, (d) every request's tokens, (e) the
padded count, (f) the two counters, the benchmark's metric over them, and
the step programs the short cell forms under a ridge.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2 import FastGenScheduler, SamplingParams
from deepspeed_tpu.inference.v2.lattice import BucketLattice
from deepspeed_tpu.inference.v2.model import serving_tokens_at_ridge
from deepspeed_tpu.profiling import flops_profiler
from deepspeed_tpu.telemetry import get_tracer
from deepspeed_tpu.utils.comms_logging import serving_counters

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "tests", "data", "ridge_parent_steps.json")
VOCAB = 128


@pytest.fixture(autouse=True)
def _kv_debug(monkeypatch):
    # the page-accounting audit after every step
    monkeypatch.setenv("DS_KV_DEBUG", "1")


def state_ridge(monkeypatch, tokens):
    """The tables say the CPU's ridge is ``tokens`` for float32 weights
    (``peak * 4 / (2 * hbm)``); None takes the CPU's entries away."""
    for table, value in ((flops_profiler.PEAK_FLOPS, tokens),
                         (flops_profiler.HBM_BYTES_PER_S, 2.0)):
        if tokens is None:
            monkeypatch.delitem(table, "cpu", raising=False)
        else:
            monkeypatch.setitem(table, "cpu", float(value))


def tiny_engine(lattice=None, **kw):
    from test_fused_serving import _tiny_engine
    engine = _tiny_engine(**kw)
    if lattice is not None:
        engine._lattice = engine.model.lattice = lattice
    return engine


def prompt(n, seed):
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


def greedy(new):
    return SamplingParams(max_new_tokens=new, temperature=0.0)


def record_steps(engine):
    """Every dispatched step of ``engine`` as (uids, piece lengths, key),
    appended to the list that comes back."""
    log, real = [], engine.step_sample

    def step_sample(uids, tokens, *args, **kwargs):
        out = real(uids, tokens, *args, **kwargs)
        log.append([[int(u) for u in uids], [len(t) for t in tokens],
                    list(engine.model._last_key)])
        return out

    engine.step_sample = step_sample
    return log


def pending(sched):
    return [(r.uid, r.passed_over) for r in sched._pending]


# ---------------------------------------------------------------------------
# the ridge itself: computed from the tables and the weights, never stated
# ---------------------------------------------------------------------------

def test_the_ridge_comes_from_the_tables_and_the_weights(monkeypatch):
    import jax.numpy as jnp
    # keyed and sourced the same way: a row for every chip with a peak
    assert set(flops_profiler.HBM_BYTES_PER_S) == set(
        flops_profiler.PEAK_FLOPS)
    weights = {"w": jnp.zeros((8, 8), jnp.bfloat16),
               "norm": jnp.zeros((8,), jnp.float32)}
    assert serving_tokens_at_ridge(weights) is None     # the CPU: no entry
    for kind, want in (("TPU v5 lite", 240), ("TPU v5e", 240)):
        monkeypatch.setitem(flops_profiler.PEAK_FLOPS, "cpu",
                            flops_profiler.PEAK_FLOPS[kind])
        monkeypatch.setitem(flops_profiler.HBM_BYTES_PER_S, "cpu",
                            flops_profiler.HBM_BYTES_PER_S[kind])
        assert int(serving_tokens_at_ridge(weights)) == want
    # weights of one byte halve it; float32 doubles it
    assert int(serving_tokens_at_ridge(
        {"w": jnp.zeros((8, 8), jnp.int8)})) == 120
    assert int(serving_tokens_at_ridge(
        {"w": jnp.zeros((8, 8), jnp.float32)})) == 481
    # one table alone states no ridge, and DS_PEAK_FLOPS is not one
    monkeypatch.delitem(flops_profiler.HBM_BYTES_PER_S, "cpu")
    monkeypatch.setenv("DS_PEAK_FLOPS", "1e12")
    assert serving_tokens_at_ridge(weights) is None


def test_no_knob_states_the_ridge():
    """No constructor argument and no config key: the tables alone."""
    import dataclasses
    import inspect
    from deepspeed_tpu.inference.v2 import ServingOptimizationConfig
    assert list(inspect.signature(FastGenScheduler.__init__).parameters) \
        == ["self", "engine", "token_budget", "rng", "serving", "role"]
    assert not [f.name for f in dataclasses.fields(ServingOptimizationConfig)
                if "ridge" in f.name or "pass" in f.name]


# ---------------------------------------------------------------------------
# (a) one, then the two it held together; FIFO with a fourth in between
# ---------------------------------------------------------------------------

def test_three_pending_are_admitted_one_two_none(monkeypatch):
    state_ridge(monkeypatch, 24)
    engine = tiny_engine(num_pages=128, max_seqs=16)
    log = record_steps(engine)
    sched = FastGenScheduler(engine)
    assert sched._ridge == 24
    for uid in range(4):
        sched.submit(uid, prompt(10, uid), greedy(12))
    sched.step()
    # no decode row: the first step takes all four, as without a ridge
    assert log[-1][:2] == [[0, 1, 2, 3], [10] * 4]
    for uid in (4, 5, 6):
        sched.submit(uid, prompt(10, uid), greedy(12))
    sched.step()
    # 4 rows + one piece of 16 = 20 padded tokens; a second makes 36
    assert log[-1][:2] == [[0, 1, 2, 3, 4], [1, 1, 1, 1, 10]]
    assert pending(sched) == [(5, 1), (6, 1)]
    sched.submit(7, prompt(10, 7), greedy(12))
    sched.step()
    # the two it held, together and whatever they cost (8 + 2 x 16 = 40),
    # ahead of the one that came in between, which waits once
    assert log[-1][:2] == [[0, 1, 2, 3, 4, 5, 6], [1] * 5 + [10, 10]]
    assert pending(sched) == [(7, 1)]
    sched.step()
    assert log[-1][:2] == [list(range(8)), [1] * 7 + [10]]
    sched.step()
    assert log[-1][1] == [1] * 8 and not sched._pending
    out = sched.run_to_completion()
    assert sorted(out) == list(range(8)) and not sched.errors
    assert all(len(v) == 12 for v in out.values())


# ---------------------------------------------------------------------------
# (b) where the rule stands aside, the parent's steps
# ---------------------------------------------------------------------------

#: a lattice of few tops: the schedules are compared, not the bucket rule
FEW = dict(s_tops=(2, 8), q_tops=(8, 64), p_tops=(8,))


def closed_loop(engine, new_tokens, steps=200, clients=6, budget=48,
                seed=5):
    """``clients`` callers over a seeded supply of prompts of 3 to 90
    tokens (the budget chunks the long ones); returns what ``steps``
    steps dispatched."""
    log = record_steps(engine)
    sched = FastGenScheduler(engine, token_budget=budget)
    rng = np.random.default_rng(seed)
    uid = 0
    while len(log) < steps:
        while sched.backlog < clients:
            sched.submit(uid, prompt(int(rng.integers(3, 91)), 1000 + uid),
                         greedy(int(rng.choice(new_tokens))))
            uid += 1
        sched.step()
    assert not sched.errors
    return log[:steps], sched


LOOPS = {
    # decode rows beside prompt pieces, whole and chunked
    "loop": (1, 3, 6, 9, 14),
    # every request ends with its first token: no step has a decode row
    "first_token_only": (1,),
}


def record_parent_steps(path=RECORDED):
    """Write what THIS tree's scheduler plans (run at the parent commit:
    ``python3 -c 'import test_ridge_admission as t;
    t.record_parent_steps()'`` with the parent's package on the path)."""
    out = {}
    for name, new_tokens in LOOPS.items():
        engine = tiny_engine(BucketLattice(**FEW), num_pages=96)
        out[name] = closed_loop(engine, new_tokens)[0]
    with open(path, "w") as f:
        json.dump(out, f, separators=(",", ":"))


@pytest.mark.parametrize("case,loop,ridge", [
    ("no_table_entry", "loop", None),
    # bucket_s(1 or 2 rows) = 2: at the ridge; 8: past it
    ("decode_rows_at_or_past_the_ridge", "loop", 2),
    ("no_decode_row", "first_token_only", 1000),
])
def test_the_parents_steps_where_the_rule_stands_aside(
        monkeypatch, case, loop, ridge):
    with open(RECORDED) as f:
        want = json.load(f)[loop]
    state_ridge(monkeypatch, ridge)
    engine = tiny_engine(BucketLattice(**FEW), num_pages=96)
    held = serving_counters.prompts_held
    got, sched = closed_loop(engine, LOOPS[loop])
    assert sched._ridge == ridge
    assert len(want) == 200
    # the one has steps of decode rows alone, the other no decode row
    assert any(key[4] == "chain" for _, _, key in want) == (loop == "loop")
    assert got == want
    assert serving_counters.prompts_held == held


# ---------------------------------------------------------------------------
# (c) a request mid-prefill is never held; a held one holds nothing
# ---------------------------------------------------------------------------

def test_mid_prefill_is_continued_and_a_held_request_holds_nothing(
        monkeypatch):
    state_ridge(monkeypatch, 24)
    engine = tiny_engine(num_pages=128, max_seqs=16)
    log = record_steps(engine)
    sched = FastGenScheduler(engine, token_budget=64)
    state = engine.state_manager
    assert state.prefix_cache is not None and sched._prefix_cfg
    for uid in (0, 1):
        sched.submit(uid, prompt(6, uid), greedy(10))
    sched.step()
    # a prompt of 100 tokens under a budget of 64: chunked over two steps
    sched.submit(2, prompt(100, 2), greedy(4))
    sched.step()
    assert log[-1][:2] == [[0, 1, 2], [1, 1, 62]]
    sched.submit(3, prompt(20, 3), greedy(4))
    sched.submit(4, prompt(20, 4), greedy(4))
    tracked, indexed = state.n_tracked_sequences, len(state.prefix_cache)
    free = engine.free_blocks
    sched.step()
    # the piece that continues is planned first and whatever it pads to
    # (2 + 64 > 24); the budget has 24 tokens for the pending requests
    # (20 of one, 4 of the next), both of which would be further pieces
    assert log[-1][:2] == [[0, 1, 2], [1, 1, 38]]
    assert pending(sched) == [(3, 1), (4, 1)]
    for uid in (3, 4):
        assert state.get_sequence(uid) is None
        req = sched._pending[uid - 3]
        assert not req.prefix_checked and req.admit_s == 0.0
        assert req.prompt_sent == 0
    assert state.n_tracked_sequences == tracked
    # the index grew by the pages the running prompt filled, no other
    assert len(state.prefix_cache) == indexed + 100 // 16 - 62 // 16
    # pages went to the piece that ran, none to the held ones
    assert free - engine.free_blocks <= -(-38 // 16) + 1
    sched.step()
    # passed over once: admitted now, whatever the step costs
    assert log[-1][:2] == [[0, 1, 2, 3, 4], [1, 1, 1, 20, 20]]
    out = sched.run_to_completion()
    assert sorted(out) == [0, 1, 2, 3, 4] and not sched.errors
    state.check_invariants()


def test_a_failed_dispatch_hands_the_held_requests_back_unmarked(
        monkeypatch):
    """``_plan_step`` marks nothing; the step that takes the plan does,
    and ``_degrade_oom`` undoes it with the admissions."""
    from deepspeed_tpu.inference.v2.ragged.blocked_allocator import \
        KVAllocationError
    state_ridge(monkeypatch, 24)
    engine = tiny_engine(num_pages=128, max_seqs=16)
    sched = FastGenScheduler(engine)
    for uid in range(3):
        sched.submit(uid, prompt(10, uid), greedy(8))
    sched.step()
    for uid in (3, 4, 5):
        sched.submit(uid, prompt(10, uid), greedy(8))
    real = engine.step_sample

    def no_page(*args, **kwargs):
        raise KVAllocationError("no page (injected)")

    engine.step_sample = no_page
    sched.step()
    # the admitted one keeps the sequence its prefix lookup tracked and is
    # continued from token 0; the two the plan held are as they were
    assert sched._running[3].prompt_sent == 0
    assert pending(sched) == [(4, 0), (5, 0)]
    assert sched._step_prompts == (0, 0)
    engine.step_sample = real
    log = record_steps(engine)
    sched.step()
    assert log[-1][:2] == [[0, 1, 2, 3], [1, 1, 1, 10]]
    assert pending(sched) == [(4, 1), (5, 1)]
    assert sorted(sched.run_to_completion()) == list(range(6))


# ---------------------------------------------------------------------------
# (d) the same tokens for every request, with and without a ridge
# ---------------------------------------------------------------------------

def serve_all(engine, supply, clients):
    sched = FastGenScheduler(engine)
    streams = {uid: [] for uid, _, _ in supply}
    todo, steps = list(supply)[::-1], 0
    while todo or sched.has_work:
        while todo and sched.backlog < clients:
            sched.submit(*todo.pop())
        sched.step(on_token=lambda uid, tok: streams[uid].append(tok))
        steps += 1
    assert not sched.errors
    return streams, steps


def test_every_request_generates_the_same_tokens(monkeypatch):
    """64 callers, 300 steps and more, greedy: only the step that carries
    a prompt moves."""
    engine = tiny_engine(
        BucketLattice(s_tops=(2, 64), q_tops=(16,), p_tops=(8,)),
        num_pages=320, max_batch=1280, max_seqs=64)
    rng = np.random.default_rng(7)
    supply = [(uid, prompt(int(rng.integers(9, 17)), uid),
               greedy(int(rng.integers(8, 41)))) for uid in range(900)]
    held = serving_counters.prompts_held
    want, steps = serve_all(engine, supply, 64)
    assert steps >= 300 and serving_counters.prompts_held == held
    engine.reset_prefix_cache()
    # 64 rows + 2 x 16 = 96 at most: a third prompt waits for the next step
    state_ridge(monkeypatch, 100)
    offers = serving_counters.prompt_offers
    got, steps_ridge = serve_all(engine, supply, 64)
    assert got == want
    held = serving_counters.prompts_held - held
    assert serving_counters.prompt_offers - offers == len(supply) + held
    assert held > len(supply) // 10
    assert steps_ridge >= 300
    engine.state_manager.check_invariants()


# ---------------------------------------------------------------------------
# (e) the padded count is the lattice's, not the true lengths'
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [65, 128])
def test_two_prompts_beside_64_rows_are_held_by_their_bucket(
        monkeypatch, length):
    # 64 + 128 = 192 under a ridge of 240, 64 + 2 x 128 = 320 past it,
    # whether the prompts hold 65 tokens or 128
    state_ridge(monkeypatch, 240)
    engine = tiny_engine(num_pages=640, max_batch=1024, max_seqs=72)
    log = record_steps(engine)
    sched = FastGenScheduler(engine)
    for uid in range(64):
        sched.submit(uid, prompt(4, uid), greedy(6))
    sched.step()
    sched.submit(100, prompt(length, 100), greedy(2))
    sched.submit(101, prompt(length, 101), greedy(2))
    assert sched._padded_tokens(64, [length]) == 192
    assert sched._padded_tokens(64, [length, length]) == 320
    assert sched._padded_tokens(0, [length, 3]) == 2 * 128
    assert sched._padded_tokens(3, [1, length]) == 4 + 128
    sched.step()
    assert log[-1][1] == [1] * 64 + [length]
    # the count the rule held against the ridge is the program's own
    from deepspeed_tpu.inference.v2.step_key import StepKey
    assert StepKey.parse(log[-1][2]).padded_tokens == 192
    assert pending(sched) == [(101, 1)]
    sched.step()
    assert log[-1][1] == [1] * 65 + [length]
    assert sorted(sched.run_to_completion()) == list(range(64)) + [100, 101]


# ---------------------------------------------------------------------------
# (f) the two counters, the metric that reads them, the cell's programs
# ---------------------------------------------------------------------------

@pytest.fixture
def traced():
    from deepspeed_tpu.telemetry import get_registry
    from deepspeed_tpu.telemetry.tracer import set_component
    telemetry.disable()
    get_tracer().clear()
    set_component("")
    telemetry.enable()
    yield get_tracer()
    telemetry.disable()
    get_tracer().clear()
    get_registry().reset()


def test_offers_and_held_add_up_to_the_pending_requests_seen(
        monkeypatch, traced):
    state_ridge(monkeypatch, 24)
    engine = tiny_engine(num_pages=128, max_seqs=16)
    sched = FastGenScheduler(engine)
    rng = np.random.default_rng(3)
    todo = [(uid, prompt(int(rng.integers(5, 15)), uid),
             greedy(int(rng.integers(2, 9)))) for uid in range(40)][::-1]
    before = (serving_counters.prompt_offers, serving_counters.prompts_held)
    reqs = []
    while todo or sched.has_work:
        while todo and sched.backlog < 8:
            sched.submit(*todo.pop())
            reqs.append(sched._pending[-1])
        sched.step()
    steps = [r[5] for r in traced.records() if r[0] == "fastgen.step"]
    offers = sum(s["prompt_offers"] for s in steps)
    held = sum(s["prompts_held"] for s in steps)
    # every request was admitted once, and considered once more for every
    # time it was passed over: once at most
    assert held == sum(r.passed_over for r in reqs) > 0
    assert {r.passed_over for r in reqs} == {0, 1}
    assert offers == len(reqs) + held
    assert all(s["prompts_held"] <= s["prompt_offers"] for s in steps)
    snap = serving_counters.snapshot()
    assert (snap["prompt_offers"] - before[0],
            snap["prompts_held"] - before[1]) == (offers, held)


def test_the_benchmarks_metric_reads_the_share_back():
    """``benchmark/metrics/prompt_held_share.json`` through its reader,
    over a ring of ``fastgen.step`` spans with known counts; its entry in
    ``BENCHMARK.json`` lists exactly the cells of ``itl_p95_ms``."""
    import importlib
    import types
    sys.path.insert(0, ROOT)
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "prompt_held_share.json")) as f:
        how = json.load(f)
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "readers", how["reader"] + ".py"))
    reader = importlib.import_module("benchmark.readers." + how["reader"])
    ctx = types.SimpleNamespace(
        profiler=types.SimpleNamespace(started_at=10.0, stopped_at=20.0,
                                       steps=4),
        setup_s=5.0, process_start=0.0, config={})

    def ring(counts, attrs=("prompt_offers", "prompts_held")):
        return [("fastgen.step", 11.0 + i, 0.5, "", i,
                 dict(zip(attrs, pair), rows=64), 100 + i, None, "")
                for i, pair in enumerate(counts)]

    counts = [(0, 0), (3, 2), (2, 0), (3, 0)]
    assert reader.reduce(ring(counts), ctx, how["args"]) == \
        pytest.approx(100.0 * 2 / 8)
    # past the ridge nothing is held: 0, not nothing
    assert reader.reduce(ring([(1, 0), (0, 0)]), ctx, how["args"]) == 0.0
    # a step outside the slice is not read
    late = ring([(5, 5)])[0]
    late = late[:1] + (30.0,) + late[2:]
    assert reader.reduce(ring(counts) + [late], ctx, how["args"]) == \
        pytest.approx(25.0)
    # the parent's spans carry neither count: the metric is left out
    assert reader.reduce(ring(counts, ("tokens", "budget")), ctx,
                         how["args"]) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(m for m in spec["per_layer"]
                 if m["name"] == "prompt_held_share")
    tail = next(m for m in spec["end_to_end"] if m["name"] == "itl_p95_ms")
    assert entry["name"] == "prompt_held_share"
    assert entry["workloads"] == tail["workloads"]
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        how["unit"], how["layer"], how["moves"])


REHEARSE = """
import sys
from deepspeed_tpu.profiling import flops_profiler as fp
fp.PEAK_FLOPS["cpu"], fp.HBM_BYTES_PER_S["cpu"] = 197e12, 819e9
from benchmark import run
sys.exit(run.main(["--workload", "serve.short-closed64", "--rehearse",
                   "--seed", str(2 ** 31 + 53), "--seconds", "2",
                   "--trace", "1"]))
"""


def test_the_short_cell_forms_no_program_of_its_own_under_a_ridge():
    """The cell rehearsed on the CPU with a v5e's two peaks in the tables:
    every step program it dispatches is in the cell's hints (which the
    parent's rehearsal dispatches, and none beside them), except the ONE
    the ramp's second step forms, four rows and the one prompt the ridge
    lets in; the window is warm and the metric is reported."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run([sys.executable, "-c", REHEARSE], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    keys = next(json.loads(l.partition(": ")[2]) for l in lines
                if l.startswith("step programs dispatched: "))
    with open(os.path.join(ROOT, "benchmark", "hints",
                           "short-closed64.json")) as f:
        hinted = json.load(f)["keys"]
    ramp = [4, 1, 8, False, "mixed", 1, 128, 8, True, True]
    assert [k for k in keys if k not in hinted] == [ramp]
    # (``failed`` is left alone: a loaded CPU may not end the drain in time)
    result = json.loads(lines[-1])
    assert result["correct"]
    assert "prompt_held_share" in result["metrics"]
    facts = next(json.loads(l.partition(": ")[2]) for l in lines
                 if l.startswith("facts: "))
    assert facts["compiles_in_window"] == 0 and facts["window_warm"]
