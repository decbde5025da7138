"""A step that admits a prompt is dispatched before the step in flight
drains (ISSUE 33).

The double buffer's rule is "every decode row's token is in the step in
flight", whatever program the next step runs: a ``chain``, a ``sample``
or a ``mixed`` one.  Held here: the streams and the page accounting are
those of the drained-first schedule (dense, latent and two-group
families), the order of the spans inside such a step, each condition
that makes the drain come first, and that running ahead forms no step
key of its own.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2 import (FastGenScheduler, SamplingParams,
                                        ServingOptimizationConfig)
from deepspeed_tpu.telemetry import get_tracer


@pytest.fixture(autouse=True)
def _kv_debug(monkeypatch):
    # the page-accounting audit after every step
    monkeypatch.setenv("DS_KV_DEBUG", "1")


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


KEYED = ServingOptimizationConfig(keyed_sampling=True)
DRAINED_FIRST = ServingOptimizationConfig(keyed_sampling=True,
                                          async_scheduling=False)


def dense(serving=KEYED, **kw):
    from test_fused_serving import _tiny_engine
    return _tiny_engine(serving=serving, **kw), 128


def latent(serving=KEYED):
    from test_pangu_moe import SOURCE, engine_of, family
    return engine_of(*family(), serving=serving), SOURCE["vocab_size"]


def two_group(serving=KEYED):
    # three layers hold both page groups and the routed feed-forward (a
    # leading dense full layer, two window layers): the schedules are
    # compared here, and a step program of five costs half as much again
    from test_laguna import SOURCE, engine_of, family
    return (engine_of(*family(num_hidden_layers=3), serving=serving),
            SOURCE["vocab_size"])


FAMILIES = {"dense": dense, "latent": latent, "two_group": two_group}


def supply_of(vocab, seed, stops=None):
    """A seeded set of requests: short and long prompts (the longest is
    chunked by the 48-token budget), greedy and keyed stochastic rows,
    ends by ``max_new_tokens`` and, where ``stops`` names one, by a stop
    token."""
    rng = np.random.default_rng(seed)
    out = []
    for uid, (n, new) in enumerate(zip(
            (7, 19, 70, 12, 5, 33, 9, 26, 14, 40),
            (6, 9, 4, 12, 1, 7, 10, 5, 8, 6))):
        hot = uid % 3 == 1
        out.append((uid, rng.integers(0, vocab, n).tolist(), SamplingParams(
            max_new_tokens=new, temperature=0.9 if hot else 0.0,
            top_k=12 if hot else 0,
            stop_token=(stops or {}).get(uid))))
    return out


def serve_closed_loop(engine, supply, clients=4, serving=None, budget=48):
    """``clients`` callers, each sending its next request when one ended.
    Returns (tokens a request, the scheduler)."""
    sched = FastGenScheduler(engine, token_budget=budget, serving=serving)
    streams = {uid: [] for uid, _, _ in supply}
    todo = list(supply)[::-1]
    while todo or sched.has_work:
        while todo and sched.backlog < clients:
            sched.submit(*todo.pop())
        sched.step(on_token=lambda uid, tok: streams[uid].append(tok))
    assert not sched.errors
    return streams, sched


def free_pages(engine):
    return engine.free_blocks, engine.free_window_blocks


# ---------------------------------------------------------------------------
# (a) the streams and the pages of the drained-first schedule
# ---------------------------------------------------------------------------

def one_bucket_a_dimension(engine, slots=4, tokens=64, pages=16):
    """Serve under a lattice of one top a dimension (what the supply's
    four callers, 48-token budget and 74-token sequences fit): the
    schedules are the thing compared here, not the bucket rule, and every
    distinct bucket is a step program to form (two dozen of them under the
    power-of-two default, a dozen so)."""
    from deepspeed_tpu.inference.v2.lattice import BucketLattice
    engine._lattice = engine.model.lattice = BucketLattice(
        s_tops=(slots,), q_tops=(tokens,), p_tops=(pages,))


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_streams_and_pages_equal_the_drained_first_schedule(name):
    engine, vocab = FAMILIES[name]()
    one_bucket_a_dimension(engine)
    at_rest = free_pages(engine)
    # one engine, so one set of compiled programs; the scheduler's
    # ``serving`` view says whether a step may run ahead of the drain
    plain, _ = serve_closed_loop(engine, supply_of(vocab, 11),
                                 serving=DRAINED_FIRST)
    # stop tokens that do stop a stream mid-way (and one that never comes)
    stops = {0: plain[0][2], 3: plain[3][5], 6: plain[6][4], 7: vocab + 1}
    supply = supply_of(vocab, 11, stops)
    want, _ = serve_closed_loop(engine, supply, serving=DRAINED_FIRST)
    assert free_pages(engine) == at_rest
    engine.reset_prefix_cache()
    got, sched = serve_closed_loop(engine, supply)
    assert got == want
    for uid, _, params in supply:
        assert 1 <= len(got[uid]) <= params.max_new_tokens
    assert got[0][-1] == stops[0] and len(got[0]) <= 3
    assert len(got[7]) == 5 and len(got[4]) == 1
    # the stochastic rows drew what the drained-first schedule drew, and
    # something other than the arg-max somewhere
    assert free_pages(engine) == at_rest
    engine.state_manager.check_invariants()
    assert sched._inflight is None and not sched._running
    kinds = {k.kind for k in engine.compiled_keys()}
    assert {"sample", "chain", "mixed"} <= kinds


# ---------------------------------------------------------------------------
# (b) the order inside a step, and what makes the drain come first
# ---------------------------------------------------------------------------

def children(recs, parent):
    return sorted((r for r in recs if r[7] == parent[6]),
                  key=lambda r: r[1])


def steps_of(tracer):
    recs = [r for r in tracer.records()
            if not r[0].startswith("engine.program")]
    return recs, [r for r in recs if r[0] == "fastgen.step"]


def prompt(n, seed=0, vocab=128):
    return np.random.default_rng(seed).integers(0, vocab, n).tolist()


GREEDY = SamplingParams(max_new_tokens=8, temperature=0.0)


def test_a_step_that_admits_a_prompt_is_dispatched_before_the_drain(traced):
    engine, _ = dense(serving=None)
    sched = FastGenScheduler(engine)
    sched.submit(0, prompt(20), GREEDY)
    sched.step()                    # nothing in flight: drained first
    sched.submit(1, prompt(9, 1), GREEDY)
    sched.step()                    # decode 0 (in flight) + prompt 1
    sched.step()                    # decode 0 and 1, both in flight
    recs, steps = steps_of(traced)
    first, mixed, chain = (s[5] for s in steps)
    assert (first["path"], first["program"]) == ("fused", "sample")
    assert (mixed["path"], mixed["program"]) == ("chain", "mixed")
    assert (mixed["rows"], mixed["prefill_rows"], mixed["prefill_tokens"],
            mixed["tokens"]) == (2, 1, 9, 10)
    assert (chain["path"], chain["program"]) == ("chain", "chain")
    for step in steps[1:]:
        # planned, dispatched, and only then drained
        assert [k[0] for k in children(recs, step)] == [
            "fastgen.admission", "fastgen.dispatch.chain", "fastgen.drain"]
    assert [k[0] for k in children(recs, steps[0])] == [
        "fastgen.admission", "fastgen.dispatch.fused"]
    out = sched.run_to_completion()
    assert [len(out[u]) for u in (0, 1)] == [8, 8]


def test_a_prompt_alone_runs_ahead_as_a_sample_program(traced):
    """A chunked prompt samples nothing until its last piece ran: the
    pieces follow each other ahead of the drain, through the program a
    drained-first step would run."""
    engine, _ = dense(serving=None)
    sched = FastGenScheduler(engine, token_budget=16)
    sched.submit(0, prompt(40), GREEDY)
    for _ in range(4):
        sched.step()
    _, steps = steps_of(traced)
    assert [(s[5]["path"], s[5]["program"]) for s in steps] == [
        ("fused", "sample"), ("chain", "sample"), ("chain", "sample"),
        ("chain", "chain")]
    assert [s[5]["prefill_tokens"] for s in steps] == [16, 16, 8, 0]
    assert len(sched.run_to_completion()[0]) == 8


def _decoding(sched, n=2, **params):
    """``n`` requests decoding with a step in flight."""
    p = SamplingParams(max_new_tokens=12, temperature=0.0, **params)
    for uid in range(n):
        sched.submit(uid, prompt(10 + uid, uid), p)
    sched.step()
    sched.step()
    assert sched._inflight is not None
    return p


def _last_step(tracer):
    recs, steps = steps_of(tracer)
    return steps[-1][5], [k[0] for k in children(recs, steps[-1])]


class TestTheDrainComesFirst:
    """Each fall-back is a property of the step, read from what the batch
    holds: no switch selects it."""

    def test_where_async_scheduling_is_off(self, traced):
        engine, _ = dense(serving=None)
        sched = FastGenScheduler(engine, serving=ServingOptimizationConfig(
            async_scheduling=False))
        sched.submit(0, prompt(10), GREEDY)
        sched.step()
        sched.submit(1, prompt(7, 1), GREEDY)
        sched.step()
        attrs, kids = _last_step(traced)
        assert (attrs["path"], attrs["program"]) == ("fused", "mixed")
        assert sched._inflight is None and "fastgen.drain" in kids

    def test_where_a_decode_rows_token_is_on_the_host(self, traced):
        # a row the step in flight does not hold: here one whose
        # membership was lost (a restored or imported sequence is such)
        engine, _ = dense(serving=None)
        sched = FastGenScheduler(engine)
        _decoding(sched)
        sched._inflight.rows.pop()
        held = len(sched._running[1].generated)
        sched.step()
        attrs, kids = _last_step(traced)
        assert attrs["path"] == "fused" and kids[0] == "fastgen.drain"
        assert sched._inflight_rows() is not None    # and chains again
        sched.step()
        assert _last_step(traced)[0]["path"] == "chain"
        assert len(sched._running[1].generated) > held

    def test_where_a_preempted_sequence_waits(self, traced):
        engine, _ = dense(serving=None)
        sched = FastGenScheduler(engine)
        _decoding(sched, n=3)
        assert sched._preempt_largest() and sched._preempted
        assert sched._inflight is not None
        assert sched._inflight_rows() is None
        sched.step()        # drains, restores it: its token is on the host
        attrs, kids = _last_step(traced)
        assert attrs["path"] == "fused" and kids[0] == "fastgen.drain"
        assert not sched._preempted
        sched.step()
        assert _last_step(traced)[0]["path"] == "chain"
        out = sched.run_to_completion()
        assert [len(out[u]) for u in range(3)] == [12, 12, 12]

    def test_where_a_running_row_finds_no_page(self, traced):
        # 4 pages of 16 tokens: two rows of 15 and 14 tokens fill one
        # each; the third prompt takes the rest, and a row that crosses a
        # page boundary then finds none
        engine, _ = dense(serving=None, num_pages=4)
        sched = FastGenScheduler(engine)
        p = SamplingParams(max_new_tokens=30, temperature=0.0)
        sched.submit(0, prompt(14), p)
        sched.submit(1, prompt(13, 1), p)
        sched.step()
        sched.submit(2, prompt(30, 2), p)
        for _ in range(3):
            sched.step()
        recs, steps = steps_of(traced)
        drained_first = [s for s in steps[1:] if s[5]["path"] != "chain"]
        assert drained_first, [s[5]["path"] for s in steps]
        # the plan ran ahead, found no page, drained and planned again
        kids = [k[0] for k in children(recs, drained_first[0])]
        assert kids[:3] == ["fastgen.admission", "fastgen.drain",
                            "fastgen.admission"], kids
        out = sched.run_to_completion()
        assert [len(out[u]) for u in range(3)] == [30, 30, 30]

    def test_where_the_speculation_gate_is_open(self, traced):
        engine, _ = dense(serving=ServingOptimizationConfig(
            speculative=True, spec_max_draft=3))
        sched = FastGenScheduler(engine)
        p = SamplingParams(max_new_tokens=16, temperature=0.0)
        sched.submit(0, [7] * 12, p)
        for _ in range(4):
            sched.step()
        recs, steps = steps_of(traced)
        spec = [s for s in steps if s[5]["path"] == "spec"]
        assert spec and spec[0][5]["program"] == "spec"
        # the drafter reads committed tokens: a step in flight drains
        # before anything is dispatched
        for s in spec:
            kids = [k[0] for k in children(recs, s)]
            assert kids[0] in ("fastgen.drain", "fastgen.dispatch.spec")
            assert "fastgen.dispatch.chain" not in kids
        assert len(sched.run_to_completion()[0]) == 16

    def test_where_strict_shapes_would_take_the_split_path(self, traced):
        engine, _ = dense(serving=None, num_pages=64, max_batch=64,
                          max_seqs=2)
        engine.precompile(max_prompt=8, max_new_tokens=8, strict=True,
                          sampling=True)
        sched = FastGenScheduler(engine)
        sched.submit(0, prompt(8), GREEDY)
        sched.step()
        sched.step()                # pure decode: chains under strict too
        assert _last_step(traced)[0]["path"] == "chain"
        sched.submit(1, prompt(5, 1), GREEDY)
        sched.step()                # a mixed key is in no lattice: split
        attrs, kids = _last_step(traced)
        assert (attrs["path"], attrs["program"]) == ("split", "logits")
        assert kids[0] == "fastgen.drain"
        out = sched.run_to_completion()
        assert [len(out[u]) for u in (0, 1)] == [8, 8]

    def test_where_the_dispatch_finds_the_pool_empty(self, traced):
        """A ``KVAllocationError`` on a dispatch ahead of the drain: the
        step in flight drains, the prompt's advance is rolled back, and
        the request is served later."""
        from deepspeed_tpu.inference.v2.ragged.blocked_allocator import (
            KVAllocationError)
        engine, _ = dense(serving=None)
        sched = FastGenScheduler(engine)
        sched.submit(0, prompt(10), GREEDY)
        sched.step()
        sched.submit(1, prompt(20, 1), GREEDY)
        real, calls = engine.step_sample, []

        def failing(*args, **kw):
            calls.append(kw.get("prev"))
            raise KVAllocationError("injected")

        engine.step_sample = failing
        out = sched.step()
        engine.step_sample = real
        assert calls and calls[0] is not None        # it ran ahead
        assert out and sched._inflight is None       # and drained
        # (its sequence is tracked since the prefix lookup: it stays a
        # running row whose prompt starts again)
        waiting = (sched._pending + list(sched._running.values()))
        assert [r.prompt_sent for r in waiting if r.uid == 1] == [0]
        assert _last_step(traced)[0]["path"] == "idle"
        out = sched.run_to_completion()
        assert [len(out[u]) for u in (0, 1)] == [8, 8]


def test_a_row_that_ends_by_count_is_left_out_from_host_counts(traced):
    engine, _ = dense(serving=None)
    sched = FastGenScheduler(engine)
    sched.submit(0, prompt(10), SamplingParams(max_new_tokens=2,
                                               temperature=0.0))
    sched.submit(1, prompt(12, 1), GREEDY)
    first = sched._pending[0]
    sched.step()
    sched.step()        # both decode; request 0's second token is its last
    sched.step()
    attrs, _ = _last_step(traced)
    assert (attrs["path"], attrs["rows"]) == ("chain", 1)
    out = sched.run_to_completion()
    assert len(first.generated) == 2 and first.done and len(out[1]) == 8


def test_a_one_token_prompt_piece_rides_the_decode_segment():
    """A prompt whose last piece is one token long shares the decode
    segment, whose other ids come from the step in flight: the gather
    keeps the id the host gave that row."""
    def run(serving):
        engine, _ = dense(serving=serving)
        sched = FastGenScheduler(engine, token_budget=18)
        sched.submit(0, prompt(9), GREEDY)
        sched.step()
        sched.submit(1, prompt(18, 1), GREEDY)  # 17 tokens, then 1
        out = sched.run_to_completion()
        return out, {k for k in engine.compiled_keys()}

    got, keys = run(None)
    want, _ = run(ServingOptimizationConfig(async_scheduling=False))
    assert got == want and [len(got[u]) for u in (0, 1)] == [8, 8]
    assert not any(k.kind == "mixed" and k.prefill[1] == 1 for k in keys)


# ---------------------------------------------------------------------------
# (c) running ahead forms no step key of its own
# ---------------------------------------------------------------------------

def test_running_ahead_forms_no_key_the_drained_first_path_does_not(
        monkeypatch):
    """Over one supply, the keys of a scheduler that runs every eligible
    step ahead of the drain against one held to the rule before ISSUE 33
    (ahead only where no request waits and no row is mid-prefill: the
    pure-decode chain): the same programs, so a mix's hints and a lattice
    artifact hold."""
    def keys(hold_to_the_old_rule):
        engine, vocab = dense()
        if hold_to_the_old_rule:
            ahead = FastGenScheduler._inflight_rows

            def old_rule(self):
                if self._pending or any(r.prefill_remaining
                                        for r in self._running.values()):
                    return None
                return ahead(self)

            monkeypatch.setattr(FastGenScheduler, "_inflight_rows", old_rule)
        streams, _ = serve_closed_loop(engine, supply_of(vocab, 5))
        monkeypatch.undo()
        return streams, set(engine.compiled_keys())

    got, ahead_keys = keys(False)
    want, first_keys = keys(True)
    assert got == want
    assert ahead_keys == first_keys
    assert {"sample", "chain", "mixed"} <= {k.kind for k in ahead_keys}


def test_the_gathers_of_a_mixed_program_form_with_it():
    """The token gather of a decode segment is a closed set of tiny
    programs, a previous vector's length each, formed where the mixed
    program forms: a later step of another previous length compiles
    nothing."""
    engine, _ = dense(serving=None, max_seqs=8)
    assert engine._gathers == {}
    engine.precompile_keys([(2, 1, 8, False, "mixed", 1, 16, 8, True, True)])
    assert sorted(engine._gathers) == [(2, 2), (4, 2), (8, 2), (16, 2)]
    import jax.numpy as jnp
    prev = jnp.arange(100, 104, dtype=jnp.int32)
    ids = np.array([[7], [9]], np.int32)
    got = engine._gather_tokens(prev, np.array([3, -1], np.int32), ids)
    assert np.asarray(got).tolist() == [[103], [9]]
    assert sorted(engine._gathers) == [(2, 2), (4, 2), (8, 2), (16, 2)]
    # a sample program of one-token rows can take such an operand too (a
    # prompt's one-token last piece beside rows in flight); a prompt's not
    engine.precompile_keys([(4, 8, 8, False, "sample", True)])
    assert len(engine._gathers) == 4
    engine.precompile_keys([(4, 1, 8, False, "sample", True)])
    assert sorted(k for k in engine._gathers if k[1] == 4) == [
        (4, 4), (8, 4), (16, 4)]
    latent_engine, _ = latent()
    latent_engine._form_gathers(4)     # the counts ride the vector's tail
    assert sorted(latent_engine._gathers) == [(7, 4), (11, 4), (19, 4)]
