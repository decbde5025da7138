"""The span tree (ISSUE 24): span ids and parents in the tracer, one whole
tree per scheduler step with its counts, three spans per request from one
set of always-on stamps, and a record of every step program formed —
written whether or not telemetry is on."""

import functools
import gc
import sys
import threading
import time

import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import get_registry, get_tracer, trace_span
from deepspeed_tpu.telemetry import metrics as tm
from deepspeed_tpu.telemetry.tracer import (_NULL_SPAN, SpanTracer,
                                            set_component)

from test_telemetry import _slo_engine


@pytest.fixture(autouse=True)
def _telemetry_hygiene():
    telemetry.disable()
    get_tracer().clear()
    # a pool test that ran earlier in this worker may have left its
    # replica label on the thread: it would join every span's attrs
    set_component("")
    yield
    telemetry.disable()
    get_tracer().clear()
    get_registry().reset()


def by_id(records):
    return {r[6]: r for r in records}


def children_of(records, span_id):
    return [r for r in records if r[7] == span_id]


def self_s(records, rec):
    return rec[2] - sum(c[2] for c in children_of(records, rec[6]))


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestSpanIds:
    def test_ids_and_parents_nest_on_one_thread(self):
        telemetry.enable()
        with trace_span("a"):
            with trace_span("b"):
                with trace_span("c"):
                    pass
            with trace_span("d"):
                pass
        recs = {r[0]: r for r in get_tracer().records()}
        assert recs["a"][7] is None
        assert recs["b"][7] == recs["a"][6] == recs["d"][7]
        assert recs["c"][7] == recs["b"][6]
        assert len({r[6] for r in recs.values()}) == 4

    def test_parents_do_not_cross_threads(self):
        telemetry.enable()
        ready, release = threading.Event(), threading.Event()

        def other():
            with trace_span("other.root"):
                ready.set()
                release.wait(5)
                with trace_span("other.child"):
                    pass

        t = threading.Thread(target=other)
        with trace_span("main.root"):
            t.start()
            ready.wait(5)
            with trace_span("main.child"):
                pass
            release.set()
            t.join()
        recs = {r[0]: r for r in get_tracer().records()}
        assert recs["other.root"][7] is None
        assert recs["main.root"][7] is None
        assert recs["other.child"][7] == recs["other.root"][6]
        assert recs["main.child"][7] == recs["main.root"][6]
        assert recs["other.root"][4] != recs["main.root"][4]

    def test_set_lands_on_the_record_and_not_on_the_shared_dict(self):
        telemetry.enable()
        shared = {"k": 1}
        with trace_span("x", shared) as sp:
            sp.set("rows", 3)
        rec = get_tracer().records()[-1]
        assert rec[5] == {"k": 1, "rows": 3} and shared == {"k": 1}

    def test_null_span_has_the_same_interface(self):
        assert not telemetry.enabled()
        sp = trace_span("ghost")
        assert sp is _NULL_SPAN and sp.live is False
        with sp as inner:
            assert inner.set("rows", 3) is None
        assert get_tracer().records() == []
        telemetry.enable()
        with trace_span("real") as live:
            assert live.live is True
            for name in ("set", "live", "__enter__", "__exit__"):
                assert hasattr(sp, name) and hasattr(live, name)

    def test_disabled_call_allocates_nothing(self):
        """Counted, not timed: the interpreter's live block count does
        not grow over a hundred thousand disabled spans."""
        assert not telemetry.enabled()

        def spin(n):
            for _ in range(n):
                with trace_span("hot") as sp:
                    sp.set("k", 1)

        spin(1000)                       # warm the code object's caches
        before = sys.getallocatedblocks()
        spin(100_000)
        assert sys.getallocatedblocks() - before < 50

    def test_record_after_the_fact_takes_parent_and_uid(self):
        tr = SpanTracer(capacity=8)
        root = tr.record("step", 1.0, 2.0)
        kid = tr.record("request.decode", 1.5, 0.5, {"new_tokens": 4},
                        parent=root, uid=17)
        recs = by_id(tr.records())
        assert recs[kid][7] == root and recs[kid][8] == 17
        assert recs[root][7] is None and recs[root][8] is None
        args = {e["name"]: e["args"] for e in tr.chrome_events()}
        assert args["request.decode"]["parent"] == root
        assert args["request.decode"]["uid"] == 17
        assert args["request.decode"]["id"] == kid
        assert "uid" not in args["step"]

    def test_span_is_live_with_telemetry_off(self):
        assert not telemetry.enabled()
        with get_tracer().span("always", {"key": (1, 2)}) as sp:
            sp.set("cache", "hit")
        rec = get_tracer().records()[-1]
        assert rec[0] == "always" and rec[5]["cache"] == "hit"


# ---------------------------------------------------------------------------
# the serving step's tree on the tiny model
# ---------------------------------------------------------------------------

DISPATCH_CHILDREN = ["engine.admit", "engine.build_batch",
                     "engine.dispatch", "engine.commit"]
#: records that come when they come, not with a step's shape: a program
#: formed, a collection (wherever the interpreter starts one), a stall
NOT_OF_THE_TREE = ("engine.program", "fastgen.gc", "fastgen.stall")


def serve(n_req=3, prompt=12, new=5, before_enable=0):
    """A scheduler on the debug model; ``before_enable`` requests are
    submitted and stepped once with telemetry off, then it is switched
    on and the rest follow."""
    from deepspeed_tpu.inference.v2 import FastGenScheduler, SamplingParams
    sched = FastGenScheduler(_slo_engine())
    rng = np.random.default_rng(0)

    def submit(uid):
        sched.submit(uid, rng.integers(0, 32, size=prompt).tolist(),
                     SamplingParams(max_new_tokens=new, temperature=0.0))

    for uid in range(before_enable):
        submit(uid)
    if before_enable:
        sched.step()
    telemetry.enable()
    for uid in range(before_enable, n_req):
        submit(uid)
    out = sched.run_to_completion()
    assert all(len(out[u]) == new for u in range(n_req))
    return sched, [r for r in get_tracer().records()
                   if not r[0].startswith(NOT_OF_THE_TREE)]


class TestStepTree:
    def test_a_host_path_step_has_exactly_the_tree(self):
        # two requests are decoding, their tokens in the step in flight,
        # when two more arrive: the step admits, dispatches one mixed
        # program (one batch segment per kind of row) AHEAD of the drain
        # (PR 33: ``path=chain`` means "dispatched ahead of the drain",
        # whatever program ran; ``program`` names that), then drains
        _, recs = serve(n_req=4, prompt=20, new=4, before_enable=2)
        steps = [r for r in recs if r[0] == "fastgen.step"]
        mixed = next(r for r in steps if r[5]["prefill_rows"])
        assert (mixed[5]["path"], mixed[5]["program"]) == ("chain", "mixed")
        kids = sorted(children_of(recs, mixed[6]), key=lambda r: r[1])
        assert [k[0] for k in kids] == ["fastgen.admission",
                                        "fastgen.dispatch.chain",
                                        "fastgen.drain"]
        adm, dispatch, drain = kids
        under = [k[0] for k in sorted(children_of(recs, dispatch[6]),
                                      key=lambda r: r[1])]
        assert under == ["engine.admit", "engine.build_batch",
                         "engine.build_batch", "engine.dispatch",
                         "engine.commit"]
        assert [k[0] for k in sorted(children_of(recs, drain[6]),
                                     key=lambda r: r[1])] == [
            "fastgen.drain.wait", "fastgen.drain.deliver"]
        assert {k[0] for k in children_of(recs, adm[6])} == {
            "fastgen.prefix_match"}
        # the call of the program is a leaf: nothing forms on a warm key
        # (the token gather of the decode segment is a warm call too)
        call = next(k for k in children_of(recs, dispatch[6])
                    if k[0] == "engine.dispatch")
        assert children_of(recs, call[6]) == []
        # what it prepared and what it called, as attributes: no child
        # (``dispatch_ms_per_step`` reads the span's self time); the rest
        # of the span is the pool's return
        assert set(call[5]) == {"prepare_ms", "call_ms"}
        assert 0 < call[5]["prepare_ms"] + call[5]["call_ms"] \
            <= call[2] * 1e3

    def test_a_step_with_nothing_in_flight_keeps_the_fused_path(self):
        # the first step has no step to run ahead of: ``path=fused``,
        # and no drain under it
        _, recs = serve()
        first = next(r for r in recs if r[0] == "fastgen.step")
        assert (first[5]["path"], first[5]["program"]) == ("fused",
                                                           "sample")
        kids = sorted(children_of(recs, first[6]), key=lambda r: r[1])
        assert [k[0] for k in kids] == ["fastgen.admission",
                                        "fastgen.dispatch.fused"]
        assert [k[0] for k in sorted(children_of(recs, kids[1][6]),
                                     key=lambda r: r[1])] \
            == DISPATCH_CHILDREN

    def test_a_chained_step_dispatches_then_drains(self):
        _, recs = serve()
        chain = next(r for r in recs if r[0] == "fastgen.step"
                     and r[5]["program"] == "chain")
        assert chain[5]["path"] == "chain"
        # the one plan of a step runs here too, and finds no prompt
        kids = sorted(children_of(recs, chain[6]), key=lambda r: r[1])
        assert [k[0] for k in kids] == ["fastgen.admission",
                                        "fastgen.dispatch.chain",
                                        "fastgen.drain"]
        assert children_of(recs, kids[0][6]) == []
        assert [k[0] for k in sorted(children_of(recs, kids[1][6]),
                                     key=lambda r: r[1])] \
            == DISPATCH_CHILDREN
        assert [k[0] for k in sorted(children_of(recs, kids[2][6]),
                                     key=lambda r: r[1])] \
            == ["fastgen.drain.wait", "fastgen.drain.deliver"]

    def test_step_counts_add_up(self):
        sched, recs = serve(n_req=4, prompt=20, new=4, before_enable=2)
        steps = [r[5] for r in recs if r[0] == "fastgen.step"]
        # every step here has its decode rows' tokens in flight
        assert {s["path"] for s in steps} == {"chain", "idle"}
        assert {s["program"] for s in steps} == {"mixed", "chain", "idle"}
        for s in steps:
            # a row decodes one token or carries a prompt piece
            assert s["tokens"] == (s["rows"] - s["prefill_rows"]
                                   + s["prefill_tokens"])
            assert s["budget"] == 256 and s["tokens"] <= s["budget"]
            assert s["kv_tokens_held"] <= s["kv_pages_reserved"] * 16
        mixed = next(s for s in steps if s["prefill_rows"])
        # two decoding rows from before, two 20-token prompts admitted
        assert (mixed["rows"], mixed["prefill_rows"],
                mixed["prefill_tokens"], mixed["tokens"]) == (4, 2, 40, 42)
        assert mixed["kv_pages_reserved"] > 0
        idle = next(s for s in steps if s["path"] == "idle")
        assert idle["rows"] == idle["tokens"] == 0
        # every program streams its weights once, the mixed step's too
        assert mixed["program"] == "mixed" and idle["trunk_passes"] == 0
        assert {s["trunk_passes"] for s in steps
                if s["path"] != "idle"} == {1}
        # last_step_scheduled keeps its meaning: sequences, not tokens
        assert sched.last_step_scheduled == 0

    def test_self_times_sum_to_the_step(self):
        _, recs = serve()
        ids = by_id(recs)

        def root_of(r):
            while r[7] is not None and r[7] in ids:
                r = ids[r[7]]
            return r[6]

        for step in (r for r in recs if r[0] == "fastgen.step"):
            tree = [r for r in recs if root_of(r) == step[6]]
            assert len(tree) > 1
            total = sum(self_s(recs, r) for r in tree)
            assert total == pytest.approx(step[2], rel=0.01)
            # properly nested: a child lies inside its parent
            for r in tree:
                if r[7] is not None:
                    p = ids[r[7]]
                    assert p[1] <= r[1] and r[1] + r[2] <= p[1] + p[2]


def test_every_attribute_on_the_serving_path_has_a_metric_that_reads_it():
    """What a span of the serving step or of a request carries, some
    metric file of the benchmark names (``attr:<key>`` or a ``where``):
    nothing is recorded there for nobody."""
    import glob
    import json
    import os
    import re
    _, recs = serve(n_req=4, prompt=20, new=4, before_enable=2)
    carried = {key for r in recs if r[5] for key in r[5]}
    assert carried == {"path", "rows", "prefill_rows", "prefill_tokens",
                       "tokens", "budget", "prompt_offers", "prompts_held",
                       "kv_pages_reserved",
                       "kv_tokens_held", "new_tokens", "trunk_passes",
                       "program", "kv_slots_held", "kv_slots_live",
                       "kv_slots_bucket", "prepare_ms", "call_ms"}
    # a paused step's one record and a collection's span (ISSUE 52), fed
    # by hand: a step of 80 ms after ten of 1 ms, a full collection
    from deepspeed_tpu.telemetry.watchdog import (StepMeter, get_collector,
                                                  get_watchdog,
                                                  install_collector)
    meter, wd = StepMeter(), get_watchdog()
    wd.reset()
    get_tracer().clear()
    meter.begin()                   # the CPU clocks' baseline
    for wall in [0.001] * 10 + [0.08]:
        meter.t1 = meter.t0 + wall
        wd.observe_serving_step(meter, rows=4)
    install_collector().loop = "fastgen"
    gc.collect()
    get_collector().loop = None
    stall, = [r for r in get_tracer().records() if r[0] == "fastgen.stall"]
    spans = {r[0]: set(r[5]) for r in get_tracer().records()
             if r[0] in ("fastgen.stall", "fastgen.gc")}
    assert spans["fastgen.gc"] == {"generation", "collected"}
    # the stall's sums are metrics (``stall_lost_ms.serve``, ``stall_gc_ms.
    # serve``, ``stall_offcpu_ms.serve``; the record itself ``stall_steps.
    # serve``); the rest of the record is the line an operator reads
    # (docs/DESIGN.md) and PERF.md section 7's table, the collection's pair
    # is for whoever reads a trace: by decision no metric
    carried |= spans["fastgen.stall"] - {
        "wall_ms", "between_ms", "ewma_ms", "phase", "phase_ms", "wait_ms",
        "cpu_ms", "proc_cpu_ms", "between_cpu_ms", "between_proc_cpu_ms",
        "gc_n", "gc_gen2", "programs", "rows", "cause"}
    assert {"lost_ms", "gc_ms", "offcpu_ms"} <= carried
    # the engagement counter of the one-pass mixed step (PR 30): held in
    # the span ring for whoever reads a trace, by decision no metric
    carried.remove("trunk_passes")
    # which program ran the step (PR 33): ``path=chain`` is "dispatched
    # ahead of the drain" whatever ran it, and ``chained_step_share``
    # reads that; the kind is held beside it, by decision no metric
    carried.remove("program")
    # page slots of the decode rows under the paged kernel's fetch table
    # (PR 44): what share of a step's page fetches the null page used to
    # be; the rooflines and time shares that were here read the effect,
    # the pair is held beside them, by decision no metric
    # (PR 45: and the slots of the rows' tables, so that live / bucket is
    # the share of the bucket the decode kernel's walk visits)
    carried -= {"kv_slots_held", "kv_slots_live", "kv_slots_bucket"}
    read = set()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for path in glob.glob(os.path.join(root, "benchmark", "metrics",
                                       "*.json")):
        with open(path) as f:
            args = json.load(f).get("args", {})
        for value in (args.get("value", ""), args.get("of_value", "")):
            if value.startswith("attr:"):
                read.add(value[5:])
        read.update(re.split("!?=", w)[0] for w in args.get("where", []))
    assert carried <= read


def test_a_step_with_telemetry_off_takes_no_counts():
    from deepspeed_tpu.inference.v2 import FastGenScheduler, SamplingParams
    from deepspeed_tpu.inference.v2.scheduler import _IDLE_STEP
    assert not telemetry.enabled()
    sched = FastGenScheduler(_slo_engine())
    for uid in range(2):
        sched.submit(uid, list(range(12)),
                     SamplingParams(max_new_tokens=3, temperature=0.0))
    while sched.has_work:
        sched.step()
        assert sched._step_shape is _IDLE_STEP
    # (a program formed on the path may be a stall too: the one record a
    # step leaves with telemetry off)
    assert [r for r in get_tracer().records()
            if not r[0].startswith(NOT_OF_THE_TREE)] == []


def test_a_collection_is_a_span_under_the_span_that_was_open(monkeypatch):
    """With telemetry on a collection inside ``engine.dispatch`` is a
    ``fastgen.gc`` span that names the dispatch as its parent, so the
    dispatch's SELF time (``dispatch_ms_per_step``) no longer holds the
    collector; one inside the delivery names the delivery."""
    from deepspeed_tpu.inference.v2.model import RaggedInferenceModel
    run_step = RaggedInferenceModel.run_step

    forced = []

    def collecting(self, *args, **kwargs):
        if telemetry.enabled() and not forced:
            forced.append(gc.collect())     # once: a full one is slow
        return run_step(self, *args, **kwargs)

    monkeypatch.setattr(RaggedInferenceModel, "run_step", collecting)
    serve()
    recs = get_tracer().records()
    ids = by_id(recs)
    full = [r for r in recs if r[0] == "fastgen.gc"
            and r[5]["generation"] == 2 and r[7] in ids
            and ids[r[7]][0] == "engine.dispatch"]
    assert len(full) == 1 and full[0][5]["collected"] >= 0
    for r in full:
        parent = ids[r[7]]
        assert parent[1] <= r[1] and r[1] + r[2] <= parent[1] + parent[2]
        # the dispatch's self time is its duration less the collection
        assert self_s(recs, parent) <= parent[2] - r[2] + 1e-9
        # and what it prepared holds the collection, what it called not
        assert parent[5]["prepare_ms"] >= r[2] * 1e3
        assert parent[5]["call_ms"] <= (parent[2] - r[2]) * 1e3
    # every collection of the run is nested where it fell, or a root
    assert all(r[7] is None or r[7] in ids for r in recs
               if r[0] == "fastgen.gc")


def test_a_paused_step_leaves_one_record_with_telemetry_off():
    """The tiny engine, telemetry off: a step whose delivery sleeps 80 ms
    leaves one ``fastgen.stall`` in the ring (``phase=deliver``, off the
    CPU) and nothing else new; a sleep in the caller's loop one with
    ``phase=between``."""
    from deepspeed_tpu.inference.v2 import FastGenScheduler, SamplingParams
    from deepspeed_tpu.telemetry.watchdog import get_watchdog
    assert not telemetry.enabled()
    sched = FastGenScheduler(_slo_engine())
    sched.submit(0, list(range(12)),
                 SamplingParams(max_new_tokens=40, temperature=0.0))
    seen = []

    def on_token(uid, tok):
        seen.append(tok)
        if len(seen) == 30:
            time.sleep(0.08)

    for _ in range(4):              # the programs form here
        sched.step(on_token)
    get_watchdog().reset()
    get_tracer().clear()
    base = tm.FASTGEN_STALL.value
    while sched.has_work:
        if len(seen) == 35:
            seen.append(None)
            time.sleep(0.08)
        sched.step(on_token)
    recs = get_tracer().records()
    assert [r[0] for r in recs] == ["fastgen.stall"] * 2
    assert tm.FASTGEN_STALL.value == base + 2
    in_step, between = (r[5] for r in recs)
    assert (in_step["phase"], in_step["cause"]) == ("deliver", "offcpu")
    assert in_step["wall_ms"] >= 80 > in_step["cpu_ms"] + in_step["wait_ms"]
    assert in_step["offcpu_ms"] >= 70 and in_step["programs"] == 0
    assert (between["phase"], between["cause"]) == ("between", "offcpu")
    assert between["between_ms"] >= 80 > between["wall_ms"]
    for a in (in_step, between):
        assert a["cpu_ms"] <= a["wall_ms"] - a["wait_ms"] + 1
        assert a["gc_ms"] <= a["wall_ms"] + a["between_ms"]
        assert a["rows"] == 1 and a["lost_ms"] >= 70


class TestRequestSpans:
    def test_three_spans_tile_a_request_submitted_before_enable(self):
        for h in (tm.FASTGEN_TTFT_MS, tm.FASTGEN_ITL_MS,
                  tm.FASTGEN_QUEUE_WAIT_MS):
            h.reset()
        from deepspeed_tpu.inference.v2 import (FastGenScheduler,
                                                SamplingParams)
        sched = FastGenScheduler(_slo_engine())
        t0 = time.perf_counter()
        sched.submit(7, list(range(12)),
                     SamplingParams(max_new_tokens=4, temperature=0.0))
        t1 = time.perf_counter()
        assert get_tracer().records() == []      # nothing while off
        telemetry.enable()
        sched.run_to_completion()
        t2 = time.perf_counter()
        spans = {r[0]: r for r in get_tracer().records()
                 if r[0].startswith("request.")}
        assert set(spans) == {"request.queue_wait", "request.prefill",
                              "request.decode"}
        q, p, d = (spans["request." + n]
                   for n in ("queue_wait", "prefill", "decode"))
        assert all(r[8] == 7 and r[7] is None for r in (q, p, d))
        # they start at the submit (taken with telemetry off) and tile
        # the request's life without a gap
        assert t0 <= q[1] <= t1
        assert q[1] + q[2] == pytest.approx(p[1], abs=1e-9)
        assert p[1] + p[2] == pytest.approx(d[1], abs=1e-9)
        assert d[1] + d[2] <= t2
        assert q[5] is None and p[5] is None
        assert d[5] == {"new_tokens": 4}
        # the histograms read the same stamps
        assert tm.FASTGEN_QUEUE_WAIT_MS.count == 1
        assert tm.FASTGEN_TTFT_MS.count == 1
        assert tm.FASTGEN_ITL_MS.count == 3

    def test_request_keeps_one_set_of_stamps(self):
        from deepspeed_tpu.inference.v2.scheduler import Request
        fields = set(Request.__dataclass_fields__)
        assert {"submit_s", "admit_s", "first_token_s", "token_s",
                "submit_mono"} <= fields
        assert not fields & {"first_sched_s", "last_token_s", "slo_gen",
                             "first_sched_mono", "first_token_mono",
                             "last_token_mono"}

    def test_stamps_are_taken_with_telemetry_off(self):
        from deepspeed_tpu.inference.v2 import (FastGenScheduler,
                                                SamplingParams)
        sched = FastGenScheduler(_slo_engine())
        sched.submit(1, list(range(12)),
                     SamplingParams(max_new_tokens=3, temperature=0.0))
        req = sched._pending[0]
        sched.run_to_completion()
        assert 0 < req.submit_s <= req.admit_s <= req.first_token_s \
            <= req.token_s
        assert [r for r in get_tracer().records()
                if not r[0].startswith(NOT_OF_THE_TREE)] == []

    def test_a_failed_request_closes_the_span_that_was_open(self):
        from deepspeed_tpu.inference.v2 import (FastGenScheduler,
                                                SamplingParams)
        telemetry.enable()
        sched = FastGenScheduler(_slo_engine())
        sched.submit(3, list(range(12)),
                     SamplingParams(max_new_tokens=3, temperature=0.0))
        sched._fail_request(sched._pending[0], "expired", "test")
        spans = [r for r in get_tracer().records()
                 if r[0].startswith("request.")]
        assert [r[0] for r in spans] == ["request.queue_wait"]
        assert spans[0][8] == 3


# ---------------------------------------------------------------------------
# step-program formation
# ---------------------------------------------------------------------------

PROGRAM_CHILDREN = ["engine.program.trace", "engine.program.lower",
                    "engine.program.compile", "engine.program.cost"]


class TestProgramFormation:
    def test_a_new_key_is_recorded_with_telemetry_off(self):
        from deepspeed_tpu.inference.v2 import (FastGenScheduler,
                                                SamplingParams)
        assert not telemetry.enabled()
        eng = _slo_engine()
        sched = FastGenScheduler(eng)
        sched.submit(0, list(range(12)),
                     SamplingParams(max_new_tokens=2, temperature=0.0))
        sched.step()
        recs = get_tracer().records()
        progs = [r for r in recs if r[0] == "engine.program"]
        assert len(progs) == 1
        prog = progs[0]
        assert prog[5]["on_path"] is True
        assert prog[5]["key"] in eng.model._step_cache
        assert prog[5]["cache"] in ("hit", "miss", "off")
        kids = sorted(children_of(recs, prog[6]), key=lambda r: r[1])
        assert [k[0] for k in kids] == PROGRAM_CHILDREN + [
            "engine.program.first_run"]
        assert sum(k[2] for k in kids) <= prog[2]
        assert kids[2][5]["cache"] == prog[5]["cache"]
        # a second dispatch of the key forms nothing
        n = len(get_tracer().records())
        key = prog[5]["key"]
        eng.model._get_step(key)
        assert len(get_tracer().records()) == n

    def test_precompile_records_the_same_tree_off_the_path(self):
        eng = _slo_engine()
        key = (4, 1, 8, False, "sample", True)
        assert eng.precompile_keys([key]) == 1
        recs = get_tracer().records()
        prog = next(r for r in recs if r[0] == "engine.program")
        assert prog[5]["on_path"] is False and prog[5]["key"] == key
        assert [k[0] for k in sorted(children_of(recs, prog[6]),
                                     key=lambda r: r[1])] \
            == PROGRAM_CHILDREN
        # already compiled: nothing forms again
        assert eng.precompile_keys([key]) == 1
        assert sum(r[0] == "engine.program"
                   for r in get_tracer().records()) == 1

    def test_an_on_path_formation_nests_under_the_dispatch(self):
        from deepspeed_tpu.inference.v2 import (FastGenScheduler,
                                                SamplingParams)
        telemetry.enable()
        sched = FastGenScheduler(_slo_engine())
        sched.submit(0, list(range(12)),
                     SamplingParams(max_new_tokens=2, temperature=0.0))
        sched.step()
        recs = get_tracer().records()
        prog = next(r for r in recs if r[0] == "engine.program")
        assert by_id(recs)[prog[7]][0] == "engine.dispatch"


def test_paged_attention_kernel_is_named_by_kind_of_row():
    """The Pallas call is named from the static query width, so a trace
    splits the kernel's time without arithmetic; ``^paged_attention``
    still matches both."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.paged_attention import paged_attention

    def text(q_rows):
        q = jnp.zeros((2, q_rows, 4, 16), jnp.float32)
        kv = jnp.zeros((2, 9, 2, 2, 16, 16), jnp.float32)
        table = jnp.zeros((2, 4), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        return str(jax.make_jaxpr(
            lambda *a: paged_attention(*a, use_kernel=True,
                                       interpret=True))(
            q, kv, jnp.int32(1), table, pos, pos + q_rows))

    assert "paged_attention_decode" in text(1)
    assert "paged_attention_prefill" in text(8)
    assert "paged_attention_prefill" not in text(1)


def test_a_model_of_two_page_groups_carries_the_window_groups_names():
    """What the step of a model with two page groups (PR 31) adds to the
    tree: on ``fastgen.step`` the window group's pages, tokens and
    releases and what the decode rows attend in a layer of each kind, each
    read by a metric file of the benchmark or held for a trace by decision;
    ``kv.evict_window`` under ``engine.commit`` (a ``kv.*`` span, so
    ``kv_host_ms_per_step`` counts it); and the window kind's kernel calls
    under a name of their own that ``^paged_attention`` still matches."""
    import glob
    import json
    import os

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import FastGenScheduler, SamplingParams
    from deepspeed_tpu.ops.paged_attention import paged_attention
    from test_laguna import engine_of, family, sequences_of
    cfg, params = family()
    sched = FastGenScheduler(engine_of(cfg, params))
    telemetry.enable()
    for uid, p in enumerate(sequences_of((21, 30), seed=2)):
        sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=20))
    sched.run_to_completion()
    recs = [r for r in get_tracer().records()
            if not r[0].startswith("engine.program")]
    carried = {key for r in recs if r[0] == "fastgen.step" and r[5]
               for key in r[5]}
    new = {"kv_pages_reserved_window", "kv_tokens_held_window",
           "kv_pages_released_window", "attn_tokens_full",
           "attn_tokens_window"}
    assert new <= carried
    assert carried - new == {
        "path", "rows", "prefill_rows", "prefill_tokens", "tokens",
        "budget", "prompt_offers", "prompts_held", "kv_pages_reserved",
        "kv_tokens_held", "trunk_passes",
        "program", "moe_pairs_here", "moe_expert_load_max",
        "moe_experts_touched", "moe_tokens", "kv_slots_held",
        "kv_slots_live", "kv_slots_bucket"}
    read = set()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for path in glob.glob(os.path.join(root, "benchmark", "metrics",
                                       "*.json")):
        with open(path) as f:
            args = json.load(f).get("args", {})
        read |= {v[5:] for v in (args.get("value", ""),
                                 args.get("of_value", ""))
                 if v.startswith("attr:")}
    # the roofline's reader names its two attributes in code
    with open(os.path.join(root, "benchmark", "readers",
                           "mixed_attention_roofline.py")) as f:
        text = f.read()
    read |= {key for key in ("attn_tokens_full", "attn_tokens_window")
             if f'"{key}"' in text}
    # pages given back a step: held in the span ring for whoever reads a
    # trace (the eviction's pace), by decision no metric
    assert new - read == {"kv_pages_released_window"}
    ids = by_id(recs)
    evicts = [r for r in recs if r[0] == "kv.evict_window"]
    assert evicts and {ids[r[7]][0] for r in evicts} == {"engine.commit"}

    def text(name, q_rows):
        q = jnp.zeros((2, q_rows, 4, 16), jnp.float32)
        kv = jnp.zeros((2, 9, 2, 2, 16, 16), jnp.float32)
        table = jnp.zeros((2, 4), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        return str(jax.make_jaxpr(lambda *a: paged_attention(
            *a, use_kernel=True, interpret=True, window=32, name=name))(
            q, kv, jnp.int32(1), table, pos, pos + q_rows))

    assert "paged_attention_window_decode" in text("paged_attention_window", 1)
    assert "paged_attention_window_prefill" in text("paged_attention_window",
                                                    8)
    assert "paged_attention_decode" in text("paged_attention", 1)
    from deepspeed_tpu.inference.v2.modules import _kernel_name
    kinds = engine_of(cfg, params).model._kind_cfg
    assert _kernel_name(kinds["window"]) == "paged_attention_window"
    assert _kernel_name(kinds["full"]) == "paged_attention"


def test_a_model_with_a_state_pool_carries_the_state_pools_names():
    """What the step of a model with state-space layers (PR 34) adds to the
    tree: on ``fastgen.step`` the slots held, the rows the update kernel
    stepped, the true tokens the scan consumed and the bytes the held slots
    hold, each read by a metric file or a reader of the benchmark or held
    for a trace by decision; no ``kv.state_slot`` span (reserving a slot is
    a list pop inside ``engine.admit``: no host time worth a span); and the
    two kernels under names that ``^ssm_`` finds and no pattern that was
    here does."""
    import glob
    import json
    import os
    import re

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import FastGenScheduler, SamplingParams
    from deepspeed_tpu.ops.ssm import ssm_scan
    from test_jamba import engine_of, family, scan_args, sequences_of
    cfg, params = family()
    sched = FastGenScheduler(engine_of(cfg, params))
    telemetry.enable()
    for uid, p in enumerate(sequences_of((21, 30), seed=2)):
        sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=8))
    sched.run_to_completion()
    recs = [r for r in get_tracer().records()
            if not r[0].startswith("engine.program")]
    carried = {key for r in recs if r[0] == "fastgen.step" and r[5]
               for key in r[5]}
    new = {"ssm_slots_held", "ssm_rows_decode", "ssm_tokens_prefill",
           "ssm_state_bytes", "ssm_layers"}
    assert new <= carried
    assert carried - new == {
        "path", "rows", "prefill_rows", "prefill_tokens", "tokens",
        "budget", "prompt_offers", "prompts_held", "kv_pages_reserved",
        "kv_tokens_held", "trunk_passes",
        "program", "kv_slots_held", "kv_slots_live", "kv_slots_bucket"}
    assert not any(r[0].startswith("kv.state") for r in recs)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    read, patterns = set(), []
    for path in glob.glob(os.path.join(root, "benchmark", "metrics",
                                       "*.json")):
        with open(path) as f:
            metric = json.load(f)
        args = metric.get("args", {})
        read |= {v[5:] for v in (args.get("value", ""),
                                 args.get("of_value", ""))
                 if v.startswith("attr:")}
        if not os.path.basename(path).startswith("ssm_"):
            patterns += args.get("patterns", [])
    with open(os.path.join(root, "benchmark", "readers",
                           "ssm_roofline.py")) as f:
        text = f.read()
    read |= {key for key in new if f'"{key}"' in text}
    # the held slots' bytes and the layers they are spread over (PR 54:
    # ``<kind>_layers`` for every slot kind): in the span ring for whoever
    # reads a trace (slots x 9.3 MB at the published widths), by decision
    # no metric
    assert new - read == {"ssm_state_bytes", "ssm_layers"}
    assert {r[5]["ssm_layers"] for r in recs if r[0] == "fastgen.step"
            and r[5]} == {cfg.layer_kinds.count("ssm")}

    def jaxpr(Q):
        args, _ = scan_args(2, Q)
        return str(jax.make_jaxpr(lambda kw: ssm_scan(
            **kw, interpret=True))(args))

    assert "ssm_state_update_decode" in jaxpr(1)
    assert "ssm_scan_prefill" in jaxpr(8)
    for name in ("ssm_state_update_decode", "ssm_scan_prefill"):
        assert re.search("^ssm_", name)
        assert not any(re.search(p, name) for p in patterns), name


# ---------------------------------------------------------------------------
# the train step's scopes and spans (PR 37)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scope, phase", [
    ("train.params", "params"), ("train.fwd_bwd", None),
    ("train.grad_reduce", "grad_reduce"),
    ("train.grad_norm_clip", "grad_norm_clip"),
    ("train.optimizer", "optimizer")])
def test_the_train_steps_scopes_are_the_programs_contract(scope, phase):
    """A scope the step program enters is a name of the map the engine
    hands to ``telemetry/program_scopes.py``, and the other way round:
    ``train_update_time_share`` and its four siblings read the phases
    (inside ``train.fwd_bwd`` JAX's own markers decide)."""
    import inspect

    from deepspeed_tpu.runtime.engine import DeepSpeedEngine, TRAIN_SCOPES
    assert TRAIN_SCOPES[scope] == phase and len(TRAIN_SCOPES) == 5
    assert f'named_scope("{scope}")' in inspect.getsource(
        DeepSpeedEngine._build_train_step)


@pytest.mark.parametrize("span", ["train.step.dispatch", "train.step.wait",
                                  "train.after_step"])
def test_the_train_steps_spans_reach_the_profilers_trace(span):
    """The three spans ``train_batch`` gained: recorded by bare name, which
    the benchmark's trace reduction keeps (``idle_ms_per_step.train`` reads
    the idle time under ``^train\\.``); ``tests/test_train_scopes.py`` holds
    the tree."""
    import inspect
    import json
    import os
    import re

    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    source = inspect.getsource(DeepSpeedEngine._train_batch_spanned)
    assert f'trace_span("{span}")' in source
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmark.trace_reduce import HOST_SPAN
    assert HOST_SPAN.match(span)
    with open(os.path.join(root, "benchmark", "metrics",
                           "idle_ms_per_step.train.json")) as f:
        patterns = json.load(f)["args"]["patterns"]
    assert any(re.search(p, span) for p in patterns)


def test_a_model_with_delta_rule_layers_carries_the_delta_kinds_names():
    """What the step of a model with gated delta-rule layers (PR 39) adds
    to the tree: on ``fastgen.step`` the rows the update kernel stepped and
    the true tokens the chunked form consumed under the KIND's names, the
    context the decode rows attend in the full layers (which only a model
    of two page groups wrote before), and the pool's own ``ssm_slots_held``
    / ``ssm_state_bytes`` under the names they have; each read by a metric
    file or a reader of the benchmark or held for a trace by decision; and
    the two kernels under names that ``^delta_`` finds and no pattern that
    was here does."""
    import glob
    import json
    import os
    import re

    import jax

    from deepspeed_tpu.inference.v2 import FastGenScheduler, SamplingParams
    from deepspeed_tpu.ops.delta_rule import delta_rule
    from test_olmo_hybrid import engine_of, family, rule_args, sequences_of
    cfg, params = family()
    sched = FastGenScheduler(engine_of(cfg, params))
    telemetry.enable()
    for uid, p in enumerate(sequences_of((21, 30), seed=2)):
        sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=8))
    sched.run_to_completion()
    recs = [r for r in get_tracer().records()
            if not r[0].startswith("engine.program")]
    carried = {key for r in recs if r[0] == "fastgen.step" and r[5]
               for key in r[5]}
    new = {"delta_rows_decode", "delta_tokens_prefill", "attn_tokens_full"}
    kept = {"ssm_slots_held", "ssm_state_bytes", "delta_layers"}
    assert new | kept <= carried
    assert carried - new - kept == {
        "path", "rows", "prefill_rows", "prefill_tokens", "tokens",
        "budget", "prompt_offers", "prompts_held", "kv_pages_reserved",
        "kv_tokens_held", "trunk_passes",
        "program", "kv_slots_held", "kv_slots_live", "kv_slots_bucket"}
    assert not any(r[0].startswith("kv.state") for r in recs)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    read, patterns = set(), []
    for path in glob.glob(os.path.join(root, "benchmark", "metrics",
                                       "*.json")):
        with open(path) as f:
            metric = json.load(f)
        args = metric.get("args", {})
        read |= {v[5:] for v in (args.get("value", ""),
                                 args.get("of_value", ""))
                 if v.startswith("attr:")}
        if not os.path.basename(path).startswith(("delta_", "hybrid_")):
            patterns += args.get("patterns", [])
    with open(os.path.join(root, "benchmark", "readers",
                           "delta_roofline.py")) as f:
        text = f.read()
    read |= {key for key in new if f'"{key}"' in text}
    # every new attribute is read by the family's reader
    assert new <= read and "ssm_slots_held" in read

    def jaxpr(Q):
        args, _ = rule_args(2, Q)
        return str(jax.make_jaxpr(lambda kw: delta_rule(
            **kw, interpret=True))(args))

    assert "delta_state_update_decode" in jaxpr(1)
    assert "delta_chunk_prefill" in jaxpr(8)
    for name in ("delta_state_update_decode", "delta_chunk_prefill"):
        assert re.search("^delta_", name)
        assert not any(re.search(p, name) for p in patterns), name


@functools.lru_cache(maxsize=None)
def every_expert_held():
    """The tiny SmallThinker engine (every expert of its 4 layers held, 3
    pairs a token), two prompts served: one step with telemetry OFF, then
    it is switched on between two steps.  Built once for the tests below:
    ``(engine, the ring's records but the programs')``."""
    from deepspeed_tpu.inference.v2 import FastGenScheduler, SamplingParams
    from test_smallthinker import engine_of, family, sequences_of
    cfg, params = family(num_hidden_layers=4)
    engine = engine_of(cfg, params)
    sched = FastGenScheduler(engine)
    for uid, p in enumerate(sequences_of((21, 30), seed=2)):
        sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=12))
    sched.step()
    telemetry.enable()
    sched.run_to_completion()
    return engine, [r for r in get_tracer().records()
                    if not r[0].startswith(NOT_OF_THE_TREE)]


def test_held_experts_counts_go_beside_their_own_steps_tokens():
    """``ROADMAP.md`` B15: the first live step after telemetry is switched
    on drains the counts of a step that took no count of its tokens; they
    go on no span (they used to go beside a stale ``moe_tokens``, 0 in a
    fresh process, and ``moe_held_pair_share.whole`` read over 100%).
    Every later span pairs a step's counts with that step's own tokens:
    with every expert held, the pairs are exactly tokens x 3 x 4 layers."""
    _, recs = every_expert_held()
    steps = sorted((r for r in recs if r[0] == "fastgen.step"),
                   key=lambda r: r[1])
    first, later = steps[0][5], [r[5] for r in steps[1:]]
    assert first["tokens"] > 0 and not [k for k in first if "moe_" in k]
    counted = [a for a in later if "moe_pairs_here" in a]
    assert len(counted) >= 10
    assert all(a["moe_tokens"] > 0 for a in counted)
    pairs = sum(a["moe_pairs_here"] for a in counted)
    assert pairs / (sum(a["moe_tokens"] for a in counted) * 3 * 4) <= 1.0
    assert pairs == sum(a["moe_tokens"] for a in counted) * 3 * 4
    # each span's divisor is the step BEFORE it: the step it drains
    tokens = [a["tokens"] for a in [first] + later]
    for at, a in enumerate(later, start=1):
        if "moe_tokens" in a:
            assert a["moe_tokens"] == tokens[at - 1]


def test_a_model_that_holds_every_expert_carries_the_names_that_were_here():
    """What the step of the SmallThinker family (PR 47: two page groups at
    one head count, every expert of every layer held, the router ahead of
    attention) carries on ``fastgen.step``: exactly what a model of two
    page groups with held experts carried before it, and nothing new; the
    nine metric names it adds read those attributes (every expert held:
    a step's pairs are all here and ``moe_held_pair_share.whole`` reads
    100%); its kernels run under the names that ``^moe_expert_ffn`` and
    ``^paged_attention_window`` find, and no pattern that was here matches
    anything new."""
    import glob
    import json
    import os
    import re

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.modules import _kernel_name
    from deepspeed_tpu.moe import held
    engine, recs = every_expert_held()
    steps = [r[5] for r in recs if r[0] == "fastgen.step" and r[5]]
    carried = {key for attrs in steps for key in attrs}
    assert carried == {
        "path", "rows", "prefill_rows", "prefill_tokens", "tokens",
        "budget", "prompt_offers", "prompts_held", "kv_pages_reserved",
        "kv_tokens_held", "trunk_passes",
        "program", "moe_pairs_here", "moe_expert_load_max",
        "moe_experts_touched", "moe_tokens", "kv_slots_held",
        "kv_slots_live", "kv_slots_bucket", "kv_pages_reserved_window",
        "kv_tokens_held_window", "kv_pages_released_window",
        "attn_tokens_full", "attn_tokens_window"}
    counted = [a for a in steps if a.get("moe_tokens")]
    # every expert is held: 3 pairs a token in each of the 4 layers; under
    # the window a window layer holds what a global layer holds
    assert counted and all(a["moe_pairs_here"] == a["moe_tokens"] * 3 * 4
                           for a in counted)
    assert all(0 < a["moe_experts_touched"] <= 8 * 4 for a in counted)
    assert all(a["kv_tokens_held_window"] == a["kv_tokens_held"]
               and a["attn_tokens_window"] == a["attn_tokens_full"]
               for a in steps)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = "serve.reason-moe-closed256"
    mine = [m["name"] for m in spec["per_layer"]
            if m.get("workloads") == [cell]]
    assert mine == [
        "moe_experts_touched_share", "gqa7_attention_roofline",
        "window_attn_time_share.w4096", "kv_window_held_share.w4096",
        "kv_window_pages_peak_share.w4096", "moe_held_pair_share.whole",
        "moe_expert_load_imbalance.whole", "moe_expert_time_share.whole",
        "moe_expert_roofline.whole"]
    read, new_patterns, old_patterns = set(), [], []
    for path in glob.glob(os.path.join(root, "benchmark", "metrics",
                                       "*.json")):
        with open(path) as f:
            args = json.load(f).get("args", {})
        name = os.path.basename(path)[:-len(".json")]
        if name in mine:
            read |= {v[5:] for v in (args.get("value", ""),
                                     args.get("of_value", ""))
                     if v.startswith("attr:")}
            new_patterns += args.get("patterns", [])
        else:
            old_patterns += args.get("patterns", [])
    with open(os.path.join(root, "benchmark", "readers",
                           "smallthinker_roofline.py")) as f:
        text = f.read()
    read |= {key for key in carried if f'"{key}"' in text}
    assert read == {
        "moe_experts_touched", "moe_pairs_here", "moe_tokens",
        "moe_expert_load_max", "kv_tokens_held_window", "kv_tokens_held",
        "kv_pages_reserved_window", "attn_tokens_full",
        "attn_tokens_window"}
    # no new kernel, no new name: the patterns the cell's metrics read
    # were all here, and find this family's kernels
    assert set(new_patterns) == {"^moe_expert_ffn", "^paged_attention",
                                 "^paged_attention_window"} \
        <= set(old_patterns)
    kinds = engine.model._kind_cfg
    assert _kernel_name(kinds["window"]) == "paged_attention_window"
    assert _kernel_name(kinds["full"]) == "paged_attention"
    assert kinds["full"].num_heads == kinds["window"].num_heads == 14
    x = jnp.zeros((8, 128), jnp.float32)
    stack = {n: jnp.zeros((4, 32, 128), jnp.float32)
             for n in ("wg", "wu", "wd")}
    chosen = jnp.zeros((8, 2), jnp.int32)
    jaxpr = str(jax.make_jaxpr(lambda: held.held_experts_ffn(
        x, chosen, jnp.ones((8, 2)), stack, 0, interpret=True,
        act="relu"))())
    assert re.search(r"name=moe_expert_ffn\b", jaxpr) or \
        "moe_expert_ffn" in jaxpr
