"""The two readers of the program's span tree against a span ring and a
reduced trace worked by hand.  CPU only; the numbers are the arithmetic's,
not a device's."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.readers import span_ring, trace_idle_under  # noqa: E402


def ctx(steps=2, setup_s=10.0, slice_=(100.0, 101.0), reduced=None):
    prof = types.SimpleNamespace(started_at=slice_[0], stopped_at=slice_[1],
                                 steps=steps)
    return types.SimpleNamespace(
        process_start=50.0, setup_s=setup_s, profiler=prof, reduced=reduced,
        config={"engine": {"page_size": 64}})


def rec(name, start, dur, sid, parent=None, attrs=None, uid=None):
    return (name, start, dur, 0, 1, attrs, sid, parent, uid)


def ring():
    """Two steps in the slice [100, 101).  Step 1 [100.0, 100.4): drain
    [100.0, 100.3) = wait 0.2 + deliver 0.06 holding a kv.flush 0.02;
    dispatch.fused [100.3, 100.38) = engine.dispatch 0.05.  Step 2
    [100.5, 100.9): dispatch.chain 0.1 = engine.dispatch 0.04 + engine.admit
    0.01; drain 0.2 = wait 0.2.  One step before the slice, two programs
    formed in set-up [50, 60), one loaded and one compiled; three request
    spans ending in the slice."""
    return [
        rec("fastgen.step", 99.0, 0.5, 90, attrs={"path": "chain",
                                                 "tokens": 999}),
        rec("fastgen.step", 100.0, 0.4, 1, attrs={
            "path": "fused", "tokens": 180, "rows": 54, "prefill_rows": 2,
            "prefill_tokens": 128, "budget": 768, "kv_tokens_held": 6000,
            "kv_pages_reserved": 120}),
        rec("fastgen.drain", 100.0, 0.3, 2, 1),
        rec("fastgen.drain.wait", 100.0, 0.2, 3, 2),
        rec("fastgen.drain.deliver", 100.2, 0.06, 4, 2),
        rec("kv.flush", 100.21, 0.02, 5, 4),
        rec("fastgen.dispatch.fused", 100.3, 0.08, 6, 1),
        rec("engine.dispatch", 100.31, 0.05, 7, 6),
        rec("fastgen.step", 100.5, 0.4, 11, attrs={
            "path": "chain", "tokens": 64, "rows": 64, "prefill_rows": 0,
            "prefill_tokens": 0, "budget": 768, "kv_tokens_held": 6400,
            "kv_pages_reserved": 130}),
        rec("fastgen.dispatch.chain", 100.5, 0.1, 12, 11),
        rec("engine.admit", 100.5, 0.01, 13, 12),
        rec("engine.dispatch", 100.52, 0.04, 14, 12),
        rec("fastgen.drain", 100.6, 0.2, 15, 11),
        rec("fastgen.drain.wait", 100.6, 0.2, 16, 15),
        rec("request.queue_wait", 98.0, 2.3, 20, uid=5),
        rec("request.prefill", 100.3, 0.1, 21, uid=5),
        rec("request.decode", 98.0, 2.5, 22, attrs={"new_tokens": 50},
            uid=4),
        rec("request.decode", 99.0, 1.9, 23, attrs={"new_tokens": 30},
            uid=3),
        rec("engine.program", 51.0, 2.0, 30,
            attrs={"on_path": False, "cache": "hit"}),
        rec("engine.program.trace", 51.0, 1.0, 31, 30),
        rec("engine.program.lower", 52.0, 0.5, 32, 30),
        rec("engine.program.compile", 52.5, 0.4, 33, 30,
            {"cache": "hit"}),
        rec("engine.program", 55.0, 4.0, 34,
            attrs={"on_path": True, "cache": "miss"}),
        rec("engine.program.trace", 55.0, 1.0, 35, 34),
        rec("engine.program.lower", 56.0, 0.5, 36, 34),
        rec("engine.program.compile", 56.5, 2.4, 38, 34,
            {"cache": "miss"}),
        rec("engine.program", 70.0, 9.0, 37, attrs={"on_path": True}),
    ]


def metric_args(name):
    """The ``args`` of a metric file this PR adds."""
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        return json.load(f)["args"]


CASES = [
    # 100 x (1 - (0.2 + 0.2) / (0.4 + 0.4))
    ({"names": [r"^fastgen\.drain\.wait$"], "of": [r"^fastgen\.step$"],
      "complement": True, "scale": 100}, 50.0),
    # self times: step 0.02 + 0.1, drain 0.04 + 0.0, dispatch 0.03 + 0.05
    ({"names": [r"^fastgen\.step$", r"^fastgen\.dispatch\.",
                r"^fastgen\.drain$"], "value": "self_ms",
      "stat": "per_step"}, 240.0 / 2),
    # deliver 0.06 less the flush inside it
    ({"names": [r"^fastgen\.drain\.deliver$"], "value": "self_ms",
      "stat": "per_step"}, 40.0 / 2),
    ({"names": [r"^engine\.admit$", r"^kv\."], "value": "self_ms",
      "stat": "per_step"}, 30.0 / 2),
    ({"names": [r"^engine\.dispatch$"], "value": "self_ms",
      "stat": "per_step"}, 90.0 / 2),
    # the step before the slice does not count
    ({"names": [r"^fastgen\.step$"], "where": ["path=chain"],
      "stat": "share"}, 50.0),
    ({"names": [r"^fastgen\.step$"], "value": "attr:tokens",
      "stat": "mean"}, 122.0),
    ({"names": [r"^fastgen\.step$"], "value": "attr:kv_tokens_held",
      "of": [r"^fastgen\.step$"], "of_value": "attr:kv_pages_reserved",
      "of_scale": "config:engine.page_size", "scale": 100},
     100.0 * 12400 / (250 * 64)),
    ({"names": [r"^request\.queue_wait$"], "stat": "p95"}, 2300.0),
    # set-up [50, 60): the program formed at 70 s is outside it
    ({"names": [r"^engine\.program$"], "stat": "p50", "scale": 0.001,
      "span": "setup"}, 2.0),
    ({"names": [r"^engine\.program\.(trace|lower)$"],
      "of": [r"^engine\.program$"], "span": "setup", "scale": 100}, 50.0),
    ({"names": [r"^engine\.program$"], "where": ["on_path=true"],
      "stat": "count", "span": "setup"}, 1.0),
    ({"names": [r"^engine\.program$"], "where": ["cache=off"],
      "stat": "count", "span": "setup"}, 0.0),
    ({"names": [r"^engine\.program$"], "where": ["cache!=hit"],
      "stat": "count", "span": "setup"}, 1.0),
    # the metric files themselves, on the same ring: seconds per program
    # formed in set-up (two), by what a cache saves and what it does not
    ("program_trace_lower_s", (1.0 + 0.5 + 1.0 + 0.5) / 2),
    ("program_compile_s", 2.4 / 2),
    ("program_cache_hit_share.setup", 50.0),
    ("programs_on_path.setup", 1.0),
    ("host_busy_share.serve", 50.0),
    ("chained_step_share", 50.0),
    ("kv_fill_share", 100.0 * 12400 / (250 * 64)),
    ("budget_fill_share", 100.0 * (180 + 64) / (2 * 768)),
    ("prefill_row_share", 100.0 * 2 / (54 + 64)),
    ("prefill_token_share", 100.0 * 128 / (180 + 64)),
    ("request_prefill_p95_ms", 100.0),
    ("decode_ms_per_token", (2500.0 + 1900.0) / 80),
    ("queue_wait_p95_ms", 2300.0),
]


@pytest.mark.parametrize("args,want", CASES,
                         ids=[str(i) for i in range(len(CASES))])
def test_span_ring_by_hand(args, want):
    if isinstance(args, str):
        args = metric_args(args)
    assert span_ring.reduce(ring(), ctx(), args) == pytest.approx(want)


def test_a_regime_with_no_such_program_reads_zero_not_nothing():
    """A warm set-up compiles nothing and a cold one loads nothing: the
    seconds per program are 0 there, so the line never lacks the metric."""
    warm = [r for r in ring() if not (r[5] or {}).get("cache") == "miss"]
    assert span_ring.reduce(warm, ctx(), metric_args("program_compile_s")) \
        == 0.0
    assert span_ring.reduce(
        warm, ctx(), metric_args("program_cache_hit_share.setup")) == 100.0
    cold = [r for r in ring() if not (r[5] or {}).get("cache") == "hit"]
    assert span_ring.reduce(cold, ctx(), metric_args("program_compile_s")) \
        == pytest.approx(2.4 / 1)
    assert span_ring.reduce(
        cold, ctx(), metric_args("program_cache_hit_share.setup")) == 0.0


def test_covered_time_counts_overlapping_threads_once():
    """Two threads trace at once, [51, 52) and [51.5, 52.5), and one of
    them lowers on [52.5, 53): 2.0 s in which somebody traces or lowers,
    where the spans' durations (each holding its wait for the interpreter
    lock) sum to 2.5."""
    two = [rec("engine.program", 51.0, 1.0, 1),
           rec("engine.program.trace", 51.0, 1.0, 2, 1),
           rec("engine.program", 51.5, 1.5, 3),
           rec("engine.program.trace", 51.5, 1.0, 4, 3),
           rec("engine.program.lower", 52.5, 0.5, 5, 3)]
    args = metric_args("program_trace_lower_s")
    assert span_ring.reduce(two, ctx(), args) == pytest.approx(2.0 / 2)
    assert span_ring.reduce(two, ctx(), dict(args, value="dur_ms")) \
        == pytest.approx(2.5 / 2)


def test_span_ring_has_nothing_to_read():
    args = {"names": [r"^fastgen\.step$"], "stat": "count"}
    assert span_ring.reduce([], ctx(), args) is None
    # a program from before the span tree: records without ids
    old = [r[:6] for r in ring()]
    assert span_ring.reduce(old, ctx(), args) is None
    assert span_ring.reduce(ring(), ctx(), {"names": ["^nope$"]}) is None
    # no slice was traced, no window opened
    assert span_ring.reduce(ring(), ctx(slice_=(None, None)), args) is None
    assert span_ring.reduce(ring(), ctx(setup_s=None),
                            dict(args, span="setup")) is None
    # spans but no steps to divide by
    assert span_ring.reduce(ring(), ctx(steps=0), dict(
        args, value="self_ms", stat="per_step")) is None


def test_span_ring_reads_the_programs_ring():
    """``read`` takes the live ring: a formation recorded with telemetry
    off is there to be counted."""
    from deepspeed_tpu.telemetry import get_tracer
    tracer = get_tracer()
    if not hasattr(tracer, "span"):
        pytest.skip("a program from before the span tree")
    tracer.clear()
    try:
        with tracer.span("engine.program", {"on_path": False}):
            pass
        start = tracer.records()[0][1]
        c = ctx()
        c.process_start, c.setup_s = start - 1.0, 2.0
        assert span_ring.read(c, {}, {
            "names": [r"^engine\.program$"], "stat": "count",
            "span": "setup"}) == 1.0
    finally:
        tracer.clear()


def reduced():
    """Window [0, 1000).  The device is busy on [0, 100), [400, 500) and
    [900, 1000): idle 300 + 400.  The host: one step [50, 950) holding
    drain [90, 300) (wait [90, 150), deliver [150, 300) with a kv.flush
    [200, 250) inside), admission [300, 360), dispatch.fused [360, 520)
    holding engine.build_batch [380, 420), and nothing on [520, 950).  The
    first gap [100, 400) crosses drain, admission and dispatch; its middle
    (250) lies in the deliver."""
    ops = [("fusion.1", 0, 100), ("paged_attention_decode.2", 400, 500),
           ("fusion.3", 900, 1000)]
    host = [("bench.traced", 0, 1000), ("fastgen.step", 50, 950),
            ("fastgen.drain", 90, 300), ("fastgen.drain.wait", 90, 150),
            ("fastgen.drain.deliver", 150, 300), ("kv.flush", 200, 250),
            ("fastgen.admission", 300, 360),
            ("fastgen.dispatch.fused", 360, 520),
            ("engine.build_batch", 380, 420)]
    return tr.Reduced({0: ops}, host, (0, 1000))


DRAIN = metric_args("idle_ms_per_step.drain")["patterns"]
SCHED = metric_args("idle_ms_per_step.schedule")["patterns"]


def test_idle_under_splits_a_gap_that_crosses_three_spans():
    red = reduced()
    c = ctx(steps=2, reduced=red)
    assert trace_idle_under.idle_intervals(red, 0) == [(100, 400),
                                                       (500, 900)]
    # idle_gaps names the whole 300 ns gap by its middle
    assert red.idle_gaps(0)[1] == ("fastgen.drain.deliver", 300)
    # under drain: [100, 300) less the KV manager's flush inside it
    assert trace_idle_under.read(c, {}, {"patterns": DRAIN}) \
        == pytest.approx(150 / 1e6 / 2)
    # the flush [200, 250), admission [300, 360), dispatch [360, 400) and
    # [500, 520)
    assert trace_idle_under.read(c, {}, {"patterns": SCHED}) \
        == pytest.approx(170 / 1e6 / 2)


def test_idle_under_innermost_span_wins():
    red = reduced()
    c = ctx(steps=1, reduced=red)
    # the flush alone: [200, 250); the delivery it lies in does not hold
    # it, whichever metric names it
    assert trace_idle_under.read(c, {}, {"patterns": [r"^kv\."]}) \
        == pytest.approx(50 / 1e6)
    assert trace_idle_under.read(
        c, {}, {"patterns": [r"^fastgen\.drain\.deliver$"]}) \
        == pytest.approx(100 / 1e6)
    # under the step and no child of it: [520, 900), nobody's pattern
    assert trace_idle_under.read(c, {}, {"patterns": [r"^fastgen\.step$"]}) \
        == pytest.approx(380 / 1e6)
    pieces = trace_idle_under.innermost(
        [s for s in red.host if s[0] != "bench.traced"])
    assert ("kv.flush" in {n for a, b, n in pieces if a >= 200 and b <= 250}
            and (150, 200, "fastgen.drain.deliver") in pieces)


def test_idle_by_name_accounts_for_all_of_it():
    # the program's tool beside the reader (not under the benchmark's paths)
    idle_by_span = pytest.importorskip("tools.idle_by_span")
    named = dict(idle_by_span.by_name(reduced()))
    assert named == {
        "fastgen.step": 380,             # [520, 900): under no child
        "fastgen.drain.wait": 50, "fastgen.drain.deliver": 100,
        "kv.flush": 50, "fastgen.admission": 60,
        # [360, 380) and [500, 520); [380, 400) is the batch build's
        "fastgen.dispatch.fused": 40, "engine.build_batch": 20,
        "(no host span)": 0}
    assert sum(named.values()) == 700


def test_idle_under_has_nothing_to_read():
    args = {"patterns": DRAIN}
    assert trace_idle_under.read(ctx(reduced=None), {}, args) is None
    assert trace_idle_under.read(ctx(steps=0, reduced=reduced()), {},
                                 args) is None
    bare = tr.Reduced({0: [("fusion.1", 0, 100)]}, [], (0, 1000))
    assert trace_idle_under.read(ctx(reduced=bare), {}, args) is None
