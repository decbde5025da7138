"""Reader ``span_window`` against records written by hand: what a program
writes with its telemetry off (one ``fastgen.stall`` a paused step) is read
over the whole measured window, 0 where nothing paused, nothing for a tree
without the meter.  CPU only; no engine."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.readers import span_window  # noqa: E402

NEW = ("stall_steps.serve", "stall_lost_ms.serve", "stall_gc_ms.serve",
       "stall_offcpu_ms.serve", "gc_ms_per_step.serve",
       "idle_ms_per_step.gc", "dispatch_prepare_ms_per_step",
       "dispatch_call_ms_per_step", "gc_ms_per_step.train")


def ctx(setup_s=10.0, seconds=30.0, slice_=(87.0, 90.0), steps=3):
    """Process start 50, window [60, 90), traced slice [87, 90)."""
    prof = types.SimpleNamespace(started_at=slice_[0], stopped_at=slice_[1],
                                 steps=steps)
    return types.SimpleNamespace(process_start=50.0, setup_s=setup_s,
                                 seconds=seconds, profiler=prof)


def rec(name, start, dur, attrs=None, sid=1, parent=None):
    return (name, start, dur, 0, 1, attrs, sid, parent, None)


def stall(start, dur, lost, gc=0.0, offcpu=0.0, phase="deliver"):
    return rec("fastgen.stall", start, dur, {
        "lost_ms": lost, "gc_ms": gc, "offcpu_ms": offcpu, "phase": phase})


def ring():
    """A stall in the rehearsal (before the window), two in the window's
    untraced seconds, one in the slice, one in the drain after it; three
    collections in the slice, one before it."""
    return [
        stall(55.0, 0.2, 190.0, gc=180.0),
        stall(61.0, 0.1, 90.0, gc=85.0),
        stall(70.0, 2.0, 1980.0, offcpu=1900.0, phase="between"),
        stall(88.0, 0.09, 75.0, gc=10.0, offcpu=60.0),
        stall(90.5, 0.3, 280.0),
        rec("fastgen.gc", 80.0, 0.004, {"generation": 0}),
        rec("fastgen.gc", 87.5, 0.003, {"generation": 0}),
        rec("fastgen.gc", 88.0, 0.030, {"generation": 2}),
        rec("fastgen.gc", 89.0, 0.0015, {"generation": 1}),
        rec("fastgen.step", 88.0, 0.09),
    ]


def metric(name):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, want", [
    ("stall_steps.serve", 3.0),
    ("stall_lost_ms.serve", 90.0 + 1980.0 + 75.0),
    ("stall_gc_ms.serve", 85.0 + 10.0),
    ("stall_offcpu_ms.serve", 1900.0 + 60.0),
    ("gc_ms_per_step.serve", (3.0 + 30.0 + 1.5) / 3),
    ("gc_ms_per_step.train", 0.0)])
def test_the_metric_files_over_records_by_hand(name, want):
    how = metric(name)
    assert how["reader"] == "span_window"
    assert span_window.reduce(ring(), ctx(), how["args"]) \
        == pytest.approx(want)


def test_nothing_paused_reads_zero_and_a_tree_without_the_meter_nothing():
    quiet = [r for r in ring() if r[0] == "fastgen.step"]
    for name in NEW[:5]:
        assert span_window.reduce(quiet, ctx(), metric(name)["args"]) == 0.0
    # an empty ring too: the run paused nowhere and formed no program
    assert span_window.reduce([], ctx(), metric(NEW[0])["args"]) == 0.0
    # the window never opened, or the slice never ran: nothing to say
    assert span_window.reduce(ring(), ctx(setup_s=None),
                              metric(NEW[0])["args"]) is None
    assert span_window.reduce(ring(), ctx(slice_=(None, None)),
                              metric("gc_ms_per_step.serve")["args"]) is None
    # the program's own ring, through ``read``: the tree has the meter (a
    # number), a tree without the attribute the file names has not (None)
    args = metric(NEW[0])["args"]
    assert span_window.read(ctx(), {}, args) is not None
    assert span_window.read(ctx(), {}, dict(args, meter="NoSuchMeter")) \
        is None


def test_where_and_the_other_windows():
    args = dict(metric("stall_steps.serve")["args"])
    assert span_window.reduce(ring(), ctx(), dict(
        args, where=["phase!=between"])) == 2.0
    assert span_window.reduce(ring(), ctx(), dict(args, span="slice")) == 1.0
    assert span_window.reduce(ring(), ctx(), dict(args, span="setup")) == 1.0
    assert span_window.reduce(ring(), ctx(), dict(
        args, value="attr:lost_ms", stat="sum", scale=0.001)) \
        == pytest.approx(2.145)


def test_the_nine_metrics_are_listed_with_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    listed = [m["name"] for m in spec["per_layer"]]
    at = listed.index(NEW[0])
    assert tuple(listed[at:at + len(NEW)]) == NEW
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    serving = [w["name"] for w in spec["workloads"]
               if w["name"].startswith("serve.")]
    tail = per_layer["dispatch_ms_per_step"]["workloads"][:4]
    for name in NEW:
        m, how = per_layer[name], metric(name)
        assert (m["unit"], m["layer"], m["better"], m["source"],
                m["moves"]) == (how["unit"], how["layer"], how["better"],
                                how["source"], how["moves"])
        assert len(how["what"]) >= 80
        # (a later family's cell may follow in a list: it is its to join)
        if name.endswith(".train"):
            assert m["workloads"][0] == "train.zero3-fsdp4"
        elif name.startswith("dispatch_"):
            assert m["workloads"][:4] == tail and len(tail) == 4
            assert m["moves"] == "itl_p95_ms"
        else:
            # every serving cell but the two whose lists a test holds shut
            assert m["workloads"][:5] == serving[:5] and len(serving) >= 7
            assert set(how["not_reported"]) == {
                "smallthinker-21b-serve-8l", "ling-3.0-flash-serve-7l-ep16"}
    assert per_layer["stall_steps.serve"]["unit"] == "count"
