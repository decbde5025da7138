"""The interval arithmetic of ``benchmark/trace_reduce.py`` on a synthetic
timeline worked by hand, and the reduction of a small recorded chip trace
(kept under ``data/``) to the numbers kept beside it."""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_clip_subtract():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert tr.total(tr.union([(0, 10), (2, 3)])) == 10
    assert tr.clip([(0, 4), (5, 9)], 3, 6) == [(3, 4), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                        (7, 10)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]


def synthetic():
    """One device, window [0, 100): a ``while`` [10, 60) holding a fusion
    [10, 30) and an all-gather [30, 50) that a kernel overlaps on [40, 50);
    then an all-reduce alone on [70, 80).  Host: step spans [0, 65) and
    [65, 100)."""
    ops = [("while.1", 10, 60), ("fusion.2", 10, 30),
           ("all-gather.3", 30, 50), ("flash_attention_fwd", 40, 50),
           ("all-reduce.4", 70, 80)]
    host = [("bench.traced", 0, 100), ("bench.step", 0, 65),
            ("bench.step", 65, 100), ("fastgen.plan", 60, 68)]
    return tr.Reduced({0: ops}, host, (0, 100))


def test_busy_names_exposed_and_gaps():
    red = synthetic()
    assert red.busy_ns(0) == 60                     # [10, 60) + [70, 80)
    assert red.window_s() == pytest.approx(100e-9)
    assert red.busy_s() == pytest.approx(60e-9)
    coll = ["all-gather", "all-reduce"]
    assert red.name_ns(0, coll) == 30               # [30, 50) + [70, 80)
    assert red.name_ns(0, ["flash_attention"]) == 10
    assert red.exposed_ns(0, coll) == 20            # [30, 40) + [70, 80)
    # self time: the while keeps only [50, 60), the all-gather [30, 40)
    own = tr.self_times(red.devices[0])
    assert own == {"while.1": 10, "fusion.2": 20, "all-gather.3": 10,
                   "flash_attention_fwd": 10, "all-reduce.4": 10}
    gaps = sorted(red.idle_gaps(0), key=lambda g: -g[1])
    assert gaps == [("bench.step", 20), ("bench.step", 10),
                    ("fastgen.plan", 10)]
    top = red.breakdown(top=1)
    assert top["device_ops"] == [["fusion.2", pytest.approx(20e-9)]]
    assert top["idle_gaps"] == [["bench.step", pytest.approx(20e-9)]]


def test_window_clips_events():
    red = synthetic()
    red.window = (20, 75)
    assert red.busy_ns(0) == 45                     # [20, 60) + [70, 75)
    assert red.name_ns(0, ["all-reduce"]) == 5


def test_recorded_chip_trace(tmp_path):
    """Three traced steps of the closed-loop serving cell on the chip (PR 23,
    then named ``serve.chat-closed64``),
    kept gzipped; the numbers were read off this file when it was cut."""
    want = json.load(open(os.path.join(DATA, "expected.json")))
    path = tmp_path / "recorded.xplane.pb"
    path.write_bytes(gzip.open(os.path.join(DATA, want["file"])).read())
    red = tr.load(str(path))
    dev = min(red.devices)
    assert sorted(red.devices) == want["devices"]
    assert red.window[1] - red.window[0] == want["window_ns"]
    assert red.busy_ns(dev) == want["busy_ns"]
    for pattern, ns in want["name_ns"].items():
        assert red.name_ns(dev, [pattern]) == ns, pattern
    assert red.exposed_ns(dev, want["collectives"]) == want["exposed_ns"]
    assert sum(n == "bench.step" for n, _, _ in red.host) == want["host_steps"]
    top = red.breakdown(top=1)
    assert top["device_ops"][0][0] == want["top_op"]
    assert 0.9 < red.busy_s() / red.window_s() < 1.0
