"""``benchmark/metrics/kv_copy_time_share.json`` (PR 25) read against the
recorded chip trace under ``tests/benchmark/data/``: the trace of the step
program that still copied the KV pool."""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402

DATA = os.path.join(ROOT, "tests", "benchmark", "data")


def test_kv_copy_time_share_on_the_recorded_trace(tmp_path):
    """The patterns cover the pool movers (``copy.98``,
    ``copy_bitcast_fusion.20``, ``constant_dynamic-slice_fusion.9``, ...),
    70.6% of busy.  ``^copy`` alone would also match the ``copy-start``
    events of the async line, which hold a step's small operands in flight
    across its whole layer loop (86.8%, and 99.9% on a program with no mover
    left)."""
    want = json.load(open(os.path.join(DATA, "expected.json")))
    args = json.load(open(os.path.join(
        os.path.dirname(tr.__file__), "metrics",
        "kv_copy_time_share.json")))["args"]
    path = tmp_path / "recorded.xplane.pb"
    path.write_bytes(gzip.open(os.path.join(DATA, want["file"])).read())
    red = tr.load(str(path))
    dev = min(red.devices)
    share = 100.0 * red.name_ns(dev, args["patterns"]) / red.busy_ns(dev)
    assert share == pytest.approx(70.6, abs=0.05)
    assert not tr.matching([("copy-start.4", 0, 1)], args["patterns"])
    assert red.name_ns(dev, ["^copy"]) > 1.2 * red.name_ns(dev, ["^copy[._]"])
