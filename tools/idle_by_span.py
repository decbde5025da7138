#!/usr/bin/env python3
"""Idle time of the first device in a kept trace, by the innermost host
span over each idle instant (the arithmetic of the benchmark's reader
``trace_idle_under``, for every span name at once):

    python tools/idle_by_span.py <file.xplane.pb>

``python -m benchmark.run ... --trace 1 --keep-trace <dir>`` keeps the
file."""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.readers.trace_idle_under import (  # noqa: E402
    idle_intervals, innermost, under)
from benchmark.trace_reduce import load, total  # noqa: E402


def by_name(red, window_span="bench.traced"):
    """``[(name, idle ns)]`` of the first device, most first; the window's
    own span is left out, so what lies under no other span is named
    ``(no host span)``."""
    idle = idle_intervals(red, min(red.devices))
    pieces = innermost([s for s in red.host if s[0] != window_span])
    out = {}
    for name in {n for _, _, n in pieces}:
        ns = under(idle, pieces, re.compile("^" + re.escape(name) + "$"))
        if ns:
            out[name] = ns
    out["(no host span)"] = total(idle) - sum(out.values())
    return sorted(out.items(), key=lambda kv: -kv[1])


if __name__ == "__main__":
    for name, ns in by_name(load(sys.argv[1])):
        print(f"{ns / 1e6:10.3f} ms  {name}")
