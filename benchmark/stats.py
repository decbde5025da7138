"""Arithmetic on samples, kept with the yardstick."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float):
    """Nearest-rank percentile (the rule of ``tools/replay_trace.py``,
    copied: the original is listed in PERF.md for deletion); None when
    there is no sample."""
    vals = sorted(v for v in values if v is not None)
    if not vals:
        return None
    k = min(len(vals) - 1, int(round(q / 100.0 * (len(vals) - 1))))
    return float(vals[k])


def stat(values, name: str):
    """``p<q>``, ``mean``, ``max``, ``min`` or ``sum`` of a list."""
    vals = [v for v in values if v is not None]
    if not vals:
        return None
    if name.startswith("p"):
        return percentile(vals, float(name[1:]))
    return float({"mean": statistics.fmean, "max": max, "min": min,
                  "sum": math.fsum}[name](vals))
