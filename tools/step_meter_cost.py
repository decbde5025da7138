#!/usr/bin/env python
"""What the serving step's always-on meter costs the host (ISSUE 52).

    python tools/step_meter_cost.py [--steps 100000]

Runs the clock reads and adds that one ``FastGenScheduler.step`` makes for
``telemetry/watchdog.py::StepMeter`` with telemetry off (``begin``, two
admission marks, one build, one dispatch, one wait, one deliver, ``end``
and the detector's two samples; the CPU clocks' baseline once in 0.25 s,
as in a run), around an empty step, and prints the microseconds a step.  On a tree without the meter it prints ``null``: the
parent's cost is nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def metered(steps: int) -> float:
    from deepspeed_tpu.telemetry.watchdog import StepMeter
    meter, now = StepMeter(), time.perf_counter
    start = now()
    for _ in range(steps):
        meter.begin()
        t = now()
        meter.admission += now() - t
        t = now()
        meter.admission += now() - t
        t = now()
        meter.build += now() - t
        t = now()
        meter.dispatch += now() - t
        t = now()
        t, then = now(), t
        meter.wait += t - then
        meter.deliver += now() - t
        meter.end(64, 0)
    return (now() - start) / steps * 1e6


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=100_000)
    args = ap.parse_args()
    try:
        each = [metered(args.steps) for _ in range(5)]
    except ImportError:
        each = None
    print(json.dumps({"steps": args.steps, "us_per_step": each and min(each),
                      "runs_us": each}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
