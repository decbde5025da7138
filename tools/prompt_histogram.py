#!/usr/bin/env python3
"""One traced run of a serving cell, then its steps counted by the prompt
pieces they carried: the ``fastgen.step`` spans of the traced slice, from
the process's own span ring (``prefill_rows``; beside it the mean wall of
the steps of each count, and ``prompts_held`` / ``prompt_offers`` where
the program writes them):

    python tools/prompt_histogram.py --workload serve.short-closed64 \\
        --seed <n> --seconds 30

The arguments are ``benchmark.run``'s (``--trace 1`` is added).  It runs
the benchmark of the directory it is called from, so that a copy of
another commit is read with the same tool: ``cd .parent && python
../tools/prompt_histogram.py ...``."""

import json
import os
import sys

sys.path.insert(0, os.getcwd())


def histogram(records):
    """``{prompt pieces: [steps, summed span seconds]}`` and the two
    totals of the ridge's counters (None where no span carries them)."""
    by_count, offers, held = {}, None, None
    for name, _, dur, _, _, attrs, *_ in records:
        if name != "fastgen.step" or not attrs or "rows" not in attrs:
            continue
        cell = by_count.setdefault(int(attrs.get("prefill_rows", 0)),
                                   [0, 0.0])
        cell[0] += 1
        cell[1] += dur
        if "prompt_offers" in attrs:
            offers = (offers or 0) + int(attrs["prompt_offers"])
            held = (held or 0) + int(attrs["prompts_held"])
    return by_count, offers, held


def main(argv) -> int:
    from benchmark import run
    rc = run.main(list(argv) + ["--trace", "1"])
    from deepspeed_tpu.telemetry import get_tracer
    by_count, offers, held = histogram(get_tracer().records())
    steps = sum(n for n, _ in by_count.values())
    print("prompt_histogram: " + json.dumps({
        "steps": steps,
        "by_prompts": {str(k): {"steps": n,
                                "share": round(100.0 * n / steps, 2),
                                "span_ms": round(1e3 * s / n, 3)}
                       for k, (n, s) in sorted(by_count.items())},
        "two_or_more_share": round(100.0 * sum(
            n for k, (n, _) in by_count.items() if k >= 2) / max(steps, 1),
            2),
        "prompt_offers": offers, "prompts_held": held}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
