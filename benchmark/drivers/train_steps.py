"""Driver ``train_steps``: a cycle of seeded batches fed from the host, one
``train_batch`` per step; a step is complete when its loss is on the host.

The rate is taken over every step *started* inside ``--seconds`` and the
time from the window's first instant to the end of the last of them: the
step that straddles the window's end runs to its end and counts in both
terms, so a stall anywhere costs time and the rate is not quantised to the
whole steps that happen to fit."""

from __future__ import annotations

import math
import time

import numpy as np


def run(ctx, system) -> dict:
    mix = ctx.traffic
    engine = system.engine
    rng = np.random.default_rng([int(ctx.seed) % (2 ** 63), 11])
    batches = [{"input_ids": rng.integers(
        0, system.vocab, (system.rows, system.seq_len), dtype=np.int32)}
        for _ in range(mix["batches"])]
    warm_losses = [engine.train_batch(batches[i % len(batches)])
                   for i in range(mix["warmup_steps"])]
    print(f"warm-up losses: {warm_losses}", flush=True)

    losses, walls = [], []
    clock = time.perf_counter
    t0 = ctx.window_opens()
    last_end = t0
    step = mix["warmup_steps"]
    while True:
        start = clock()
        if start - t0 >= ctx.seconds:
            break
        ctx.profiler.tick(start - t0, ctx.seconds, len(walls))
        with ctx.annotate("bench.train_batch"):
            loss = float(engine.train_batch(batches[step % len(batches)]))
        end = clock()
        step += 1
        losses.append(loss)
        walls.append((end - start) * 1e3)
        last_end = end
    ctx.profiler.finish(len(walls))

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in system.devices)
    tokens = len(losses) * system.rows * system.seq_len
    span = last_end - t0
    first = warm_losses[0] if warm_losses else (losses[0] if losses else 0.0)
    finite = all(math.isfinite(x) for x in warm_losses + losses)
    want = math.log(system.vocab)
    return {
        "attempted": len(losses), "failed": sum(
            not math.isfinite(x) for x in losses),
        "correct": bool(finite and len(losses) >= 2
                        and losses[-1] < losses[0]
                        and abs(first - want)
                        <= ctx.config["loss"]["first_within"]),
        "first_loss": first, "window_first_loss": losses[0] if losses else None,
        "window_last_loss": losses[-1] if losses else None,
        "train_tok_s_chip": (tokens / span / len(system.devices)
                             if span > 0 else None),
        "step_wall_ms": walls, "steps": len(walls),
        "seq_len": system.seq_len,
        "rows_per_chip": system.rows // len(system.devices),
        "peak_hbm_bytes": peak,
    }
