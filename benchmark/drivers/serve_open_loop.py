"""Driver ``serve_open_loop``: requests arrive on a schedule whatever the
server does (independent users), so a queue can grow and each request is
timed from when it was due.

Set-up serves a rehearsal stream of the same mix from a seed derived from
``--seed`` (its first ``warmup.fill`` requests at once, so the system
reaches its steady concurrency quickly) until ``serving.Rehearsal`` says
no program has formed for ``warmup.quiet_steps`` steps; ``warmup.hints``
may name a manifest of step programs to load first.  The window then opens
on the running system with the measured stream, whose due times count from
the window's first instant: the same seed offers the same requests however
long the rehearsal took."""

from __future__ import annotations

import time

from .. import serving, traffic_gen


def _offer(loop, reqs, i: int, origin: float, now: float, limit: int) -> int:
    """Submit what is due at ``now`` (at most ``limit``); returns the
    index of the next request."""
    sent = 0
    while i < len(reqs) and origin + reqs[i].due_s <= now and sent < limit:
        loop.submit(reqs[i], origin + reqs[i].due_s)
        i += 1
        sent += 1
    return i


def _turn(loop, reqs, i: int, origin: float, now: float, end: float) -> None:
    """One step, or a short sleep when the server is empty."""
    if loop.sched.has_work:
        loop.step()
    else:
        nxt = origin + reqs[i].due_s if i < len(reqs) else end
        time.sleep(max(0.0, min(nxt - now, 0.0005)))


def serve_stream(loop: serving.ServeLoop, reqs, seconds: float,
                 drain_s: float, rehearsal=(), warmed=None,
                 ramp_per_step: int = 1 << 30, at_open=None,
                 profiler=None, at_close=None) -> float:
    """Offer the ``rehearsal`` requests unmeasured until ``warmed(elapsed)``
    says so, then ``reqs`` for the ``seconds`` of the window (due times
    from its first instant), then drain; returns the window's first
    instant on the loop's clock."""
    clock = loop.clock
    start, i = clock(), 0
    while warmed is not None:
        now = clock()
        if warmed(now - start):
            break
        i = _offer(loop, rehearsal, i, start, now, ramp_per_step)
        _turn(loop, rehearsal, i, start, now, now + 0.0005)
    loop.reset_counters()
    if at_open is not None:
        at_open()
    t0, i = clock(), 0
    end = t0 + seconds
    while True:
        now = clock()
        if now >= end:
            break
        if profiler is not None:
            profiler.tick(now - t0, seconds, len(loop.step_wall_ms))
        i = _offer(loop, reqs, i, t0, now, 1 << 30)
        _turn(loop, reqs, i, t0, now, end)
    if at_close is not None:
        at_close()
    _offer(loop, reqs, i, t0, end, 1 << 30)   # due in the window, sent late
    loop.drain(end + drain_s)
    return t0


def steady_state_fill(mix: dict, n: int, seed: int, vocab: int):
    """``n`` extra requests due at the stream's start, each cut to a random
    remaining share of its output, as the requests in flight in a steady
    state are: the window then opens near the mix's own concurrency
    (rate x time in system) instead of climbing to it."""
    import numpy as np
    fill = traffic_gen.requests(mix, n, seed + 104729, vocab,
                                first_uid=1_000_000_000) if n else []
    share = np.random.default_rng([seed % (2 ** 63), 5]).uniform(0, 1, n)
    for r, u in zip(fill, share):
        r.new_tokens = max(1, int(np.ceil(u * r.new_tokens)))
    return fill


def run(ctx, system) -> dict:
    mix = ctx.traffic
    warm = mix["warmup"]
    hinted = serving.warm_hints(system, warm.get("hints"))
    print(f"hints: {hinted}", flush=True)

    reqs = traffic_gen.open_loop(mix, ctx.seconds, ctx.seed, system.vocab)
    warm_seed = ctx.seed + 15485863
    rehearse = steady_state_fill(
        mix, int(warm.get("fill", 0)), warm_seed, system.vocab
    ) + traffic_gen.open_loop(mix, float(warm["max_seconds"]), warm_seed,
                              system.vocab, first_uid=2_000_000_000)
    loop = serving.ServeLoop(system, annotate=ctx.annotate)
    marks = serving.WindowMarks(ctx, system, loop)
    rehearsal = serving.Rehearsal(system, loop, warm)

    t0 = serve_stream(loop, reqs, ctx.seconds, mix.get("drain_s", 10.0),
                      rehearse, rehearsal.ready,
                      int(warm.get("ramp_per_step", 4)), marks.open,
                      ctx.profiler, marks.close)
    return serving.finish(loop, system, marks,
                          dict(hinted, rehearsal=rehearsal.report),
                          t0, ctx.seconds, [r.uid for r in reqs])
