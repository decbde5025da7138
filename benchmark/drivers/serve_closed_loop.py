"""Driver ``serve_closed_loop``: ``clients`` callers, each sending its next
request the moment the last one completed (a pipeline of workers, or a
server driven at a fixed concurrency), so a slower server is offered less.

One loop runs through set-up and the window: the clients start
``ramp_per_step`` to a step and are served unmeasured (the rehearsal: the
same mix, the same supply) until every client is in flight and
``serving.Rehearsal`` says no program has formed for ``warmup.quiet_steps``
steps; the window then opens on the running system.  ``warmup.hints`` may
name a manifest of step programs to load first (``serving.warm_hints``).
Requests sent inside the window are the measured ones."""

from __future__ import annotations

from .. import serving, traffic_gen


class Supply:
    """The mix's fixed set of requests, handed out in an order the seed
    fixes; a new permutation of the same set when it runs out."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.next_uid = 0
        self.cycle = 0
        self.queue = []

    def take(self) -> traffic_gen.Request:
        if not self.queue:
            n = int(self.mix["set_size"])
            self.queue = traffic_gen.requests(
                self.mix, n, self.seed + 7919 * self.cycle, self.vocab,
                first_uid=self.next_uid)[::-1]
            self.next_uid += n
            self.cycle += 1
        return self.queue.pop()


def serve_clients(loop: serving.ServeLoop, supply: Supply, clients: int,
                  ramp_per_step: int, warmed, seconds: float,
                  drain_s: float, at_open=None, profiler=None,
                  at_close=None):
    """``warmed(elapsed) -> bool`` ends the rehearsal.  Returns (window's
    first instant, uids sent inside the window)."""
    clock = loop.clock
    start = t0 = clock()
    opened = False
    sent = []
    while True:
        now = clock()
        if not opened and loop.live >= clients and warmed(now - start):
            opened = True
            loop.reset_counters()
            if at_open is not None:
                at_open()
            t0 = now = clock()
        if opened and now - t0 >= seconds:
            break
        if profiler is not None and opened:
            profiler.tick(now - t0, seconds, len(loop.step_wall_ms))
        for _ in range(min(ramp_per_step, clients - loop.live)):
            req = supply.take()
            loop.submit(req, clock())
            if opened:
                sent.append(req.uid)
        loop.step()
    if at_close is not None:
        at_close()
    loop.drain(t0 + seconds + drain_s)
    return t0, sent


def run(ctx, system) -> dict:
    mix = ctx.traffic
    warm = mix["warmup"]
    hinted = serving.warm_hints(system, warm.get("hints"))
    print(f"hints: {hinted}", flush=True)
    loop = serving.ServeLoop(system, annotate=ctx.annotate)
    supply = Supply(mix, ctx.seed, system.vocab)
    marks = serving.WindowMarks(ctx, system, loop)
    rehearsal = serving.Rehearsal(system, loop, warm)

    t0, sent = serve_clients(
        loop, supply, int(mix["clients"]), int(mix.get("ramp_per_step", 4)),
        rehearsal.ready, ctx.seconds, mix.get("drain_s", 10.0), marks.open,
        ctx.profiler, marks.close)
    return serving.finish(loop, system, marks,
                          dict(hinted, rehearsal=rehearsal.report),
                          t0, ctx.seconds, sent)
