"""What both serving drivers share: the clock on each request and step.

The benchmark stamps tokens itself through ``FastGenScheduler.step(
on_token=...)``: time to first token runs from when a request was *due*,
not from ``submit`` (the program's own histograms time from submit and only
with telemetry on), so a stalled server is charged for the wait it imposes
on later requests.
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import Callable, Dict, List

from . import stats
from .traffic_gen import Request


class Stamp:
    __slots__ = ("req", "due", "submitted", "tokens", "bad", "done_at",
                 "rejected")

    def __init__(self, req: Request, due: float, submitted: float):
        self.req = req
        self.due = due
        self.submitted = submitted
        self.tokens: List[float] = []
        self.bad = 0
        self.done_at = None
        self.rejected = False

    @property
    def context(self) -> int:
        return len(self.req.prompt) + len(self.tokens)


class ServeLoop:
    """Submits requests to one scheduler, steps it, and keeps the stamps
    and per-step host counters the metrics are reduced from."""

    def __init__(self, system, clock: Callable[[], float] = time.perf_counter,
                 annotate=None):
        from deepspeed_tpu.inference.v2 import SamplingParams
        self._params = SamplingParams
        self.system = system
        self.sched = system.sched
        self.engine = system.engine
        self.clock = clock
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.stamps: Dict[int, Stamp] = {}
        self.live = 0                 # submitted and not ended
        self.on_done: Callable[[Stamp], None] = lambda s: None
        # per-step host counters
        self.step_wall_ms: List[float] = []
        self.step_tokens: List[int] = []
        self.step_decode_rows: List[int] = []
        self.step_decode_context: List[int] = []
        self.step_prefill_tokens: List[int] = []
        self.step_prefill_sq: List[int] = []
        self.free_pages_min = self.engine.free_blocks
        self._decoding_rows = 0
        self._decoding_context = 0
        self._prefilled = self._prefilled_sq = 0

    def submit(self, req: Request, due: float) -> None:
        with self.annotate("bench.submit"):
            now = self.clock()
            stamp = self.stamps[req.uid] = Stamp(req, due, now)
            err = self.sched.submit(
                req.uid, req.prompt,
                self._params(max_new_tokens=req.new_tokens))
        if err is not None:
            stamp.rejected = True
        else:
            self.live += 1

    def _on_token(self, uid: int, tok: int) -> None:
        stamp = self.stamps.get(uid)
        if stamp is None or stamp.done_at is not None:
            return
        now = self.clock()
        if not 0 <= tok < self.system.vocab:
            stamp.bad += 1
        first = not stamp.tokens
        stamp.tokens.append(now)
        if first:
            # the prompt's prefill ended with this token: charged to the
            # step that delivered it
            self._prefilled += len(stamp.req.prompt)
            self._prefilled_sq += len(stamp.req.prompt) ** 2
            self._decoding_rows += 1
            self._decoding_context += stamp.context
        else:
            self._decoding_context += 1
        if len(stamp.tokens) >= stamp.req.new_tokens:
            stamp.done_at = now
            self.live -= 1
            self._decoding_rows -= 1
            self._decoding_context -= stamp.context
            self.on_done(stamp)

    def step(self) -> None:
        rows, context = self._decoding_rows, self._decoding_context
        self._prefilled = self._prefilled_sq = 0
        with self.annotate("bench.step"):
            t0 = self.clock()
            self.sched.step(on_token=self._on_token)
            wall = self.clock() - t0
        self.step_wall_ms.append(wall * 1e3)
        self.step_tokens.append(int(self.sched.last_step_scheduled))
        self.step_decode_rows.append(rows)
        self.step_decode_context.append(context)
        self.step_prefill_tokens.append(self._prefilled)
        self.step_prefill_sq.append(self._prefilled_sq)
        self.free_pages_min = min(self.free_pages_min,
                                  self.engine.free_blocks)

    def reset_counters(self) -> None:
        """Forget the per-step counters (the window opens on a running
        system; what the warm-up stepped is not measured)."""
        self.step_wall_ms, self.step_tokens = [], []
        self.step_decode_rows, self.step_decode_context = [], []
        self.step_prefill_tokens, self.step_prefill_sq = [], []
        self.free_pages_min = self.engine.free_blocks

    def drain(self, deadline: float) -> None:
        """Keep stepping until every submitted request ended or the
        clock passes ``deadline``."""
        while self.live > 0 and self.sched.has_work \
                and self.clock() < deadline:
            self.step()


def programs_seen(system) -> int:
    """A number that grows whenever the system forms, compiles or loads a
    program: the engine's step cache plus JAX's own compile-cache events."""
    from deepspeed_tpu.utils.compile_cache import cache_counts
    return (len(system.engine.compiled_keys(dispatched_only=False))
            + sum(cache_counts().values()))


#: threads that load the hinted programs (tracing and lowering release the
#: interpreter lock for much of their time)
HINT_THREADS = 4


def warm_hints(system, name) -> dict:
    """Optional head start for the rehearsal: ``hints/<name>.json`` holds a
    manifest of step-program keys in the program's own export format
    (``engine.compiled_keys()``: the JSON list every run prints as ``step
    programs dispatched:``), compiled or
    loaded from the persistent cache through ``engine.precompile_keys`` on
    a few threads (a program costs seconds of tracing and lowering in
    every new process, much of it outside the interpreter lock).  A key
    this build cannot form is skipped and counted: the rehearsal that
    follows is what makes the window warm, with or without hints."""
    import concurrent.futures as cf
    import os

    keys = []
    if name:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "hints", name + ".json")
        with open(path) as f:
            keys = [tuple(k) for k in json.load(f)["keys"]]
    t0 = time.perf_counter()

    def one(key) -> int:
        try:
            return int(system.engine.precompile_keys([key]))
        except Exception as e:  # noqa: BLE001 — a hint never stops a run
            print(f"hint {key!r}: {type(e).__name__}: {e}", flush=True)
            return 0

    done = 0
    if keys:
        with cf.ThreadPoolExecutor(HINT_THREADS) as pool:
            done = sum(pool.map(one, keys))
    return {"hints": name or None, "listed": len(keys), "compiled": done,
            "skipped": len(keys) - done,
            "seconds": round(time.perf_counter() - t0, 2)}


class Rehearsal:
    """Decides when the unmeasured rehearsal stream of a mix has warmed
    the system: at least ``min_seconds`` served, and no program formed,
    compiled or loaded for ``quiet_steps`` steps in a row.  After
    ``max_seconds`` without that the run fails: a window that still
    compiles measures nothing.  ``program_events`` counts what
    ``programs_seen`` counts: a new step program once as a key, and once
    more when JAX compiles it or loads it from the persistent cache."""

    def __init__(self, system, loop: ServeLoop, spec: dict):
        self.system, self.loop = system, loop
        self.min_s = float(spec["min_seconds"])
        self.quiet_steps = int(spec["quiet_steps"])
        self.max_s = float(spec["max_seconds"])
        self.seen = programs_seen(system)
        self.quiet_since = len(loop.step_wall_ms)
        self.new_programs = 0
        self.report: dict = {}      # as of the last call to ready()

    def ready(self, elapsed: float) -> bool:
        seen, steps = programs_seen(self.system), len(self.loop.step_wall_ms)
        if seen != self.seen:
            self.new_programs += seen - self.seen
            self.seen, self.quiet_since = seen, steps
        if elapsed > self.max_s:
            raise SystemExit(
                f"warm-up: programs still forming after {self.max_s:.0f} s "
                f"({self.new_programs} since the hints)")
        self.report = {"seconds": round(elapsed, 2), "steps": steps,
                       "program_events": self.new_programs}
        return (elapsed >= self.min_s
                and steps - self.quiet_since >= self.quiet_steps)


def check_and_reduce(loop: ServeLoop, window_start: float, seconds: float,
                     measured_uids) -> dict:
    """The end-to-end numbers and the correctness facts of one window."""
    stamps = [loop.stamps[u] for u in measured_uids if u in loop.stamps]
    end = window_start + seconds
    ttft, itl = [], []
    failed = wrong = 0
    # all the work of the window, whoever sent it: every token generated
    # inside it, and the prompt of every request whose prefill ended inside
    # it (its first token)
    window_tokens = 0
    for s in loop.stamps.values():
        window_tokens += sum(window_start <= t <= end for t in s.tokens)
        if s.tokens and window_start <= s.tokens[0] <= end:
            window_tokens += len(s.req.prompt)
    for s in stamps:
        ended = s.done_at is not None
        if not ended or s.rejected:
            failed += 1
        if s.bad or (ended and len(s.tokens) != s.req.new_tokens):
            wrong += 1
        if s.tokens:
            ttft.append((s.tokens[0] - s.due) * 1e3)
            itl.extend((b - a) * 1e3 for a, b in zip(s.tokens, s.tokens[1:]))
    invariants = True
    try:
        loop.engine.state_manager.check_invariants()
    except Exception as e:  # noqa: BLE001 — reported as incorrect, not raised
        invariants = False
        print(f"invariants: {type(e).__name__}: {e}", flush=True)
    # a request that never produced a token misses every limit: it enters
    # the tail as the longest wait the run could have seen
    miss = [(end + 10.0 - s.due) * 1e3 for s in stamps if not s.tokens]
    return {
        "attempted": len(stamps), "failed": failed, "wrong": wrong,
        "invariants_clean": invariants, "probe_ok": loop.system.probe["ok"],
        "correct": bool(wrong == 0 and invariants
                        and loop.system.probe["ok"]),
        "ttft_samples": len(ttft) + len(miss), "itl_samples": len(itl),
        "ttft_ms": ttft + miss, "itl_ms": itl,
        "ttft_p95_ms": stats.percentile(ttft + miss, 95),
        "itl_p95_ms": stats.percentile(itl, 95),
        "ttft_p50_ms": stats.percentile(ttft + miss, 50),
        "itl_p50_ms": stats.percentile(itl, 50),
        "serve_tok_s": window_tokens / seconds,
        "gen_late_ms": [(s.submitted - s.due) * 1e3 for s in stamps],
    }


class WindowMarks:
    """What is read at the window's first and last instant: JAX's compile
    cache events, the compiled step programs, the requests in flight."""

    def __init__(self, ctx, system, loop: ServeLoop):
        self.ctx, self.system, self.loop = ctx, system, loop
        self.at = {}

    def _read(self, tag: str) -> None:
        from deepspeed_tpu.utils.compile_cache import cache_counts
        self.at["counts" + tag] = cache_counts()
        self.at["programs" + tag] = set(
            self.system.engine.compiled_keys(dispatched_only=False))
        self.at["live" + tag] = self.loop.live

    def open(self) -> None:
        self._read("0")
        self.ctx.window_opens()

    def close(self) -> None:
        self._read("1")
        self.ctx.profiler.finish(len(self.loop.step_wall_ms))


def window_facts(loop: ServeLoop, system, marks: dict, warm: dict) -> dict:
    """Host counters of the window: per-step samples, pages, and what was
    compiled or loaded between the window's first and last instant."""
    keys = sorted(system.engine.compiled_keys(), key=repr)
    # the manifest a hints file holds, as JSON
    print("step programs dispatched: "
          + json.dumps([list(k) for k in keys]), flush=True)
    return {
        "warmup": warm, "programs": len(keys),
        # programs compiled or loaded from the persistent cache (JAX's own
        # events) or first dispatched inside the window, whichever is more
        "compiles_in_window": max(
            sum(marks["counts1"].values()) - sum(marks["counts0"].values()),
            len(marks["programs1"] - marks["programs0"])),
        "programs_new_in_window": repr(sorted(
            marks["programs1"] - marks["programs0"], key=repr)),
        "live_at_open": marks["live0"], "live_at_close": marks["live1"],
        "step_wall_ms": loop.step_wall_ms, "step_tokens": loop.step_tokens,
        "step_decode_rows": loop.step_decode_rows,
        "step_decode_context": loop.step_decode_context,
        "step_prefill_tokens": loop.step_prefill_tokens,
        "step_prefill_sq": loop.step_prefill_sq,
        "kv_pages_peak_share": 100.0 * (1.0 - loop.free_pages_min
                                        / system.num_pages),
        "steps": len(loop.step_wall_ms)}


def finish(loop: ServeLoop, system, marks: "WindowMarks", warm: dict,
           window_start: float, seconds: float, measured_uids) -> dict:
    """One window's facts.  A window in which a program was formed,
    compiled or loaded measured the stall, not the mix: it is not
    ``correct``."""
    facts = check_and_reduce(loop, window_start, seconds, measured_uids)
    facts.update(window_facts(loop, system, marks.at, warm))
    facts["window_warm"] = facts["compiles_in_window"] == 0
    facts["correct"] = bool(facts["correct"] and facts["window_warm"])
    return facts
