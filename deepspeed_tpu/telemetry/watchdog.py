"""Health watchdog (ISSUE 5): the layer that ACTS on the telemetry
spine's signals instead of just recording them.

Four detectors, all fed from values the engines already hold on the
host (no new device syncs):

- **non-finite sentinel** — the training engine's host-fetched loss /
  grad-norm / fp16 overflow flag mint ``ds_train_nonfinite_total`` /
  ``ds_train_overflow_skip_total`` and a warn-once, so a NaN'd run is
  loud on step 1 instead of silently burning its budget.
- **step-time anomaly detector** — an EWMA mean + EWMA absolute
  deviation over ``train``/``fastgen`` step wall times; a step slower
  than ``threshold ×`` the running mean (after warmup) increments
  ``ds_train_anomaly_total``, warns once per storm, and auto-dumps the
  span ring (Chrome trace) around the offending step.
- **goodput accounting** — wallclock split into compile / input-wait /
  step / checkpoint / idle fractions via callback gauges fed from the
  same boundaries the spans cover (``ds_train_goodput_ratio`` = the
  step fraction, the number a fleet scheduler optimizes for).
- **serving recompile accounting** — step-cache hits vs misses and XLA
  compiles on the request path (``ds_fastgen_step_cache_miss_total`` /
  ``ds_fastgen_compile_on_path_total``), with a recompile-storm warning
  naming the uncovered ``(S, Q, P, fresh, kind)`` keys — the failure
  mode the AOT bucket lattice exists to prevent, now measured.

- **the host's pauses** (ISSUE 52) — one ``gc.callbacks`` hook a
  process (:class:`HostCollector`: seconds and counts always, a span a
  collection with telemetry on) and one ``fastgen.stall`` record a
  paused serving step (:class:`StepMeter`, :meth:`Watchdog.
  observe_serving_step`), written with telemetry off too: the step-time
  detector above, fed on every step, with a floor of 50 ms.

Disabled-path contract: every per-step entry point reads
``state.enabled`` first and returns — the same one-attribute-read cost
bound the spans keep.  Two exceptions count unconditionally: the
recompile counters (like ``ServingCounters``: a compile is ~10^7× their
cost and a storm must be visible even telemetry-off) and the serving
step's meter (a few clock reads a step: the pauses it is for fall into
the runs that are measured with telemetry off).
"""

from __future__ import annotations

import collections
import gc
import os
import threading
import time
from typing import Any, Dict, Optional

from .state import state
from . import metrics as tm
from .tracer import get_tracer

#: process start reference for /healthz uptime
_T0 = time.monotonic()
#: the meter's three clocks: the wall, this thread's CPU, the process's
_now, _thread_cpu, _process_cpu = (time.perf_counter, time.thread_time,
                                   time.process_time)


class _KindState:
    """Per-stream (``train`` / ``fastgen``) EWMA step-time state."""
    __slots__ = ("mean_ms", "dev_ms", "n", "in_storm", "calm",
                 "anomalies", "last_ms", "last_anomaly_ms")

    def __init__(self):
        self.mean_ms = 0.0
        self.dev_ms = 0.0
        self.n = 0
        self.in_storm = False
        self.calm = 0
        self.anomalies = 0
        self.last_ms = 0.0
        self.last_anomaly_ms = 0.0


class _DriftState:
    """Resident-bytes EWMA state for the memory-drift detector
    (ISSUE 20) — the step-time machinery with bytes in place of ms."""
    __slots__ = ("mean_b", "n", "in_storm", "calm", "anomalies",
                 "last_b", "last_anomaly_b")

    def __init__(self):
        self.mean_b = 0.0
        self.n = 0
        self.in_storm = False
        self.calm = 0
        self.anomalies = 0
        self.last_b = 0.0
        self.last_anomaly_b = 0.0


#: goodput phases; ``idle`` is derived (wall − accounted), never noted
GOODPUT_PHASES = ("compile", "input_wait", "step", "checkpoint")


class _PhaseTimer:
    """Tiny context manager accumulating one goodput phase (the enabled
    path of :meth:`Watchdog.track`)."""
    __slots__ = ("wd", "phase", "t0")

    def __init__(self, wd: "Watchdog", phase: str):
        self.wd = wd
        self.phase = phase

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.wd.note_phase(self.phase, time.perf_counter() - self.t0)
        return False


class _NullTrack:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_TRACK = _NullTrack()


class HostCollector:
    """The process's one ``gc.callbacks`` hook (:func:`install_collector`,
    when an engine is built).  Always, telemetry off too: seconds spent in
    the collector, collections and full collections, from two clock reads
    a collection; a :class:`StepMeter` reads them before and after a step.
    With telemetry on every collection is a span, nested under the span
    open on its thread and mirrored into the profiler's trace from the
    ``start`` to the ``stop`` callback: ``fastgen.gc`` or ``train.gc`` by
    the loop that stepped last (``loop``, one attribute write at a step's
    entry; no span before any loop has stepped).  Collections do not
    nest, so one start stamp and one open span serve the process."""
    __slots__ = ("seconds", "collections", "full", "loop", "_t0", "_span")

    def __init__(self):
        self.seconds = 0.0
        self.collections = 0
        self.full = 0
        self.loop: Optional[str] = None
        self._t0 = 0.0
        self._span = None

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            if state.enabled and self.loop is not None:
                self._span = get_tracer().span(self.loop + ".gc")
                self._span.__enter__()
            self._t0 = _now()
            return
        seconds = _now() - self._t0
        self.seconds += seconds
        self.collections += 1
        if info["generation"] == 2:
            self.full += 1
        tm.HOST_GC_SECONDS.inc(seconds)
        span, self._span = self._span, None
        if span is not None:
            span.set("generation", info["generation"])
            span.set("collected", info["collected"])
            span.__exit__(None, None, None)


#: process-wide singleton
_COLLECTOR = HostCollector()


def get_collector() -> HostCollector:
    return _COLLECTOR


def install_collector() -> HostCollector:
    """Hook the collector's callbacks, once a process."""
    if _COLLECTOR not in gc.callbacks:
        gc.callbacks.append(_COLLECTOR)
    return _COLLECTOR


#: a serving step under this is no stall, whatever the mean: no sound step
#: of any cell passes it, every pause seen starts at 86 ms (PERF.md §7)
STALL_FLOOR_MS = 50.0
#: seconds between two readings of the CPU clocks' baseline
CPU_BASELINE_S = 0.25
#: the record of a paused serving step in the span ring
STALL_SPAN = "fastgen.stall"
#: the phases of a serving step, marked where their spans stand
STALL_PHASES = ("admission", "build", "dispatch", "wait", "deliver")


class StepMeter:
    """What one serving loop's step cost the host, taken with telemetry
    off too: the wall, the collector's accumulators, the step programs
    formed on the path, the seconds of five phases (added by the scheduler
    and the engine where their spans stand) and the gap since the last
    step returned: wall-clock reads only, a few microseconds a step.
    ``begin`` / ``end`` bracket ``FastGenScheduler.step``; ``end`` hands
    the meter to :meth:`Watchdog.observe_serving_step`, which writes one
    ``fastgen.stall`` record where the step, or the gap before it, passed
    the step-time rule.

    The two CPU clocks are system calls (5.7 us each on the benchmark's
    host, in 10 ms ticks), so no sound step reads them: a baseline is read
    once in :data:`CPU_BASELINE_S`, and a paused stretch's CPU time is
    what the clocks gained since the baseline less what the sound steps
    since then account for (:meth:`pause_clocks`)."""
    __slots__ = STALL_PHASES + (
        "steps", "t0", "t1", "between_s", "gc_s0", "gc_n0", "gc_full0",
        "programs0", "gap", "base_t", "base_cpu", "base_proc",
        "busy_since", "others_rate")

    def __init__(self):
        for phase in STALL_PHASES:
            setattr(self, phase, 0.0)
        #: steps begun, live or not (a stall's line counts by it)
        self.steps = 0
        self.t0 = self.t1 = self.between_s = 0.0
        self.gc_s0, self.gc_n0, self.gc_full0 = 0.0, 0, 0
        self.programs0 = 0
        #: the gap's own EWMA: the rule of the step's stream, held apart
        self.gap = _KindState()
        #: the CPU clocks' baseline, this thread's seconds of sound steps
        #: since (a sound step runs but for its wait), and the other
        #: threads' CPU seconds a second over the last whole baseline
        self.base_t = self.base_cpu = self.base_proc = 0.0
        self.busy_since = self.others_rate = 0.0

    def begin(self) -> None:
        self.admission = self.build = self.dispatch = 0.0
        self.wait = self.deliver = 0.0
        now = _now()
        if self.steps:
            self.between_s = now - self.t1
        else:
            # the clocks and the accumulators open with the first step
            self.base_t, self.base_cpu, self.base_proc = (
                now, _thread_cpu(), _process_cpu())
            self._mark_collector()
        self.steps += 1
        self.programs0 = tm.FASTGEN_COMPILE_ON_PATH.value
        self.t0 = now

    def end(self, rows: int, step: int) -> None:
        self.t1 = now = _now()
        if not _WATCHDOG.observe_serving_step(self, rows, step):
            self.busy_since += now - self.t0 - self.wait + self.between_s
            if now - self.base_t >= CPU_BASELINE_S:
                cpu, proc = _thread_cpu(), _process_cpu()
                self.others_rate = ((proc - self.base_proc)
                                    - (cpu - self.base_cpu)) \
                    / (now - self.base_t)
                self.base_t, self.base_cpu, self.base_proc = now, cpu, proc
                self.busy_since = 0.0
        # what the collector did from here on belongs to the next step's
        # record: the gap before it, and the step
        self._mark_collector()

    def _mark_collector(self) -> None:
        self.gc_s0, self.gc_n0, self.gc_full0 = (
            _COLLECTOR.seconds, _COLLECTOR.collections, _COLLECTOR.full)

    def pause_clocks(self):
        """``(this thread's, the other threads')`` CPU seconds of the
        stretch that just paused (the gap and the step): what the clocks
        gained since the baseline, less the sound steps' own running time
        since then and the other threads' usual rate.  Read once a stall;
        the reading is the next baseline."""
        cpu, proc = _thread_cpu(), _process_cpu()
        mine = cpu - self.base_cpu
        sound_s = self.t0 - self.between_s - self.base_t
        others = (proc - self.base_proc) - mine \
            - self.others_rate * max(sound_s, 0.0)
        mine -= self.busy_since
        self.base_t, self.base_cpu, self.base_proc = self.t1, cpu, proc
        self.busy_since = 0.0
        return max(mine, 0.0), max(others, 0.0)


def stall_cause(lost_ms: float, wait_ms: float, programs: int,
                gc_ms: float, cpu_ms: float, proc_cpu_ms: float) -> str:
    """One word for a stall, from its own numbers, in this order: the
    device (the wait for the step's tokens is at least half of what was
    lost), a step program formed on the path, the collector, another
    thread (the process's CPU time less this thread's), this thread's
    Python (its CPU time), else time off the CPU: the thread neither ran
    nor waited for the device."""
    half = 0.5 * lost_ms
    if wait_ms >= half:
        return "device"
    if programs:
        return "compile"
    if gc_ms >= half:
        return "gc"
    if proc_cpu_ms - cpu_ms >= half:
        return "other_thread"
    if cpu_ms >= half:
        return "python"
    return "offcpu"


class Watchdog:
    """Process-wide health watchdog over the telemetry spine."""

    def __init__(self):
        self.enabled = True          # config gate ON TOP of state.enabled
        self.threshold = 3.0         # anomaly: ms > threshold * EWMA mean
        self.warmup = 8              # EWMA samples before verdicts fire
        self.alpha = 0.2             # EWMA smoothing factor
        self.min_delta_ms = 1.0      # absolute floor under the ratio rule
        self.calm_steps = 8          # normal steps that end a storm
        self.storm_compiles = 3      # on-path compiles within...
        self.storm_window_s = 60.0   # ...this window = a recompile storm
        # memory-drift detector (ISSUE 20): resident bytes fed from the
        # ledger's time-series hook; growth past threshold × EWMA (and
        # past the absolute floor) is a drift anomaly — a leaking codec
        # path shows here in production mode, not just under DS_KV_DEBUG
        self.mem_threshold = 1.5
        self.mem_min_delta_bytes = 32 << 20
        self._mem = _DriftState()
        self.postmortem_dir = os.environ.get("DS_POSTMORTEM_DIR", "")
        # RLock, not Lock: the DS_POSTMORTEM_ON_EXIT SIGTERM handler
        # runs dump_postmortem -> health() on the main thread, possibly
        # interrupting a frame that already holds this lock — a plain
        # Lock would deadlock the dying process instead of dumping
        self._lock = threading.RLock()
        self._kinds: Dict[str, _KindState] = {}
        self._nonfinite_warned: set = set()
        #: train steps the non-finite verdict stays raised after the
        #: last non-finite observation (recency: /healthz must recover
        #: once finite steps resume, not latch 503 for process life)
        self._nonfinite_recent = 0
        self._phase_s: Dict[str, float] = {}
        self._phase_t0: Optional[float] = None
        self._gauges_bound = False
        self._compile_times: collections.deque = collections.deque(
            maxlen=32)
        self._compile_keys: collections.deque = collections.deque(
            maxlen=8)
        self._in_compile_storm = False

    # -- non-finite sentinel (training engine, host-fetched values) ----------
    def note_nonfinite(self, what: str, step: int, value: float) -> None:
        """A host-fetched training scalar (loss / grad_norm) came back
        non-finite.  Counts always-on via the caller's enabled gate;
        warns once per scalar name."""
        if not (state.enabled and self.enabled):
            return
        tm.TRAIN_NONFINITE.inc()
        with self._lock:
            self._nonfinite_recent = self.calm_steps + 1
        self._record_event("watchdog.nonfinite", what=what,
                           at_step=step, value=repr(value))
        if what not in self._nonfinite_warned:
            self._nonfinite_warned.add(what)
            self._logger().warning(
                "watchdog: non-finite %s (%r) at global step %d — "
                "further occurrences count in ds_train_nonfinite_total "
                "without logging", what, value, step)

    def note_overflow_skip(self, step: int) -> None:
        """One fp16 dynamic-loss-scale overflow skip (the engine's
        device-side skip counter already exists; this mirrors the
        per-step host-visible flag into the registry)."""
        if not (state.enabled and self.enabled):
            return
        tm.TRAIN_OVERFLOW_SKIP.inc()
        self._record_event("watchdog.overflow_skip", at_step=step)

    # -- step-time anomaly detector ------------------------------------------
    def _sample(self, w: _KindState, ms: float):
        """THE step-time rule, on one stream's state (under the lock):
        ``(the mean the sample passed, first of its storm)`` where it is
        anomalous, else ``(None, False)`` and the sample joins the EWMA."""
        w.last_ms = ms
        mean = w.mean_ms
        anomalous = (
            w.n >= self.warmup and mean > 0.0
            and ms > mean * self.threshold
            and ms - mean > self.min_delta_ms)
        if not anomalous:
            d = ms - mean
            w.mean_ms = mean + self.alpha * d
            w.dev_ms += self.alpha * (abs(d) - w.dev_ms)
            w.n += 1
            if w.in_storm:
                w.calm += 1
                if w.calm >= self.calm_steps:
                    w.in_storm = False
            return None, False
        w.anomalies += 1
        w.last_anomaly_ms = ms
        first_of_storm = not w.in_storm
        w.in_storm = True
        w.calm = 0
        return mean, first_of_storm

    def _stream(self, kind: str) -> _KindState:
        w = self._kinds.get(kind)
        if w is None:
            w = self._kinds[kind] = _KindState()
        return w

    def _announce(self, kind: str, ms: float, step: int, mean: float,
                  first_of_storm: bool) -> None:
        """An anomaly's counter, flight event, warning (once a storm) and
        span-ring dump: with telemetry on only."""
        tm.TRAIN_ANOMALY.inc()
        self._record_event("watchdog.anomaly", stream=kind,
                           at_step=step, ms=round(ms, 3),
                           ewma_ms=round(mean, 3))
        if first_of_storm:
            self._logger().warning(
                "watchdog: %s step %d took %.1fms vs EWMA %.1fms "
                "(>%.1fx) — step-time anomaly storm begins; further "
                "anomalies count in ds_train_anomaly_total without "
                "logging until %d normal steps pass",
                kind, step, ms, mean, self.threshold, self.calm_steps)
            self._dump_anomaly_trace(kind, step)

    # dslint: disabled-path
    def observe_step_time(self, kind: str, ms: float,
                          step: int = 0) -> None:
        """Feed one step wall time (``kind`` ∈ {train, fastgen}).  After
        ``warmup`` samples, a step slower than ``threshold ×`` the EWMA
        mean (and at least ``min_delta_ms`` over it) is an anomaly:
        counter + warn-once-per-storm + span-ring dump.  Anomalous
        samples do NOT update the EWMA (a storm must not drag the
        baseline up and mask itself)."""
        if not (state.enabled and self.enabled):
            return
        with self._lock:
            if kind == "train" and self._nonfinite_recent > 0:
                # one train step elapsed since the last non-finite
                # observation: the /healthz verdict heals after
                # calm_steps finite steps (a still-NaN'ing run keeps
                # re-raising it every step)
                self._nonfinite_recent -= 1
            mean, first_of_storm = self._sample(self._stream(kind), ms)
        if mean is not None:
            self._announce(kind, ms, step, mean, first_of_storm)

    def observe_serving_step(self, meter: "StepMeter", rows: int,
                             step: int = 0) -> bool:
        """One serving step's meter, telemetry on or off: its wall feeds
        the ``fastgen`` stream (the one detector: the anomaly verdict of
        ``observe_step_time`` rides it while telemetry is on), the gap
        before it the meter's own stream under the same rule.  Where
        either passes the rule AND :data:`STALL_FLOOR_MS`, ONE
        ``fastgen.stall`` record goes into the span ring after the fact,
        the same fields into one warning line, and
        ``ds_fastgen_stall_total`` counts it.  True where it stalled."""
        if not self.enabled:
            return False
        wall_ms = (meter.t1 - meter.t0) * 1e3
        between_ms = meter.between_s * 1e3
        with self._lock:
            mean, first_of_storm = self._sample(self._stream("fastgen"),
                                                wall_ms)
            gap_mean, _ = self._sample(meter.gap, between_ms)
        if state.enabled:
            tm.FASTGEN_STEP_MS.observe(wall_ms)
            if mean is not None:
                self._announce("fastgen", wall_ms, step, mean,
                               first_of_storm)
        if mean is not None and wall_ms < STALL_FLOOR_MS:
            mean = None
        if gap_mean is not None and between_ms < STALL_FLOOR_MS:
            gap_mean = None
        if mean is None and gap_mean is None:
            return False
        self._note_stall(meter, rows, wall_ms, between_ms, mean, gap_mean)
        return True

    def _note_stall(self, meter: "StepMeter", rows: int,
                    wall_ms: float, between_ms: float,
                    mean: Optional[float],
                    gap_mean: Optional[float]) -> None:
        """The record of one paused step (PERF.md §3 names each field);
        its line counts the loop's steps, live or not (``meter.steps``)."""
        phase_ms, phase = max(
            (getattr(meter, p) * 1e3, p) for p in STALL_PHASES)
        lost_ms = ((wall_ms - mean if mean is not None else 0.0)
                   + (between_ms - gap_mean if gap_mean is not None
                      else 0.0))
        wait_ms = meter.wait * 1e3
        gc_ms = (_COLLECTOR.seconds - meter.gc_s0) * 1e3
        programs = tm.FASTGEN_COMPILE_ON_PATH.value - meter.programs0
        mine_ms, others_ms = (1e3 * s for s in meter.pause_clocks())
        in_gap = mean is None or (gap_mean is not None
                                  and between_ms > phase_ms)
        # the clocks gained over the gap AND the step: the half that did
        # not pause ran as a sound one does (all of the gap, the step but
        # for its wait), the rest is the paused half's
        if in_gap:
            cpu_ms = wall_ms - wait_ms
            between_cpu_ms = min(max(mine_ms - cpu_ms, 0.0), between_ms)
        else:
            between_cpu_ms = between_ms
            cpu_ms = min(max(mine_ms - between_ms, 0.0), wall_ms - wait_ms)
        proc_cpu_ms = cpu_ms + (0.0 if in_gap else others_ms)
        between_proc_cpu_ms = between_cpu_ms + (others_ms if in_gap else 0.0)
        if in_gap:
            # the pause fell between two steps, in the caller's loop: its
            # cause is read from the gap's clocks (no wait, no program)
            phase, phase_ms = "between", between_ms
            cause = stall_cause(lost_ms, 0.0, 0, gc_ms, between_cpu_ms,
                                between_proc_cpu_ms)
        else:
            cause = stall_cause(lost_ms, wait_ms, programs, gc_ms, cpu_ms,
                                proc_cpu_ms)
        fields = {
            "wall_ms": wall_ms, "between_ms": between_ms,
            "ewma_ms": mean if mean is not None else gap_mean,
            "lost_ms": lost_ms, "phase": phase, "phase_ms": phase_ms,
            "wait_ms": wait_ms, "cpu_ms": cpu_ms,
            "proc_cpu_ms": proc_cpu_ms,
            "offcpu_ms": max(wall_ms - wait_ms - cpu_ms, 0.0),
            "between_cpu_ms": between_cpu_ms,
            "between_proc_cpu_ms": between_proc_cpu_ms,
            "gc_ms": gc_ms,
            "gc_n": _COLLECTOR.collections - meter.gc_n0,
            "gc_gen2": _COLLECTOR.full - meter.gc_full0,
            "programs": programs, "rows": rows, "cause": cause}
        fields = {k: round(v, 3) if isinstance(v, float) else v
                  for k, v in fields.items()}
        tm.FASTGEN_STALL.inc()
        get_tracer().record(STALL_SPAN, meter.t0, meter.t1 - meter.t0,
                            fields)
        self._logger().warning(
            "watchdog: fastgen.stall step=%d %s", meter.steps,
            " ".join(f"{k}={v}" for k, v in fields.items()))

    # -- memory-drift detector (ISSUE 20) ------------------------------------
    # dslint: disabled-path
    def observe_resident_bytes(self, nbytes: float,
                               step: int = 0) -> None:
        """Feed one post-step resident-bytes observation (the memory
        ledger's time-series hook).  After ``warmup`` samples, resident
        bytes above ``mem_threshold ×`` the EWMA mean (and at least
        ``mem_min_delta_bytes`` over it) is a drift anomaly: counter +
        flight event + warn-once-per-storm.  Anomalous samples do NOT
        update the EWMA (a leak must not drag the baseline up and mask
        itself); the storm ends after ``calm_steps`` normal samples."""
        if not (state.enabled and self.enabled):
            return
        with self._lock:
            w = self._mem
            w.last_b = nbytes
            anomalous = (
                w.n >= self.warmup and w.mean_b > 0.0
                and nbytes > w.mean_b * self.mem_threshold
                and nbytes - w.mean_b > self.mem_min_delta_bytes)
            if not anomalous:
                w.mean_b += self.alpha * (nbytes - w.mean_b)
                w.n += 1
                if w.in_storm:
                    w.calm += 1
                    if w.calm >= self.calm_steps:
                        w.in_storm = False
                return
            w.anomalies += 1
            w.last_anomaly_b = nbytes
            first_of_storm = not w.in_storm
            w.in_storm = True
            w.calm = 0
            mean = w.mean_b
        tm.MEM_DRIFT_ANOMALY.inc()
        self._record_event("watchdog.anomaly", stream="memory",
                           at_step=step, bytes=int(nbytes),
                           ewma_bytes=int(mean))
        if first_of_storm:
            self._logger().warning(
                "watchdog: resident memory %.1fMB vs EWMA %.1fMB "
                "(>%.1fx) — memory-drift storm begins; further "
                "anomalies count in ds_mem_drift_anomaly_total "
                "without logging until %d normal samples pass "
                "(breakdown: /memory endpoint or memory.json "
                "postmortem)",
                nbytes / 2**20, mean / 2**20, self.mem_threshold,
                self.calm_steps)

    def _dump_anomaly_trace(self, kind: str, step: int) -> None:
        """Write the span ring around the offending step as a Chrome
        trace (best-effort: forensics must never take the run down).
        Requires a configured ``postmortem_dir`` — without one the
        verdict stays counter+warning only, so a test/bench process
        never litters its cwd with trace artifacts."""
        if not self.postmortem_dir:
            return
        path = os.path.join(self.postmortem_dir,
                            f"anomaly_{kind}_step{step}.json")
        try:
            os.makedirs(self.postmortem_dir, exist_ok=True)
            get_tracer().dump(path)
            self._logger().warning(
                "watchdog: span ring dumped to %s", path)
        except OSError as e:
            self._logger().warning(
                "watchdog: could not dump anomaly trace to %s (%s)",
                path, e)

    # -- goodput accounting --------------------------------------------------
    # dslint: disabled-path
    def track(self, phase: str):
        """Context manager accumulating wall time into ``phase``
        (one of :data:`GOODPUT_PHASES`).  Disabled: a shared no-op, no
        allocation."""
        if not (state.enabled and self.enabled):
            return _NULL_TRACK
        return _PhaseTimer(self, phase)

    def note_phase(self, phase: str, seconds: float) -> None:
        if not (state.enabled and self.enabled):
            return
        with self._lock:
            if self._phase_t0 is None:
                # wallclock origin opens at the first tracked phase, so
                # pre-training setup is not billed as idle
                self._phase_t0 = time.perf_counter() - seconds
            self._phase_s[phase] = self._phase_s.get(phase, 0.0) + seconds
        if not self._gauges_bound:
            self._bind_goodput_gauges()

    def _bind_goodput_gauges(self) -> None:
        self._gauges_bound = True

        def frac(phase):
            def _read(p=phase):
                return self._phase_fraction(p)
            return _read

        tm.TRAIN_GOODPUT_RATIO.bind(frac("step"))
        tm.TRAIN_COMPILE_FRACTION.bind(frac("compile"))
        tm.TRAIN_INPUT_WAIT_FRACTION.bind(frac("input_wait"))
        tm.TRAIN_STEP_FRACTION.bind(frac("step"))
        tm.TRAIN_CHECKPOINT_FRACTION.bind(frac("checkpoint"))
        tm.TRAIN_IDLE_FRACTION.bind(frac("idle"))

    def _phase_fraction(self, phase: str) -> float:
        with self._lock:
            if self._phase_t0 is None:
                return 0.0
            wall = max(time.perf_counter() - self._phase_t0, 1e-9)
            if phase == "idle":
                accounted = sum(self._phase_s.values())
                return max(0.0, 1.0 - accounted / wall)
            return min(self._phase_s.get(phase, 0.0) / wall, 1.0)

    def goodput(self) -> Dict[str, float]:
        out = {p: round(self._phase_fraction(p), 4)
               for p in GOODPUT_PHASES + ("idle",)}
        out["goodput_ratio"] = out["step"]
        return out

    # -- serving step-cache / recompile accounting ---------------------------
    def note_step_cache(self, hit: bool, key: Any = None,
                        compiled_on_path: bool = False) -> None:
        """One step-cache lookup on the serving request path.  Counters
        are unconditional (a compile is ~10^7× their cost, and a
        recompile storm must be visible even telemetry-off); the storm
        warning names the uncovered keys."""
        if hit:
            tm.FASTGEN_STEP_CACHE_HIT.inc()
            return
        tm.FASTGEN_STEP_CACHE_MISS.inc()
        if not compiled_on_path:
            return
        tm.FASTGEN_COMPILE_ON_PATH.inc()
        self._record_event("watchdog.compile_on_path", key=repr(key))
        # workload observatory (ISSUE 9): an on-path compile is exactly
        # a key the precompiled lattice missed — ship it to the ledger
        # so tools/analyze_trace.py can recommend a lattice covering it
        from .workload_trace import get_workload_trace
        get_workload_trace().record_compile(key)
        now = time.monotonic()
        with self._lock:
            self._compile_times.append(now)
            self._compile_keys.append(key)
            recent = [t for t in self._compile_times
                      if now - t <= self.storm_window_s]
            storm = len(recent) >= self.storm_compiles
            if not storm:
                self._in_compile_storm = False
                return
            if self._in_compile_storm:
                return      # warn once per storm
            self._in_compile_storm = True
            keys = list(self._compile_keys)
        wt = get_workload_trace()
        trace_hint = ((getattr(wt, "_path", "")
                       or "<workload-trace.jsonl>")
                      if wt.active else "<workload-trace.jsonl>")
        self._logger().warning(
            "watchdog: recompile storm on the serving request path — "
            "%d XLA compiles in %.0fs; uncovered (S, Q, P, fresh, kind) "
            "step-cache keys: %s.  Widen precompile()'s lattice to "
            "cover them (sampling=True for fused sample/chain "
            "variants), or mine a covering lattice from the workload "
            "trace: `python tools/analyze_trace.py --trace %s "
            "--emit-lattice lattice.json` and rebuild the engine with "
            "serving_optimization.lattice=\"auto:lattice.json\" "
            "(the persistent compile cache then turns later "
            "processes' compiles into loads)",
            len(recent), self.storm_window_s, keys, trace_hint)

    # -- health verdicts (/healthz) ------------------------------------------
    def health(self) -> Dict[str, Any]:
        with self._lock:
            kinds = {
                k: {"ewma_ms": round(w.mean_ms, 3),
                    "dev_ms": round(w.dev_ms, 3),
                    "samples": w.n,
                    "anomalies": w.anomalies,
                    "in_storm": w.in_storm,
                    "last_ms": round(w.last_ms, 3)}
                for k, w in self._kinds.items()}
            nonfinite_recent = self._nonfinite_recent
            m = self._mem
            mem_drift = {"ewma_bytes": int(m.mean_b),
                         "samples": m.n,
                         "anomalies": m.anomalies,
                         "in_storm": m.in_storm,
                         "last_bytes": int(m.last_b)}
        nonfinite = tm.TRAIN_NONFINITE.value
        status = "ok"
        if (any(w["in_storm"] for w in kinds.values())
                or mem_drift["in_storm"]):
            status = "anomaly"
        if nonfinite_recent > 0:
            # recency, not history: the verdict heals after calm_steps
            # finite train steps (the cumulative counter still reports)
            status = "nonfinite"
        return {
            "status": status,
            "uptime_s": round(time.monotonic() - _T0, 3),
            "telemetry_enabled": state.enabled,
            "watchdog_enabled": self.enabled,
            "step_time": kinds,
            "memory_drift": mem_drift,
            "nonfinite_total": nonfinite,
            "overflow_skip_total": tm.TRAIN_OVERFLOW_SKIP.value,
            "anomaly_total": tm.TRAIN_ANOMALY.value,
            "step_cache": {
                "hit_total": tm.FASTGEN_STEP_CACHE_HIT.value,
                "miss_total": tm.FASTGEN_STEP_CACHE_MISS.value,
                "compile_on_path_total": tm.FASTGEN_COMPILE_ON_PATH.value,
            },
            "goodput": self.goodput(),
        }

    # -- plumbing ------------------------------------------------------------
    def configure(self, enabled: Optional[bool] = None,
                  threshold: float = 0.0, warmup: int = -1,
                  postmortem_dir: str = "") -> None:
        """Config-block entry point (0 / -1 / "" = keep current)."""
        if enabled is not None:
            self.enabled = bool(enabled)
        if threshold:
            self.threshold = float(threshold)
        if warmup >= 0:
            self.warmup = int(warmup)
        if postmortem_dir:
            self.postmortem_dir = postmortem_dir

    def reset(self) -> None:
        """Drop all learned state (tests / measured-window control);
        configuration and gauge bindings survive."""
        with self._lock:
            self._kinds.clear()
            self._nonfinite_warned.clear()
            self._nonfinite_recent = 0
            self._phase_s.clear()
            self._phase_t0 = None
            self._compile_times.clear()
            self._compile_keys.clear()
            self._in_compile_storm = False
            self._mem = _DriftState()

    @staticmethod
    def _record_event(event: str, **fields) -> None:
        from .flight_recorder import get_flight_recorder
        get_flight_recorder().record(event, **fields)

    @staticmethod
    def _logger():
        from ..utils.logging import logger
        return logger


#: process-wide singleton
_WATCHDOG = Watchdog()


def get_watchdog() -> Watchdog:
    return _WATCHDOG
