"""Span tracer: bounded ring buffer of (name, start, dur, step, tid, attrs,
id, parent, uid) records, exportable as Chrome-trace JSON
(Perfetto/chrome://tracing).

``trace_span("fastgen.dispatch")`` is the only public entry point on hot
paths.  Disabled (the default): one attribute read and a shared no-op
span — no allocation, no clock read.  Enabled: a
``jax.profiler.TraceAnnotation`` is entered under the same name (the bare
name: attributes stay in the ring), so when an XProf/Perfetto device
profile is being captured the host spans line up with the device timeline
(TraceAnnotation is a no-op outside an active profile — the gating lives
in its C++ TraceMe).

Every record carries a span id and the id of the span that was open on
the same thread when it began (``None`` at a root), so a step's spans
form a tree and a span's self time is its duration less the children
that name it as parent.  ``SpanTracer.span`` opens a live span whatever
the switch says (step-program formation: seconds each, tens a process);
``SpanTracer.record`` writes a span after the fact from stamps taken
elsewhere (the per-request spans, which share the request's ``uid``).
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from .state import state

#: record = (name, start_s, dur_s, step, thread_id, attrs-or-None,
#:           span id, parent span id or None, request uid or None)
Record = Tuple[str, float, float, int, int, Optional[Dict[str, Any]],
               int, Optional[int], Optional[int]]

#: span ids are process-wide; ``next`` on a count is atomic under the GIL
_IDS = itertools.count(1)
#: per thread: the ids of the spans open right now, outermost first
_OPEN = threading.local()

#: thread-local replica/component label (ISSUE 19 satellite): pool
#: stepper threads interleave anonymously in the one process-wide span
#: ring — a component label on each record (and on flight events, and
#: on journey segments' ``at``) tells the replicas apart in Perfetto
#: and in stitched journeys
_COMPONENT = threading.local()


def set_component(label: str) -> None:
    """Label every span/flight-event/journey-segment this thread
    records from now on (e.g. ``r0``, ``prefill``, ``decode``)."""
    _COMPONENT.value = str(label)


def current_component() -> str:
    return getattr(_COMPONENT, "value", "")

def _default_capacity() -> int:
    """``DS_TRACE_BUFFER`` is a tuning knob, not a correctness switch —
    a malformed value (``64k``) must not kill every ``import
    deepspeed_tpu`` in the process (this module is reached from any
    engine build via utils.comms_logging)."""
    raw = os.environ.get("DS_TRACE_BUFFER", "")
    if raw:
        try:
            return int(raw)
        except ValueError:
            import warnings
            warnings.warn(
                f"DS_TRACE_BUFFER={raw!r} is not an integer — using the "
                "default trace-buffer capacity 65536")
    return 65536


DEFAULT_CAPACITY = _default_capacity()


class SpanTracer:
    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._cap = max(int(capacity), 1)
        self._buf: List[Optional[Record]] = [None] * self._cap
        self._n = 0          # total records ever written
        self.step = 0        # current step label (set_step)
        # RLock: the postmortem SIGTERM handler dumps the ring on the
        # main thread and may interrupt a record() holding this lock
        self._lock = threading.RLock()

    def set_step(self, step: int) -> None:
        self.step = step

    def resize(self, capacity: int) -> None:
        with self._lock:
            self._cap = max(int(capacity), 1)
            self._buf = [None] * self._cap
            self._n = 0

    def record(self, name: str, start: float, dur: float,
               attrs: Optional[Dict[str, Any]] = None, *,
               parent: Optional[int] = None, uid: Optional[int] = None,
               span_id: Optional[int] = None) -> int:
        """Write one span (``start``/``dur`` in ``perf_counter``
        seconds); returns its id.  Spans written after the fact name
        their ``parent`` and the request's ``uid`` themselves."""
        comp = getattr(_COMPONENT, "value", "")
        if comp:
            # merged, not mutated: the caller's attrs dict may be shared
            attrs = {"component": comp, **(attrs or {})}
        if span_id is None:
            span_id = next(_IDS)
        rec = (name, start, dur, self.step,
               threading.get_ident(), attrs, span_id, parent, uid)
        with self._lock:
            self._buf[self._n % self._cap] = rec
            self._n += 1
        return span_id

    def span(self, name: str,
             attrs: Optional[Dict[str, Any]] = None) -> "_Span":
        """A live span whether or not telemetry is on (``trace_span`` is
        the switched entry point)."""
        return _Span(name, attrs)

    def records(self) -> List[Record]:
        """Retained records, oldest first.  The critical section is
        O(1) — only the buffer reference and write count are read under
        the lock, so a slow /trace scrape or dump never stalls a
        ``record()`` on the serving hot path.  Slots written while the
        copy runs may surface a newer record in an "old" position
        (records are immutable tuples, slot stores are atomic); callers
        sort by start time, so the benign race costs nothing."""
        with self._lock:
            buf, n, cap = self._buf, self._n, self._cap
        if n <= cap:
            return [r for r in buf[:n] if r is not None]
        i = n % cap
        return [r for r in buf[i:] + buf[:i] if r is not None]

    def clear(self) -> None:
        with self._lock:
            self._buf = [None] * self._cap
            self._n = 0

    def chrome_events(self) -> List[Dict[str, Any]]:
        """Retained spans as Chrome-trace complete events, sorted by
        start time (the single source for :meth:`dump` and the HTTP
        ``/trace`` view — the record shape is defined once)."""
        events = [{
            "name": name,
            "ph": "X",
            "ts": start * 1e6,      # µs, perf_counter epoch
            "dur": dur * 1e6,
            "pid": os.getpid(),
            "tid": tid,
            "args": {"step": step, "id": sid, "parent": parent,
                     **({"uid": uid} if uid is not None else {}),
                     **(attrs or {})},
        } for name, start, dur, step, tid, attrs, sid, parent, uid
            in self.records()]
        events.sort(key=lambda e: e["ts"])
        return events

    def dump(self, path: str) -> str:
        """Write retained spans as Chrome-trace JSON (the object form:
        ``{"traceEvents": [...]}``) loadable in Perfetto."""
        doc = {"traceEvents": self.chrome_events(),
               "displayTimeUnit": "ms"}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


#: process-wide singleton
_TRACER = SpanTracer()


def get_tracer() -> SpanTracer:
    return _TRACER


class _NullSpan:
    """Shared disabled-path span: no state, no allocation."""
    __slots__ = ()
    #: False: counts that cost something to take are skipped
    live = False

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set(self, key: str, value: Any) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("name", "attrs", "t0", "id", "parent", "_ann", "_stack")
    live = True

    def __init__(self, name: str, attrs: Optional[Dict[str, Any]]):
        self.name = name
        # copied: the dict handed in may be shared between calls
        self.attrs = dict(attrs) if attrs else None

    def set(self, key: str, value: Any) -> None:
        """An attribute known only once the work is done (a count, the
        path taken); lands on the record at exit."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value

    def __enter__(self):
        stack = getattr(_OPEN, "stack", None)
        if stack is None:
            stack = _OPEN.stack = []
        self._stack = stack
        self.parent = stack[-1] if stack else None
        self.id = next(_IDS)
        stack.append(self.id)
        ann = jax.profiler.TraceAnnotation(self.name)
        ann.__enter__()
        self._ann = ann
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self.t0
        self._ann.__exit__(exc_type, exc, tb)
        self._stack.pop()
        _TRACER.record(self.name, self.t0, dur, self.attrs,
                       parent=self.parent, span_id=self.id)
        return False


# dslint: disabled-path
def trace_span(name: str, attrs: Optional[Dict[str, Any]] = None):
    """Context manager recording a named host span when telemetry is
    enabled.  ``attrs`` (an optional plain dict — not kwargs, so the
    disabled call allocates nothing) lands in the Chrome-trace ``args``;
    ``with trace_span(...) as sp: ...; sp.set(key, value)`` adds what is
    known only at the end (a no-op on the disabled path).
    """
    if not state.enabled:
        return _NULL_SPAN
    return _Span(name, attrs)


#: step programs that can say which scope each of their instructions
#: belongs to: name -> a thunk giving the table of
#: ``telemetry/program_scopes.py`` (or None).  Registered by the program
#: while telemetry is on; evaluated by whoever reads, after the fact.
_PROGRAMS: Dict[str, Any] = {}


def register_program(name: str, thunk) -> None:
    """Publish a step program's scope table under ``name`` as a thunk:
    registering lowers, compiles and parses nothing."""
    _PROGRAMS[name] = thunk


def program_table(name: str) -> Optional[Dict[str, Any]]:
    """The scope table registered under ``name``, evaluated now (the
    program caches it), or None where nothing was registered."""
    thunk = _PROGRAMS.get(name)
    return thunk() if thunk is not None else None


def dump_trace(path: str) -> str:
    """Export the process ring buffer as Chrome-trace JSON."""
    return _TRACER.dump(path)
