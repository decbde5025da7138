"""From a profiler trace (``.xplane.pb``) to intervals, and from intervals to
the numbers the per-layer metrics read.

``load`` keeps, per device, the events of the device's op line as
``(name, start_ns, end_ns)`` and, from the host planes, the annotation
spans (the harness's ``bench.*`` and the program's mirrored telemetry
spans).  Everything after that is interval arithmetic on plain lists, which
the tests exercise without a trace.

    python -m benchmark.trace_reduce <file.xplane.pb>    # look at a trace
"""

from __future__ import annotations

import dataclasses
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]
Event = Tuple[str, int, int]

#: planes that are chips, and the line of each that holds the operations
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
#: the line of collectives and copies in flight beside the operations (a
#: ``-start`` event there lasts until its ``-done``)
ASYNC_LINE = "Async XLA Ops"
#: host spans worth keeping (annotations; not the runtime's own chatter)
HOST_SPAN = re.compile(r"^(bench\.|fastgen\.|engine\.|train\.|serving\.|"
                       r"zero\.|sched\.|kv\.)")


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same instants."""
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Instants of ``a`` (disjoint, sorted) not covered by ``b`` (same)."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events that contain no other event (a ``while`` holds its body's
    operations; only the innermost ones are work)."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[Event] = []
    for i, ev in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt[1] >= ev[2]:
            out.append(ev)
    return out


def self_times(events: Sequence[Event]) -> Dict[str, int]:
    """Per name, the time its events ran less the time of the events
    nested inside them."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    out: Dict[str, int] = {}
    stack: List[List] = []            # [name, end, self_ns]

    def close(until: int) -> None:
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0) + own

    for name, lo, hi in ordered:
        close(lo)
        if stack:
            stack[-1][2] -= min(hi, stack[-1][1]) - lo
        stack.append([name, hi, hi - lo])
    close(1 << 62)
    return out


def matching(events: Sequence[Event], patterns: Sequence[str]) -> List[Event]:
    rx = re.compile("|".join(f"(?:{p})" for p in patterns))
    return [e for e in events if rx.search(e[0])]


# ---------------------------------------------------------------------------
# the reduced trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Reduced:
    devices: Dict[int, List[Event]]
    host: List[Event]
    window: Interval
    in_flight: Dict[int, List[Event]] = dataclasses.field(
        default_factory=dict)

    def _clip(self, events: Sequence[Event]) -> List[Event]:
        lo, hi = self.window
        return [(n, max(a, lo), min(b, hi)) for n, a, b in events
                if min(b, hi) > max(a, lo)]

    def _clipped(self, dev: int) -> List[Event]:
        return self._clip(self.devices[dev])

    def _in_flight(self, dev: int) -> List[Event]:
        return self._clip(self.in_flight.get(dev, []))

    def busy_ns(self, dev: int) -> int:
        return total(union((a, b) for _, a, b in self._clipped(dev)))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(self.busy_ns(d) for d in self.devices) / max(
            len(self.devices), 1) / 1e9

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def name_ns(self, dev: int, patterns: Sequence[str]) -> int:
        """Time covered by the operations whose name matches, on the
        operation line or in flight beside it."""
        return total(union((a, b) for _, a, b in matching(
            self._clipped(dev) + self._in_flight(dev), patterns)))

    def exposed_ns(self, dev: int, patterns: Sequence[str]) -> int:
        """Time in which a matching operation runs and no other innermost
        operation does (a ``while`` around it is not other work)."""
        ops = self._clipped(dev)
        rx = re.compile("|".join(f"(?:{p})" for p in patterns))
        mine = union((a, b) for n, a, b in ops + self._in_flight(dev)
                     if rx.search(n))
        rest = union((a, b) for n, a, b in leaves(ops) if not rx.search(n))
        return total(subtract(mine, rest))

    def idle_gaps(self, dev: int, top: Optional[int] = None
                  ) -> List[Tuple[str, int]]:
        """The idle gaps of the device inside the window, longest first
        (the ``top`` longest, or all), each labelled with the innermost
        host span that covers its middle."""
        lo, hi = self.window
        busy = union((a, b) for _, a, b in self._clipped(dev))
        gaps = sorted(subtract([(lo, hi)], busy),
                      key=lambda g: g[0] - g[1])[:top]
        spans = sorted(self.host, key=lambda e: e[2] - e[1])
        out = []
        for a, b in gaps:
            mid = (a + b) // 2
            label = next((n for n, s, e in spans if s <= mid < e
                          and n != "bench.traced"), "(no host span)")
            out.append((label, b - a))
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The contract's ``breakdown``: device operations with most self
        time and the longest idle gaps by host span, first device."""
        dev = min(self.devices) if self.devices else None
        if dev is None:
            return {"device_ops": [], "idle_gaps": []}
        ops = sorted(self_times(self._clipped(dev)).items(),
                     key=lambda kv: -kv[1])[:top]
        gaps = self.idle_gaps(dev, top)
        return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
                "idle_gaps": [[n, ns / 1e9] for n, ns in gaps]}


def op_name(text: str) -> str:
    """The operation's own name: a TPU trace names an event by the whole
    HLO instruction (``%paged_attention.21 = bf16[...] custom-call(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str, window_span: str = "bench.traced") -> Reduced:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    in_flight: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name not in (OP_LINE, ASYNC_LINE):
                    continue
                events = (devices if line.name == OP_LINE else
                          in_flight).setdefault(int(m.group(1)), [])
                for ev in line.events:
                    start = int(ev.start_ns)
                    events.append((op_name(ev.name), start,
                                   start + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if HOST_SPAN.match(ev.name):
                        start = int(ev.start_ns)
                        host.append((ev.name, start,
                                     start + int(ev.duration_ns)))
    window = next(((s, e) for n, s, e in host if n == window_span), None)
    if window is None:
        flat = [e for evs in devices.values() for e in evs]
        window = ((min(e[1] for e in flat), max(e[2] for e in flat))
                  if flat else (0, 0))
    return Reduced(devices, host, window, in_flight)


def describe(path: str) -> None:
    """What a trace holds: planes, lines, and the names with most time."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            by_name: Dict[str, List[int]] = {}
            sample = {}
            for ev in events:
                by_name.setdefault(ev.name, []).append(int(ev.duration_ns))
                sample.setdefault(ev.name, ev)
            ranked = sorted(by_name.items(), key=lambda kv: -sum(kv[1]))
            for name, durs in ranked[:25]:
                ev = sample[name]
                stats = {k: (v if not isinstance(v, str) else v[:120])
                         for k, v in ev.stats}
                print(f"    {sum(durs) / 1e6:10.3f} ms  x{len(durs):<6} "
                      f"{name[:80]!r} start={int(ev.start_ns)} "
                      f"stats={stats}")


if __name__ == "__main__":
    describe(sys.argv[1])
