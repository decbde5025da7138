"""Reader ``trace_idle_under``: the first device's idle time inside the
traced window that lies under the host spans whose names match
``patterns``, in milliseconds per step of the slice.  Where host spans
nest, an instant belongs to the innermost span over it, whatever its
name, so metrics whose patterns name different spans split the idle time
without overlap; what no metric's patterns name is idle time elsewhere
(``tools/idle_by_span.py`` prints all of it by name).  Exact where
``trace_reduce.idle_gaps`` labels a whole gap by its middle."""

from ..trace_reduce import subtract, total, union
from .span_ring import compiled


def idle_intervals(red, dev):
    lo, hi = red.window
    busy = union((max(a, lo), min(b, hi)) for _, a, b in red.devices[dev])
    return subtract([(lo, hi)], busy)


def innermost(spans):
    """``[(lo, hi, name)]``: the time the spans cover, cut at every span's
    edge, each piece named by the shortest span over it."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    order = sorted(spans, key=lambda x: x[1])
    active, out, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(order) and order[i][1] <= a:
            active.append(order[i])
            i += 1
        active = [x for x in active if x[2] > a]
        if active:
            out.append((a, b, min(active, key=lambda x: x[2] - x[1])[0]))
    return out


def under(idle, pieces, rx):
    """ns of ``idle`` (sorted, disjoint) under the pieces whose name
    matches ``rx``."""
    mine = union((a, b) for a, b, n in pieces if rx.search(n))
    return total(idle) - total(subtract(idle, mine))


def read(ctx, facts, args):
    red, steps = ctx.reduced, ctx.profiler.steps
    if red is None or not red.devices or not steps:
        return None
    rx = compiled(args["patterns"])
    if not any(rx.search(s[0]) for s in red.host):
        return None
    idle = idle_intervals(red, min(red.devices))
    return under(idle, innermost(red.host), rx) / 1e6 / steps
