"""Reader ``span_window``: ``span_ring``'s ``values`` and ``where`` over a
window of the run, for records a program writes with its telemetry off too
(one ``fastgen.stall`` a paused step: ``telemetry/watchdog.py``).

``names`` ``value`` ``where`` ``scale``  as ``span_ring`` takes them
``span``   ``window`` (the whole measured window: ``process_start +
           setup_s`` to ``+ seconds``; the default) | ``slice`` | ``setup``
``stat``   ``sum`` (the default) | ``count`` | ``per_step`` (sum / steps of
           the slice)

0.0 where the ring holds no such record in the window but the program has
the meter that writes them (``args["meter"]``, an attribute of
``deepspeed_tpu.telemetry.watchdog``): nothing paused.  None only for a
tree without the meter, or a run whose window never opened."""

from . import span_ring


def window(ctx, which):
    if which != "window":
        return span_ring.window(ctx, which)
    if ctx.setup_s is None:
        return None
    lo = ctx.process_start + ctx.setup_s
    return lo, lo + ctx.seconds


def reduce(records, ctx, args):
    span = window(ctx, args.get("span", "window"))
    if span is None:
        return None
    got, _ = span_ring.values(
        [r for r in records if len(r) >= 9], args["names"],
        args.get("value", "dur_ms"), args.get("where", []), *span)
    stat = args.get("stat", "sum")
    if stat == "count":
        out = float(len(got))
    elif stat == "per_step":
        if not ctx.profiler.steps:
            return None
        out = sum(got) / ctx.profiler.steps
    else:
        out = float(sum(got))
    return out * float(args.get("scale", 1.0))


def read(ctx, facts, args):
    try:
        from deepspeed_tpu.telemetry import get_tracer, watchdog
    except ImportError:
        return None
    if not hasattr(watchdog, args["meter"]):
        return None
    return reduce(get_tracer().records(), ctx, args)
