"""Reader ``span_ring``: the program's own spans, from its span ring
(``deepspeed_tpu.telemetry.get_tracer().records()``; ``perf_counter``
seconds, the clock of ``ctx.process_start`` and of the profiler's stamps).

``names``  patterns of the span names that are read
``value``  ``dur_ms`` | ``self_ms`` (duration less the spans that name it
           as parent) | ``attr:<key>`` | ``one`` (1 for each span) |
           ``covered_ms`` (the time some span of ``names`` is open, counted
           once where spans of several threads overlap: each one's duration
           holds its wait for the interpreter lock)
``where``  ``key=value`` or ``key!=value`` filters on the spans' attributes
``span``   ``slice`` (spans that end inside the traced slice) | ``setup``
           (process start to the window's first instant)
``stat``   ``mean`` ``p50`` ``p95`` ``sum`` ``count`` | ``per_step`` (sum /
           steps of the slice) | ``share`` (100 x spans passing ``where`` /
           spans of ``names``)
``of``     patterns of a second set of spans: the result is the sum over
           ``names`` / (sum of ``of_value`` over ``of``, times ``of_scale``:
           a number, or ``config:<dotted key>`` of the configuration file),
           0 where no span of ``names`` passes ``where``; ``complement``
           gives 1 less that
``scale``  multiplies the result (100 for a share in %, 0.001 for seconds)

None where the ring holds no such span, or holds records without span ids
(a program from before the span tree)."""

import re

from .. import stats
from ..trace_reduce import total, union


def compiled(patterns):
    return re.compile("|".join(f"(?:{p})" for p in patterns))


def window(ctx, which):
    if which == "setup":
        if ctx.setup_s is None:
            return None
        return ctx.process_start, ctx.process_start + ctx.setup_s
    prof = ctx.profiler
    if prof.started_at is None or prof.stopped_at is None:
        return None
    return prof.started_at, prof.stopped_at


def passes(attrs, where):
    for item in where:
        key, differs, want = item.partition("!=")
        if not differs:
            key, _, want = item.partition("=")
        same = str((attrs or {}).get(key)).lower() == want.lower()
        if same == bool(differs):
            return False
    return True


def values(records, names, value, where, lo, hi):
    """One number per span of ``names`` that ends in ``[lo, hi]`` and
    passes ``where`` (``covered_ms``: one number for all of them); and
    how many spans of ``names`` ended there."""
    rx = compiled(names)
    child_s = {}
    if value == "self_ms":
        for rec in records:
            if rec[7] is not None:
                child_s[rec[7]] = child_s.get(rec[7], 0.0) + rec[2]
    out, named = [], 0
    for name, start, dur, _, _, attrs, sid, _, _ in records:
        if not rx.search(name) or not lo <= start + dur <= hi:
            continue
        named += 1
        if not passes(attrs, where):
            continue
        if value == "dur_ms":
            out.append(dur * 1e3)
        elif value == "self_ms":
            out.append((dur - child_s.get(sid, 0.0)) * 1e3)
        elif value == "one":
            out.append(1.0)
        elif value == "covered_ms":
            out.append((start, start + dur))
        else:
            v = (attrs or {}).get(value.partition(":")[2])
            if v is not None:
                out.append(float(v))
    if value == "covered_ms":
        out = [total(union(out)) * 1e3]
    return out, named


def scale_of(ctx, spec):
    if isinstance(spec, str) and spec.startswith("config:"):
        node = ctx.config
        for key in spec.partition(":")[2].split("."):
            node = node[key]
        return float(node)
    return float(spec)


def reduce(records, ctx, args):
    records = [r for r in records if len(r) >= 9]
    span = window(ctx, args.get("span", "slice"))
    if not records or span is None:
        return None
    lo, hi = span
    value, where = args.get("value", "dur_ms"), args.get("where", [])
    got, named = values(records, args["names"], value, where, lo, hi)
    stat = args.get("stat", "sum")
    if not named:
        return None
    if "of" in args:
        base, _ = values(records, args["of"], args.get("of_value", value),
                         [], lo, hi)
        base = sum(base) * scale_of(ctx, args.get("of_scale", 1.0))
        if base <= 0:
            return None
        out = sum(got) / base
        out = 1.0 - out if args.get("complement") else out
    elif stat == "share":
        out = 100.0 * len(got) / named
    elif stat == "count":
        out = float(len(got))
    elif not got:
        return None
    elif stat == "per_step":
        if not ctx.profiler.steps:
            return None
        out = sum(got) / ctx.profiler.steps
    else:
        out = stats.stat(got, stat)
    return out * float(args.get("scale", 1.0))


def read(ctx, facts, args):
    try:
        from deepspeed_tpu.telemetry import get_tracer
    except ImportError:
        return None
    return reduce(get_tracer().records(), ctx, args)
