"""Reader ``trace_scope_share``: time of the device operations that belong
to the given phases and modules of a step program, as a percentage ``of``
the device's busy time (first device).

The trace names an event by its HLO instruction; the program says which
scope each instruction of its own compiled step belongs to
(``deepspeed_tpu.telemetry.program_table(args["program"])``: the table of
``telemetry/program_scopes.py``).  ``phase`` and ``module`` are lists; an
absent one matches all.  An instruction the table lacks is ``other`` /
``none``.  Time is innermost time: an event's duration less the events
nested in it, so a ``while`` counts only what no operation inside it
covers.  Events in flight on the async line are not counted (the
``collective_*_share`` metrics do that); a collective's ``-start`` /
``-done`` on the operation line counts under the scope its metadata names.

The slice can open in the middle of a device step, which would weight the
phase it opened in: the events are clipped to whole steps, from the first
to the last start inside the window of the instruction that opens the
entry computation (the first of the table's ``entry_order`` the trace
holds).

None where the program exports no table (a program from before it did),
and, for all but the three phases that JAX's own markers tell apart, where
the table is stale: read from an executable that a tree without the
program's scopes had cached.  One ``scope_table:`` line a run says which.
One ``scope_inherited:`` line a run says how much of the busy time belongs
to instructions whose scope is no metadata's but a neighbour's (the
table's ``inherited``): what a split without that pass would leave
unattributed.
``--keep-trace <dir>`` keeps the table beside the trace, as
``<program>.scopes.json`` (``tools/trace_scopes.py`` reads the pair)."""

import json
import os
import time

from ..trace_reduce import self_times, total, union

#: phases a stale table still tells apart (``jvp``, ``transpose`` and the
#: checkpoint's rematerialised computation are in either tree's text)
JAX_MARKED = {"forward", "recompute", "backward"}


def table_of(ctx, program):
    """The program's table, asked for once a run."""
    tables = getattr(ctx, "scope_tables", None)
    if tables is None:
        tables = ctx.scope_tables = {}
    if program not in tables:
        try:
            from deepspeed_tpu.telemetry import program_table
            from deepspeed_tpu.utils.compile_cache import cache_counts
        except ImportError:
            tables[program] = None
            return None
        t0, before = time.perf_counter(), cache_counts()
        table = tables[program] = program_table(program)
        seconds, after = time.perf_counter() - t0, cache_counts()
        # ``compiled`` above 0: the table's compile missed the cache the
        # step itself was loaded from, which is a fault to find
        print("scope_table: " + json.dumps({
            "program": program, "found": table is not None,
            "stale": bool(table and table["stale"]),
            "instructions": len(table["instructions"]) if table else 0,
            "seconds": round(seconds, 3),
            "compiled": after["misses"] - before["misses"],
            "loaded": after["hits"] - before["hits"]}), flush=True)
        keep = getattr(getattr(ctx, "profiler", None), "keep_dir", "")
        if table is not None and keep:
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, program + ".scopes.json"),
                      "w") as f:
                json.dump(table, f)
    return tables[program]


def whole_steps(events, lo, hi, entry_order):
    """``(lo, hi, steps)``: the window narrowed to whole device steps, from
    the first to the last start of the step's opening instruction; the
    window itself (and 0 steps) where it starts fewer than twice."""
    names = {n for n, _, _ in events}
    opener = next((n for n in entry_order if n in names), None)
    starts = sorted(a for n, a, _ in events if n == opener and lo <= a <= hi)
    if len(starts) < 2:
        return lo, hi, 0
    return starts[0], starts[-1], len(starts) - 1


def innermost_times(events, window, entry_order):
    """``({name: innermost ns}, busy ns, stretch ns, whole steps)`` of one
    device's events over the whole steps of the window."""
    lo, hi, steps = whole_steps(events, *window, entry_order)
    events = [(n, max(a, lo), min(b, hi)) for n, a, b in events
              if min(b, hi) > max(a, lo)]
    return (self_times(events), total(union((a, b) for _, a, b in events)),
            hi - lo, steps)


def read(ctx, facts, args):
    red = ctx.reduced
    if red is None or not red.devices:
        return None
    program = args.get("program", "train.step")
    table = table_of(ctx, program)
    if table is None:
        return None
    phases, modules = args.get("phase"), args.get("module")
    if table["stale"] and (modules is not None or phases is None
                           or not set(phases) <= JAX_MARKED):
        return None
    # one pass over the events a run, whatever the number of metrics
    times = getattr(ctx, "scope_times", None)
    if times is None:
        times = ctx.scope_times = {}
    if program not in times:
        times[program] = innermost_times(
            red.devices[min(red.devices)], red.window,
            table.get("entry_order", ()))
        own, busy, _, steps = times[program]
        inherited = set(table.get("inherited", ()))
        print("scope_inherited: " + json.dumps({
            "program": program, "whole_steps": steps,
            "instructions": len(inherited), "share_of_busy_pct": round(
                100.0 * sum(ns for n, ns in own.items() if n in inherited)
                / busy, 3) if busy > 0 else None}), flush=True)
    own, busy, stretch, _ = times[program]
    base = busy if args.get("of", "busy") == "busy" else stretch
    if base <= 0:
        return None
    scopes = table["instructions"]
    mine = 0
    for name, ns in own.items():
        phase, module = scopes.get(name, ("other", "none"))
        if (phases is None or phase in phases) and (
                modules is None or module in modules):
            mine += ns
    return 100.0 * mine / base
