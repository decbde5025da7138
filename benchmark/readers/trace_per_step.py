"""Reader ``trace_per_step``: device-busy milliseconds (union of the
operation intervals, first device) per step of the traced slice."""


def read(ctx, facts, args):
    red, steps = ctx.reduced, ctx.profiler.steps
    if red is None or not red.devices or not steps:
        return None
    return red.busy_ns(min(red.devices)) / 1e6 / steps
