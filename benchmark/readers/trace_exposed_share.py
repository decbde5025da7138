"""Reader ``trace_exposed_share``: time in which an operation matching
``patterns`` runs and no other operation does, as a percentage of the
traced window (first device)."""


def read(ctx, facts, args):
    red = ctx.reduced
    if red is None or not red.devices:
        return None
    dev = min(red.devices)
    span = red.window[1] - red.window[0]
    if span <= 0:
        return None
    return 100.0 * red.exposed_ns(dev, args["patterns"]) / span
