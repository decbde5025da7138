"""Reader ``trace_name_share``: time of the device operations whose name
matches one of ``patterns``, as a percentage ``of`` the device's busy time
or of the traced window (first device)."""


def read(ctx, facts, args):
    red = ctx.reduced
    if red is None or not red.devices:
        return None
    dev = min(red.devices)
    base = (red.busy_ns(dev) if args.get("of", "busy") == "busy"
            else red.window[1] - red.window[0])
    if base <= 0:
        return None
    return 100.0 * red.name_ns(dev, args["patterns"]) / base
