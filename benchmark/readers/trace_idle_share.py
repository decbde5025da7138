"""Reader ``trace_idle_share``: 100 x (1 - busy / traced window), busy
averaged over the cell's chips."""


def read(ctx, facts, args):
    red = ctx.reduced
    if red is None or not red.devices or red.window_s() <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s() / red.window_s())
