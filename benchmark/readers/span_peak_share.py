"""Reader ``span_peak_share``: the largest value an attribute of the
program's spans took in the traced slice, as a share (%) of a size the
configuration file states: ``names`` the spans, ``value`` the attribute
(``attr:<key>``), ``of_config`` the dotted key of the whole.  None where no
span carries the attribute (a program from before it)."""

from . import span_ring


def read(ctx, facts, args):
    try:
        from deepspeed_tpu.telemetry import get_tracer
    except ImportError:
        return None
    span = span_ring.window(ctx, "slice")
    records = [r for r in get_tracer().records() if len(r) >= 9]
    if span is None or not records:
        return None
    got, _ = span_ring.values(records, args["names"], args["value"], [],
                              *span)
    whole = span_ring.scale_of(ctx, "config:" + args["of_config"])
    if not got or whole <= 0:
        return None
    return 100.0 * max(got) / whole
