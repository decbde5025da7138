"""Reader ``ssm_roofline``: the least time the chip's memory could take for
the recurrence of the traced steps' state-space rows, over the time of the
kernels that match ``patterns``.

``kind: decode``: the rows are the one-token rows the update kernel stepped
(``attr:ssm_rows_decode`` of the program's ``fastgen.step`` spans); each
must have its state read and written once in every Mamba layer, in the
configuration's ``ssm_state_dtype``, with its operands and ``y``
(``flops_jamba.recurrence_decode_bytes``).  ``kind: prefill``: prompt rows
(``attr:prefill_rows``) and their true tokens (``attr:ssm_tokens_prefill``):
the state once a row, operands and ``y`` a token.  The count is of the rows
stepped and of the configuration's shapes, never of what a kernel chose to
move, so it reads the same work whatever implements the kernel.  The
convolution's tail (31 KB of a slot's 358 KB a layer) is NOT in the count:
the program reads it in an XLA gather outside the kernels' names and only
its write rides the kernel, and bytes a kernel did not move would flatter
its share (it reads up to 4% low for the write instead).  A program
without the attributes (one from before the family) gives None."""

from .. import flops_jamba as flops
from . import span_ring


def read(ctx, facts, args):
    red = ctx.reduced
    if red is None or not red.devices or ctx.peaks is None:
        return None
    try:
        from deepspeed_tpu.telemetry import get_tracer
    except ImportError:
        return None
    span = span_ring.window(ctx, "slice")
    records = [r for r in get_tracer().records() if len(r) >= 9]
    if span is None or not records:
        return None

    def attr(key):
        return span_ring.values(records, [r"^fastgen\.step$"],
                                "attr:" + key, [], *span)[0]

    kernel_s = red.name_ns(min(red.devices), args["patterns"]) / 1e9
    if kernel_s <= 0:
        return None
    if args["kind"] == "decode":
        rows = attr("ssm_rows_decode")
        if not rows:
            return None
        steps = sum(1 for r in rows if r > 0)
        least = flops.recurrence_decode_bytes(ctx.config, int(sum(rows)),
                                              steps)
    else:
        tokens, rows = attr("ssm_tokens_prefill"), attr("prefill_rows")
        if not tokens or len(tokens) != len(rows):
            return None
        steps = sum(1 for t in tokens if t > 0)
        least = flops.recurrence_prefill_bytes(
            ctx.config, int(sum(r for r, t in zip(rows, tokens) if t > 0)),
            int(sum(tokens)), steps)
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / kernel_s
