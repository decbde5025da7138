"""Reader ``moe_expert_roofline``: the least time the chip could take for
the grouped expert matmuls of the traced steps, over the time of the
kernels that match ``patterns``.  Per step the least time is the larger of
bytes over HBM bandwidth (the weights of the held experts a pass touched,
once each, and the pairs' rows) and FLOPs over the bf16 peak (three
projections a pair); a decode step's handful of pairs an expert leaves the
weights' bytes the bound.  Experts touched and pairs are the program's own
counts, attributes of its ``fastgen.step`` spans (they ride the step's
token transfer); a program without them gives None."""

from .. import flops_pangu_moe as flops
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
    per_step = [
        span_ring.values(records, [r"^fastgen\.step$"], "attr:" + key, [],
                         *span)[0]
        for key in ("moe_experts_touched", "moe_pairs_here")]
    kernel_s = red.name_ns(min(red.devices), args["patterns"]) / 1e9
    if kernel_s <= 0 or not per_step[0] \
            or len(per_step[0]) != len(per_step[1]):
        return None
    least = sum(max(
        flops.grouped_expert_bytes(ctx.config, int(touched), int(pairs))
        / ctx.peaks["hbm_bytes_per_s"],
        flops.grouped_expert_flops(ctx.config, int(pairs))
        / ctx.peaks["bf16_flops_per_s"])
        for touched, pairs in zip(*per_step))
    return 100.0 * least / kernel_s
