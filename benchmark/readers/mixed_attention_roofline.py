"""Reader ``mixed_attention_roofline``: the least time the chip could take
for the paged attention of the traced steps' decoding rows in a model whose
layers are of two kinds, over the time of the kernels that match
``patterns`` (both kinds' calls).  Per step the least time is the larger of
bytes over HBM bandwidth and FLOPs over the bf16 peak, summed over the full
layers at their head count and the rows' whole contexts and the window
layers at theirs and ``min(context, window)`` (``flops_laguna``).  The two
token sums are the program's own, attributes of its ``fastgen.step`` spans
(``attn_tokens_full``, ``attn_tokens_window``: what the step's decode rows
attend in a layer of each kind); a program without them gives None.
Queries, outputs, the tables and the whole pages the kernel fetches where it
needs part of one are left out, so the share errs low."""

from .. import flops_laguna as flops
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
    full, window = (
        span_ring.values(records, [r"^fastgen\.step$"], "attr:" + key, [],
                         *span)[0]
        for key in ("attn_tokens_full", "attn_tokens_window"))
    kernel_s = red.name_ns(min(red.devices), args["patterns"]) / 1e9
    if kernel_s <= 0 or not full or len(full) != len(window):
        return None
    least = sum(max(
        flops.attention_bytes(ctx.config, int(f), int(w))
        / ctx.peaks["hbm_bytes_per_s"],
        flops.attention_flops(ctx.config, int(f), int(w))
        / ctx.peaks["bf16_flops_per_s"]) for f, w in zip(full, window))
    return 100.0 * least / kernel_s
