"""Reader ``smallthinker_roofline``: the least time the chip could take for
one of the SmallThinker cell's two kernels over the traced steps, over the
time of the kernels that match ``patterns``.  Per step the least time is
the larger of bytes over HBM bandwidth and FLOPs over the bf16 peak, from
``flops_smallthinker`` and the program's own counts (attributes of its
``fastgen.step`` spans); a program without them gives None.

``kind``
  ``experts``    the grouped expert matmuls: the weights of the experts a
                 pass touched, once each (``moe_experts_touched``), and the
                 pairs' rows and three projections (``moe_pairs_here``):
                 ``moe_expert_roofline``'s count from this family's keys
                 (``moe_ffn_hidden_size``)
  ``attention``  paged attention of the decoding rows at 7 query heads a
                 KV head: K and V of ``attn_tokens_full`` in the global
                 layers and of ``attn_tokens_window`` (min(context,
                 window)) in the window layers, 2,048 B a token and layer.
                 Queries, outputs, the tables and the whole pages the
                 kernel fetches where it needs part of one are left out,
                 so the share errs low
"""

from .. import flops_smallthinker as flops
from . import span_ring

KEYS = {"experts": ("moe_experts_touched", "moe_pairs_here"),
        "attention": ("attn_tokens_full", "attn_tokens_window")}


def least_seconds(config, peaks, kind, a, b) -> float:
    if kind == "experts":
        nbytes = flops.grouped_expert_bytes(config, a, b)
        nflops = flops.grouped_expert_flops(config, b)
    else:
        nbytes = flops.attention_bytes(config, a, b)
        nflops = flops.attention_flops(config, a, b)
    return max(nbytes / peaks["hbm_bytes_per_s"],
               nflops / peaks["bf16_flops_per_s"])


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
    first, second = (
        span_ring.values(records, [r"^fastgen\.step$"], "attr:" + key, [],
                         *span)[0] for key in KEYS[args["kind"]])
    kernel_s = red.name_ns(min(red.devices), args["patterns"]) / 1e9
    if kernel_s <= 0 or not first or len(first) != len(second):
        return None
    least = sum(least_seconds(ctx.config, ctx.peaks, args["kind"], int(a),
                              int(b)) for a, b in zip(first, second))
    return 100.0 * least / kernel_s
