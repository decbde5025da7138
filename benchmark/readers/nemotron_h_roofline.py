"""Reader ``nemotron_h_roofline``: the least time the chip could take for
one of the Nemotron-H cell's kernels over the traced steps, over the time of
the kernels that match ``patterns``.  The counts are of the rows stepped
(attributes of the program's ``fastgen.step`` spans, the driver's contexts)
and of the configuration's shapes (``flops_nemotron_h``), never of what a
kernel chose to move.  A program without the attributes (one from before
the family) gives None.

``kind``
  ``ssd_decode``   the one-token rows the update kernel stepped
                   (``ssd_rows_decode``): each row's float32 state read and
                   written once in every Mamba-2 layer, its operands and
                   read-out, at the memory's rate.  The convolution's tail
                   (37 KB of a slot's 2.13 MB a layer) is NOT in the bytes:
                   the program reads it in an XLA gather outside the
                   kernel's name and only its write rides the kernel
  ``ssd_prefill``  prompt rows (``prefill_rows``) and their true tokens
                   (``ssd_tokens_prefill``): the larger of the bytes (the
                   state once a row, operands a token) at the memory's rate
                   and the chunked form's operations at the bf16 peak (the
                   kernel multiplies in float32: the share says so)
  ``attention``    paged attention of the decoding rows in the TWO
                   attention layers at 16 query heads a KV head: the larger
                   of each context token's K and V at the memory's rate and
                   every query head against them at the bf16 peak; queries,
                   outputs, tables and the whole pages the kernel fetches
                   where it needs part of one are left out: it errs low
  ``experts``      the grouped expert matmuls: the TWO matrices of the
                   experts a pass touched, once each
                   (``moe_experts_touched``), and the pairs' rows and two
                   projections (``moe_pairs_here``)
"""

from .. import flops_nemotron_h as flops
from . import span_ring


def read(ctx, facts, args):
    red, prof = ctx.reduced, ctx.profiler
    if red is None or not red.devices or ctx.peaks is None:
        return None
    kernel_s = red.name_ns(min(red.devices), args["patterns"]) / 1e9
    if kernel_s <= 0:
        return None
    rate, peak = (ctx.peaks["hbm_bytes_per_s"],
                  ctx.peaks["bf16_flops_per_s"])
    kind, c = args["kind"], ctx.config
    if kind == "attention":
        contexts = facts["step_decode_context"][
            prof.first_step:prof.first_step + prof.steps] \
            if prof.steps else []
        if not contexts:
            return None
        return 100.0 * sum(max(
            flops.attention_bytes(c, n) / rate,
            flops.attention_flops(c, n) / peak) for n in contexts) / kernel_s
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

    if kind == "ssd_decode":
        rows = attr("ssd_rows_decode")
        if not rows:
            return None
        least = flops.update_decode_bytes(c, int(sum(rows))) / rate
    elif kind == "ssd_prefill":
        tokens, rows = attr("ssd_tokens_prefill"), attr("prefill_rows")
        if not tokens or len(tokens) != len(rows):
            return None
        n_rows = int(sum(r for r, t in zip(rows, tokens) if t > 0))
        least = max(
            flops.chunk_prefill_bytes(c, n_rows, int(sum(tokens))) / rate,
            flops.chunk_prefill_ops(c, int(sum(tokens))) / peak)
    else:
        touched, pairs = attr("moe_experts_touched"), attr("moe_pairs_here")
        if not touched or len(touched) != len(pairs):
            return None
        least = sum(max(
            flops.grouped_expert_bytes(c, int(a), int(b)) / rate,
            flops.grouped_expert_flops(c, int(b)) / peak)
            for a, b in zip(touched, pairs))
    return 100.0 * least / kernel_s
