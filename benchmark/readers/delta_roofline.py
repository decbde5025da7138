"""Reader ``delta_roofline``: the least time the chip could take for one
of the olmo_hybrid family's three kernels over the traced steps, over the
time of the kernels that match ``patterns``.

``kind: decode``: the rows are the one-token rows the update kernel stepped
(``attr:delta_rows_decode`` of the program's ``fastgen.step`` spans); each
must have its matrix state read and written once in every linear layer, in
the configuration's ``delta_state_dtype``, with its operands and read-out
(``flops_olmo_hybrid.update_decode_bytes``), at the memory's rate.
``kind: prefill``: prompt rows (``attr:prefill_rows``) and their true
tokens (``attr:delta_tokens_prefill``): the larger of the bytes (the state
once a row, operands a token) at the memory's rate and the chunked form's
operations at the bf16 peak.  ``kind: attention``: the K and V of the
context the decode rows attend (``attr:attn_tokens_full``) in the full
layers that are run, at the memory's rate.  The counts are of the rows
stepped and of the configuration's shapes, never of what a kernel chose to
move.  The convolution's tail (69 KB of a slot's 2.28 MB a layer) is NOT in
the bytes: the program reads it in an XLA gather outside the kernels' names
and only its write rides the kernel.  A program without the attributes (one
from before the family) gives None."""

from .. import flops_olmo_hybrid as flops
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
    rate = ctx.peaks["hbm_bytes_per_s"]
    if args["kind"] == "decode":
        rows = attr("delta_rows_decode")
        if not rows:
            return None
        least = flops.update_decode_bytes(ctx.config, int(sum(rows))) / rate
    elif args["kind"] == "prefill":
        tokens, rows = attr("delta_tokens_prefill"), attr("prefill_rows")
        if not tokens or len(tokens) != len(rows):
            return None
        n_rows = int(sum(r for r, t in zip(rows, tokens) if t > 0))
        least = max(
            flops.chunk_prefill_bytes(ctx.config, n_rows,
                                      int(sum(tokens))) / rate,
            flops.chunk_prefill_ops(ctx.config, int(sum(tokens)))
            / ctx.peaks["bf16_flops_per_s"])
    else:
        attended = attr("attn_tokens_full")
        if not attended:
            return None
        kv_bytes = 2 if ctx.config["engine"]["kv_dtype"] == "bfloat16" else 4
        least = flops.attention_decode_bytes(
            ctx.config, int(sum(attended)), kv_bytes) / rate
    return 100.0 * least / kernel_s
