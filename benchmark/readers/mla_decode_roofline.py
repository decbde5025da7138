"""Reader ``mla_decode_roofline``: the least time the chip could take for
the absorbed latent attention of the traced steps' decoding rows, over the
time of the kernels that match ``patterns``.  Per step the least time is
the larger of bytes over HBM bandwidth (each context token's 576-value
plane, once for all heads) and FLOPs over the bf16 peak (every head
against the plane, and the probabilities' sum of its 512 values): at 128
heads the FLOPs bound it.  Rows and contexts are what the driver counted;
queries, outputs and the page table are left out, so the share errs low."""

from .. import flops_pangu_moe as flops


def read(ctx, facts, args):
    red, prof = ctx.reduced, ctx.profiler
    if red is None or not red.devices or not prof.steps or ctx.peaks is None:
        return None
    kernel_s = red.name_ns(min(red.devices), args["patterns"]) / 1e9
    traced = slice(prof.first_step, prof.first_step + prof.steps)
    contexts = facts["step_decode_context"][traced]
    if kernel_s <= 0 or not contexts:
        return None
    least = sum(max(
        flops.mla_decode_bytes(ctx.config, context)
        / ctx.peaks["hbm_bytes_per_s"],
        flops.mla_decode_flops(ctx.config, context)
        / ctx.peaks["bf16_flops_per_s"]) for context in contexts)
    return 100.0 * least / kernel_s
