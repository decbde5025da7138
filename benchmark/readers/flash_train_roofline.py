"""Reader ``flash_train_roofline``: the least time the chip could take for
the flash-attention work of the traced steps (FLOPs from shapes over the
bf16 peak: compute-bound at these shapes) over the kernels' time."""

from .. import flops


def read(ctx, facts, args):
    red, steps = ctx.reduced, ctx.profiler.steps
    if red is None or not red.devices or not steps or ctx.peaks is None:
        return None
    dev = min(red.devices)
    kernel_s = red.name_ns(dev, args["patterns"]) / 1e9
    if kernel_s <= 0:
        return None
    need = flops.flash_train_flops(ctx.config, facts["seq_len"],
                                   facts["rows_per_chip"]) * steps
    return 100.0 * need / ctx.peaks["bf16_flops_per_s"] / kernel_s
