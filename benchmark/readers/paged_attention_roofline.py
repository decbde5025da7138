"""Reader ``paged_attention_roofline``: the least time the chip could take
for the attention of the traced steps over the paged-attention kernel's
time, all of its calls.  Per step the least time is the larger of bytes over
HBM bandwidth and FLOPs over the bf16 peak, each summed over both kinds of
row the kernel serves: decoding rows (K and V of every context token, 4 KB
against 16 kFLOP a token a layer: the bytes bound them) and the prompts
whose prefill ended in the step (their K and V read once, and the causal
score and value matmuls: FLOPs bound a long prompt).  Rows, contexts and
prompts are what the driver counted; queries, outputs, the page table and
the re-reads of a prompt split into chunks are left out, so the share errs
low, never high."""

from .. import flops


def read(ctx, facts, args):
    red, prof = ctx.reduced, ctx.profiler
    if red is None or not red.devices or not prof.steps or ctx.peaks is None:
        return None
    kernel_s = red.name_ns(min(red.devices), args["patterns"]) / 1e9
    traced = slice(prof.first_step, prof.first_step + prof.steps)
    steps = list(zip(facts["step_decode_context"][traced],
                     facts["step_prefill_tokens"][traced],
                     facts["step_prefill_sq"][traced]))
    if kernel_s <= 0 or not steps:
        return None
    least = sum(max(
        flops.paged_bytes(ctx.config, context + prompt)
        / ctx.peaks["hbm_bytes_per_s"],
        (flops.paged_decode_flops(ctx.config, context)
         + flops.paged_prefill_flops(ctx.config, prompt_sq))
        / ctx.peaks["bf16_flops_per_s"])
        for context, prompt, prompt_sq in steps)
    return 100.0 * least / kernel_s
