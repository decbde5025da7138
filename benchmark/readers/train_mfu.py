"""Reader ``train_mfu``: forward+backward FLOPs per token from the
configuration's shapes (recomputation not counted) x tokens/s/chip over the
chip's peak."""

from .. import flops


def read(ctx, facts, args):
    rate = facts.get(args.get("rate_key", "train_tok_s_chip"))
    if rate is None or ctx.peaks is None:
        return None
    per_token = flops.train_flops_per_token(ctx.config, facts["seq_len"])
    return 100.0 * per_token * rate / ctx.peaks["bf16_flops_per_s"]
