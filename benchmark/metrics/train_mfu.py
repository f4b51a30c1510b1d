"""Forward and backward operations a token needs (matmuls and causal
attention, from shapes) x the tokens of the traced run's whole window
over its seconds x peak."""
from benchmark import measure


def read(ctx):
    t = ctx.get("train")
    if not t or not ctx.get("peak"):
        return None
    ops = ctx["family"].train_flops_token(ctx, t["seq_len"]) \
        * t["steps"] * t["tokens_per_step"]
    return measure.share(ops, t["elapsed"]
                         * ctx["peak"]["bf16_flops_per_s"])
