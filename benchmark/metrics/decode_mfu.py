"""Operations the model needs for the tokens decoded while the trace
ran, over the traced seconds x peak: the whole serving loop's share of
the chip, idle and prefill time included."""
from benchmark import measure


def read(ctx):
    tr = measure.traced(ctx)
    if not tr:
        return None
    ops = sum(ctx["family"].decode_token_flops(ctx, c)
              for c in measure.decoded_in_trace(ctx))
    return measure.share(ops, tr["window_s"]
                         * ctx["peak"]["bf16_flops_per_s"])
