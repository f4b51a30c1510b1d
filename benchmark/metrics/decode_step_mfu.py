"""Operations of the decode dispatches of the traced span over their
device time x peak: bounds a claim on the gap between tokens."""
from benchmark import measure


def read(ctx):
    if not measure.traced(ctx):
        return None
    secs, _ = measure.module_time(ctx, "step_fn")
    ops = sum(ctx["family"].decode_token_flops(ctx, c)
              for c in measure.decoded_in_trace(ctx))
    return measure.share(ops, secs * ctx["peak"]["bf16_flops_per_s"])
