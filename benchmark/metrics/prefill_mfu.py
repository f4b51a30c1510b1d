"""Operations of the prompts prefilled while the trace ran over the
prefill programs' device time x peak."""
from benchmark import measure


def read(ctx):
    if not measure.traced(ctx):
        return None
    secs, _ = measure.module_time(ctx, "prefill_fn")
    _, ops, _ = measure.prefilled_in_trace(ctx)
    return measure.share(ops, secs * ctx["peak"]["bf16_flops_per_s"])
