"""Device time of the prefill programs per 1,000 prompt tokens
prefilled while the trace ran."""
from benchmark import measure


def read(ctx):
    if not measure.traced(ctx):
        return None
    secs, _ = measure.module_time(ctx, "prefill_fn")
    tokens, _, _ = measure.prefilled_in_trace(ctx)
    return 1e3 * secs / (tokens / 1e3) if tokens and secs else None
