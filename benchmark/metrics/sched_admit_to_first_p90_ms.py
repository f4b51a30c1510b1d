"""90th percentile, over the requests due in the window, of `admitted`
to `first_token` of the request's own life (`request.prefill`): the
prefill, and whatever holds the first token after it."""
from benchmark import spans, stats


def read(ctx):
    waits = spans.stage_ms(ctx, "admitted", "first_token")
    return stats.percentile(waits, 90) if waits else None
