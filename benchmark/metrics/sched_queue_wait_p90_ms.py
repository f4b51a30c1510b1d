"""90th percentile, over the requests due in the window, of the wait in
the admission queue: `submitted` to `admitted` of the request's own
life (`request.queued`)."""
from benchmark import spans, stats


def read(ctx):
    waits = spans.stage_ms(ctx, "submitted", "admitted")
    return stats.percentile(waits, 90) if waits else None
