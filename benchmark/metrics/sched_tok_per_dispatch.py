"""Tokens streamed per decode dispatch over the window, from the
scheduler's own `snapshot()` counts."""
from benchmark import measure


def read(ctx):
    n = measure.snap_delta(ctx, "dispatches")
    return measure.snap_delta(ctx, "tokens_streamed") / n if n else None
