"""Window seconds over the scheduler's dispatches in it."""
from benchmark import measure


def read(ctx):
    n = measure.snap_delta(ctx, "dispatches")
    start, end = ctx["window"]
    return 1e3 * (end - start) / n if n else None
