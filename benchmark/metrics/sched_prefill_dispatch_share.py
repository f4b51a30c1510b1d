"""Share of the window's dispatches whose pass also ran at least one
prefill (`decode.prefill_dispatch`): the token gaps a prefill
lengthened."""
from benchmark import measure


def read(ctx):
    if "prefill_passes" not in ctx.get("snap1", ()):
        return None
    n = measure.snap_delta(ctx, "dispatches")
    return 100.0 * measure.snap_delta(ctx, "prefill_passes") / n \
        if n else None
