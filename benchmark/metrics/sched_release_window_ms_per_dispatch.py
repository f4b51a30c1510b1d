"""The scheduler's milliseconds a dispatch in `decode.release_window`,
returning the window kind's pages that fell out, over the window."""
from benchmark import measure, spans

PHASE = "decode.release_window"


def read(ctx):
    if PHASE not in (ctx.get("snap1") or {}).get("phases", ()):
        return None
    n = measure.snap_delta(ctx, "dispatches")
    if not n:
        return None
    return 1e3 * spans.phase_seconds(ctx, PHASE) / n
