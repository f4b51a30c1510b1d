"""The scheduler's own milliseconds a dispatch over the window: seconds
in `decode.tick` less those in `decode.d2h` (the wait on the device),
over the dispatches."""
from benchmark import measure, spans


def read(ctx):
    tick = spans.phase_seconds(ctx, "decode.tick")
    n = measure.snap_delta(ctx, "dispatches")
    if tick is None or not n:
        return None
    return 1e3 * (tick - spans.phase_seconds(ctx, "decode.d2h")) / n
