"""Bytes a decode step must move (every weight once, the live K/V once)
over the chip's bandwidth, over the decode program's device time."""
from benchmark import measure


def read(ctx):
    if not measure.traced(ctx):
        return None
    secs, calls = measure.module_time(ctx, "step_fn")
    n = measure.trace_dispatches(ctx)
    if not calls or not n:
        return None
    # keys read per step: the decoded tokens' contexts, a step's worth
    keys = sum(measure.decoded_in_trace(ctx)) / n
    byts = ctx["family"].decode_step_bytes(ctx, [keys])
    least = byts / ctx["peak"]["hbm_bytes_per_s"]
    return measure.share(least, secs / calls)
