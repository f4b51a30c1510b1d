"""Device time of one train step, from the trace."""
from benchmark import measure


def read(ctx):
    if not measure.traced(ctx):
        return None
    secs, calls = measure.module_time(ctx, "jit_step")
    return 1e3 * secs / calls if calls else None
