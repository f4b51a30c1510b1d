"""Device time of the decode program per dispatch, from the trace."""
from benchmark import measure


def read(ctx):
    if not measure.traced(ctx):
        return None
    secs, calls = measure.module_time(ctx, "step_fn")
    return 1e3 * secs / calls if calls else None
