"""Device time of one prefill program in the traced span: with a bound
on the tokens a pass prefills, the length a prefill adds to the token
gap of every running stream (it is the mode `itl_p98_ms` lies in where
one pass in twenty-four holds a prefill)."""
from benchmark import measure


def read(ctx):
    if not measure.traced(ctx):
        return None
    secs, calls = measure.module_time(ctx, "prefill_fn")
    return 1e3 * secs / calls if calls else None
