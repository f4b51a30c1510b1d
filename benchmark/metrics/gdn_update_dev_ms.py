"""Device time of the one-token state update of the linear layers (the
family names the device operation) per decode dispatch, from the trace:
every linear layer's call of one step together."""
from benchmark import measure, trace_reduce


def read(ctx):
    tr = measure.traced(ctx)
    fam = ctx["family"]
    if not tr or not hasattr(fam, "GDN_UPDATE_OPS"):
        return None
    secs = sum(trace_reduce.matching(tr["op_s"], name)
               for name in fam.GDN_UPDATE_OPS)
    n = measure.trace_dispatches(ctx)
    return 1e3 * secs / n if secs and n else None
