"""Share of the device's idle time in the traced window that no host
span covers; 0 where that is not among the ten largest idle gaps."""
from benchmark import measure, trace_reduce


def read(ctx):
    tr = measure.traced(ctx)
    if not tr or tr["window_s"] <= tr["busy_s"]:
        return None
    nobody = dict(tr["idle_gaps"]).get(trace_reduce.UNATTRIBUTED, 0.0)
    return 100.0 * nobody / (tr["window_s"] - tr["busy_s"])
