"""1 - the union of the device's operation intervals over the traced
window."""
from benchmark import measure


def read(ctx):
    tr = measure.traced(ctx)
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
