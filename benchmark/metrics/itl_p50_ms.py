"""50th percentile of the gap between successive tokens of a request,
over all gaps of all requests in the window."""
from benchmark import measure, stats


def read(ctx):
    return stats.percentile(measure.window_gaps(ctx), 50)
