"""50th percentile, over all requests due in the window, of due time to
first token; a failed or refused request counts as the worst."""
from benchmark import measure, stats


def read(ctx):
    return stats.percentile(measure.window_ttft_ms(ctx), 50)
