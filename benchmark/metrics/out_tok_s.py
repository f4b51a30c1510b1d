"""All output tokens emitted in the window over the window's seconds."""
from benchmark import stats


def read(ctx):
    start, end = ctx["window"]
    n = sum(stats.in_window(r["times"], start, end)
            for r in ctx["requests"])
    return stats.rate(n, end - start)
