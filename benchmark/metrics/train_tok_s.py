"""Steps completed in the window x tokens a step over the window's
seconds (the window ends when its last step has ended)."""
from benchmark import stats


def read(ctx):
    t = ctx["train"]
    return stats.rate(t["steps"] * t["tokens_per_step"], t["elapsed"])
