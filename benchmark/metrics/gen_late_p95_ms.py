"""How late the generator ran: sent minus due, 95th percentile over the
requests due in the window, by the generator's own clock."""
from benchmark import stats


def read(ctx):
    start, end = ctx["window"]
    late = [1e3 * (r["sent"] - r["due"]) for r in ctx["requests"]
            if start <= r["due"] < end and r["sent"] is not None]
    return stats.percentile(late, 95)
