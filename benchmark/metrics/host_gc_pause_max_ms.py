"""The longest collector pause that started in the window, of the
longest of each interval that `snapshot()["host"]["gc"]["slowest"]`
keeps; 0 where none started in it."""


def read(ctx):
    try:
        kept = ctx["snap1"]["host"]["gc"]["slowest"]
    except (KeyError, TypeError):
        return None
    start, end = ctx["window"]
    return max((e["dur_ms"] for e in kept if start <= e["start_s"] < end),
               default=0.0)
