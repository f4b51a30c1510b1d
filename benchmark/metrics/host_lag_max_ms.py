"""The heartbeat's longest delay that started in the window (from the
time the beat was due), of the longest of each interval that
`snapshot()["host"]["lag"]["slowest"]` keeps: how long the whole
interpreter did not run; 0 where no beat was due in it."""


def read(ctx):
    try:
        kept = ctx["snap1"]["host"]["lag"]["slowest"]
    except (KeyError, TypeError):
        return None
    start, end = ctx["window"]
    return max((e["dur_ms"] for e in kept if start <= e["start_s"] < end),
               default=0.0)
