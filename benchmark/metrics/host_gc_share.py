"""The collector's pauses over the window, in per cent of its seconds:
the growth of `snapshot()["host"]["gc"]["seconds"]` (the `gc.callbacks`
hook of `telemetry/host.py`, every thread's collections)."""


def read(ctx):
    try:
        paused = (ctx["snap1"]["host"]["gc"]["seconds"]
                  - ctx["snap0"]["host"]["gc"]["seconds"])
    except (KeyError, TypeError):
        return None
    start, end = ctx["window"]
    return 100.0 * paused / (end - start)
