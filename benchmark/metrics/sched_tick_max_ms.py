"""The longest scheduler pass (`decode.tick`) that started in the
window, of those the program still holds at its end
(`snapshot()["slow_ticks"]`)."""


def read(ctx):
    start, end = ctx["window"]
    mine = [t["dur_ms"] for t in ctx.get("snap1", {}).get("slow_ticks", ())
            if start <= t["start_s"] < end]
    return max(mine) if mine else None
