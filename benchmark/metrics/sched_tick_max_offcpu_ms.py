"""`offcpu_ms` of the pass that `sched_tick_max_ms` reads (the longest
`snapshot()["slow_ticks"]` entry that started in the window): the
milliseconds of it that the scheduler's thread neither ran nor waited
on the device."""


def read(ctx):
    start, end = ctx["window"]
    mine = [t for t in ctx.get("snap1", {}).get("slow_ticks", ())
            if start <= t["start_s"] < end]
    if not mine:
        return None
    return max(mine, key=lambda t: t["dur_ms"]).get("offcpu_ms")
