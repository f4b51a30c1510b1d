"""The scheduler's own milliseconds a dispatch in which its thread did
not run: (`decode.tick` less `decode.d2h`) wall seconds less the same
phases' CPU seconds (`snapshot()["phases"][...]["cpu_seconds"]`, the
thread's own clock), over the window, over the dispatches."""
from benchmark import measure, spans

PHASES = ("decode.tick", "decode.d2h")


def _cpu_seconds(ctx, phase):
    try:
        return (ctx["snap1"]["phases"][phase]["cpu_seconds"]
                - ctx["snap0"]["phases"][phase]["cpu_seconds"])
    except (KeyError, TypeError):
        return None


def read(ctx):
    cpu = [_cpu_seconds(ctx, p) for p in PHASES]
    n = measure.snap_delta(ctx, "dispatches")
    if None in cpu or not n:
        return None
    wall = [spans.phase_seconds(ctx, p) for p in PHASES]
    return 1e3 * ((wall[0] - wall[1]) - (cpu[0] - cpu[1])) / n
