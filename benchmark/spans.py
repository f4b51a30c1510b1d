"""What the readers of the scheduler's own spans and counters share.

The program stamps a request's life (`GenerationStream.timeline()`) and
counts the phases of its passes (`DecodeLoop.snapshot()["phases"]`). A
program that has neither, as the one before these metrics had not, gives
every reader here nothing to read: they return None and do not raise.
"""

from __future__ import annotations

from typing import List, Optional


def stage_ms(ctx: dict, begin: str, end: str) -> Optional[List[float]]:
    """Milliseconds from stamp `begin` to stamp `end` in the life of
    every request due in the window. A request that failed, was refused
    or never reached `end` counts as the worst, the time the run waited
    for it, as `ttft_p90_ms` counts it. None where the program stamps
    no lives."""
    start, stop = ctx["window"]
    worst = 1e3 * (max([stop] + [t for r in ctx["requests"]
                                 for t in r["times"][-1:]]) - start)
    out = []
    for r in ctx["requests"]:
        if not start <= r["due"] < stop:
            continue
        stream = r.get("stream")
        if stream is None or r.get("failed"):
            out.append(worst)
            continue
        if not hasattr(stream, "timeline"):
            return None
        life = stream.timeline()
        if life[begin] is None or life[end] is None:
            out.append(worst)
        else:
            out.append(1e3 * (life[end] - life[begin]))
    return out


def phase_seconds(ctx: dict, phase: str) -> Optional[float]:
    """Seconds the scheduler spent in `phase` over the window."""
    if "phases" not in ctx.get("snap1", ()):
        return None
    return (ctx["snap1"]["phases"][phase]["seconds"]
            - ctx["snap0"]["phases"][phase]["seconds"])
