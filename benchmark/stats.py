"""Percentile, rate and worst-for-failed arithmetic: the benchmark's
own, so that no later PR can change how a number is taken."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks; None for no sample. Infinite samples (failed
    requests) sort last and are returned as they are."""
    vals = sorted(values)
    if not vals:
        return None
    pos = (len(vals) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or math.isinf(vals[hi]):
        return float(vals[hi] if pos > lo else vals[lo])
    return float(vals[lo] + (vals[hi] - vals[lo]) * (pos - lo))


def rate(count: float, seconds: float) -> float:
    """Work over ALL the time of the window, idle and stalls included."""
    if seconds <= 0:
        raise ValueError("a window has to last")
    return count / seconds


def in_window(times: Iterable[float], start: float, end: float) -> int:
    return sum(1 for t in times if start <= t < end)


def gaps_in_window(token_times: Sequence[float], start: float,
                   end: float) -> List[float]:
    """Gaps between successive tokens of one request, counted where the
    later token fell inside the window."""
    return [b - a for a, b in zip(token_times, token_times[1:])
            if start <= b < end]


def ttft_samples(requests: Iterable[dict], start: float, end: float,
                 worst: float = math.inf) -> List[float]:
    """Due time to first token for every request DUE in the window. One
    that failed, was refused or never gave a token counts as the worst,
    so it can only move a tail up."""
    out = []
    for r in requests:
        if not start <= r["due"] < end:
            continue
        if r.get("failed") or r.get("first") is None:
            out.append(worst)
        else:
            out.append(r["first"] - r["due"])
    return out
