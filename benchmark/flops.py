"""The roofline of a kernel call. What a call needs (operations and
bytes, from shapes and from what ran) is its family's to count
(`benchmark/families/<family>.py`); the peaks are `peaks.json`'s."""

from __future__ import annotations

from typing import Sequence


def least_seconds(work: dict, peak: dict) -> float:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])


def least_seconds_for(works: Sequence[dict], calls: float,
                      peak: dict) -> float:
    """`works` lists the work of each call of one pass (a family's
    `*_work`); the trace counted `calls` of them, so `calls /
    len(works)` passes."""
    return sum(least_seconds(w, peak) for w in works) * calls / len(works)
