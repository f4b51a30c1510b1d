"""Quantities that several metric readers share, worked out from the
run's record (`ctx`). A reader stays a few lines; the arithmetic that
two of them need is here, once."""

from __future__ import annotations

from typing import List, Optional, Tuple

from benchmark import flops, stats, trace_reduce


def window_gaps(ctx: dict) -> List[float]:
    """Every gap between successive tokens of a request whose later
    token fell inside the window, in milliseconds."""
    start, end = ctx["window"]
    return [1e3 * g for r in ctx.get("requests", ())
            for g in stats.gaps_in_window(r["times"], start, end)]


def window_ttft_ms(ctx: dict) -> List[float]:
    """Due time to first token of every request due in the window; one
    that failed or never answered counts as the worst: the time the run
    waited for it."""
    start, end = ctx["window"]
    worst = 1e3 * (max([end] + [t for r in ctx["requests"]
                                for t in r["times"][-1:]]) - start)
    return [1e3 * v if v != float("inf") else worst
            for v in stats.ttft_samples(ctx["requests"], start, end)]


def snap_delta(ctx: dict, key: str) -> Optional[int]:
    if "snap0" not in ctx:
        return None
    return ctx["snap1"][key] - ctx["snap0"][key]


def traced(ctx: dict) -> Optional[dict]:
    tr = ctx.get("trace")
    return tr if tr and "busy_s" in tr else None


def decoded_in_trace(ctx: dict) -> List[int]:
    """The context (keys seen) of every token the DECODE step emitted
    while the trace ran: token i > 0 of a request sees prompt + i
    keys."""
    t0, t1 = ctx["trace"]["host"]
    return [r["prompt_len"] + i for r in ctx["requests"]
            for i, t in enumerate(r["times"]) if i > 0 and t0 <= t < t1]


def trace_dispatches(ctx: dict) -> Optional[int]:
    tr = ctx["trace"]
    if not tr.get("snap0"):
        return None
    return tr["snap1"]["dispatches"] - tr["snap0"]["dispatches"]


def module_time(ctx: dict, needle: str) -> Tuple[float, float]:
    """(seconds, calls) of the compiled programs whose name holds
    `needle`, in the traced window."""
    tr = ctx["trace"]
    return (trace_reduce.matching(tr["module_s"], needle),
            trace_reduce.matching(tr["module_n"], needle))


def prefilled_in_trace(ctx: dict) -> Tuple[int, float, float]:
    """(prompt tokens prefilled while the trace ran, the operations
    those prompts need, the flash kernel's least seconds for them). The
    tokens are the program's own count; the per-prompt work is that of
    the requests whose first token fell in the traced span, scaled to
    that count, because the count has no lengths."""
    tr = ctx["trace"]
    t0, t1 = tr["host"]
    tokens = tr["snap1"]["prefill_tokens"] - tr["snap0"]["prefill_tokens"]
    mine = [r["prompt_len"] for r in ctx["requests"]
            if r["first"] is not None and t0 <= r["first"] < t1]
    if not tokens or not mine:
        return 0, 0.0, 0.0
    scale = tokens / sum(mine)
    family = ctx["family"]
    ops = sum(family.prefill_flops(ctx, n) for n in mine) * scale
    least = sum(flops.least_seconds(w, ctx["peak"]) for n in mine
                for w in family.flash_fwd_work(ctx, 1, n)) * scale
    return tokens, ops, least


def share(part: float, whole: float) -> Optional[float]:
    """A share in per cent, or None where there is nothing to divide
    by: a share of a roofline is never reported as 0."""
    if not whole or whole <= 0 or part is None or part <= 0:
        return None
    return 100.0 * part / whole
