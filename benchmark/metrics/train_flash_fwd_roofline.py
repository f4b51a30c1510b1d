"""The flash forward kernel's least time for its calls in the trace
(one a layer, all rows of the step), over its time there."""
from benchmark import flops, measure, trace_reduce


def read(ctx):
    tr = measure.traced(ctx)
    if not tr:
        return None
    secs = trace_reduce.matching(tr["op_s"], "flash_fwd")
    calls = trace_reduce.matching(tr["op_n"], "flash_fwd")
    t = ctx["train"]
    works = ctx["family"].flash_fwd_work(ctx, t["rows"], t["seq_len"])
    return measure.share(
        flops.least_seconds_for(works, calls, ctx["peak"]), secs)
