"""Both flash backward kernels of a layer together: their least time
per layer x the layers in the trace, over their time there."""
from benchmark import flops, measure, trace_reduce


def read(ctx):
    tr = measure.traced(ctx)
    if not tr:
        return None
    secs = trace_reduce.matching(tr["op_s"], "flash_bwd")
    calls = trace_reduce.matching(tr["op_n"], "flash_bwd_dq")
    t = ctx["train"]
    works = ctx["family"].flash_bwd_work(ctx, t["rows"], t["seq_len"])
    return measure.share(
        flops.least_seconds_for(works, calls, ctx["peak"]), secs)
