"""The flash forward kernel's least time per call (one layer, all rows
of the step) x its calls in the trace, over its time there."""
from benchmark import flops, measure, trace_reduce


def read(ctx):
    tr = measure.traced(ctx)
    if not tr:
        return None
    secs = trace_reduce.matching(tr["op_s"], "flash_fwd")
    calls = trace_reduce.matching(tr["op_n"], "flash_fwd")
    t = ctx["train"]
    work = flops.flash_fwd_work(ctx["shape"], t["rows"], t["seq_len"],
                                ctx["itemsize"])
    return measure.share(
        flops.least_seconds(work, ctx["peak"]) * calls, secs)
