"""The flash forward kernel's least time for the prompts prefilled
while the trace ran (their real lengths, not the bucket's) over its
time in the trace."""
from benchmark import measure, trace_reduce


def read(ctx):
    tr = measure.traced(ctx)
    if not tr:
        return None
    secs = trace_reduce.matching(tr["op_s"], "flash_fwd")
    _, _, least = measure.prefilled_in_trace(ctx)
    return measure.share(least, secs)
