"""The state update's least time over its device time in the traced
span: each live slot's state read once and written once and the step's
rows (bandwidth-bound), as the family counts a call; a call is one
linear layer of one dispatch, the live slots a dispatch the tokens
decoded while the trace ran over its dispatches."""
from benchmark import flops, measure, trace_reduce


def read(ctx):
    tr = measure.traced(ctx)
    fam = ctx["family"]
    if not tr or not hasattr(fam, "gdn_update_work"):
        return None
    secs = sum(trace_reduce.matching(tr["op_s"], name)
               for name in fam.GDN_UPDATE_OPS)
    calls = sum(trace_reduce.matching(tr["op_n"], name)
                for name in fam.GDN_UPDATE_OPS)
    n = measure.trace_dispatches(ctx)
    if not secs or not calls or not n:
        return None
    live = len(measure.decoded_in_trace(ctx)) / n
    work = fam.gdn_update_work(ctx, live)
    return measure.share(calls * flops.least_seconds(work, ctx["peak"]),
                         secs)
