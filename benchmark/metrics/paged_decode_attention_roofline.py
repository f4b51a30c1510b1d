"""The paged decode kernel's least time (whole pages of K and V read
once, bandwidth-bound) over its time in the trace. A call is one layer
of one dispatch; a dispatch's work is the mean over the traced span,
from the contexts of the tokens decoded while the trace ran."""
from benchmark import flops, measure, trace_reduce


def read(ctx):
    tr = measure.traced(ctx)
    if not tr:
        return None
    secs = trace_reduce.matching(tr["op_s"], "paged_decode_attention")
    calls = trace_reduce.matching(tr["op_n"], "paged_decode_attention")
    n = measure.trace_dispatches(ctx)
    contexts = measure.decoded_in_trace(ctx)
    if not secs or not n or not contexts:
        return None
    works = ctx["family"].paged_decode_attention_work(ctx, contexts)
    least = flops.least_seconds_for(works, calls, ctx["peak"]) / n
    return measure.share(least, secs)
