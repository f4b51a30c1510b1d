"""The paged decode kernel's least time (whole pages of K and V read
once, bandwidth-bound) over its time in the trace. A call is one layer
of one dispatch; its work is a dispatch's mean, from the contexts of the
tokens decoded while the trace ran."""
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
    srv = ctx["config"]["serving"]
    work = flops.paged_decode_attention_work(
        ctx["shape"], contexts, int(srv["page_size"]), ctx["itemsize"])
    least = flops.least_seconds(work, ctx["peak"]) / n * calls
    return measure.share(least, secs)
