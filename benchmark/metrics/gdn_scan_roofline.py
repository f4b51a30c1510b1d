"""The chunked scan's least time over its device time in the traced
span: the family counts one call (a linear layer of one prefill pass)
at the real length of the prompts whose first token came while the
trace ran; the calls are the trace's."""
from benchmark import flops, measure, trace_reduce


def read(ctx):
    tr = measure.traced(ctx)
    fam = ctx["family"]
    if not tr or not hasattr(fam, "gdn_scan_work"):
        return None
    secs = sum(trace_reduce.matching(tr["op_s"], name)
               for name in fam.GDN_SCAN_OPS)
    calls = sum(trace_reduce.matching(tr["op_n"], name)
                for name in fam.GDN_SCAN_OPS)
    t0, t1 = tr["host"]
    mine = [r["prompt_len"] for r in ctx["requests"]
            if r["first"] is not None and t0 <= r["first"] < t1]
    if not secs or not calls or not mine:
        return None
    work = fam.gdn_scan_work(ctx, 1, sum(mine) / len(mine))
    return measure.share(calls * flops.least_seconds(work, ctx["peak"]),
                         secs)
