"""The chunked scan's least time over its device time in the traced
span where prompts are prefilled in pieces: the family counts one call
(a linear layer of one prefill program) at the published widths, at the
mean of the real tokens a program prefilled in the span (the program's
own count over the programs the trace counted), with the share of
calls that started from a kept state (the program's count of pieces)
reading that state once more."""
from benchmark import flops, measure, trace_reduce


def read(ctx):
    tr = measure.traced(ctx)
    fam = ctx["family"]
    if not tr or not hasattr(fam, "gdn_scan_work") \
            or "prefill_chunks" not in (tr.get("snap1") or {}):
        return None
    secs = sum(trace_reduce.matching(tr["op_s"], name)
               for name in fam.GDN_SCAN_OPS)
    calls = sum(trace_reduce.matching(tr["op_n"], name)
                for name in fam.GDN_SCAN_OPS)
    tokens = tr["snap1"]["prefill_tokens"] - tr["snap0"]["prefill_tokens"]
    programs = calls / fam.sizes(ctx["config"])["n_linear"]
    if not secs or not calls or not tokens:
        return None
    carried = (tr["snap1"]["prefill_chunks"]["carried"]
               - tr["snap0"]["prefill_chunks"]["carried"]) / programs
    least = sum(
        share * flops.least_seconds(
            fam.gdn_scan_work(ctx, 1, tokens / programs, carried=kept),
            ctx["peak"])
        for kept, share in ((True, carried), (False, 1.0 - carried)))
    return measure.share(calls * least, secs)
