"""The grouped expert products' least time over their device time in
the traced span: the weights of the experts touched once, each pair's
rows in and out, 6 d f operations a pair, whatever implements them (the
family names the device operations and counts the work). Decode steps
take pairs and touched experts from the program's counters; a prefill
pass touches every held expert of every layer."""
from benchmark import flops, measure, trace_reduce


def read(ctx):
    tr = measure.traced(ctx)
    fam = ctx["family"]
    if not tr or not hasattr(fam, "moe_expert_work"):
        return None
    a, b = tr.get("snap0") or {}, tr.get("snap1") or {}
    if "moe" not in a or "moe" not in b:
        return None
    secs = sum(trace_reduce.matching(tr["op_s"], name)
               for name in fam.MOE_EXPERT_OPS)
    pairs = b["moe"]["pairs"] - a["moe"]["pairs"]
    touched = b["moe"]["experts_touched"] - a["moe"]["experts_touched"]
    _, prefills = measure.module_time(ctx, "prefill_fn")
    s = fam.sizes(ctx["config"])
    touched += prefills * s["n_layers"] * s["n_held"]
    if not secs or not pairs:
        return None
    work = fam.moe_expert_work(ctx, pairs, touched)
    return measure.share(flops.least_seconds(work, ctx["peak"]), secs)
