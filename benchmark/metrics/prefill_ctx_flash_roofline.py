"""The flash kernel with a query offset (a full layer of a later piece
of a prompt, over the pages of the pieces before it and its own): its
least time, Q and O of the piece and K and V of context and piece moved
once, operations over the keys each query row may see (the family's
`ctx_flash_work`), over its device time in the traced span. The pieces
are those of the prompts whose first token came in the span (their
lengths say where each piece starts); the calls are the trace's."""
from benchmark import flops, measure, trace_reduce


def read(ctx):
    tr = measure.traced(ctx)
    fam = ctx["family"]
    if not tr or not hasattr(fam, "ctx_flash_work"):
        return None
    secs = sum(trace_reduce.matching(tr["op_s"], name)
               for name in fam.CTX_FLASH_OPS)
    calls = sum(trace_reduce.matching(tr["op_n"], name)
                for name in fam.CTX_FLASH_OPS)
    t0, t1 = tr["host"]
    pieces = [cut for r in ctx["requests"]
              if r["first"] is not None and t0 <= r["first"] < t1
              for cut in fam.pieces_of(ctx["config"], r["prompt_len"])[1:]]
    if not secs or not calls or not pieces:
        return None
    mean = sum(flops.least_seconds(fam.ctx_flash_work(ctx, at, n),
                                   ctx["peak"])
               for at, n in pieces) / len(pieces)
    return measure.share(calls * mean, secs)
