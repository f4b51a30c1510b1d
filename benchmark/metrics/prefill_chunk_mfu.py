"""The share of the whole step's peak for what leads a cell whose
prompts are prefilled in pieces: the operations of the prompt tokens
prefilled while the trace ran (the family's count, each piece's
attention over its real context, the head once a prompt) over the
device time of EVERY prefill program, a whole prompt's, a first
piece's and a later piece's (`PREFILL_MODULES`), times the peak. The
tokens are the program's own count; the work is that of the prompts
whose first token came in the traced span, scaled to that count
(`measure.prefilled_in_trace`)."""
from benchmark import measure


def prefill_seconds(ctx):
    names = getattr(ctx["family"], "PREFILL_MODULES", None)
    if not names or not measure.traced(ctx):
        return None
    return sum(measure.module_time(ctx, name)[0] for name in names)


def read(ctx):
    secs = prefill_seconds(ctx)
    if not secs:
        return None
    _, ops, _ = measure.prefilled_in_trace(ctx)
    return measure.share(ops, secs * ctx["peak"]["bf16_flops_per_s"])
