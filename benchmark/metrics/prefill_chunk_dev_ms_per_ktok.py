"""Device time of every prefill program (whole prompts, first pieces
and later pieces) per 1,000 prompt tokens prefilled while the trace
ran: what a piece of 4,096 tokens adds to the token gap of every
running stream, by the thousand."""
import os

from benchmark import manifest, measure

_seconds = manifest.module_at(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "prefill_chunk_mfu.py")).prefill_seconds


def read(ctx):
    secs = _seconds(ctx)
    if not secs:
        return None
    tokens, _, _ = measure.prefilled_in_trace(ctx)
    return 1e3 * secs / (tokens / 1e3) if tokens else None
