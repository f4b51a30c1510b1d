"""Of the pieces of prompts that were prefilled a piece a pass in the
window, the share that started from a kept state (every piece of such a
prompt but its first), from the program's own counts
(`snapshot()["prefill_chunks"]`)."""


def read(ctx):
    a, b = ctx.get("snap0") or {}, ctx.get("snap1") or {}
    if "prefill_chunks" not in a or "prefill_chunks" not in b:
        return None
    first = b["prefill_chunks"]["first"] - a["prefill_chunks"]["first"]
    carried = b["prefill_chunks"]["carried"] \
        - a["prefill_chunks"]["carried"]
    return 100.0 * carried / (first + carried) if first + carried else None
