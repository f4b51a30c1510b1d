"""The most pages of the window kind one slot ever held, from
`snapshot()`: a window layer keeps the pages of its window and the page
it ends in, and gives the rest back."""


def read(ctx):
    kinds = (ctx.get("snap1") or {}).get("pages_by_kind") or {}
    if "window" not in kinds:
        return None
    return kinds["window"]["pages_per_slot_peak"]
