"""Peak pages in use over the pool's pages, from `snapshot()`."""


def read(ctx):
    snap = ctx.get("snap1")
    if not snap or not snap["pages_total"]:
        return None
    return 100.0 * snap["peak_pages_in_use"] / snap["pages_total"]
