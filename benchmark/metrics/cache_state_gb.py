"""What the cache holds by SLOT for the layers that keep no pages (a
linear layer's recurrent state and kept convolution columns, every
slot), from `snapshot()["state"]`, in GB."""


def read(ctx):
    state = (ctx.get("snap1") or {}).get("state")
    return state["bytes"] / 1e9 if state else None
