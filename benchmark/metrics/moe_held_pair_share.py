"""Pairs that fell on experts this chip holds over all the pairs the
router made (tokens x layers x experts a token), over the window: the
chip's share of the expert work, 12.5% for an even router and an eighth
of the experts."""


def read(ctx):
    a, b = ctx.get("snap0") or {}, ctx.get("snap1") or {}
    if "moe" not in a or "moe" not in b:
        return None
    tokens = b["moe"]["tokens"] - a["moe"]["tokens"]
    layers = len(b["moe"]["pairs_by_layer_expert"])
    made = tokens * layers * b["moe"]["experts_per_token"]
    if not made:
        return None
    return 100.0 * (b["moe"]["pairs"] - a["moe"]["pairs"]) / made
