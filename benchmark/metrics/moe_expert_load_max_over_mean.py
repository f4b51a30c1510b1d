"""The busiest (layer, held expert)'s pairs over the mean's, over the
window, from the program's counter: 1 is an even router."""


def read(ctx):
    a, b = ctx.get("snap0") or {}, ctx.get("snap1") or {}
    if "moe" not in a or "moe" not in b:
        return None
    grown = [y - x
             for row0, row1 in zip(a["moe"]["pairs_by_layer_expert"],
                                   b["moe"]["pairs_by_layer_expert"])
             for x, y in zip(row0, row1)]
    if not grown or not sum(grown):
        return None
    return max(grown) * len(grown) / sum(grown)
