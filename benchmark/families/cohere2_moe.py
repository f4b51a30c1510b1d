"""The `cohere2_moe` family: `config.json` of `model_type cohere2_moe`
(`hidden_size`, `num_attention_heads` over `num_key_value_heads` of
`head_dim`, `layer_types` of sliding and full attention, `num_experts`
with `num_experts_per_tok` chosen by a sigmoid router, `num_shared_
experts` averaged, a parallel block), run by `models/moe_transformer.py`'s
one block through `InferenceEngine.for_moe_transformer` and the
`DecodeLoop`'s cache of two kinds. The six answers of
`benchmark/families/__init__.py`.

The configuration is one chip's share of a deployment: `num_experts` and
`vocab_size` in the file count what is HELD here (both listed in
`reduced`), `router_width` is the published count of experts the router
still scores, `held_experts_first` says which experts these are.

Counts: a multiply-add is two operations. Only what the algorithm needs
is counted. What depends on what ran is taken from `ctx`: the pairs
that fell on held experts and the experts a step touched come from the
program's counters (`snapshot()["moe"]`), not from expectation.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from benchmark import schedule

KINDS = {"sliding_attention": "window", "full_attention": "full"}
#: the device operations that are the grouped expert products
MOE_EXPERT_OPS = ("gmm",)


# ------------------------------------------------------------- 1. sizes
def sizes(config: dict) -> dict:
    kinds = tuple(KINDS[k] for k in config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers disagree")
    return {"vocab_size": int(config["vocab_size"]),
            "max_len": int(config["max_position_embeddings"]),
            "d_model": int(config["hidden_size"]),
            "n_heads": int(config["num_attention_heads"]),
            "n_kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "d_ff": int(config["intermediate_size"]),
            "n_layers": len(kinds), "kinds": kinds,
            "window": int(config["sliding_window"]),
            "n_held": int(config["num_experts"]),
            "held_first": int(config["held_experts_first"]),
            "router_width": int(config["router_width"]),
            "k": int(config["num_experts_per_tok"]),
            "n_shared": int(config["num_shared_experts"])}


# -------------------------------------------------------------- 2. tree
def require_program() -> None:
    """A checkout whose program cannot run this family says so at once,
    before any weight is made (the driver tries a new cell on the parent
    commit first, and that has to fail soon and cleanly)."""
    import importlib.util

    if importlib.util.find_spec(
            "deeplearning4j_tpu.models.moe_transformer") is None:
        raise RuntimeError(
            "the program in this checkout has no "
            "deeplearning4j_tpu/models/moe_transformer.py: it cannot run "
            "a configuration of family cohere2_moe")


def param_shapes(config: dict) -> dict:
    """The layout `models/moe_transformer.py` takes: experts stacked
    over the held ones, shared experts stacked, a router over all the
    published experts, no position table, gains without biases."""
    require_program()
    s = sizes(config)
    d, f = s["d_model"], s["d_ff"]
    q, kv = s["n_heads"] * s["head_dim"], s["n_kv_heads"] * s["head_dim"]

    def stack(n):
        return {"gate": (n, d, f), "up": (n, d, f), "down": (n, f, d)}

    block = {"ln": {"g": (d,)}, "Wq": (d, q), "Wk": (d, kv),
             "Wv": (d, kv), "Wo": (q, d),
             "router": (d, s["router_width"]),
             "experts": stack(s["n_held"]), "shared": stack(s["n_shared"])}
    return {"embed": (s["vocab_size"], d), "ln_f": {"g": (d,)},
            "blocks": [block for _ in range(s["n_layers"])]}


def is_gain(path: str) -> bool:
    return path.endswith("['g']")


# ------------------------------------------------ 3. the program's objects
def model_config(config: dict):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.moe_transformer import MoEConfig

    s = sizes(config)
    return MoEConfig(
        vocab_size=s["vocab_size"], d_model=s["d_model"],
        n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"],
        head_dim=s["head_dim"], d_ff=s["d_ff"], layer_kinds=s["kinds"],
        window=s["window"], n_experts=s["router_width"],
        experts_per_token=s["k"], n_shared=s["n_shared"],
        n_held=s["n_held"], held_first=s["held_first"],
        rope_theta=float(config["rope_theta"]), max_len=s["max_len"],
        ln_eps=float(config["layer_norm_eps"]),
        logit_scale=float(config["logit_scale"]),
        dtype=jnp.dtype(config["dtype"])).check()


def build_engine(config: dict, params):
    from deeplearning4j_tpu.serving.engine import InferenceEngine

    srv = config["serving"]
    for key, off in (("prefix_cache", False), ("speculation", 0),
                     ("horizon", 1)):
        if srv[key] != off:
            raise ValueError(f"serving.{key} must be {off!r} for this "
                             f"family: the program refuses it by name")
    return InferenceEngine.for_moe_transformer(
        params, model_config(config), decode_slots=int(srv["slots"]),
        page_size=int(srv["page_size"]), kv_pages=int(srv["kv_pages"]),
        window_pages=int(srv["window_pages"]),
        prefill_tokens_per_pass=int(srv["prefill_tokens_per_pass"]),
        decode_kernel=srv["decode_kernel"])


def _no_trainer(*_a, **_k):
    raise NotImplementedError(
        "the cohere2_moe family trains nothing: no trainer for the "
        "expert layer is written, and at this cut parameters, gradients "
        "and momentum would take 25 GB")


make_train_step = first_gradient = _no_trainer
train_flops_token = flash_bwd_work = _no_trainer


# ------------------------------------------------------- 4. the reference
def reference():
    from benchmark.reference import cohere2_moe

    return cohere2_moe


# ------------------------------------------------------------ 5. counts
def layer_params(config: dict) -> dict:
    """Weights of one layer by part: attention (Wq, Wo, Wk, Wv), the
    shared experts, the router, the gain; and of one routed expert."""
    s = sizes(config)
    d, f, hd = s["d_model"], s["d_ff"], s["head_dim"]
    return {"attention": 2 * d * s["n_heads"] * hd
            + 2 * d * s["n_kv_heads"] * hd,
            "shared": s["n_shared"] * 3 * d * f,
            "router": d * s["router_width"], "gain": d,
            "expert": 3 * d * f}


def params_total(config: dict) -> int:
    s, p = sizes(config), layer_params(config)
    layer = (p["attention"] + p["shared"] + p["router"] + p["gain"]
             + s["n_held"] * p["expert"])
    return s["n_layers"] * layer + s["vocab_size"] * s["d_model"] \
        + s["d_model"]


def kv_bytes_token_layer(ctx: dict) -> int:
    s = sizes(ctx["config"])
    return 2 * s["n_kv_heads"] * s["head_dim"] * ctx["itemsize"]


def visible(kind: str, context: int, window: int) -> int:
    """Keys a query sees that has `context` keys up to and with its
    own."""
    return int(context) if kind == "full" else min(int(context), window)


def visible_pages(kind: str, context: int, window: int, page: int) -> int:
    """Whole pages the query's kernel call must fetch."""
    pos = int(context) - 1
    first = max(0, pos - window + 1) if kind == "window" else 0
    return pos // page - first // page + 1


def causal_pairs(kind: str, t: int, window: int) -> int:
    """(query, key) pairs of a prompt of t tokens in a layer."""
    if kind == "full" or t <= window:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def _moe_delta(ctx: dict) -> Optional[dict]:
    """The growth of the program's expert counters over the window."""
    a, b = ctx.get("snap0"), ctx.get("snap1")
    if not a or not b or "moe" not in a or "moe" not in b:
        return None
    return {k: b["moe"][k] - a["moe"][k]
            for k in ("tokens", "pairs", "decode_tokens", "decode_pairs",
                      "decode_steps", "experts_touched")}


def held_pairs_per_token(ctx: dict, decode: bool) -> float:
    """Pairs on held experts a token, summed over the layers: what ran
    where the program counted it, else what a uniform router gives."""
    s = sizes(ctx["config"])
    moe = _moe_delta(ctx)
    if moe:
        pairs = moe["decode_pairs"] if decode \
            else moe["pairs"] - moe["decode_pairs"]
        tokens = moe["decode_tokens"] if decode \
            else moe["tokens"] - moe["decode_tokens"]
        if tokens:
            return pairs / tokens
    return s["n_layers"] * s["k"] * s["n_held"] / s["router_width"]


def experts_touched_per_step(ctx: dict) -> float:
    """Held experts with a pair in a decode step, summed over layers:
    the program's counter, else every held expert."""
    moe = _moe_delta(ctx)
    if moe and moe["decode_steps"]:
        return moe["experts_touched"] / moe["decode_steps"]
    s = sizes(ctx["config"])
    return s["n_layers"] * s["n_held"]


def _body_flops_token(ctx: dict, decode: bool) -> float:
    """Products of one token outside attention's scores and the head."""
    s, p = sizes(ctx["config"]), layer_params(ctx["config"])
    return 2 * s["n_layers"] * (p["attention"] + p["shared"]
                                + p["router"]) \
        + 2 * p["expert"] * held_pairs_per_token(ctx, decode)


def decode_token_flops(ctx: dict, context: int) -> float:
    """One decoded token whose query sees `context` keys (capped at the
    window in window layers)."""
    s = sizes(ctx["config"])
    attn = sum(4 * s["n_heads"] * s["head_dim"]
               * visible(kind, context, s["window"])
               for kind in s["kinds"])
    return _body_flops_token(ctx, decode=True) \
        + 2 * s["vocab_size"] * s["d_model"] + attn


def prefill_flops(ctx: dict, prompt_len: int) -> float:
    """A prompt of `prompt_len` tokens: the head on the last position
    only, as the program computes it."""
    s = sizes(ctx["config"])
    attn = sum(4 * s["n_heads"] * s["head_dim"]
               * causal_pairs(kind, prompt_len, s["window"])
               for kind in s["kinds"])
    return _body_flops_token(ctx, decode=False) * prompt_len \
        + 2 * s["vocab_size"] * s["d_model"] + attn


def _step_contexts(ctx: dict, contexts: Sequence[float]):
    """The contexts of ONE step's tokens. `decode_hbm_share` hands over
    one number, the keys of a whole step; a window caps each sequence,
    not their sum, so where that number is more than a sequence can
    hold, take the traced tokens' own contexts, weighted to one step."""
    from benchmark import measure

    s = sizes(ctx["config"])
    if (len(contexts) == 1 and contexts[0] > s["max_len"]
            and measure.traced(ctx)):
        n = measure.trace_dispatches(ctx)
        if n:
            return measure.decoded_in_trace(ctx), 1.0 / n
    return contexts, 1.0


def decode_step_bytes(ctx: dict, contexts: Sequence[float]) -> float:
    """What one decode step must move: the weights outside the routed
    experts once, the head once, the routed experts that have a pair
    once (the program's count, not all that are held), the visible K/V
    once."""
    s, p = sizes(ctx["config"]), layer_params(ctx["config"])
    itemsize = ctx["itemsize"]
    outside = s["n_layers"] * (p["attention"] + p["shared"] + p["router"]
                               + p["gain"]) \
        + s["vocab_size"] * s["d_model"] + s["d_model"]
    experts = p["expert"] * experts_touched_per_step(ctx)
    seqs, weight = _step_contexts(ctx, contexts)
    keys = sum(visible(kind, c, s["window"])
               for c in seqs for kind in s["kinds"]) * weight
    return (outside + experts) * itemsize \
        + kv_bytes_token_layer(ctx) * keys


def paged_decode_attention_work(ctx: dict, contexts: Sequence[int]
                                ) -> List[dict]:
    """The calls of the paged decode kernel in one dispatch, one a
    layer, by the layer's kind: each slot's 128 query heads read K and
    V of the pages that hold a visible key, whole pages, once;
    operations count the visible keys."""
    s = sizes(ctx["config"])
    page = int(ctx["config"]["serving"]["page_size"])
    q_bytes = 2 * s["n_heads"] * s["head_dim"] * ctx["itemsize"]
    page_bytes = kv_bytes_token_layer(ctx) * page
    calls = []
    for kind in s["kinds"]:
        keys = sum(visible(kind, c, s["window"]) for c in contexts)
        pages = sum(visible_pages(kind, c, s["window"], page)
                    for c in contexts)
        calls.append({"flops": 4 * s["n_heads"] * s["head_dim"] * keys,
                      "bytes": pages * page_bytes
                      + len(contexts) * q_bytes})
    return calls


def flash_fwd_work(ctx: dict, rows: int, seq_len: int) -> List[dict]:
    """The calls of the flash forward kernel in one forward pass over
    `rows` sequences of `seq_len`, one a layer, by kind: read Q, K and V
    once (K and V have the fewer heads), write O once; operations over
    the visible pairs."""
    s = sizes(ctx["config"])
    byts = rows * seq_len * (2 * s["n_heads"] + 2 * s["n_kv_heads"]) \
        * s["head_dim"] * ctx["itemsize"]
    return [{"flops": 4 * s["n_heads"] * s["head_dim"] * rows
             * causal_pairs(kind, seq_len, s["window"]), "bytes": byts}
            for kind in s["kinds"]]


def moe_expert_work(ctx: dict, pairs: float, touched: float) -> dict:
    """The grouped expert products of `pairs` (token, expert) pairs that
    touch `touched` (layer, expert) weights, whatever implements them:
    6 d f operations a pair; each touched expert's three matrices once,
    each pair's row in (d), its gate and up rows out and back in (f
    each, twice) and its result out (d, float32)."""
    s, p = sizes(ctx["config"]), layer_params(ctx["config"])
    item = ctx["itemsize"]
    rows = pairs * (s["d_model"] * item + 4 * s["d_ff"] * item
                    + s["d_ff"] * item + s["d_model"] * 4)
    return {"flops": 2 * p["expert"] * pairs,
            "bytes": touched * p["expert"] * item + rows}


# ------------------------------------------- 6. programs a schedule reaches
def prompt_buckets(max_len: int, page_size: int) -> Tuple[int, ...]:
    """The program's prefill buckets, the benchmark's own copy of the
    rule in `serving/paged_kv.py`."""
    top = -(-max_len // page_size) * page_size
    out, b = [], page_size
    while b < top:
        out.append(b)
        b *= 2
    out.append(top)
    return tuple(out)


def warm_requests(config: dict, traffic: dict, seconds: float
                  ) -> List[Tuple[int, int]]:
    """The groups the bound on a pass's prefill leaves reachable: for
    every bucket the prompts touch, every count of rows from 1 up to
    what the bound admits into one pass (one row of the top bucket at
    the cell's bound); with them the decode step."""
    srv, max_len = config["serving"], sizes(config)["max_len"]
    plan = schedule.warm_groups(
        traffic, seconds, int(srv["slots"]),
        prompt_buckets(max_len, int(srv["page_size"])))
    bound = int(srv["prefill_tokens_per_pass"])
    return [(n, min(tb, max_len - 2)) for tb in plan["buckets"]
            for n in plan["sizes"] if n <= max(1, bound // tb)]
