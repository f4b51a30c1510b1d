"""The `lfm2_moe` family: `config.json` of `model_type lfm2_moe`
(`hidden_size`; `layer_types` a layer, "conv" or "full_attention"; conv
layers the gated short convolution of `conv_L_cache` taps, no bias;
attention layers of `num_attention_heads` over `num_key_value_heads` of
`hidden_size / num_attention_heads`, q and k normed a head, every pair
of a head turning by `rope_parameters.rope_theta`; a dense gated-SiLU
feed-forward of `intermediate_size` in the first `num_dense_layers`
layers, then `num_experts` of `moe_intermediate_size` with
`num_experts_per_tok` chosen by a sigmoid router whose choice takes a
selection bias (`use_expert_bias`), no shared expert; RMSNorm before
each sublayer; the head tied to the embedding), run by
`models/hybrid_transformer.py`'s one block (told that its layers are
conv and full, that the first are dense, that the router has a bias and
the head is tied) through `InferenceEngine.for_hybrid_transformer` and
the `DecodeLoop`, whose cache holds pages for the attention layers and
two columns a slot for the conv ones. The six answers of
`benchmark/families/__init__.py`.

Every expert and the whole vocabulary are held: the routed sum is the
whole layer's.

Counts: a multiply-add is two operations. Only what the algorithm needs
is counted. The expert pairs and the experts a step touched come from
the program's counters (`snapshot()["moe"]`, whose dense layers count
none). A decode step reads each live slot's kept columns once and
writes them once; K/V is read in whole pages by the kernel and as
visible keys by the step's count.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

from benchmark import schedule
from benchmark.families.qwen3_next import (_moe_delta, causal_pairs,
                                           prompt_buckets)

KINDS = {"conv": "conv", "full_attention": "full"}


# ------------------------------------------------------------- 1. sizes
def sizes(config: dict) -> dict:
    kinds = tuple(KINDS[t] for t in config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers disagree")
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    dense = int(config["num_dense_layers"])
    return {"vocab_size": int(config["vocab_size"]),
            "max_len": int(config["max_position_embeddings"]),
            "d_model": d, "n_heads": h,
            "n_kv_heads": int(config["num_key_value_heads"]),
            "head_dim": d // h,
            "d_ff": int(config["moe_intermediate_size"]),
            "d_dense": int(config["intermediate_size"]),
            "n_layers": len(kinds), "kinds": kinds,
            "n_full": kinds.count("full"), "n_conv": kinds.count("conv"),
            "n_dense": dense, "n_moe": len(kinds) - dense,
            "conv_kernel": int(config["conv_L_cache"]),
            "n_experts": int(config["num_experts"]),
            "k": int(config["num_experts_per_tok"])}


# -------------------------------------------------------------- 2. tree
def require_program() -> None:
    """A checkout whose program cannot run this family says so at once,
    before any weight is made: a new cell tried on an older commit has
    to fail soon and cleanly."""
    from deeplearning4j_tpu.models import hybrid_transformer

    if not hasattr(hybrid_transformer, "conv_mix"):
        raise RuntimeError(
            "the program in this checkout has no conv layer in "
            "models/hybrid_transformer.py: it cannot run a configuration "
            "of family lfm2_moe")


def _check_router(config: dict) -> None:
    if (not config["norm_topk_prob"] or not config["use_expert_bias"]
            or float(config["routed_scaling_factor"]) != 1.0
            or config["conv_bias"]):
        raise ValueError("the program's lfm2_moe router normalises the "
                         "chosen scores, takes a selection bias and "
                         "scales by 1; its convolution has no bias")


def param_shapes(config: dict) -> dict:
    """The layout `models/hybrid_transformer.py` takes: per block two
    gains, the kind's mixer, a dense feed-forward or a router with its
    selection bias and every expert stacked; the embedding, which is
    also the head."""
    require_program()
    _check_router(config)
    s = sizes(config)
    d, f, hd = s["d_model"], s["d_ff"], s["head_dim"]
    fd = s["d_dense"]

    def block(i, kind):
        p = {"ln1": {"g": (d,)}, "ln2": {"g": (d,)}}
        if i < s["n_dense"]:
            p.update({"W_gate": (d, fd), "W_up": (d, fd),
                      "W_down": (fd, d)})
        else:
            n = s["n_experts"]
            p.update({"router": (d, n), "expert_bias": (n,),
                      "experts": {"gate": (n, d, f), "up": (n, d, f),
                                  "down": (n, f, d)}})
        if kind == "full":
            p.update({"Wq": (d, s["n_heads"] * hd),
                      "Wk": (d, s["n_kv_heads"] * hd),
                      "Wv": (d, s["n_kv_heads"] * hd),
                      "Wo": (s["n_heads"] * hd, d),
                      "q_norm": {"g": (hd,)}, "k_norm": {"g": (hd,)}})
        else:
            p.update({"W_in": (d, 3 * d), "conv": (s["conv_kernel"], d),
                      "W_out": (d, d)})
        return p

    return {"embed": (s["vocab_size"], d), "ln_f": {"g": (d,)},
            "blocks": [block(i, kind) for i, kind in enumerate(s["kinds"])]}


def is_gain(path: str) -> bool:
    return path.endswith("['g']")


# ------------------------------------------------ 3. the program's objects
def model_config(config: dict):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.hybrid_transformer import HybridConfig

    _check_router(config)
    s = sizes(config)
    return HybridConfig(
        vocab_size=s["vocab_size"], d_model=s["d_model"],
        n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"],
        head_dim=s["head_dim"], d_ff=s["d_ff"], layer_kinds=s["kinds"],
        n_experts=s["n_experts"], experts_per_token=s["k"], n_shared=0,
        n_held=s["n_experts"], held_first=0,
        conv_kernel=s["conv_kernel"], rotary_dim=s["head_dim"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        max_len=s["max_len"], rms_eps=float(config["norm_eps"]),
        dtype=jnp.dtype(config["dtype"]), router_score="sigmoid",
        attn_gate=False, qk_norm="head", n_dense_layers=s["n_dense"],
        d_ff_dense=s["d_dense"], router_bias=True,
        tied_head=True).check()


def build_engine(config: dict, params):
    from deeplearning4j_tpu.serving.engine import InferenceEngine

    srv = config["serving"]
    for key, off in (("prefix_cache", False), ("speculation", 0),
                     ("horizon", 1)):
        if srv[key] != off:
            raise ValueError(f"serving.{key} must be {off!r} for this "
                             f"family: the program refuses it by name")
    return InferenceEngine.for_hybrid_transformer(
        params, model_config(config), decode_slots=int(srv["slots"]),
        page_size=int(srv["page_size"]), kv_pages=int(srv["kv_pages"]),
        prefill_tokens_per_pass=int(srv["prefill_tokens_per_pass"]),
        decode_kernel=srv["decode_kernel"])


def _no_trainer(*_a, **_k):
    raise NotImplementedError(
        "the lfm2_moe family trains nothing: a trainer would need the "
        "backward of the grouped expert products and of grouped-head "
        "flash, neither of which is written")


make_train_step = first_gradient = _no_trainer
train_flops_token = flash_bwd_work = _no_trainer


# ------------------------------------------------------- 4. the reference
def reference():
    from benchmark.reference import lfm2_moe

    return lfm2_moe


# ------------------------------------------------------------ 5. counts
def layer_params(config: dict) -> dict:
    """Weights by part: a conv mixer, an attention mixer, a dense
    feed-forward, the router with its bias, one expert, a layer's two
    gains."""
    s = sizes(config)
    d, hd = s["d_model"], s["head_dim"]
    return {"conv": 4 * d * d + s["conv_kernel"] * d,
            "full": 2 * d * s["n_heads"] * hd
            + 2 * d * s["n_kv_heads"] * hd + 2 * hd,
            "dense": 3 * d * s["d_dense"],
            "router": (d + 1) * s["n_experts"],
            "expert": 3 * d * s["d_ff"], "gains": 2 * d}


def params_total(config: dict) -> int:
    """The tied head counts once, as the embedding."""
    s, p = sizes(config), layer_params(config)
    return s["n_conv"] * p["conv"] + s["n_full"] * p["full"] \
        + s["n_dense"] * p["dense"] \
        + s["n_moe"] * (p["router"] + s["n_experts"] * p["expert"]) \
        + s["n_layers"] * p["gains"] + s["vocab_size"] * s["d_model"] \
        + s["d_model"]


def kv_bytes_token_layer(ctx: dict) -> int:
    s = sizes(ctx["config"])
    return 2 * s["n_kv_heads"] * s["head_dim"] * ctx["itemsize"]


def state_bytes_slot_layer(ctx: dict) -> int:
    """What one slot keeps in one conv layer: the last `conv_L_cache -
    1` columns of u."""
    s = sizes(ctx["config"])
    return (s["conv_kernel"] - 1) * s["d_model"] * ctx["itemsize"]


def pairs_per_token(ctx: dict, decode: bool) -> float:
    """Expert pairs a token, summed over the layers: what ran where the
    program counted it, else k a layer."""
    s = sizes(ctx["config"])
    moe = _moe_delta(ctx)
    if moe:
        pairs = moe["decode_pairs"] if decode \
            else moe["pairs"] - moe["decode_pairs"]
        tokens = moe["decode_tokens"] if decode \
            else moe["tokens"] - moe["decode_tokens"]
        if tokens:
            return pairs / tokens
    return s["n_moe"] * s["k"]


def experts_touched_per_step(ctx: dict) -> float:
    """Experts with a pair in a decode step, summed over layers: the
    program's counter, else every expert."""
    moe = _moe_delta(ctx)
    if moe and moe["decode_steps"]:
        return moe["experts_touched"] / moe["decode_steps"]
    s = sizes(ctx["config"])
    return s["n_moe"] * s["n_experts"]


def _body_flops_token(ctx: dict, decode: bool) -> float:
    """Products of one token outside attention's scores and the head:
    the mixers' and dense layers' weights, the routers, the chosen
    experts, the convolution's taps."""
    s, p = sizes(ctx["config"]), layer_params(ctx["config"])
    return 2 * (s["n_conv"] * p["conv"] + s["n_full"] * p["full"]
                + s["n_dense"] * p["dense"] + s["n_moe"] * p["router"]) \
        + 2 * p["expert"] * pairs_per_token(ctx, decode)


def decode_token_flops(ctx: dict, context: int) -> float:
    """One decoded token whose query sees `context` keys in the
    attention layers."""
    s = sizes(ctx["config"])
    return _body_flops_token(ctx, decode=True) \
        + 2 * s["vocab_size"] * s["d_model"] \
        + s["n_full"] * 4 * s["n_heads"] * s["head_dim"] * int(context)


def prefill_flops(ctx: dict, prompt_len: int) -> float:
    """A prompt of `prompt_len` tokens: the head on the last position
    only, as the program computes it."""
    s = sizes(ctx["config"])
    return _body_flops_token(ctx, decode=False) * prompt_len \
        + 2 * s["vocab_size"] * s["d_model"] \
        + s["n_full"] * 4 * s["n_heads"] * s["head_dim"] \
        * causal_pairs(prompt_len)


def _step_contexts(ctx: dict, contexts: Sequence[float]):
    """The contexts of ONE step's tokens. `decode_hbm_share` hands over
    one number, the keys of a whole step; the kept columns are a
    slot's, not a key's, so where that number is more than a sequence
    can hold, take the traced tokens' own contexts, weighted to one
    step."""
    from benchmark import measure

    if (len(contexts) == 1
            and contexts[0] > sizes(ctx["config"])["max_len"]
            and measure.traced(ctx)):
        n = measure.trace_dispatches(ctx)
        if n:
            return measure.decoded_in_trace(ctx), 1.0 / n
    return contexts, 1.0


def decode_step_bytes(ctx: dict, contexts: Sequence[float]) -> float:
    """What one decode step must move: the weights outside the experts
    once, the tied head once, the experts that have a pair once (the
    program's count), the visible K/V of the attention layers once, and
    each live slot's kept columns in every conv layer once read and
    once written."""
    s, p = sizes(ctx["config"]), layer_params(ctx["config"])
    itemsize = ctx["itemsize"]
    outside = s["n_conv"] * p["conv"] + s["n_full"] * p["full"] \
        + s["n_dense"] * p["dense"] + s["n_moe"] * p["router"] \
        + s["n_layers"] * p["gains"] + s["vocab_size"] * s["d_model"] \
        + s["d_model"]
    experts = p["expert"] * experts_touched_per_step(ctx)
    seqs, weight = _step_contexts(ctx, contexts)
    keys = sum(int(c) for c in seqs) * weight * s["n_full"]
    live = len(seqs) * weight
    return (outside + experts) * itemsize \
        + kv_bytes_token_layer(ctx) * keys \
        + 2 * live * s["n_conv"] * state_bytes_slot_layer(ctx)


def paged_decode_attention_work(ctx: dict, contexts: Sequence[int]
                                ) -> List[dict]:
    """The calls of the paged decode kernel in one dispatch, one an
    attention layer: each slot's query heads read K and V of the pages
    that hold a visible key, whole pages, once."""
    s = sizes(ctx["config"])
    page = int(ctx["config"]["serving"]["page_size"])
    q_bytes = 2 * s["n_heads"] * s["head_dim"] * ctx["itemsize"]
    page_bytes = kv_bytes_token_layer(ctx) * page
    keys = sum(int(c) for c in contexts)
    pages = sum((int(c) - 1) // page + 1 for c in contexts)
    return [{"flops": 4 * s["n_heads"] * s["head_dim"] * keys,
             "bytes": pages * page_bytes + len(contexts) * q_bytes}
            for _ in range(s["n_full"])]


def flash_fwd_work(ctx: dict, rows: int, seq_len: int) -> List[dict]:
    """The calls of the flash forward kernel in one forward pass, one an
    attention layer: read Q, K and V once (K and V have the fewer
    heads), write O once; operations over the causal pairs."""
    s = sizes(ctx["config"])
    byts = rows * seq_len * (2 * s["n_heads"] + 2 * s["n_kv_heads"]) \
        * s["head_dim"] * ctx["itemsize"]
    return [{"flops": 4 * s["n_heads"] * s["head_dim"] * rows
             * causal_pairs(seq_len), "bytes": byts}
            for _ in range(s["n_full"])]


# ------------------------------------------- 6. programs a schedule reaches
def warm_requests(config: dict, traffic: dict, seconds: float
                  ) -> List[Tuple[int, int]]:
    """The groups the bound on a pass's prefill leaves reachable: for
    every bucket the prompts touch, every count of rows from 1 up to
    what the bound admits into one pass; with them the decode step. No
    prompt of the mix is longer than the bound, so none is prefilled
    in pieces."""
    srv, max_len = config["serving"], sizes(config)["max_len"]
    bound = int(srv["prefill_tokens_per_pass"])
    if schedule.length_range(traffic["prompt_len"])[1] > bound:
        raise ValueError("a prompt longer than the bound on a pass's "
                         "prefill would be prefilled in pieces, whose "
                         "programs this family does not warm")
    plan = schedule.warm_groups(
        traffic, seconds, int(srv["slots"]),
        prompt_buckets(max_len, int(srv["page_size"])))
    return [(n, min(tb, max_len - 2)) for tb in plan["buckets"]
            for n in plan["sizes"] if n <= max(1, bound // tb)]
