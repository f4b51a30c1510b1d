"""The `qwen3_next` family: `config.json` of `model_type qwen3_next`
(`hidden_size`; full-attention layers of `num_attention_heads` over
`num_key_value_heads` of `head_dim`, every `full_attention_interval`-th
layer; the other layers linear attention, the gated delta rule, with
`linear_num_key_heads` / `linear_num_value_heads` of `linear_key_head_
dim` / `linear_value_head_dim` and a convolution of `linear_conv_kernel_
dim`; `num_experts` of `moe_intermediate_size` with `num_experts_per_
tok` chosen by a softmax router, one shared expert behind a sigmoid
gate), run by `models/hybrid_transformer.py`'s one block through
`InferenceEngine.for_hybrid_transformer` and the `DecodeLoop`, whose
cache holds pages for the full layers and a state a slot for the linear
ones. The six answers of `benchmark/families/__init__.py`.

The configuration is one chip's share of a deployment: `num_experts` and
`vocab_size` in the file count what is HELD here (both listed in
`reduced`), `router_width` is the published count of experts the router
still scores, `held_experts_first` says which experts these are.

Counts: a multiply-add is two operations. Only what the algorithm needs
is counted. What depends on what ran is taken from `ctx`: the pairs that
fell on held experts and the experts a step touched come from the
program's counters (`snapshot()["moe"]`). A decode step reads each live
slot's recurrent state once and writes it once; K/V is read in whole
pages by the kernel and as visible keys by the step's count.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from benchmark import schedule

#: the device operations that are the grouped expert products, the
#: chunked scan of a prefill and the one-token state update of a step
MOE_EXPERT_OPS = ("gmm",)
GDN_SCAN_OPS = ("gdn_scan",)
GDN_UPDATE_OPS = ("gdn_update",)
#: tokens of a chunk of the scan (the program's
#: `attention/gdn_pallas.CHUNK`; the count of its products depends on it)
SCAN_CHUNK = 64


# ------------------------------------------------------------- 1. sizes
def kinds_of(config: dict) -> Tuple[str, ...]:
    """Layer l is full where (l + 1) % full_attention_interval == 0."""
    every = int(config["full_attention_interval"])
    return tuple("full" if (i + 1) % every == 0 else "linear"
                 for i in range(int(config["num_hidden_layers"])))


def sizes(config: dict) -> dict:
    kinds = kinds_of(config)
    hd = int(config["head_dim"])
    return {"vocab_size": int(config["vocab_size"]),
            "max_len": int(config["max_position_embeddings"]),
            "d_model": int(config["hidden_size"]),
            "n_heads": int(config["num_attention_heads"]),
            "n_kv_heads": int(config["num_key_value_heads"]),
            "head_dim": hd,
            "rotary_dim": int(round(hd * float(
                config["partial_rotary_factor"]))),
            "d_ff": int(config["moe_intermediate_size"]),
            "d_shared": int(config["shared_expert_intermediate_size"]),
            "n_layers": len(kinds), "kinds": kinds,
            "n_full": kinds.count("full"),
            "n_linear": kinds.count("linear"),
            "lin_k_heads": int(config["linear_num_key_heads"]),
            "lin_v_heads": int(config["linear_num_value_heads"]),
            "lin_k_dim": int(config["linear_key_head_dim"]),
            "lin_v_dim": int(config["linear_value_head_dim"]),
            "conv_kernel": int(config["linear_conv_kernel_dim"]),
            "n_held": int(config["num_experts"]),
            "held_first": int(config["held_experts_first"]),
            "router_width": int(config["router_width"]),
            "k": int(config["num_experts_per_tok"]),
            "n_shared": 1}


def conv_channels(s: dict) -> int:
    return 2 * s["lin_k_heads"] * s["lin_k_dim"] \
        + s["lin_v_heads"] * s["lin_v_dim"]


# -------------------------------------------------------------- 2. tree
def require_program() -> None:
    """A checkout whose program cannot run this family says so at once,
    before any weight is made (the driver tries a new cell on the parent
    commit first, and that has to fail soon and cleanly)."""
    import importlib.util

    if importlib.util.find_spec(
            "deeplearning4j_tpu.models.hybrid_transformer") is None:
        raise RuntimeError(
            "the program in this checkout has no "
            "deeplearning4j_tpu/models/hybrid_transformer.py: it cannot "
            "run a configuration of family qwen3_next")


def param_shapes(config: dict) -> dict:
    """The layout `models/hybrid_transformer.py` takes: per block two
    gains, the kind's mixer, a router over all the published experts,
    experts stacked over the held ones, the shared expert and its gate;
    an untied head."""
    require_program()
    s = sizes(config)
    if s["d_shared"] != s["d_ff"]:
        raise ValueError("the program gives the shared expert the routed "
                         "experts' width")
    d, f, hd = s["d_model"], s["d_ff"], s["head_dim"]
    hk, hv = s["lin_k_heads"], s["lin_v_heads"]
    dk, dv = s["lin_k_dim"], s["lin_v_dim"]

    def stack(n):
        return {"gate": (n, d, f), "up": (n, d, f), "down": (n, f, d)}

    def block(kind):
        p = {"ln1": {"g": (d,)}, "ln2": {"g": (d,)},
             "router": (d, s["router_width"]),
             "experts": stack(s["n_held"]), "shared": stack(1),
             "shared_gate": (d, 1)}
        if kind == "full":
            p.update({"Wq": (d, 2 * s["n_heads"] * hd),
                      "Wk": (d, s["n_kv_heads"] * hd),
                      "Wv": (d, s["n_kv_heads"] * hd),
                      "Wo": (s["n_heads"] * hd, d),
                      "q_norm": {"g": (hd,)}, "k_norm": {"g": (hd,)}})
        else:
            p.update({"W_qkvz": (d, 2 * hk * dk + 2 * hv * dv),
                      "W_ba": (d, 2 * hv),
                      "conv": (s["conv_kernel"], conv_channels(s)),
                      "A_log": (hv,), "dt_bias": (hv,),
                      "norm": {"g": (dv,)}, "W_out": (hv * dv, d)})
        return p

    return {"embed": (s["vocab_size"], d), "head": (d, s["vocab_size"]),
            "ln_f": {"g": (d,)},
            "blocks": [block(kind) for kind in s["kinds"]]}


def is_gain(path: str) -> bool:
    return path.endswith("['g']")


# ------------------------------------------------ 3. the program's objects
def model_config(config: dict):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.hybrid_transformer import HybridConfig

    s = sizes(config)
    return HybridConfig(
        vocab_size=s["vocab_size"], d_model=s["d_model"],
        n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"],
        head_dim=s["head_dim"], d_ff=s["d_ff"], layer_kinds=s["kinds"],
        n_experts=s["router_width"], experts_per_token=s["k"],
        n_shared=s["n_shared"], n_held=s["n_held"],
        held_first=s["held_first"], lin_k_heads=s["lin_k_heads"],
        lin_v_heads=s["lin_v_heads"], lin_k_dim=s["lin_k_dim"],
        lin_v_dim=s["lin_v_dim"], conv_kernel=s["conv_kernel"],
        rotary_dim=s["rotary_dim"], rope_theta=float(config["rope_theta"]),
        max_len=s["max_len"], rms_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(config["dtype"])).check()


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
        "the qwen3_next family trains nothing: a trainer would need the "
        "backward of the chunked scan, of the grouped expert products "
        "and of grouped-head flash, none of which is written")


make_train_step = first_gradient = _no_trainer
train_flops_token = flash_bwd_work = _no_trainer


# ------------------------------------------------------- 4. the reference
def reference():
    from benchmark.reference import qwen3_next

    return qwen3_next


# ------------------------------------------------------------ 5. counts
def layer_params(config: dict) -> dict:
    """Weights by part: a linear layer's mixer, a full layer's mixer,
    what every layer has outside its routed experts (router, shared
    expert and its gate, the two gains), and one routed expert."""
    s = sizes(config)
    d, f, hd = s["d_model"], s["d_ff"], s["head_dim"]
    hk, hv = s["lin_k_heads"], s["lin_v_heads"]
    dk, dv = s["lin_k_dim"], s["lin_v_dim"]
    return {"linear": d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv
            + s["conv_kernel"] * conv_channels(s) + hv * dv * d
            + 2 * hv + dv,
            "full": 2 * d * s["n_heads"] * hd
            + 2 * d * s["n_kv_heads"] * hd + s["n_heads"] * hd * d
            + 2 * hd,
            "router": d * s["router_width"],
            "shared": 3 * d * s["d_shared"] + d,
            "gains": 2 * d, "expert": 3 * d * f}


def params_total(config: dict) -> int:
    s, p = sizes(config), layer_params(config)
    every = p["router"] + p["shared"] + p["gains"] \
        + s["n_held"] * p["expert"]
    return s["n_linear"] * p["linear"] + s["n_full"] * p["full"] \
        + s["n_layers"] * every + 2 * s["vocab_size"] * s["d_model"] \
        + s["d_model"]


def kv_bytes_token_layer(ctx: dict) -> int:
    s = sizes(ctx["config"])
    return 2 * s["n_kv_heads"] * s["head_dim"] * ctx["itemsize"]


def state_bytes_slot_layer(ctx: dict) -> int:
    """What one slot keeps in one linear layer: the float32 state and
    the convolution's kept columns."""
    s = sizes(ctx["config"])
    return s["lin_v_heads"] * s["lin_k_dim"] * s["lin_v_dim"] * 4 \
        + (s["conv_kernel"] - 1) * conv_channels(s) * ctx["itemsize"]


def causal_pairs(t: int) -> int:
    return t * (t + 1) // 2


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


def recurrence_flops_token_layer(s: dict) -> int:
    """The gated delta rule, one token of one linear layer, as the
    recurrence states it: three contractions of a (dk, dv) state a value
    head (what it remembers of k, the rank-one write, the read by q)."""
    return s["lin_v_heads"] * 6 * s["lin_k_dim"] * s["lin_v_dim"]


def _body_flops_token(ctx: dict, decode: bool) -> float:
    """Products of one token outside attention's scores, the recurrence
    and the head."""
    s, p = sizes(ctx["config"]), layer_params(ctx["config"])
    return 2 * (s["n_linear"] * p["linear"] + s["n_full"] * p["full"]
                + s["n_layers"] * (p["router"] + p["shared"])) \
        + 2 * p["expert"] * held_pairs_per_token(ctx, decode)


def decode_token_flops(ctx: dict, context: int) -> float:
    """One decoded token whose query sees `context` keys in the full
    layers and moves the state of every linear layer once."""
    s = sizes(ctx["config"])
    return _body_flops_token(ctx, decode=True) \
        + 2 * s["vocab_size"] * s["d_model"] \
        + s["n_full"] * 4 * s["n_heads"] * s["head_dim"] * int(context) \
        + s["n_linear"] * recurrence_flops_token_layer(s)


def prefill_flops(ctx: dict, prompt_len: int) -> float:
    """A prompt of `prompt_len` tokens: the head on the last position
    only, as the program computes it; the recurrence as it is stated,
    not the chunked form's products."""
    s = sizes(ctx["config"])
    return (_body_flops_token(ctx, decode=False)
            + s["n_linear"] * recurrence_flops_token_layer(s)) \
        * prompt_len + 2 * s["vocab_size"] * s["d_model"] \
        + s["n_full"] * 4 * s["n_heads"] * s["head_dim"] \
        * causal_pairs(prompt_len)


def _step_contexts(ctx: dict, contexts: Sequence[float]):
    """The contexts of ONE step's tokens. `decode_hbm_share` hands over
    one number, the keys of a whole step; the state is a slot's, not a
    key's, so where that number is more than a sequence can hold, take
    the traced tokens' own contexts, weighted to one step."""
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
    once (the program's count), the visible K/V of the full layers
    once, and each live slot's state in every linear layer once read
    and once written."""
    s, p = sizes(ctx["config"]), layer_params(ctx["config"])
    itemsize = ctx["itemsize"]
    outside = s["n_linear"] * p["linear"] + s["n_full"] * p["full"] \
        + s["n_layers"] * (p["router"] + p["shared"] + p["gains"]) \
        + s["vocab_size"] * s["d_model"] + s["d_model"]
    experts = p["expert"] * experts_touched_per_step(ctx)
    seqs, weight = _step_contexts(ctx, contexts)
    keys = sum(int(c) for c in seqs) * weight * s["n_full"]
    live = len(seqs) * weight
    return (outside + experts) * itemsize \
        + kv_bytes_token_layer(ctx) * keys \
        + 2 * live * s["n_linear"] * state_bytes_slot_layer(ctx)


def paged_decode_attention_work(ctx: dict, contexts: Sequence[int]
                                ) -> List[dict]:
    """The calls of the paged decode kernel in one dispatch, one a full
    layer: each slot's query heads read K and V of the pages that hold
    a visible key, whole pages, once."""
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
    """The calls of the flash forward kernel in one forward pass, one a
    full layer: read Q, K and V once (K and V have the fewer heads),
    write O once; operations over the causal pairs."""
    s = sizes(ctx["config"])
    byts = rows * seq_len * (2 * s["n_heads"] + 2 * s["n_kv_heads"]) \
        * s["head_dim"] * ctx["itemsize"]
    return [{"flops": 4 * s["n_heads"] * s["head_dim"] * rows
             * causal_pairs(seq_len), "bytes": byts}
            for _ in range(s["n_full"])]


def gdn_scan_work(ctx: dict, rows: int, seq_len: int) -> dict:
    """ONE call of the chunked scan (a linear layer of a prefill pass)
    over `rows` sequences of `seq_len` tokens, in chunks of C: a value
    head and chunk the products `(k beta) k^T` and `q k^T` (2 C C dk
    each), `T (v beta)` and `lower(q k^T) v_new` (2 C C dv each), `T (k
    beta e^gc)` (2 C C dk), and against the state `w S`, `q S` and the
    state's update (2 C dk dv each); the triangular inverse is the
    implementation's and is not counted. Bytes: q and k of the key
    heads and v in, o out, g and beta (float32), the final state."""
    s = sizes(ctx["config"])
    c, dk, dv = SCAN_CHUNK, s["lin_k_dim"], s["lin_v_dim"]
    hk, hv = s["lin_k_heads"], s["lin_v_heads"]
    item = ctx["itemsize"]
    tokens = rows * seq_len
    per_token_head = c * (6 * dk + 4 * dv) + 6 * dk * dv
    return {"flops": tokens * hv * per_token_head,
            "bytes": tokens * ((2 * hk * dk + 2 * hv * dv) * item
                               + 2 * hv * 4) + rows * hv * dk * dv * 4}


def gdn_update_work(ctx: dict, slots: float) -> dict:
    """ONE call of the one-token state update (a linear layer of a
    decode step) with `slots` live slots: the state read once and
    written once, the step's q, k and v rows in and o out; three
    contractions of the state a value head."""
    s = sizes(ctx["config"])
    dk, dv, hv = s["lin_k_dim"], s["lin_v_dim"], s["lin_v_heads"]
    rows = (2 * hv * dk + hv * dv) * ctx["itemsize"] + hv * dv * 4
    return {"flops": slots * hv * 6 * dk * dv,
            "bytes": slots * (2 * hv * dk * dv * 4 + rows)}


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
