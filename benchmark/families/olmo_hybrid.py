"""The `olmo_hybrid` family: `config.json` of `model_type olmo_hybrid`
(`hidden_size`; `layer_types` a layer, "linear_attention" or
"full_attention"; full layers of `num_attention_heads` over
`num_key_value_heads` of `hidden_size / num_attention_heads`, q and k
normed over their whole width, no rotation (`rope_parameters.rope_theta`
null), no output gate; linear layers the gated delta rule with
`linear_num_key_heads` / `linear_num_value_heads` of `linear_key_head_
dim` / `linear_value_head_dim`, a convolution of `linear_conv_kernel_
dim` and, with `linear_allow_neg_eigval`, a write strength in (0, 2); a
dense gated-SiLU feed-forward of `intermediate_size`; RMSNorm on each
sublayer's OUTPUT; an untied head), run by `models/hybrid_transformer.
py`'s one block (told where its norms stand, that the feed-forward is
dense, that beta is doubled) through `InferenceEngine.
for_hybrid_transformer` and the `DecodeLoop`, whose cache holds pages
for the full layers and a state a slot for the linear ones, and which
prefills a prompt longer than `serving.prefill_tokens_per_pass` a piece
a pass. The six answers of `benchmark/families/__init__.py`.

Counts: a multiply-add is two operations. Only what the algorithm needs
is counted, at the PUBLISHED widths whatever a kernel pads to: the head
once a prompt (the program computes it once a piece), attention over
the keys a query may see, the recurrence as it is stated. A decode step
reads each live slot's recurrent state once and writes it once; K/V is
read in whole pages by the kernel and as visible keys by the step's
count. A piece of a prompt after the first reads the state it starts
from once more.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

from benchmark import schedule

#: the device operations that are the chunked scan of a prefill, the
#: one-token state update of a step and the flash kernel of a piece
#: over its context; the programs that prefill
GDN_SCAN_OPS = ("gdn_scan",)
GDN_UPDATE_OPS = ("gdn_update",)
CTX_FLASH_OPS = ("prefill_ctx_flash",)
PREFILL_MODULES = ("prefill_fn", "prefill_chunk_fn")
#: tokens of a chunk of the scan (the program's
#: `attention/gdn_pallas.CHUNK`; the count of its products depends on it)
SCAN_CHUNK = 64
#: the last piece of a prompt takes a bucket no narrower than a piece
#: over this (the program's rule, `DecodeLoop._continue_prefills`)
LAST_PIECE_FLOOR = 4


# ------------------------------------------------------------- 1. sizes
def kinds_of(config: dict) -> Tuple[str, ...]:
    names = {"linear_attention": "linear", "full_attention": "full"}
    return tuple(names[t] for t in config["layer_types"])


def sizes(config: dict) -> dict:
    kinds = kinds_of(config)
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers disagree")
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {"vocab_size": int(config["vocab_size"]),
            "max_len": int(config["max_position_embeddings"]),
            "d_model": d, "n_heads": h,
            "n_kv_heads": int(config["num_key_value_heads"]),
            "head_dim": d // h,
            "d_ff": int(config["intermediate_size"]),
            "n_layers": len(kinds), "kinds": kinds,
            "n_full": kinds.count("full"),
            "n_linear": kinds.count("linear"),
            "lin_k_heads": int(config["linear_num_key_heads"]),
            "lin_v_heads": int(config["linear_num_value_heads"]),
            "lin_k_dim": int(config["linear_key_head_dim"]),
            "lin_v_dim": int(config["linear_value_head_dim"]),
            "conv_kernel": int(config["linear_conv_kernel_dim"]),
            "neg_eigval": bool(config["linear_allow_neg_eigval"])}


def conv_channels(s: dict) -> int:
    return 2 * s["lin_k_heads"] * s["lin_k_dim"] \
        + s["lin_v_heads"] * s["lin_v_dim"]


# -------------------------------------------------------------- 2. tree
def require_program() -> None:
    """A checkout whose program cannot run this family says so at once,
    before any weight is made (the driver tries a new cell on the parent
    commit first, and that has to fail soon and cleanly)."""
    from deeplearning4j_tpu.models import hybrid_transformer

    if "norm_place" not in hybrid_transformer.HybridConfig._fields:
        raise RuntimeError(
            "the program in this checkout runs no configuration of "
            "family olmo_hybrid: models/hybrid_transformer.py has no "
            "norms on a sublayer's output and no dense feed-forward")


def param_shapes(config: dict) -> dict:
    """The layout `models/hybrid_transformer.py` takes for a dense,
    post-norm configuration: per block two gains, the kind's mixer and
    the three feed-forward matrices; an untied head."""
    require_program()
    s = sizes(config)
    d, f, hd = s["d_model"], s["d_ff"], s["head_dim"]
    hk, hv = s["lin_k_heads"], s["lin_v_heads"]
    dk, dv = s["lin_k_dim"], s["lin_v_dim"]

    def block(kind):
        p = {"ln1": {"g": (d,)}, "ln2": {"g": (d,)},
             "W_gate": (d, f), "W_up": (d, f), "W_down": (f, d)}
        if kind == "full":
            p.update({"Wq": (d, s["n_heads"] * hd),
                      "Wk": (d, s["n_kv_heads"] * hd),
                      "Wv": (d, s["n_kv_heads"] * hd),
                      "Wo": (s["n_heads"] * hd, d),
                      "q_norm": {"g": (s["n_heads"] * hd,)},
                      "k_norm": {"g": (s["n_kv_heads"] * hd,)}})
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

    require_program()
    if config["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("this family's full layers have no rotation")
    s = sizes(config)
    return HybridConfig(
        vocab_size=s["vocab_size"], d_model=s["d_model"],
        n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"],
        head_dim=s["head_dim"], d_ff=s["d_ff"], layer_kinds=s["kinds"],
        n_experts=0, experts_per_token=0, n_shared=0, n_held=0,
        lin_k_heads=s["lin_k_heads"], lin_v_heads=s["lin_v_heads"],
        lin_k_dim=s["lin_k_dim"], lin_v_dim=s["lin_v_dim"],
        conv_kernel=s["conv_kernel"], rotary_dim=0,
        max_len=s["max_len"], rms_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(config["dtype"]), norm_place="post",
        allow_neg_eigval=s["neg_eigval"], attn_gate=False,
        qk_norm="width").check()


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
        "the olmo_hybrid family trains nothing: a trainer would need "
        "the backward of the chunked scan, which is not written")


make_train_step = first_gradient = _no_trainer
train_flops_token = flash_bwd_work = _no_trainer


# ------------------------------------------------------- 4. the reference
def reference():
    from benchmark.reference import olmo_hybrid

    return olmo_hybrid


# ------------------------------------------------------------ 5. counts
def layer_params(config: dict) -> dict:
    """Weights by part: a linear layer's mixer, a full layer's mixer,
    the feed-forward, a layer's two gains."""
    s = sizes(config)
    d, hd = s["d_model"], s["head_dim"]
    hk, hv = s["lin_k_heads"], s["lin_v_heads"]
    dk, dv = s["lin_k_dim"], s["lin_v_dim"]
    return {"linear": d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv
            + s["conv_kernel"] * conv_channels(s) + hv * dv * d
            + 2 * hv + dv,
            "full": 2 * d * s["n_heads"] * hd
            + 2 * d * s["n_kv_heads"] * hd
            + (s["n_heads"] + s["n_kv_heads"]) * hd,
            "ff": 3 * d * s["d_ff"], "gains": 2 * d}


def params_total(config: dict) -> int:
    s, p = sizes(config), layer_params(config)
    return s["n_linear"] * p["linear"] + s["n_full"] * p["full"] \
        + s["n_layers"] * (p["ff"] + p["gains"]) \
        + 2 * s["vocab_size"] * s["d_model"] + s["d_model"]


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


def recurrence_flops_token_layer(s: dict) -> int:
    """The gated delta rule, one token of one linear layer, as the
    recurrence states it: three contractions of a (dk, dv) state a value
    head (what it remembers of k, the rank-one write, the read by q)."""
    return s["lin_v_heads"] * 6 * s["lin_k_dim"] * s["lin_v_dim"]


def _body_flops_token(config: dict) -> int:
    """Products of one token outside attention's scores, the recurrence
    and the head."""
    s, p = sizes(config), layer_params(config)
    return 2 * (s["n_linear"] * p["linear"] + s["n_full"] * p["full"]
                + s["n_layers"] * p["ff"])


def _score_flops(s: dict, pairs: int) -> int:
    return s["n_full"] * 4 * s["n_heads"] * s["head_dim"] * pairs


def decode_token_flops(ctx: dict, context: int) -> float:
    """One decoded token whose query sees `context` keys in the full
    layers and moves the state of every linear layer once."""
    s = sizes(ctx["config"])
    return _body_flops_token(ctx["config"]) \
        + 2 * s["vocab_size"] * s["d_model"] \
        + _score_flops(s, int(context)) \
        + s["n_linear"] * recurrence_flops_token_layer(s)


def piece_flops(ctx: dict, ctx_len: int, tokens: int, last: bool
                ) -> float:
    """One piece of a prompt, `tokens` long on top of `ctx_len`: every
    query sees the context and what of the piece comes before it; the
    head only where the piece ends the prompt."""
    s = sizes(ctx["config"])
    pairs = causal_pairs(ctx_len + tokens) - causal_pairs(ctx_len)
    return (_body_flops_token(ctx["config"])
            + s["n_linear"] * recurrence_flops_token_layer(s)) * tokens \
        + _score_flops(s, pairs) \
        + (2 * s["vocab_size"] * s["d_model"] if last else 0)


def pieces_of(config: dict, prompt_len: int) -> List[Tuple[int, int]]:
    """(context, tokens) of every piece the program prefills a prompt
    in: pieces of `serving.prefill_tokens_per_pass` and what is left."""
    piece = int(config["serving"]["prefill_tokens_per_pass"])
    return [(at, min(piece, prompt_len - at))
            for at in range(0, prompt_len, piece)]


def prefill_flops(ctx: dict, prompt_len: int) -> float:
    """A prompt of `prompt_len` tokens, however many pieces it is
    prefilled in: the sum of its pieces is the whole prompt's count."""
    cuts = pieces_of(ctx["config"], prompt_len)
    return sum(piece_flops(ctx, at, n, at + n == prompt_len)
               for at, n in cuts)


def decode_step_bytes(ctx: dict, contexts: Sequence[float]) -> float:
    """What one decode step must move: every weight and the head once,
    the visible K/V of the full layers once, and each live slot's state
    in every linear layer once read and once written. `decode_hbm_share`
    hands over ONE number, the keys of a whole step; the live slots are
    then the tokens decoded in the traced span over its dispatches."""
    from benchmark import measure

    s = sizes(ctx["config"])
    live = float(len(contexts))
    if (len(contexts) == 1 and contexts[0] > s["max_len"]
            and measure.traced(ctx) and measure.trace_dispatches(ctx)):
        live = len(measure.decoded_in_trace(ctx)) \
            / measure.trace_dispatches(ctx)
    weights = params_total(ctx["config"]) \
        - s["vocab_size"] * s["d_model"]          # the embedding: a row
    return weights * ctx["itemsize"] \
        + kv_bytes_token_layer(ctx) * sum(contexts) * s["n_full"] \
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
    """The calls of the flash forward kernel in one COLD prefill pass
    (a whole prompt or a prompt's first piece), one a full layer: Q, K
    and V read once, O written once; operations over the causal pairs."""
    s = sizes(ctx["config"])
    byts = rows * seq_len * (2 * s["n_heads"] + 2 * s["n_kv_heads"]) \
        * s["head_dim"] * ctx["itemsize"]
    return [{"flops": 4 * s["n_heads"] * s["head_dim"] * rows
             * causal_pairs(seq_len), "bytes": byts}
            for _ in range(s["n_full"])]


def ctx_flash_work(ctx: dict, ctx_len: int, tokens: int) -> dict:
    """ONE call of the flash kernel with a query offset (a full layer
    of a later piece of a prompt): the piece's Q read and O written
    once, K and V of the context and of the piece read once; operations
    over the keys each query row may see."""
    s = sizes(ctx["config"])
    item, hd = ctx["itemsize"], s["head_dim"]
    pairs = causal_pairs(ctx_len + tokens) - causal_pairs(ctx_len)
    return {"flops": 4 * s["n_heads"] * hd * pairs,
            "bytes": (2 * tokens * s["n_heads"]
                      + 2 * (ctx_len + tokens) * s["n_kv_heads"])
            * hd * item}


def gdn_scan_work(ctx: dict, rows: float, seq_len: float,
                  carried: bool = False) -> dict:
    """ONE call of the chunked scan (a linear layer of a prefill pass)
    over `rows` sequences of `seq_len` tokens, in chunks of C: a value
    head and chunk the products `(k beta) k^T` and `q k^T` (2 C C dk
    each), `T (v beta)` and `lower(q k^T) v_new` (2 C C dv each), `T (k
    beta e^gc)` (2 C C dk), and against the state `w S`, `q S` and the
    state's update (2 C dk dv each); the triangular inverse is the
    implementation's and is not counted. Bytes: q, k and v in, o out, g
    and beta (float32), the final state out and, where the call is
    `carried` (a later piece of a prompt), the state it starts from in,
    once."""
    s = sizes(ctx["config"])
    c, dk, dv = SCAN_CHUNK, s["lin_k_dim"], s["lin_v_dim"]
    hk, hv = s["lin_k_heads"], s["lin_v_heads"]
    item = ctx["itemsize"]
    tokens = rows * seq_len
    per_token_head = c * (6 * dk + 4 * dv) + 6 * dk * dv
    states = rows * hv * dk * dv * 4 * (2 if carried else 1)
    return {"flops": tokens * hv * per_token_head,
            "bytes": tokens * ((2 * hk * dk + 2 * hv * dv) * item
                               + 2 * hv * 4) + states}


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


def reachable_programs(config: dict, traffic: dict, seconds: float
                       ) -> dict:
    """The prefill programs the cell's schedule can reach: `cold` the
    (rows, bucket) of whole prompts and first pieces under the bound on
    a pass, `carried` the buckets of later pieces (one row, one width of
    context table)."""
    srv, max_len = config["serving"], sizes(config)["max_len"]
    piece = int(srv["prefill_tokens_per_pass"])
    buckets = [b for b in prompt_buckets(max_len, int(srv["page_size"]))
               if b <= piece]
    lo, hi = schedule.length_range(traffic["prompt_len"])
    plan = schedule.warm_groups(
        dict(traffic, prompt_len={"dist": "uniform", "min": min(lo, piece),
                                  "max": min(hi, piece)}),
        seconds, int(srv["slots"]), buckets)
    carried = [b for b in buckets
               if b >= piece // LAST_PIECE_FLOOR] if hi > piece else []
    return {"cold": [(n, tb) for tb in plan["buckets"]
                     for n in plan["sizes"] if n <= max(1, piece // tb)],
            "carried": carried}


def warm_requests(config: dict, traffic: dict, seconds: float
                  ) -> List[Tuple[int, int]]:
    """Groups of throw-away requests that execute every program of
    `reachable_programs`: for each bucket of a cold pass every count of
    rows the bound admits, then one prompt of a full piece and a last
    piece in each carried bucket (its first piece runs the (1, piece)
    cold program again) and the longest prompt the mix sends (the one
    width of context table at its fullest); with them the decode
    step."""
    plan = reachable_programs(config, traffic, seconds)
    piece = int(config["serving"]["prefill_tokens_per_pass"])
    max_len = sizes(config)["max_len"]
    longest = schedule.length_range(traffic["prompt_len"])[1]
    return [(n, min(tb, max_len - 2)) for n, tb in plan["cold"]] \
        + [(1, min(piece + tb, max_len - 2)) for tb in plan["carried"]] \
        + [(1, min(longest, max_len - 2))] * bool(plan["carried"])
