"""The GPT-2 family: `config.json` of `model_type gpt2` (`n_embd`,
`n_head`, `n_layer`, `n_inner`, `n_positions`), run by
`models/transformer.py`'s one block through `InferenceEngine.
for_transformer` and `make_train_step`. The six answers of
`benchmark/families/__init__.py`.

Counts: a multiply-add is two operations. Only what the algorithm needs
is counted: matmuls and attention; recomputation, padding rows and
masked-out half tiles are not work.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

from benchmark import schedule


# ------------------------------------------------------------- 1. sizes
def sizes(config: dict) -> dict:
    d = int(config["n_embd"])
    return {"vocab_size": int(config["vocab_size"]), "d_model": d,
            "n_heads": int(config["n_head"]),
            "n_layers": int(config["n_layer"]),
            "d_ff": int(config.get("n_inner") or 4 * d),
            "max_len": int(config["n_positions"])}


# -------------------------------------------------------------- 2. tree
def param_shapes(config: dict) -> dict:
    """The layout `models/transformer.py` takes: embed, pos, ln_f,
    blocks of ln1/Wq/Wk/Wv/Wo/ln2/W1/b1/W2/b2."""
    s = sizes(config)
    v, d, f, t = s["vocab_size"], s["d_model"], s["d_ff"], s["max_len"]
    block = {"ln1": {"g": (d,), "b": (d,)},
             "Wq": (d, d), "Wk": (d, d), "Wv": (d, d), "Wo": (d, d),
             "ln2": {"g": (d,), "b": (d,)},
             "W1": (d, f), "b1": (f,), "W2": (f, d), "b2": (d,)}
    return {"embed": (v, d), "pos": (t, d),
            "ln_f": {"g": (d,), "b": (d,)},
            "blocks": [block for _ in range(s["n_layers"])]}


def is_gain(path: str) -> bool:
    return path.endswith("['g']")


# ------------------------------------------------ 3. the program's objects
def transformer_config(config: dict):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import TransformerConfig

    s = sizes(config)
    return TransformerConfig(
        vocab_size=s["vocab_size"], d_model=s["d_model"],
        n_heads=s["n_heads"], n_layers=s["n_layers"], d_ff=s["d_ff"],
        max_len=s["max_len"], dtype=jnp.dtype(config["dtype"]))


def build_engine(config: dict, params):
    from deeplearning4j_tpu.serving.engine import InferenceEngine

    srv = config["serving"]
    return InferenceEngine.for_transformer(
        params, transformer_config(config),
        decode_slots=int(srv["slots"]), page_size=int(srv["page_size"]),
        kv_pages=int(srv["kv_pages"]),
        decode_kernel=srv["decode_kernel"], horizon=int(srv["horizon"]),
        speculation=int(srv["speculation"]),
        prefix_cache=bool(srv["prefix_cache"]))


def make_train_step(config: dict, params):
    """`make_train_step` as it stands: SGD with momentum, the velocity
    its state."""
    from deeplearning4j_tpu.models import transformer

    step = transformer.make_train_step(
        transformer_config(config), lr=float(config["training"]["lr"]))
    return step, transformer.init_velocity(params)


def first_gradient(state):
    """Momentum starts at 0, so the velocity after one step IS the
    first gradient as the optimizer got it."""
    return state


# ------------------------------------------------------- 4. the reference
def reference():
    from benchmark.reference import gpt2

    return gpt2


# ------------------------------------------------------------ 5. counts
def params_matmul(config: dict) -> int:
    """Weights that a token multiplies: four d x d projections, two FFN
    matrices a layer, and the tied head once (the embedding is a
    look-up)."""
    s = sizes(config)
    d, f = s["d_model"], s["d_ff"]
    return s["n_layers"] * (4 * d * d + 2 * d * f) + s["vocab_size"] * d


def params_total(config: dict) -> int:
    s = sizes(config)
    d, f, v = s["d_model"], s["d_ff"], s["vocab_size"]
    per_layer = 4 * d * d + 2 * d * f + f + d + 4 * d
    return v * d + s["max_len"] * d + 2 * d + s["n_layers"] * per_layer


def attn_flops_token(config: dict, context: int) -> int:
    """QK^T and PV of one query over `context` keys, all layers."""
    s = sizes(config)
    return 4 * s["n_layers"] * s["d_model"] * int(context)


def decode_token_flops(ctx: dict, context: int) -> int:
    """One decoded token whose query sees `context` keys."""
    config = ctx["config"]
    return 2 * params_matmul(config) + attn_flops_token(config, context)


def prefill_flops(ctx: dict, prompt_len: int) -> int:
    """A prompt of `prompt_len` tokens, causal: the head on the last
    position only, as the program computes it."""
    s = sizes(ctx["config"])
    d, f = s["d_model"], s["d_ff"]
    body = s["n_layers"] * (4 * d * d + 2 * d * f)
    causal_pairs = prompt_len * (prompt_len + 1) // 2
    return 2 * body * prompt_len + 2 * s["vocab_size"] * d \
        + 4 * s["n_layers"] * d * causal_pairs


def train_flops_token(ctx: dict, seq_len: int) -> int:
    """Forward and backward of one trained token in a row of `seq_len`:
    three times the forward's matmuls and causal attention (mean context
    (seq_len + 1) / 2)."""
    s = sizes(ctx["config"])
    fwd = 2 * params_matmul(ctx["config"]) \
        + 4 * s["n_layers"] * s["d_model"] * (seq_len + 1) / 2
    return int(3 * fwd)


def decode_step_bytes(ctx: dict, contexts: Sequence[int]) -> int:
    """What one decode step must move: every weight once, the live K/V
    once. The benchmark's own count, not the program's
    `decode_read_bytes`: it counts no copy."""
    s = sizes(ctx["config"])
    kv = 2 * s["n_layers"] * s["d_model"] * ctx["itemsize"]
    return params_total(ctx["config"]) * ctx["itemsize"] \
        + kv * sum(int(c) for c in contexts)


def paged_decode_attention_work(ctx: dict, contexts: Sequence[int]
                                ) -> List[dict]:
    """The calls of the paged decode kernel in one dispatch, one a
    layer, all alike: each slot's query reads K and V of its written
    pages once. Bytes count whole pages, as the kernel must fetch them;
    operations the real keys."""
    s = sizes(ctx["config"])
    d, itemsize = s["d_model"], ctx["itemsize"]
    page_size = int(ctx["config"]["serving"]["page_size"])
    pages = sum(-(-int(c) // page_size) for c in contexts)
    call = {"flops": sum(4 * d * int(c) for c in contexts),
            "bytes": 2 * pages * page_size * d * itemsize
            + 2 * len(contexts) * d * itemsize}
    return [call] * s["n_layers"]


def flash_fwd_work(ctx: dict, rows: int, seq_len: int) -> List[dict]:
    """The calls of the flash forward kernel in one forward pass over
    `rows` sequences of `seq_len`, one a layer, all alike, causal: read
    Q, K, V once, write O once."""
    s = sizes(ctx["config"])
    pairs = seq_len * (seq_len + 1) // 2
    call = {"flops": 4 * s["d_model"] * pairs * rows,
            "bytes": 4 * rows * seq_len * s["d_model"] * ctx["itemsize"]}
    return [call] * s["n_layers"]


def flash_bwd_work(ctx: dict, rows: int, seq_len: int) -> List[dict]:
    """Both backward kernels of a layer together, one entry a layer.
    The algorithm needs five products over the causal pairs (QK^T
    again, dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q); the two
    kernels each form QK^T and dP, and that second forming is
    recomputation, not counted. Q, K, V, O, dO are read by each kernel
    and dQ, dK, dV written."""
    s = sizes(ctx["config"])
    pairs = seq_len * (seq_len + 1) // 2
    call = {"flops": 10 * s["d_model"] * pairs * rows,
            "bytes": (5 + 5 + 3) * rows * seq_len * s["d_model"]
            * ctx["itemsize"]}
    return [call] * s["n_layers"]


# ------------------------------------------- 6. programs a schedule reaches
def prompt_buckets(max_len: int, page_size: int) -> Tuple[int, ...]:
    """The program's prefill buckets (page-multiple powers of two up to
    the window), the benchmark's own copy of the rule in
    `serving/paged_kv.py`."""
    top = -(-max_len // page_size) * page_size
    out, b = [], page_size
    while b < top:
        out.append(b)
        b *= 2
    out.append(top)
    return tuple(out)


def warm_requests(config: dict, traffic: dict, seconds: float
                  ) -> List[Tuple[int, int]]:
    """Every group size at the smallest bucket touched (the program's
    hand-over of first tokens compiles per (bb, count)), then every
    other (bb, tb) group; with them the decode step."""
    srv, max_len = config["serving"], sizes(config)["max_len"]
    plan = schedule.warm_groups(
        traffic, seconds, int(srv["slots"]),
        prompt_buckets(max_len, int(srv["page_size"])))
    todo = [(n, plan["buckets"][0]) for n in plan["sizes"]]
    done = {(schedule.pow2_at_least(n), tb) for n, tb in todo}
    todo += [(bb, tb) for bb, tb in plan["groups"] if (bb, tb) not in done]
    return [(n, min(tb, max_len - 2)) for n, tb in todo]
