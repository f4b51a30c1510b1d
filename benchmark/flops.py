"""Operations and bytes from shapes, for MFU and roofline shares.

`shape` is the benchmark's reading of a configuration file:
vocab_size, d_model, n_heads, n_layers, d_ff, max_len. A multiply-add is
two operations. Only what the algorithm needs is counted: matmuls and
attention; recomputation, padding rows and masked-out half tiles are not
work.
"""

from __future__ import annotations

from typing import Sequence


def params_matmul(shape: dict) -> int:
    """Weights that a token multiplies: four d x d projections, two FFN
    matrices a layer, and the tied head once (the embedding is a
    look-up)."""
    d, f = shape["d_model"], shape["d_ff"]
    return shape["n_layers"] * (4 * d * d + 2 * d * f) \
        + shape["vocab_size"] * d


def params_total(shape: dict) -> int:
    d, f, v = shape["d_model"], shape["d_ff"], shape["vocab_size"]
    per_layer = 4 * d * d + 2 * d * f + f + d + 4 * d
    return v * d + shape["max_len"] * d + 2 * d \
        + shape["n_layers"] * per_layer


def attn_flops_token(shape: dict, context: int) -> int:
    """QK^T and PV of one query over `context` keys, all layers."""
    return 4 * shape["n_layers"] * shape["d_model"] * int(context)


def decode_token_flops(shape: dict, context: int) -> int:
    """One decoded token whose query sees `context` keys."""
    return 2 * params_matmul(shape) + attn_flops_token(shape, context)


def prefill_flops(shape: dict, prompt_len: int) -> int:
    """A prompt of `prompt_len` tokens, causal: the head on the last
    position only, as the program computes it."""
    d, f = shape["d_model"], shape["d_ff"]
    body = shape["n_layers"] * (4 * d * d + 2 * d * f)
    causal_pairs = prompt_len * (prompt_len + 1) // 2
    return 2 * body * prompt_len + 2 * shape["vocab_size"] * d \
        + 4 * shape["n_layers"] * d * causal_pairs


def train_flops_token(shape: dict, seq_len: int) -> int:
    """Forward and backward of one trained token in a row of `seq_len`:
    three times the forward's matmuls and causal attention (mean context
    (seq_len + 1) / 2)."""
    fwd = 2 * params_matmul(shape) \
        + 4 * shape["n_layers"] * shape["d_model"] * (seq_len + 1) / 2
    return int(3 * fwd)


# ------------------------------------------------------ kernel rooflines
def paged_decode_attention_work(shape: dict, contexts: Sequence[int],
                                page_size: int, itemsize: int) -> dict:
    """ONE call of the paged decode kernel (one layer): each slot's
    query reads K and V of its written pages once. Bytes count whole
    pages, as the kernel must fetch them; operations the real keys."""
    d = shape["d_model"]
    pages = sum(-(-int(c) // page_size) for c in contexts)
    flops = sum(4 * d * int(c) for c in contexts)
    byts = 2 * pages * page_size * d * itemsize \
        + 2 * len(contexts) * d * itemsize
    return {"flops": flops, "bytes": byts}


def flash_fwd_work(shape: dict, rows: int, seq_len: int,
                   itemsize: int) -> dict:
    """ONE call of the flash forward kernel (one layer) over `rows`
    sequences of `seq_len`, causal: read Q, K, V once, write O once."""
    d = shape["d_model"]
    pairs = seq_len * (seq_len + 1) // 2
    return {"flops": 4 * d * pairs * rows,
            "bytes": 4 * rows * seq_len * d * itemsize}


def flash_bwd_work(shape: dict, rows: int, seq_len: int,
                   itemsize: int) -> dict:
    """Both backward kernels of one layer together. The algorithm needs
    five products over the causal pairs (QK^T again, dP = dO V^T, dV =
    P^T dO, dQ = dS K, dK = dS^T Q); the two kernels each form QK^T and
    dP, and that second forming is recomputation, not counted. Q, K, V,
    O, dO are read by each kernel and dQ, dK, dV written."""
    d = shape["d_model"]
    pairs = seq_len * (seq_len + 1) // 2
    return {"flops": 10 * d * pairs * rows,
            "bytes": (5 + 5 + 3) * rows * seq_len * d * itemsize}


def decode_step_bytes(shape: dict, contexts: Sequence[int],
                      itemsize: int) -> int:
    """What one decode step must move: every weight once, the live K/V
    once."""
    kv = 2 * shape["n_layers"] * shape["d_model"] * itemsize
    return params_total(shape) * itemsize + kv * sum(int(c) for c in contexts)


def least_seconds(work: dict, peak: dict) -> float:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
