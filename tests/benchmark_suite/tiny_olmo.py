"""The `olmo_hybrid` family at a size a test run can hold, added to the
tiny benchmark of `tiny.py` by files and entries alone: d 48, periods
`l, l, l, f`; full layers of 3 heads of 16, each with its own K/V head;
linear layers of 3 heads with a state of 24 x 48 (not square, no
multiple of anything) and a convolution of 4; a dense feed-forward of
80; pages of 8, a prompt prefilled in pieces of 32."""

from __future__ import annotations

import json
import os

CELL = "olmo.tiny-docs"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
TRAFFIC = {
    "driver": "serve_closed", "schedule_seed": 7, "clients": 3,
    "requests_per_client": 60, "stagger_first": True, "warmup_s": 0.3,
    "prompt_len": {"dist": "lognormal", "median": 32, "sigma": 0.6,
                   "min": 8, "max": 110},
    "output_len": {"dist": "cycle", "values": [4, 6, 8]},
    "trace": {"start_s": 0.1, "seconds": 0.2}, "check_requests": 16}
#: the kind of limit the real cell has; `test_olmo_hybrid.py` says what
#: was read at this size
LIMITS = {"tokens_off_best": 4}


def config(periods: int = 1, dtype: str = "bfloat16") -> dict:
    return {
        "source": "none: a test size", "family": "olmo_hybrid",
        "model_type": "olmo_hybrid", "vocab_size": 97, "hidden_size": 48,
        "intermediate_size": 80, "num_hidden_layers": 4 * periods,
        "num_attention_heads": 3, "num_key_value_heads": 3,
        "hidden_act": "silu", "max_position_embeddings": 128,
        "attention_bias": False, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False, "layer_types": PERIOD * periods,
        "linear_num_key_heads": 3, "linear_num_value_heads": 3,
        "linear_key_head_dim": 24, "linear_value_head_dim": 48,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}, "dtype": dtype,
        "serving": {"slots": 3, "page_size": 8, "kv_pages": 64,
                    "prefill_tokens_per_pass": 32,
                    "decode_kernel": "auto", "horizon": 1,
                    "speculation": 0, "prefix_cache": False},
    }


def add(root: str) -> str:
    """Add the configuration, its mix, its cell and the entries to the
    tiny benchmark under `root`; returns the cell's name."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "tiny-olmo.json"), "w") as f:
        json.dump(config(), f)
    with open(os.path.join(bench, "traffic", "tiny-docs.json"), "w") as f:
        json.dump(TRAFFIC, f)
    with open(os.path.join(bench, "cells", CELL + ".json"), "w") as f:
        json.dump({"limits": LIMITS}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-olmo", "source": "none",
                          "file": "benchmark/configs/tiny-olmo.json",
                          "reduced": [], "why": "a test size"})
    bm["workloads"].append({"name": CELL, "config": "tiny-olmo",
                            "traffic": "tiny-docs", "chips": 1,
                            "why": "a test size"})
    real = {m["name"]: m for m in json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "BENCHMARK.json")))["per_layer"]}
    for m in bm["end_to_end"]:
        if m["name"] in ("out_tok_s", "itl_p98_ms"):
            m["workloads"].append(CELL)
    for m in bm["per_layer"]:
        if "olmohyb7b-docs-chunked" in real[m["name"]]["workloads"]:
            m["workloads"] = [w for w in m["workloads"]
                              if not w.startswith("olmohyb")] + [CELL]
    with open(path, "w") as f:
        json.dump(bm, f)
    return CELL
