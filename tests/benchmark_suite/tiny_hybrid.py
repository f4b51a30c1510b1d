"""The `qwen3_next` family at a size a test run can hold, added to the
tiny benchmark of `tiny.py` by files and entries alone: d 64, layers
`l, l, l, f`; full layers of 4 query heads over 2 K/V heads of 16 (8
turning), linear layers of 2 key and 4 value heads of 8 with a
convolution of 4; 16 experts of which 4 are held (share 1 of 4), 4
chosen, 1 shared; pages of 4."""

from __future__ import annotations

import json
import os

CELL = "hybrid.tiny-long"
CONFIG = {
    "source": "none: a test size", "family": "qwen3_next",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "partial_rotary_factor": 0.5, "rope_theta": 10000000,
    "rms_norm_eps": 1e-06, "full_attention_interval": 4,
    "num_hidden_layers": 4, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_experts": 4, "router_width": 16, "held_experts_first": 4,
    "num_experts_per_tok": 4, "vocab_size": 97,
    "max_position_embeddings": 64, "dtype": "bfloat16",
    "serving": {"slots": 4, "page_size": 4, "kv_pages": 64,
                "prefill_tokens_per_pass": 32, "decode_kernel": "auto",
                "horizon": 1, "speculation": 0, "prefix_cache": False},
}
TRAFFIC = {
    "driver": "serve_closed", "schedule_seed": 5, "clients": 4,
    "requests_per_client": 50, "stagger_first": True, "warmup_s": 0.3,
    "prompt_len": {"dist": "const", "value": 21},
    "output_len": {"dist": "cycle", "values": [8, 11, 14]},
    "trace": {"start_s": 0.1, "seconds": 0.2}, "check_requests": 16}
#: the kind of limit the real cell has (a count of served tokens off
#: the float32 reference's best); `test_qwen3_next.py` says what was
#: read at this size
LIMITS = {"tokens_off_best": 4}


def add(root: str) -> str:
    """Add the configuration, its mix, its cell and the entries to the
    tiny benchmark under `root`; returns the cell's name."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "tiny-hybrid.json"),
              "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(bench, "traffic", "tiny-long64.json"),
              "w") as f:
        json.dump(TRAFFIC, f)
    with open(os.path.join(bench, "cells", CELL + ".json"), "w") as f:
        json.dump({"limits": LIMITS}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-hybrid", "source": "none",
                          "file": "benchmark/configs/tiny-hybrid.json",
                          "reduced": [], "why": "a test size"})
    bm["workloads"].append({"name": CELL, "config": "tiny-hybrid",
                            "traffic": "tiny-long64", "chips": 1,
                            "why": "a test size"})
    real = {m["name"]: m for m in json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "BENCHMARK.json")))["per_layer"]}
    for m in bm["end_to_end"]:
        if m["name"] in ("out_tok_s", "itl_p98_ms"):
            m["workloads"].append(CELL)
    for m in bm["per_layer"]:
        if "qwen3next-ep8-agent-long" in real[m["name"]]["workloads"]:
            m["workloads"] = [w for w in m["workloads"]
                              if not w.startswith("qwen3next")] + [CELL]
    with open(path, "w") as f:
        json.dump(bm, f)
    return CELL
