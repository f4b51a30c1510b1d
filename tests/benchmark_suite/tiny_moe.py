"""The `cohere2_moe` family at a size a test run can hold, added to the
tiny benchmark of `tiny.py` by files and entries alone: d 64, 8 query
heads over 2 K/V heads of 16, window 8, pages of 4, layers `s, s, s, f`,
16 experts of which 4 are held (share 1 of 4), 4 chosen, 2 shared."""

from __future__ import annotations

import json
import os

CELL = "moe.tiny-long"
CONFIG = {
    "source": "none: a test size", "family": "cohere2_moe",
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 32, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "sliding_window": 8, "rope_theta": 50000, "layer_norm_eps": 1e-05,
    "logit_scale": 1, "num_experts": 4, "router_width": 16,
    "held_experts_first": 4, "num_experts_per_tok": 4,
    "num_shared_experts": 2, "vocab_size": 97,
    "max_position_embeddings": 64, "dtype": "bfloat16",
    "serving": {"slots": 4, "page_size": 4, "kv_pages": 64,
                "window_pages": 12, "prefill_tokens_per_pass": 32,
                "decode_kernel": "auto", "horizon": 1, "speculation": 0,
                "prefix_cache": False},
}
TRAFFIC = {
    "driver": "serve_closed", "schedule_seed": 5, "clients": 4,
    "requests_per_client": 50, "stagger_first": True, "warmup_s": 0.3,
    "prompt_len": {"dist": "const", "value": 21},
    "output_len": {"dist": "cycle", "values": [8, 11, 14]},
    "trace": {"start_s": 0.1, "seconds": 0.2}, "check_requests": 16}
#: the kind of limit the real cell has. Read at this size (CPU, six
#: seeds): the program 0 of 33-39 served tokens off the float32
#: reference's best, the bf16 reference in its place 0 of 224 on four
#: seeds, the fp8 control 3, 5, 5, 6 of 224
LIMITS = {"tokens_off_best": 2}


def add(root: str) -> str:
    """Add the configuration, its mix, its cell and the entries to the
    tiny benchmark under `root`; returns the cell's name."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "tiny-moe.json"), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(bench, "traffic", "tiny-long.json"), "w") as f:
        json.dump(TRAFFIC, f)
    with open(os.path.join(bench, "cells", CELL + ".json"), "w") as f:
        json.dump({"limits": LIMITS}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-moe", "source": "none",
                          "file": "benchmark/configs/tiny-moe.json",
                          "reduced": [], "why": "a test size"})
    bm["workloads"].append({"name": CELL, "config": "tiny-moe",
                            "traffic": "tiny-long", "chips": 1,
                            "why": "a test size"})
    real = {m["name"]: m for m in json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "BENCHMARK.json")))["per_layer"]}
    for m in bm["end_to_end"]:
        if m["name"] in ("out_tok_s", "itl_p98_ms"):
            m["workloads"].append(CELL)
    for m in bm["per_layer"]:
        if "cmdaplus-ep8-agent-long" in real[m["name"]]["workloads"]:
            m["workloads"] = [w for w in m["workloads"]
                              if not w.startswith("cmdaplus")] + [CELL]
    with open(path, "w") as f:
        json.dump(bm, f)
    return CELL
