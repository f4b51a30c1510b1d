"""The `lfm2_moe` family at a size a test run can hold, added to the
tiny benchmark of `tiny.py` by files and entries alone: d 64; one
leading dense layer (width 96), then periods `full, conv, conv, conv`;
attention of 4 query heads over 2 K/V heads of 16; conv layers of 3
taps; 8 experts of 32, 2 chosen, a selection bias, every expert held;
the head tied; pages of 8."""

from __future__ import annotations

import json
import os

CELL = "lfm2.tiny-mid"
PERIOD = ["full_attention", "conv", "conv", "conv"]
TRAFFIC = {
    "driver": "serve_closed", "schedule_seed": 41, "clients": 4,
    "requests_per_client": 50, "stagger_first": True, "warmup_s": 0.3,
    "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                   "min": 10, "max": 32},
    "output_len": {"dist": "cycle", "values": [6, 9, 12]},
    "trace": {"start_s": 0.1, "seconds": 0.2}, "check_requests": 8}
#: the kind of limit the real cell has; `test_lfm2_moe.py` says what
#: was read at this size
LIMITS = {"tokens_off_best": 4}


def config(periods: int = 1, dtype: str = "bfloat16") -> dict:
    return {
        "source": "none: a test size", "family": "lfm2_moe",
        "model_type": "lfm2_moe", "vocab_size": 97, "hidden_size": 64,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_hidden_layers": 1 + 4 * periods,
        "layer_types": ["conv"] + PERIOD * periods,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
        "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": 1, "conv_L_cache": 3, "conv_bias": False,
        "norm_eps": 1e-05, "max_position_embeddings": 128,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "dtype": dtype,
        "serving": {"slots": 4, "page_size": 8, "kv_pages": 64,
                    "prefill_tokens_per_pass": 32,
                    "decode_kernel": "auto", "horizon": 1,
                    "speculation": 0, "prefix_cache": False},
    }


def add(root: str) -> str:
    """Add the configuration, its mix, its cell and the entries to the
    tiny benchmark under `root`; returns the cell's name."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "tiny-lfm2.json"), "w") as f:
        json.dump(config(), f)
    with open(os.path.join(bench, "traffic", "tiny-mid.json"), "w") as f:
        json.dump(TRAFFIC, f)
    with open(os.path.join(bench, "cells", CELL + ".json"), "w") as f:
        json.dump({"limits": LIMITS}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-lfm2", "source": "none",
                          "file": "benchmark/configs/tiny-lfm2.json",
                          "reduced": [], "why": "a test size"})
    bm["workloads"].append({"name": CELL, "config": "tiny-lfm2",
                            "traffic": "tiny-mid", "chips": 1,
                            "why": "a test size"})
    real = {m["name"]: m for m in json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "BENCHMARK.json")))["per_layer"]}
    for m in bm["end_to_end"]:
        if m["name"] in ("out_tok_s", "itl_p98_ms"):
            m["workloads"].append(CELL)
    for m in bm["per_layer"]:
        if "lfm2moe-agent-mid-sat64" in real[m["name"]].get("workloads",
                                                            []):
            m["workloads"] = [w for w in m["workloads"]
                              if not w.startswith("lfm2moe")] + [CELL]
    with open(path, "w") as f:
        json.dump(bm, f)
    return CELL
