"""A whole benchmark at a size a test run can hold: a temporary root
with its own `BENCHMARK.json`, one tiny configuration, one traffic mix
of each driver, their cells and limits, and the real metric readers,
families and peaks copied beside them. The harness's loader and drivers
run over it exactly as over the real files."""

from __future__ import annotations

import json
import os
import shutil

from benchmark import manifest

CONFIG = {
    "source": "none: a test size", "family": "gpt2",
    "vocab_size": 97, "n_embd": 32, "n_head": 4, "n_layer": 2,
    "n_inner": 64, "n_positions": 64, "dtype": "bfloat16",
    "serving": {"slots": 4, "page_size": 16, "kv_pages": 16,
                "decode_kernel": "auto", "horizon": 1, "speculation": 0,
                "prefix_cache": True},
    "training": {"optimizer": "sgd_momentum", "lr": 0.01,
                 "momentum": 0.9, "velocity_dtype": "float32",
                 "tokens_per_step": 128},
}
TRAFFIC = {
    "tiny-closed": {
        "driver": "serve_closed", "schedule_seed": 1, "clients": 4,
        "requests_per_client": 50, "stagger_first": True, "warmup_s": 0.3,
        "prompt_len": {"dist": "const", "value": 24},
        "output_len": {"dist": "cycle", "values": [6, 9, 12]},
        "trace": {"start_s": 0.1, "seconds": 0.2}, "check_requests": 3},
    "tiny-open": {
        "driver": "serve_open", "schedule_seed": 2, "rate_per_s": 20.0,
        "warmup_s": 0.3,
        "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                       "min": 8, "max": 40},
        "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                       "min": 2, "max": 12},
        "trace": {"start_s": 0.1, "seconds": 0.2}, "check_requests": 4},
    "tiny-train": {
        "driver": "train", "schedule_seed": 3, "batch": 4, "seq_len": 32,
        "sync_every": 2, "warmup_steps": 2,
        "trace": {"start_s": 0.1, "seconds": 0.2}},
}
SERVE_LIMITS = {"token_gap_max": 0.003}
TRAIN_LIMITS = {"grad_norm_gap_worst_leaf": 0.012,
                "change_norm_gap_worst_leaf": 0.012}


def _metric(name, unit, better, source, **more):
    return dict(name=name, unit=unit, better=better, source=source, **more)


def build(root: str) -> str:
    """Write the tiny benchmark under `root`; returns `root`."""
    bench = os.path.join(root, "benchmark")
    for sub in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    for sub in ("metrics", "families"):
        shutil.copytree(os.path.join(manifest.ROOT, "benchmark", sub),
                        os.path.join(bench, sub), dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(manifest.ROOT, "benchmark", "peaks.json"),
                bench)
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(CONFIG, f)
    cells = []
    for traffic, spec in TRAFFIC.items():
        with open(os.path.join(bench, "traffic", traffic + ".json"),
                  "w") as f:
            json.dump(spec, f)
        name = "tiny." + traffic
        cells.append({"name": name, "config": "tiny", "traffic": traffic,
                      "chips": 1, "why": "a test size"})
        limits = TRAIN_LIMITS if spec["driver"] == "train" \
            else SERVE_LIMITS
        with open(os.path.join(bench, "cells", name + ".json"), "w") as f:
            json.dump({"limits": limits}, f)
    serve = ["tiny.tiny-closed", "tiny.tiny-open"]
    train = ["tiny.tiny-train"]
    real = manifest.load_manifest()
    per_layer = []
    for m in real["per_layer"]:
        into = train if m["moves"] == "train_tok_s" else serve
        if m["moves"] == "ttft_p90_ms":
            into = serve[1:]
        per_layer.append(dict(m, workloads=into))
    bm = {
        "command": ["python3", "-m", "benchmark.run"],
        "paths": ["benchmark"], "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "none",
                     "file": "benchmark/configs/tiny.json",
                     "reduced": [], "why": "a test size"}],
        "workloads": cells,
        "end_to_end": [
            _metric("out_tok_s", "tokens/s", "higher", "host_clock",
                    bound=0.1, workloads=serve),
            _metric("itl_p98_ms", "ms", "lower", "host_clock", bound=0.1,
                    workloads=serve),
            _metric("ttft_p90_ms", "ms", "lower", "host_clock", bound=0.1,
                    workloads=serve[1:]),
            _metric("train_tok_s", "tokens/s", "higher", "host_clock",
                    bound=0.1, workloads=train),
            _metric("setup_s", "s", "lower", "host_clock", bound=0.1)],
        "per_layer": per_layer,
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return root


def greedy_sample(cell, seed: int, n: int, prompt_len: int,
                  tokens: int) -> list:
    """What a sound server would have served: `n` prompts from the
    seed, each decoded greedily by the cell's reference in float32, as
    the records `check.serve_numbers` takes."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import weights

    ref = cell.family.reference()
    vocab = cell.family.sizes(cell.config)["vocab_size"]
    params = weights.make_params(seed, cell.family, cell.config)
    sample = []
    for i in range(n):
        prompt = weights.token_ids(seed, 0, i, prompt_len, vocab)
        seq = list(prompt)
        for _ in range(tokens):
            lg = ref.logits(cell.config, params,
                            jnp.asarray([seq], jnp.int32),
                            len(seq) - 1, len(seq))
            seq.append(int(jnp.argmax(lg[0, 0])))
        sample.append({"prompt": np.asarray(prompt),
                       "prompt_len": prompt_len,
                       "tokens": seq[prompt_len:]})
    return sample
