"""`BENCHMARK.json` and the files its names point to.

Whatever belongs to one configuration, one traffic mix, one cell or one
metric sits in a file of its own, found by name:

    benchmark/configs/<config>.json    sizes as run, with their source
    benchmark/traffic/<traffic>.json   the mix's parameters and `driver`
    benchmark/cells/<workload>.json    the limits that decide `correct`
    benchmark/metrics/<metric>.py      `read(ctx)` -> number or None
    benchmark/reference/<name>.py      the plain reference a config names

A later PR adds a cell, a configuration, a mix or a metric by adding
files and entries, editing none.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Callable, List, NamedTuple, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRIVERS = ("serve_closed", "serve_open", "train")


class Cell(NamedTuple):
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(root: str, *parts: str) -> dict:
    with open(os.path.join(root, "benchmark", *parts)) as f:
        return json.load(f)


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, root: str = ROOT,
              manifest: Optional[dict] = None) -> Cell:
    manifest = manifest or load_manifest(root)
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json (it has "
            f"{[w['name'] for w in manifest['workloads']]})")
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = _json(root, "traffic", entry["traffic"] + ".json")
    if traffic.get("driver") not in DRIVERS:
        raise ValueError(
            f"traffic {entry['traffic']!r} names driver "
            f"{traffic.get('driver')!r}; known: {DRIVERS}")
    limits = _json(root, "cells", name + ".json")["limits"]
    return Cell(name, int(entry["chips"]), entry["config"], config,
                entry["traffic"], traffic, limits,
                _for_cell(manifest["end_to_end"], name),
                _for_cell(manifest["per_layer"], name), root)


def shape_of(config: dict) -> dict:
    """The sizes the benchmark computes with, from the published keys."""
    d = int(config["n_embd"])
    return {"vocab_size": int(config["vocab_size"]), "d_model": d,
            "n_heads": int(config["n_head"]),
            "n_layers": int(config["n_layer"]),
            "d_ff": int(config.get("n_inner") or 4 * d),
            "max_len": int(config["n_positions"])}


def itemsize_of(config: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[config["dtype"]]


def load_reader(metric: str, root: str = ROOT) -> Callable:
    """`read(ctx)` of benchmark/metrics/<metric>.py."""
    path = os.path.join(root, "benchmark", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no reader for metric {metric!r}: {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_reference(config: dict):
    """The plain reference module the configuration names."""
    return importlib.import_module(
        "benchmark.reference." + config["reference"])


def load_peak(device_kind: str, root: str = ROOT) -> dict:
    """The peaks of this device; one the table lacks is an error, never
    a default."""
    peaks = _json(root, "peaks.json")
    if device_kind not in peaks:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(it has {sorted(peaks)})")
    return peaks[device_kind]
