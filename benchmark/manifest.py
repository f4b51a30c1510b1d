"""`BENCHMARK.json` and the files its names point to.

Whatever belongs to one configuration, one family of models, one
traffic mix, one cell or one metric sits in a file of its own, found by
name:

    benchmark/configs/<config>.json    sizes as run, with their source
                                       and the `family` they belong to
    benchmark/families/<family>.py     what depends on the architecture
                                       (benchmark/families/__init__.py)
    benchmark/reference/<family>.py    the family's plain reference
    benchmark/traffic/<traffic>.json   the mix's parameters and `driver`
    benchmark/cells/<workload>.json    the limits that decide `correct`
    benchmark/metrics/<metric>.py      `read(ctx)` -> number or None

A later PR adds a cell, a configuration, a mix or a metric by adding
files and entries, editing none. A configuration of a family the
benchmark has brings only its own file, its cells' limits and, where
it needs one, a traffic file. A configuration of a NEW family brings:

    files    families/<family>.py (the six answers), reference/
             <family>.py, configs/<config>.json naming the family,
             traffic/<traffic>.json where no mix fits, cells/
             <workload>.json for each cell, metrics/<metric>.py for a
             metric that no reader yet reads
    entries  one under `configs`, one under `workloads` for each cell,
             the cell's name in the `workloads` list of every metric it
             reports (`decode_step_mfu`, the kernels' rooflines and the
             rest take their counts from the cell's family, so no second
             `*_mfu` is written), new metrics under `per_layer`
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
from types import ModuleType
from typing import Callable, List, NamedTuple, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRIVERS = ("serve_closed", "serve_open", "train")


class Cell(NamedTuple):
    name: str
    chips: int
    config_name: str
    config: dict
    family: ModuleType
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
                load_family(config, root), entry["traffic"], traffic,
                limits,
                _for_cell(manifest["end_to_end"], name),
                _for_cell(manifest["per_layer"], name), root)


@functools.lru_cache(maxsize=None)
def module_at(path: str) -> ModuleType:
    """The module in the file `path`, executed once a process: a
    reference's jitted functions keep their compiled programs. A family
    loads the reference beside it with this."""
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.splitext(path)[0])
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_family(config: dict, root: str = ROOT) -> ModuleType:
    """`benchmark/families/<family>.py` for the family the
    configuration names; one the benchmark does not have is an error
    that names it, never a default."""
    name = config.get("family")
    folder = os.path.join(root, "benchmark", "families")
    path = os.path.join(folder, f"{name}.py")
    if not name or not os.path.isfile(path):
        have = sorted(f[:-3] for f in os.listdir(folder)
                      if f.endswith(".py") and f != "__init__.py") \
            if os.path.isdir(folder) else []
        raise KeyError(
            f"the configuration names family {name!r}, and there is no "
            f"{path} (the benchmark has {have})")
    return module_at(path)


def itemsize_of(config: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[config["dtype"]]


def load_reader(metric: str, root: str = ROOT) -> Callable:
    """`read(ctx)` of benchmark/metrics/<metric>.py."""
    path = os.path.join(root, "benchmark", "metrics", metric + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no reader for metric {metric!r}: {path}")
    return module_at(path).read


def load_peak(device_kind: str, root: str = ROOT) -> dict:
    """The peaks of this device; one the table lacks is an error, never
    a default."""
    peaks = _json(root, "peaks.json")
    if device_kind not in peaks:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(it has {sorted(peaks)})")
    return peaks[device_kind]
