"""A later PR adds a cell, a configuration, a traffic mix or a
per-layer metric by adding files and entries, editing none: build a
whole benchmark in a temporary directory, add one of each, and run the
harness's loader and readers over them."""

import json
import os

from benchmark import manifest, run, schedule
from tests.benchmark_suite import tiny


def test_add_one_of_each_by_files_alone(tmp_path):
    root = tiny.build(str(tmp_path))
    bench = os.path.join(root, "benchmark")
    # a new configuration, a new mix, a new cell with its limits, and a
    # new per-layer metric with its reader: four files and four entries
    cfg = dict(tiny.CONFIG, n_layer=3)
    with open(os.path.join(bench, "configs", "tiny-deep.json"), "w") as f:
        json.dump(cfg, f)
    mix = dict(tiny.TRAFFIC["tiny-closed"], clients=2,
               output_len={"dist": "uniform", "min": 3, "max": 9})
    with open(os.path.join(bench, "traffic", "tiny-pair.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "cells", "deep.pair.json"), "w") as f:
        json.dump({"limits": {"token_gap_max": 0.1}}, f)
    with open(os.path.join(bench, "metrics", "requests_done.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    n = sum(1 for r in ctx['requests']\n"
                "            if r['finish'] == 'max_tokens')\n"
                "    return n or None\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-deep", "source": "none",
                          "file": "benchmark/configs/tiny-deep.json",
                          "reduced": [], "why": "one layer more"})
    bm["workloads"].append({"name": "deep.pair", "config": "tiny-deep",
                            "traffic": "tiny-pair", "chips": 1,
                            "why": "two clients"})
    for m in bm["end_to_end"]:
        if m["name"] in ("out_tok_s", "itl_p98_ms"):
            m["workloads"].append("deep.pair")
    bm["per_layer"].append({
        "name": "requests_done", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "out_tok_s", "workloads": ["deep.pair"]})
    with open(path, "w") as f:
        json.dump(bm, f)

    cell = manifest.load_cell("deep.pair", root)
    assert cell.config["n_layer"] == 3 and cell.traffic["clients"] == 2
    assert cell.limits == {"token_gap_max": 0.1}
    assert [m["name"] for m in cell.per_layer] == ["requests_done"]
    assert {m["name"] for m in cell.end_to_end} == \
        {"out_tok_s", "itl_p98_ms", "setup_s"}
    plan = schedule.closed_loop(cell.traffic)
    assert len(plan) == 2 and all(3 <= r.output_len <= 9
                                  for row in plan for r in row[1:])
    # the old cells are as they were
    old = manifest.load_cell("tiny.tiny-closed", root)
    assert "requests_done" not in [m["name"] for m in old.per_layer]
    # the readers run over a record, the new one among them; one that
    # finds nothing to read is left out of the line
    ctx = {"window": (0.0, 1.0), "setup_s": 2.0, "seconds": 1.0,
           "requests": [{"times": [0.1, 0.2, 0.3], "due": 0.0,
                         "finish": "max_tokens", "first": 0.1}]}
    line = run.read_metrics(cell.per_layer, ctx, root)
    assert line == {"requests_done": {"value": 1.0, "unit": "count"}}
    ctx["requests"][0]["finish"] = "cancelled"
    assert run.read_metrics(cell.per_layer, ctx, root) == {}
    e2e = run.read_metrics(cell.end_to_end, ctx, root)
    assert e2e["out_tok_s"]["value"] == 3.0
    assert e2e["setup_s"] == {"value": 2.0, "unit": "s"}
