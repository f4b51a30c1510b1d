"""A later PR adds a cell, a configuration, a traffic mix, a per-layer
metric or a whole family of models by adding files and entries, editing
none: build a whole benchmark in a temporary directory, add one of each,
and run the harness's loader, readers and drivers over them."""

import json
import os
import shutil

import pytest

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


# ----------------------------------------------------- a second family
ADDED = os.path.join(os.path.dirname(__file__), "added_family")
HF_CONFIG = {
    "source": "none: a test size", "family": "hf_dense",
    "vocab_size": 97, "hidden_size": 32, "num_attention_heads": 4,
    "num_hidden_layers": 2, "intermediate_size": 64,
    "max_position_embeddings": 64, "dtype": "bfloat16",
    "serving": {"max_num_seqs": 4, "block_size": 16, "num_blocks": 16},
}
SEED = 2 ** 31 + 2801


def snapshot(root):
    """Every file under `root` with its bytes, but `BENCHMARK.json`."""
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            path = os.path.join(d, f)
            if os.path.relpath(path, root) != "BENCHMARK.json":
                with open(path, "rb") as fh:
                    out[os.path.relpath(path, root)] = fh.read()
    return out


def add_family(root, family_file=True):
    """What a later PR brings for a configuration of a family the
    benchmark has not seen: a family module, its reference, a
    configuration, the limits of one closed-loop cell on a mix that is
    there, and three kinds of entry. Returns the files it added."""
    bench = os.path.join(root, "benchmark")
    before = snapshot(root)
    os.makedirs(os.path.join(bench, "reference"), exist_ok=True)
    if family_file:
        shutil.copy(os.path.join(ADDED, "family.py"),
                    os.path.join(bench, "families", "hf_dense.py"))
    shutil.copy(os.path.join(ADDED, "reference.py"),
                os.path.join(bench, "reference", "hf_dense.py"))
    with open(os.path.join(bench, "configs", "tiny-hf.json"), "w") as f:
        json.dump(HF_CONFIG, f)
    with open(os.path.join(bench, "cells", "hf.tiny-closed.json"),
              "w") as f:
        json.dump({"limits": tiny.SERVE_LIMITS}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    old = json.loads(json.dumps(bm))
    bm["configs"].append({"name": "tiny-hf", "source": "none",
                          "file": "benchmark/configs/tiny-hf.json",
                          "reduced": [], "why": "another family"})
    bm["workloads"].append({"name": "hf.tiny-closed", "config": "tiny-hf",
                            "traffic": "tiny-closed", "chips": 1,
                            "why": "the closed loop, another family"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "tiny.tiny-closed" in m.get("workloads", ()):
            m["workloads"].append("hf.tiny-closed")
    with open(path, "w") as f:
        json.dump(bm, f)
    # entries were added and none changed; no file that was there moved
    for key in ("configs", "workloads"):
        assert bm[key][:len(old[key])] == old[key]
    for key in ("end_to_end", "per_layer"):
        for was, now in zip(old[key], bm[key]):
            assert {k: v for k, v in now.items() if k != "workloads"} == \
                {k: v for k, v in was.items() if k != "workloads"}
    after = snapshot(root)
    assert all(after[name] == data for name, data in before.items())
    return sorted(set(after) - set(before))


@pytest.fixture(scope="module")
def hf_root(tmp_path_factory):
    root = tiny.build(str(tmp_path_factory.mktemp("hf")))
    added = add_family(root)
    assert added == ["benchmark/cells/hf.tiny-closed.json",
                     "benchmark/configs/tiny-hf.json",
                     "benchmark/families/hf_dense.py",
                     "benchmark/reference/hf_dense.py"]
    return root


@pytest.fixture(scope="module")
def hf_line(hf_root):
    """One run of the added cell, as the harness runs it but for its
    look for a chip."""
    cell = manifest.load_cell("hf.tiny-closed", hf_root)
    return run.execute(cell, SEED, 0.6, False, require_chip=False)


def test_a_second_family_by_files_alone_runs_to_a_correct_line(
        hf_root, hf_line):
    cell = manifest.load_cell("hf.tiny-closed", hf_root)
    assert cell.family.__file__.startswith(hf_root)
    assert cell.family.sizes(cell.config)["width"] == 32
    assert hf_line["correct"] is True, hf_line
    assert hf_line["failed"] == 0 and hf_line["attempted"] > 0
    assert hf_line["numbers"]["tokens_compared"] >= 20
    assert set(hf_line["metrics"]) == {"setup_s", "out_tok_s",
                                       "itl_p98_ms"}
    assert list(hf_line)[-1] == "compared"
    # the family that was there still answers for its own cells
    old = manifest.load_cell("tiny.tiny-closed", hf_root)
    assert old.family.sizes(old.config)["d_model"] == 32


def test_the_second_family_s_fp8_control_is_not_correct(hf_root):
    """The added reference in the program's place, through the same
    comparison: in float32 it is exact, in fp8 it is not correct."""
    from benchmark import check

    cell = manifest.load_cell("hf.tiny-closed", hf_root)
    sample = tiny.greedy_sample(cell, SEED, 40, 24, 16)
    numbers = check.serve_numbers(cell, SEED, sample, ("fp8",))
    assert numbers["token_gap_max"] == 0.0
    low = numbers["control_fp8_token_gap_max"]
    assert low > 1.5 * cell.limits["token_gap_max"], numbers
    assert check.verdict({"token_gap_max": low},
                         cell.limits)["correct"] is False


def test_the_shared_readers_count_with_the_cell_s_family(hf_root):
    """`decode_mfu` takes a decoded token's operations from whatever
    family the cell names: no second reader for a second family."""
    cell = manifest.load_cell("hf.tiny-closed", hf_root)
    peak = manifest.load_peak("TPU v5 lite")
    ctx = {"config": cell.config, "family": cell.family, "itemsize": 2,
           "peak": peak, "window": (0.0, 10.0),
           "trace": {"busy_s": 1.0, "window_s": 2.0, "host": (0.0, 10.0)},
           "requests": [{"prompt_len": 8, "times": [1.0, 2.0, 3.0]}]}
    entry = next(m for m in cell.per_layer if m["name"] == "decode_mfu")
    body = 2 * (4 * 32 * 32 + 2 * 32 * 64) + 97 * 32
    ops = sum(2 * body + 4 * 2 * 32 * c for c in (9, 10))
    got = run.read_metrics([entry], ctx, hf_root)["decode_mfu"]["value"]
    assert got == pytest.approx(
        100.0 * ops / (2.0 * peak["bf16_flops_per_s"]))


def test_with_the_family_s_file_absent_it_fails_by_the_family_s_name(
        tmp_path):
    root = tiny.build(str(tmp_path))
    add_family(root, family_file=False)
    with pytest.raises(KeyError) as e:
        manifest.load_cell("hf.tiny-closed", root)
    assert "'hf_dense'" in str(e.value) and "gpt2" in str(e.value)
    # the cells of the family that is there load as before
    assert manifest.load_cell("tiny.tiny-closed", root).family
