"""`BENCHMARK.json` against the contract's form, and every name against
the file it points to."""

import json
import os
import re

import pytest

from benchmark import manifest

M = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in M["workloads"]]


def metrics():
    return M["end_to_end"] + M["per_layer"]


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["paths"]) <= 16 and len(M["command"]) <= 32
    assert os.path.getsize(os.path.join(
        manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024
    for word in M["command"]:
        assert not word.startswith("/") and ".." not in word
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("entry", metrics() + M["configs"] + M["workloads"],
                         ids=lambda e: e["name"])
def test_names_units_and_text(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry and key != "source" or (
                key == "source" and "file" in entry):
            text = entry[key]
            assert 1 <= len(text) <= 200
            assert "\n" not in text and "\t" not in text


def test_entries_have_just_the_keys_shown():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
    names = [m["name"] for m in metrics()]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_at_most_four_end_to_end_metrics_besides_setup():
    names = [m["name"] for m in M["end_to_end"]]
    assert "setup_s" in names and len(names) - 1 <= 4
    setup = next(m for m in M["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1


def reports(cell):
    return {m["name"] for m in M["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}


def test_every_layer_metric_moves_what_its_cells_report():
    e2e = {m["name"] for m in M["end_to_end"]}
    for m in M["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert m["workloads"], m["name"]
        for cell in m["workloads"]:
            assert cell in CELLS
            assert m["moves"] in reports(cell), (m["name"], cell)
    by_layer = {}
    for m in M["per_layer"]:
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_every_cell_reports_enough_and_has_an_mfu_for_each_metric():
    for cell in CELLS:
        assert "setup_s" in reports(cell) and len(reports(cell)) >= 2
        layer = [m for m in M["per_layer"] if cell in m["workloads"]]
        assert layer
        for e in reports(cell) - {"setup_s"}:
            assert any("mfu" in m["name"].split("_") and m["moves"] == e
                       for m in layer), (cell, e)
    for m in M["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_configuration_has_a_cell_and_its_files():
    used = {w["config"] for w in M["workloads"]}
    for c in M["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in M["paths"]))
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            raw = json.load(f)
        assert raw["source"] == c["source"]
        assert c["reduced"] == []
        family = manifest.load_family(raw)
        assert callable(family.reference().logits)
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_with_its_traffic_limits_and_readers(cell):
    c = manifest.load_cell(cell)
    assert c.traffic["driver"] in manifest.DRIVERS
    assert c.limits and all(v > 0 for v in c.limits.values())
    for m in c.end_to_end + c.per_layer:
        assert callable(manifest.load_reader(m["name"]))


def test_files_under_paths_are_named_from_a_name_s_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in M["paths"]:
        for d, dirs, files in os.walk(os.path.join(manifest.ROOT, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), manifest.ROOT)
                assert ok.match(rel), rel


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        manifest.load_peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        manifest.load_cell("no-such-cell")


@pytest.mark.parametrize("config", [{"family": "no_such_family"}, {}],
                         ids=["unknown", "unnamed"])
def test_an_unknown_family_is_an_error_that_names_it(config):
    with pytest.raises(KeyError) as e:
        manifest.load_family(config)
    assert repr(config.get("family")) in str(e.value)
    assert "gpt2" in str(e.value)      # what the benchmark does have
