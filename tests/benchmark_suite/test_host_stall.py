"""The readers of what a scheduler pass and the host process record of
a stall (`cpu_seconds` of the scheduler's phases, `offcpu_ms` of the
slowest passes, the collector's pauses and the heartbeat's delays), on
snapshots built by hand: each returns the hand-counted value, and None
on a snapshot of the program before them. The five entries stand at the
end of the metrics and list the two expert-parallel cells alone: the
other serving cells' lists end in metrics that their own tests pin
last, so they report none of the five."""

import pytest

from benchmark import manifest

NEW = ["sched_host_offcpu_ms_per_dispatch", "sched_tick_max_offcpu_ms",
       "host_gc_share", "host_gc_pause_max_ms", "host_lag_max_ms"]
CELLS = ["cmdaplus-ep8-agent-long", "qwen3next-ep8-agent-long"]
WINDOW = (100.0, 151.0)


def read(name, ctx):
    return manifest.load_reader(name)(ctx)


def snap(tick, tick_cpu, d2h, d2h_cpu, dispatches, gc_s=0.0, slow=(),
         gc_slowest=(), lag_slowest=()):
    return {"dispatches": dispatches,
            "phases": {"decode.tick": {"seconds": tick, "count": 1,
                                       "cpu_seconds": tick_cpu},
                       "decode.d2h": {"seconds": d2h, "count": 1,
                                      "cpu_seconds": d2h_cpu}},
            "slow_ticks": list(slow),
            "host": {"gc": {"seconds": gc_s, "count": 0, "by_gen": {},
                            "slowest": list(gc_slowest)},
                     "lag": {"slowest": list(lag_slowest), "beats": 0,
                             "period_ms": 20.0}}}


def parent_shaped(s):
    """The same snapshot as the program before these fields gives it."""
    out = dict(s, phases={k: {"seconds": v["seconds"], "count": 1}
                          for k, v in s["phases"].items()},
               slow_ticks=[{k: v for k, v in t.items()
                            if k in ("start_s", "dur_ms", "phases")}
                           for t in s["slow_ticks"]])
    del out["host"]
    return out


SLOW = [{"start_s": 90.0, "dur_ms": 4000.0, "phases": {},
         "offcpu_ms": 3900.0},
        {"start_s": 101.0, "dur_ms": 95.0, "phases": {}, "offcpu_ms": 1.0},
        {"start_s": 120.0, "dur_ms": 2015.7,
         "phases": {"decode.flush_first": 1627.0}, "offcpu_ms": 1650.5},
        {"start_s": 151.0, "dur_ms": 9000.0, "phases": {},
         "offcpu_ms": 8000.0}]
PAUSES = [{"start_s": 99.5, "dur_ms": 900.0, "generation": 2},
          {"start_s": 104.0, "dur_ms": 3.5, "generation": 0},
          {"start_s": 130.0, "dur_ms": 41.25, "generation": 2},
          {"start_s": 151.0, "dur_ms": 700.0, "generation": 1}]
DELAYS = [{"start_s": 96.0, "dur_ms": 2000.0},
          {"start_s": 110.0, "dur_ms": 0.5},
          {"start_s": 120.0, "dur_ms": 1640.0},
          {"start_s": 152.0, "dur_ms": 5000.0}]


def ctx():
    return {"window": WINDOW,
            "snap0": snap(10.0, 6.0, 4.0, 0.5, 0, gc_s=1.0),
            "snap1": snap(20.0, 11.0, 8.0, 1.0, 1000, gc_s=1.51,
                          slow=SLOW, gc_slowest=PAUSES,
                          lag_slowest=DELAYS)}


@pytest.mark.parametrize("name, want", [
    # host wall (10 - 4) less host CPU (5 - 0.5): 1.5 s over 1,000
    ("sched_host_offcpu_ms_per_dispatch", 1.5),
    # the longest pass that started in the window is the 2,015.7 ms one
    ("sched_tick_max_offcpu_ms", 1650.5),
    # 0.51 s of pause over a window of 51 s
    ("host_gc_share", 1.0),
    # the 900 ms pause started before the window, the 700 ms one after
    ("host_gc_pause_max_ms", 41.25),
    ("host_lag_max_ms", 1640.0),
])
def test_each_reader_on_a_hand_made_snapshot(name, want):
    assert read(name, ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_each_reader_gives_none_on_the_parent_s_snapshot(name):
    c = ctx()
    c["snap0"], c["snap1"] = (parent_shaped(c["snap0"]),
                              parent_shaped(c["snap1"]))
    assert read(name, c) is None
    assert read(name, {"window": WINDOW}) is None


def test_nothing_in_the_window_reads_none_or_zero():
    c = ctx()
    c["window"] = (200.0, 251.0)  # after everything the rings hold
    assert read("sched_tick_max_offcpu_ms", c) is None  # no pass to read
    assert read("host_gc_pause_max_ms", c) == 0.0
    assert read("host_lag_max_ms", c) == 0.0
    c["snap1"]["dispatches"] = 0
    assert read("sched_host_offcpu_ms_per_dispatch", c) is None


def test_the_five_entries_stand_last_and_list_the_cells():
    entries = manifest.load_manifest()["per_layer"]
    assert [m["name"] for m in entries[-len(NEW):]] == NEW
    for m in entries[-len(NEW):]:
        assert m["workloads"] == CELLS
        assert (m["moves"], m["better"]) == ("itl_p98_ms", "lower")
    layers = {m["name"]: m["layer"] for m in entries}
    assert {layers[n] for n in NEW[:2]} == {layers["sched_tick_max_ms"]}
    assert {layers[n] for n in NEW[2:]} == {"host process telemetry/host.py"}


@pytest.mark.parametrize("cell, pinned", [
    ("cgpt13b-decode-sat", 4), ("cgpt13b-prompt-p80", 6),
    ("cmdaplus-ep8-agent-long", 0), ("qwen3next-ep8-agent-long", 0),
    ("olmohyb7b-docs-chunked", 6)])
def test_each_serving_cell_reports_them_or_keeps_its_last_metrics(cell,
                                                                  pinned):
    """The expert-parallel cells report the five last; sat, p80 and olm,
    whose last four, six and six metrics the accepted tests pin, report
    none of them and end as they did."""
    names = [m["name"] for m in manifest.load_cell(cell).per_layer]
    if cell in CELLS:
        assert names[-len(NEW):] == NEW
    else:
        assert not set(NEW) & set(names)
        assert len(names) >= pinned
    assert "sched_host_ms_per_dispatch" in names


@pytest.mark.parametrize("cell", ["gpt2m-train-t1024", "gpt2m-train-t128"])
def test_the_training_cells_report_none_of_them(cell):
    assert not set(NEW) & {m["name"] for m in
                           manifest.load_cell(cell).per_layer}
