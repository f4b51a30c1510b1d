"""Spans inside the decode scheduler (ISSUE 26): the phases of a pass,
the life of a request, the slowest passes, and the same spans on the
profiler's clock. CPU, tiny model; the loop is built with `start=False`
and driven by `tick()` unless the test is about the scheduler thread."""

from __future__ import annotations

import glob
import json
import os
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_transformer_params)
from deeplearning4j_tpu.serving import decode_loop as dl
from deeplearning4j_tpu.serving.decode_loop import DecodeLoop
from deeplearning4j_tpu.telemetry.trace import PhaseTotals, span
from deeplearning4j_tpu.testing import chaos

CFG = TransformerConfig(vocab_size=17, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_len=64, interpret=True)
PARAMS = None

#: the phases of a pass that lie directly under `decode.tick`
CHILDREN = [p for p in dl.PHASES
            if p not in (dl.TICK, dl.IDLE_WAIT, dl.PREFILL_DISPATCH)]


def _params():
    global PARAMS
    if PARAMS is None:
        PARAMS = init_transformer_params(jax.random.PRNGKey(0), CFG)
    return PARAMS


def _loop(**kw):
    kw.setdefault("slots", 2)
    kw.setdefault("page_size", 8)
    return DecodeLoop(_params(), CFG, start=False, **kw)


def _prompts(n, t=9, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, CFG.vocab_size, (t,)).astype(np.int32)
            for _ in range(n)]


def _serve(loop, n=3, max_tokens=4):
    streams = loop.submit_many(_prompts(n), max_tokens)
    loop.run_until_idle()
    return streams


# ------------------------------------------------------- phases of a pass
@pytest.mark.parametrize("mode, ran", [
    ("plain", ["decode.reap", "decode.admit", dl.PREFILL_DISPATCH,
               "decode.grant_pages", "decode.upload",
               "decode.step_dispatch", "decode.d2h", "decode.account",
               "decode.flush_first", "decode.emit"]),
    ("spec", ["decode.draft", "decode.step_dispatch", "decode.d2h",
              "decode.upload", "decode.emit"]),
])
def test_every_phase_is_in_the_snapshot_and_children_fit_the_tick(mode,
                                                                  ran):
    loop = _loop(speculation=2 if mode == "spec" else 0)
    if mode == "spec":
        # a prompt that repeats itself, so the n-gram drafter proposes
        loop.submit(np.tile(np.arange(4, dtype=np.int32), 5), 12)
        loop.run_until_idle()
    else:
        _serve(loop)
    phases = loop.snapshot()["phases"]
    assert set(phases) == set(dl.PHASES)
    assert phases[dl.TICK]["count"] >= 2
    for name in ran:
        assert phases[name]["count"] >= 1, name
        assert phases[name]["seconds"] > 0.0, name
    children = sum(phases[c]["seconds"] for c in CHILDREN)
    assert children <= phases[dl.TICK]["seconds"] * (1 + 1e-9)
    # a prefill is part of the admission that made it
    assert (phases[dl.PREFILL_DISPATCH]["seconds"]
            <= phases["decode.admit"]["seconds"])
    json.dumps(loop.snapshot())  # the new keys are JSON-safe


def test_counts_follow_the_work_not_the_passes():
    loop = _loop()
    _serve(loop, n=3)
    snap = loop.snapshot()
    ph = snap["phases"]
    assert ph["decode.step_dispatch"]["count"] == snap["dispatches"]
    # a read-back a step, and one more in a pass that prefilled, for
    # the first tokens
    assert ph["decode.d2h"]["count"] == (
        snap["dispatches"] + snap["prefill_passes"])
    assert ph["decode.admit"]["count"] == ph[dl.TICK]["count"]
    # three prompts of one bucket on two slots: a group of two, then
    # one more once a slot is free; both passes also decoded
    assert ph[dl.PREFILL_DISPATCH]["count"] == 2
    assert snap["prefill_passes"] == 2
    assert ph["decode.kv_jobs"]["count"] == 0
    assert ph[dl.IDLE_WAIT]["count"] == 0  # nobody ran `_run`


def test_kv_jobs_phase_counts_jobs_only():
    loop = _loop()
    done = SimpleNamespace(set=lambda: None)
    loop._kv_jobs.append({"kind": "prefill", "tokens": list(range(16)),
                          "event": done, "result": {}})
    loop.tick()
    loop.tick()
    assert loop.snapshot()["phases"]["decode.kv_jobs"]["count"] == 1


def test_scheduler_thread_counts_its_idle_wait_and_the_queue_wait():
    with DecodeLoop(_params(), CFG, slots=2, page_size=8) as loop:
        time.sleep(0.05)
        for s in loop.submit_many(_prompts(3), 3):
            s.result(timeout=120)
    assert not loop._thread.is_alive()
    snap = loop.snapshot()  # the thread has closed its last span
    assert snap["phases"][dl.IDLE_WAIT]["count"] >= 1
    assert snap["phases"][dl.IDLE_WAIT]["seconds"] >= 0.04
    assert snap["queue_wait"]["count"] == 3
    assert snap["queue_wait"]["seconds"] > 0.0
    fam = telemetry.get_registry().histogram("dl4j_decode_phase_seconds")
    mine = fam.labels(loop=loop.label, phase=dl.TICK)
    assert mine.count == snap["phases"][dl.TICK]["count"]


# -------------------------------------------------- the life of a request
def test_stamps_are_ordered_and_the_third_waits_for_a_slot():
    loop = _loop(slots=2)
    streams = _serve(loop, n=3)
    lives = [s.timeline() for s in streams]
    assert [t["request_id"] for t in lives] == [0, 1, 2]
    for t in lives:
        assert (t["submitted"] <= t["admitted"] <= t["first_token"]
                <= t["finished"])
    # one lock pass admitted the first two
    assert lives[0]["admitted"] == lives[1]["admitted"]
    assert lives[2]["admitted"] >= min(lives[0]["finished"],
                                       lives[1]["finished"])
    assert loop.snapshot()["queue_wait"]["count"] == 3


def test_a_request_cancelled_in_the_queue_never_reaches_a_slot():
    loop = _loop(slots=1)
    first, second = loop.submit_many(_prompts(2), 3)
    second.cancel()
    loop.run_until_idle()
    life = second.timeline()
    assert second.finish_reason == "cancelled"
    assert life["admitted"] is None and life["first_token"] is None
    assert life["submitted"] <= life["finished"]
    assert first.timeline()["first_token"] is not None


# ------------------------------------------------------------ the tracer
def test_traced_run_round_trips_and_joins_a_request(tmp_path):
    tracer = telemetry.start_tracing()
    loop = _loop()
    streams = _serve(loop, n=3)
    path = telemetry.save_chrome_trace(str(tmp_path / "serve.json"))
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    assert len(events) == len(tracer.spans())
    by_id = {e["args"]["span_id"]: e for e in events}
    assert len(by_id) == len(events)
    names = {e["name"] for e in events}
    assert {dl.TICK, "decode.admit", dl.PREFILL_DISPATCH, "decode.d2h",
            "request", "request.queued", "request.prefill",
            "request.decode"} <= names
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0
        parent = e["args"]["parent_id"]
        if e["name"] in (dl.TICK, "request"):
            assert parent is None
        else:
            assert parent in by_id, e["name"]
        if e["name"] == dl.PREFILL_DISPATCH:
            assert by_id[parent]["name"] == "decode.admit"
        elif e["name"].startswith("decode.") and e["name"] != dl.TICK:
            assert by_id[parent]["name"] == dl.TICK
        elif e["name"].startswith("request."):
            assert by_id[parent]["name"] == "request"
            assert by_id[parent]["args"]["request"] == e["args"]["request"]
    # the spans of one request share its identifier, and lie end to end
    for s in streams:
        mine = {e["name"]: e for e in events
                if e["name"].startswith("request")
                and e["args"]["request"] == s.request_id}
        assert set(mine) == {"request", "request.queued",
                             "request.prefill", "request.decode"}
        life = s.timeline()
        assert mine["request"]["ts"] == pytest.approx(
            life["submitted"] * 1e6, abs=1.0)
        assert (mine["request.queued"]["ts"] + mine["request.queued"]["dur"]
                == pytest.approx(mine["request.prefill"]["ts"], abs=1.0))
        assert mine["request.decode"]["args"]["finish"] == "max_tokens"
    # every prefill names the requests of its rows
    groups = [e["args"] for e in events if e["name"] == dl.PREFILL_DISPATCH]
    assert sorted(r for g in groups for r in g["requests"]) == [0, 1, 2]
    for g in groups:
        assert g["rows"] == len(g["requests"]) <= g["bb"]
        assert g["tokens"] == 9 * g["rows"] and g["tb"] >= 9
        assert g["ctx"] == 0
    ticks = [e for e in events if e["name"] == dl.TICK]
    assert all(isinstance(t["args"]["dispatched"], bool) for t in ticks)
    emits = [e for e in events if e["name"] == "decode.emit"]
    assert sum(e["args"]["tokens"] for e in emits) + 3 \
        == sum(len(s.result()) for s in streams)  # + three first tokens


def test_no_tracer_makes_no_record_and_the_same_tokens():
    assert telemetry.active_tracer() is None
    bare = [s.result() for s in _serve(_loop())]
    assert telemetry.chrome_trace() == {"traceEvents": []}
    tracer = telemetry.start_tracing()
    traced = [s.result() for s in _serve(_loop())]
    assert tracer.spans() and traced == bare


# ------------------------------------------------------ the slowest passes
def test_a_delayed_pass_is_kept_with_the_delay_in_its_self_time():
    loop = _loop()
    _serve(loop)  # every program this needs is compiled now
    streams = loop.submit_many(_prompts(2, seed=5), 3)
    loop.tick()
    # forget the passes that compiled: one of them may share this
    # interval and be longer still
    loop._slow_ticks = [None] * dl.SLOW_TICKS_KEPT
    chaos.configure([chaos.Rule("decode.step", "delay", delay_s=0.4,
                                times=1)])
    try:
        t0 = time.perf_counter()
        loop.tick()
        t1 = time.perf_counter()
    finally:
        chaos.deactivate()
    loop.run_until_idle()
    assert all(s.done for s in streams)
    slow = loop.snapshot()["slow_ticks"]
    assert 1 <= len(slow) <= dl.SLOW_TICKS_KEPT
    (worst,) = [t for t in slow if t0 <= t["start_s"] <= t1]
    assert 400.0 <= worst["dur_ms"] <= (t1 - t0) * 1e3
    assert dl.TICK not in worst["phases"]
    assert set(worst["phases"]) <= set(dl.PHASES)
    own = worst["dur_ms"] - sum(v for k, v in worst["phases"].items()
                                if k in CHILDREN)
    assert own >= 400.0
    assert all(v < 400.0 for v in worst["phases"].values())


def test_one_pass_is_kept_for_each_interval_and_old_ones_give_way():
    loop = _loop()
    iv = dl.SLOW_TICK_INTERVAL_S * 10**9

    def tick(interval, offset_ms, dur_ms):
        return SimpleNamespace(start_ns=interval * iv + offset_ms * 10**6,
                               dur_ns=dur_ms * 10**6)

    loop._phases.pass_ns["decode.d2h"] = 2 * 10**6
    for t in (tick(5, 10, 30), tick(5, 200, 70), tick(5, 400, 50),
              tick(6, 0, 20)):
        loop._keep_if_slowest(t)
    kept = loop.snapshot()["slow_ticks"]
    assert [(k["start_s"], k["dur_ms"]) for k in kept] == [
        (5 * dl.SLOW_TICK_INTERVAL_S + 0.2, 70.0),
        (6 * dl.SLOW_TICK_INTERVAL_S, 20.0)]
    assert kept[0]["phases"] == {"decode.d2h": 2.0}
    # a ring: the interval that comes around replaces the one it laps
    loop._keep_if_slowest(tick(5 + dl.SLOW_TICKS_KEPT, 0, 1))
    kept = loop.snapshot()["slow_ticks"]
    assert [k["dur_ms"] for k in kept] == [20.0, 1.0]


# ------------------------------------------------- the profiler's own trace
def test_a_profiler_window_holds_the_scheduler_s_spans(tmp_path):
    from benchmark import trace_reduce

    loop = _loop()
    _serve(loop)  # compile outside the window
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            _serve(loop)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert found
    trace = trace_reduce.load_xplane(found[-1])
    start, end = trace_reduce.find_window(trace)
    names = {n for n, _s, _e in trace_reduce.host_spans(trace, start, end)}
    assert {dl.TICK, "decode.d2h", "decode.step_dispatch",
            dl.PREFILL_DISPATCH} <= names


# ----------------------------------------------------------- the primitive
def test_span_with_everything_off_costs_little():
    """1,000 enters with no tracer, no totals and no profiler session:
    what the fit and checkpoint loops pay. Generous, so that a noisy box
    cannot fail it while a lock or a clock read on this path would."""
    assert telemetry.active_tracer() is None

    def run(n=1000):
        t0 = time.perf_counter()
        for _ in range(n):
            with span("off", step=1):
                pass
        return time.perf_counter() - t0

    assert min(run() for _ in range(5)) < 0.02  # 20 us an enter


def test_counted_span_costs_tens_of_microseconds_at_most():
    """The always-on path of the scheduler: two clock reads, one
    histogram observation, one TraceMe with no session live."""
    fam = telemetry.MetricsRegistry().histogram("phase_seconds")
    totals = PhaseTotals(fam, ["a"], loop="t")

    def run(n=1000):
        t0 = time.perf_counter()
        for _ in range(n):
            with span("a", totals):
                pass
        return time.perf_counter() - t0

    assert min(run() for _ in range(5)) < 0.05  # 50 us a span
    assert totals.totals()["a"]["count"] == 5000
    assert totals.pass_ns["a"] > 0
    totals.begin_pass()
    assert totals.pass_ns == {"a": 0}
