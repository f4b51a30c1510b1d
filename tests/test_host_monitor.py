"""The process's host monitor (`telemetry/host.py`: the collector's
pauses, the interpreter heartbeat) and what a scheduler pass records of
its own CPU time and of the process meanwhile. CPU, tiny model; the loop
is built with `start=False` and driven by `tick()`, and what a test makes
happen inside a pass runs in the pass's `decode.reap`."""

from __future__ import annotations

import contextlib
import gc
import glob
import json
import os
import random
import threading
import time

import jax
import numpy as np
import pytest

from benchmark import manifest
from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_transformer_params)
from deeplearning4j_tpu.serving import decode_loop as dl
from deeplearning4j_tpu.serving.decode_loop import DecodeLoop
from deeplearning4j_tpu.telemetry import host
from deeplearning4j_tpu.telemetry.trace import PhaseTotals, span
from deeplearning4j_tpu.testing import chaos

CFG = TransformerConfig(vocab_size=17, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_len=64, interpret=True)
PASS_KEYS = {"start_s", "dur_ms", "phases", "cpu_ms", "d2h_cpu_ms",
             "offcpu_ms", "gc_ms", "gc_gen", "lag_ms", "vcsw", "ivcsw",
             "majflt"}
NEW_READERS = ("sched_host_offcpu_ms_per_dispatch",
               "sched_tick_max_offcpu_ms", "host_gc_share",
               "host_gc_pause_max_ms", "host_lag_max_ms")


@pytest.fixture(scope="module")
def loop():
    """A loop whose programs are compiled, with two requests running."""
    loop = DecodeLoop(init_transformer_params(jax.random.PRNGKey(0), CFG),
                      CFG, slots=2, page_size=8, start=False)
    for seed in (3, 4):
        rng = np.random.RandomState(seed)
        loop.submit_many([rng.randint(0, 17, (9,)).astype(np.int32)
                          for _ in range(2)], 40 if seed == 4 else 3)
        if seed == 3:
            loop.run_until_idle()
    loop.tick()
    loop.tick()
    yield loop
    loop.run_until_idle()


def one_pass(loop, inside=lambda: None) -> dict:
    """One pass with `inside()` run in it; its `slow_ticks` entry (the
    ring is emptied first, so the pass is the longest of its
    interval)."""
    loop._slow_ticks = [None] * dl.SLOW_TICKS_KEPT
    reap = loop._reap

    def reap_and_more():
        inside()
        reap()

    loop._reap = reap_and_more
    try:
        t0 = time.perf_counter()
        loop.tick()
        t1 = time.perf_counter()
    finally:
        del loop._reap
    (entry,) = [t for t in loop.snapshot()["slow_ticks"]
                if t0 <= t["start_s"] <= t1]
    return entry


# ------------------------------------------------------------- a pass
def test_a_pass_records_its_cpu_time_and_the_process_meanwhile(loop):
    snap0 = loop.snapshot()
    entry = one_pass(loop)
    snap1 = loop.snapshot()
    assert set(entry) == PASS_KEYS
    assert 0.0 < entry["cpu_ms"] <= entry["dur_ms"] * 1.05 + 0.05
    assert entry["d2h_cpu_ms"] <= entry["phases"]["decode.d2h"] * 1.05 + 0.05
    assert entry["offcpu_ms"] == pytest.approx(
        entry["dur_ms"] - entry["phases"]["decode.d2h"]
        - (entry["cpu_ms"] - entry["d2h_cpu_ms"]))
    assert entry["gc_ms"] >= 0.0 and entry["lag_ms"] >= 0.0
    if host.thread_usage() is not None:
        assert min(entry["vcsw"], entry["ivcsw"], entry["majflt"]) >= 0
    for phase in dl.CPU_PHASES:
        assert snap1["phases"][phase]["cpu_seconds"] > \
            snap0["phases"][phase]["cpu_seconds"]
    assert "cpu_seconds" not in snap1["phases"]["decode.emit"]
    assert set(snap1["host"]) == {"gc", "lag"}
    assert set(snap1["host"]["gc"]) == {"seconds", "count", "by_gen",
                                        "slowest"}
    assert snap1["host"]["lag"]["beats"] > 0
    json.dumps(snap1)  # the new keys are JSON-safe
    fam = telemetry.get_registry().counter("dl4j_decode_phase_cpu_seconds")
    assert fam.labels(loop=loop.label, phase=dl.TICK).value == \
        snap1["phases"][dl.TICK]["cpu_seconds"]


def test_the_five_readers_read_a_real_loop(loop):
    """Snapshots around a few passes, as the benchmark's window takes
    them: each new reader gives a number."""
    mon = host.start_host_monitor()
    # forget the pauses before the window: one of them may share its
    # interval and be longer
    mon.gc_slowest[:] = [None] * len(mon.gc_slowest)
    t0 = time.perf_counter()
    snap0 = loop.snapshot()
    time.sleep(0.05)  # a beat or two fall in the window
    gc.collect()
    one_pass(loop)
    loop.tick()
    ctx = {"window": (t0, time.perf_counter()), "snap0": snap0,
           "snap1": loop.snapshot()}
    got = {n: manifest.load_reader(n)(ctx) for n in NEW_READERS}
    assert None not in got.values(), got
    assert got["host_gc_share"] > 0.0 and got["host_gc_pause_max_ms"] > 0.0


def test_a_collection_inside_a_pass_shows_in_its_gc_ms(loop):
    held = [[i] for i in range(100_000)]  # something for the collector
    before = loop.snapshot()["host"]["gc"]
    entry = one_pass(loop, gc.collect)
    after = loop.snapshot()["host"]["gc"]
    assert entry["gc_gen"] == 2
    assert 0.0 < entry["gc_ms"] <= entry["dur_ms"]
    assert after["by_gen"]["2"]["count"] >= before["by_gen"]["2"]["count"] + 1
    assert after["seconds"] - before["seconds"] >= \
        entry["gc_ms"] / 1e3 - 1e-9
    reg = telemetry.get_registry()
    assert reg.gauge("dl4j_host_gc_collections").labels(
        generation="2").value == after["by_gen"]["2"]["count"]
    assert reg.gauge("dl4j_host_gc_seconds").labels(
        generation="2").value == pytest.approx(after["by_gen"]["2"]["seconds"])
    del held


def test_a_sleeping_pass_is_off_its_cpu_with_the_heartbeat_on_time(loop):
    chaos.configure([chaos.Rule("decode.step", "delay", delay_s=0.5,
                                times=1)])
    try:
        entry = one_pass(loop)
    finally:
        chaos.deactivate()
    assert entry["dur_ms"] >= 500.0
    assert entry["offcpu_ms"] >= 400.0
    assert entry["cpu_ms"] <= entry["dur_ms"] - 400.0
    # the rest of the interpreter ran: the beats came (near) on time
    assert entry["lag_ms"] < 0.4 * entry["offcpu_ms"]
    if entry["vcsw"] is not None:
        assert entry["vcsw"] >= 1  # a sleep gives the CPU up


def test_a_thread_holding_the_interpreter_shows_as_offcpu_and_lag(loop):
    """Another thread sorts a few million floats: one C call that never
    gives up the interpreter's lock. The pass waits for it off its CPU,
    and so does the heartbeat."""
    rng = random.Random(0)
    data = [rng.random() for _ in range(2_000_000)]
    took, started = {}, threading.Event()

    def hog():
        started.set()
        t0 = time.perf_counter()
        sorted(data)
        took["ms"] = 1e3 * (time.perf_counter() - t0)

    def inside():
        th = threading.Thread(target=hog)
        th.start()
        started.wait()
        th.join()

    entry = one_pass(loop, inside)
    assert took["ms"] >= 100.0
    assert entry["offcpu_ms"] >= 0.5 * took["ms"]
    assert entry["lag_ms"] >= 0.5 * took["ms"]
    assert host.start_host_monitor().snapshot()["lag"]["slowest"]


# ---------------------------------------------------------- the monitor
def test_the_monitor_is_one_a_process_with_one_heartbeat(loop):
    mon = host.start_host_monitor()
    assert host.start_host_monitor() is mon is loop._host
    assert gc.callbacks.count(mon._on_gc) == 1
    beats = [t for t in threading.enumerate()
             if t.name == "dl4j-host-heartbeat"]
    assert len(beats) == 1 and beats[0].daemon


def test_the_collector_hook_takes_no_lock(loop):
    """A collection while the registry's, a family's, a histogram's and
    the tracer's locks are held (as when a collection starts inside
    `Histogram.observe` or `Tracer.record`): the hook would deadlock on
    any of them."""
    mon = host.start_host_monitor()
    reg = telemetry.get_registry()
    hist = reg.histogram("dl4j_decode_phase_seconds").labels(
        loop=loop.label, phase=dl.TICK)
    tracer = telemetry.start_tracing()
    locks = [reg._lock, hist._lock, tracer._lock]
    for name in ("dl4j_host_gc_seconds", "dl4j_host_gc_collections",
                 "dl4j_host_lag_seconds_max"):
        fam = reg.gauge(name)
        locks += [fam._lock] + [c._lock for _, c in fam.children()]
    count, done = mon.gc_count[2], threading.Event()

    def collect():
        with contextlib.ExitStack() as held:
            for lock in locks:
                held.enter_context(lock)
            gc.collect()
        done.set()

    try:
        threading.Thread(target=collect, daemon=True).start()
        assert done.wait(60.0), "the collector's hook waited on a lock"
    finally:
        telemetry.stop_tracing()
    assert mon.gc_count[2] == count + 1


def test_a_profiler_window_holds_the_collector_s_pauses(tmp_path):
    from benchmark import trace_reduce

    host.start_host_monitor()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            gc.collect(0)
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    (found,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                      "*", "*.xplane.pb"))
    trace = trace_reduce.load_xplane(found)
    start, end = trace_reduce.find_window(trace)
    names = {n for n, _s, _e in trace_reduce.host_spans(trace, start, end)}
    assert {"host.gc.gen0", "host.gc.gen2"} <= names


# ------------------------------------------------------------- the cost
def test_what_a_pass_adds_costs_under_twenty_microseconds():
    """Per pass: the collector's total and the thread's usage at the
    start, and the CPU clock at both ends of `decode.tick` and of
    `decode.d2h` with its counter. The same two spans without them are
    the base. Generous, so that a noisy box cannot fail it while a lock
    or a scan of the heartbeat's beats on this path would."""
    mon = host.start_host_monitor()

    def totals(**cpu):
        reg = telemetry.MetricsRegistry()
        return PhaseTotals(reg.histogram("phase_seconds"), ["t", "d"],
                           **cpu, loop="cost")

    plain = totals()
    timed = totals(cpu_family=telemetry.MetricsRegistry().counter("cpu"),
                   cpu_names=["t", "d"])

    def run(phases, marks, n=1000):
        t0 = time.perf_counter()
        for _ in range(n):
            if marks:
                gc_ns, usage = mon.gc_ns, host.thread_usage()
            with span("t", phases):
                with span("d", phases):
                    pass
        return (time.perf_counter() - t0) / n

    added = (min(run(timed, True) for _ in range(5))
             - min(run(plain, False) for _ in range(5)))
    assert added < 20e-6
    assert timed.totals()["t"]["cpu_seconds"] > 0.0
    assert "cpu_seconds" not in plain.totals()["t"]
