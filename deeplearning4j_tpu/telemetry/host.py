"""The host process as a whole: the collector's pauses and an interpreter
heartbeat, always on, one monitor a process.

A scheduler pass clocks its own wall and CPU time (`trace.span`); what it
cannot see is what the rest of the process did meanwhile. This module
records two things that stop every thread of the interpreter at once:

- **The collector.** A `gc.callbacks` hook stamps `perf_counter_ns` at a
  collection's start and stop and adds the pause to plain integers by
  generation; it keeps the longest pause of each interval
  (`trace.slowest_slot`) with its start and generation, and enters and
  leaves a `jax.profiler.TraceAnnotation` `host.gc.gen0` .. `gen2`, so
  that a device trace puts an idle gap down to a collection. The hook
  takes NO lock: a collection can start inside any code that holds one
  (a histogram's observation, the tracer's record) and would deadlock on
  it. The registry reads the integers at scrape time instead
  (`dl4j_host_gc_seconds{generation}`,
  `dl4j_host_gc_collections{generation}`).
- **The heartbeat.** A daemon thread `dl4j-host-heartbeat` sleeps
  `HEARTBEAT_S` at a time and records how late each wake came, the
  longest of each interval with the time it was due
  (`dl4j_host_lag_seconds_max` over them). A late beat means the whole
  interpreter did not run: its lock held by a call that never released
  it, a collection, or a process the OS did not schedule. A beat on time
  while a pass was off its CPU means that pass alone was blocked.

`during()` says what happened inside one pass: the collector's
milliseconds and highest generation, the heartbeat's worst delay, and
the thread's context switches and major faults (`thread_usage()`, where
Linux gives `RUSAGE_THREAD`; None elsewhere).
"""

from __future__ import annotations

import gc
import threading
import time
from typing import List, Optional

from deeplearning4j_tpu.telemetry.registry import get_registry
from deeplearning4j_tpu.telemetry.trace import (SLOWEST_KEPT,
                                                _trace_annotation, slowest,
                                                slowest_slot)

try:
    import resource
    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):  # pragma: no cover - not Linux
    resource, _RUSAGE_THREAD = None, None

__all__ = ["HostMonitor", "start_host_monitor", "thread_usage",
           "GC_SPANS", "HEARTBEAT_S"]

#: the collector's TraceMe names, by generation
GC_SPANS = ("host.gc.gen0", "host.gc.gen1", "host.gc.gen2")
#: the heartbeat's sleep
HEARTBEAT_S = 0.02
#: beats kept for `during()`: ~20 s of them on time
BEATS_KEPT = 1024


def thread_usage():
    """The calling thread's `resource.getrusage` record, or None where
    the platform has no per-thread usage."""
    if _RUSAGE_THREAD is None:
        return None
    return resource.getrusage(_RUSAGE_THREAD)


class HostMonitor:
    """The collector's pauses and the heartbeat's delays of one process.
    Plain integers and lists, written by the collector's callback and the
    heartbeat thread without a lock and read by any thread."""

    def __init__(self):
        #: nanoseconds of pause, all generations: a pass reads it at
        #: both ends
        self.gc_ns = 0
        self.gc_ns_by_gen = [0, 0, 0]
        self.gc_count = [0, 0, 0]
        #: when each generation's last collection ended
        self.gc_stop_ns = [0, 0, 0]
        self.gc_slowest: List[Optional[dict]] = [None] * SLOWEST_KEPT
        self.lag_slowest: List[Optional[dict]] = [None] * SLOWEST_KEPT
        #: (due, woke) nanoseconds of the last BEATS_KEPT beats
        self._beats: List[Optional[tuple]] = [None] * BEATS_KEPT
        self.beats = 0
        #: when the beat now asleep is due
        self._due_ns: Optional[int] = None
        self._gc_t0 = 0
        self._gc_ann = None
        self._annotation = None
        self._thread: Optional[threading.Thread] = None

    # ----------------------------------------------------------- start
    def start(self) -> None:
        self._annotation = _trace_annotation()
        gc.callbacks.append(self._on_gc)
        self._register()
        self._thread = threading.Thread(target=self._beat, daemon=True,
                                        name="dl4j-host-heartbeat")
        self._thread.start()

    def _register(self) -> None:
        reg = get_registry()
        secs = reg.gauge("dl4j_host_gc_seconds",
                         "seconds the collector held the interpreter, by "
                         "generation, since the monitor started")
        count = reg.gauge("dl4j_host_gc_collections",
                          "collections by generation since the monitor "
                          "started")
        for g in range(3):
            secs.labels(generation=str(g)).set_function(
                lambda g=g: self.gc_ns_by_gen[g] * 1e-9)
            count.labels(generation=str(g)).set_function(
                lambda g=g: self.gc_count[g])
        reg.gauge("dl4j_host_lag_seconds_max",
                  "the heartbeat's longest delay over the intervals "
                  "kept (about the last minute): how long the whole "
                  "interpreter did not run").set_function(
                      lambda: max((e["dur_ms"] for e in slowest(
                          self.lag_slowest)), default=0.0) * 1e-3)

    # -------------------------------------------------------- collector
    def _on_gc(self, phase: str, info: dict) -> None:
        """`gc.callbacks` hook. No lock, no raise."""
        if phase == "start":
            ann = self._gc_ann = self._annotation(
                GC_SPANS[info["generation"]])
            ann.__enter__()
            self._gc_t0 = time.perf_counter_ns()
            return
        t0, ann = self._gc_t0, self._gc_ann
        now = time.perf_counter_ns()
        self._gc_t0, self._gc_ann = 0, None
        if ann is not None:
            ann.__exit__(None, None, None)
        if not t0:
            return  # its start came before the hook
        gen, pause = info["generation"], now - t0
        self.gc_ns_by_gen[gen] += pause
        self.gc_count[gen] += 1
        self.gc_stop_ns[gen] = now
        self.gc_ns += pause
        start_s, dur_ms = t0 / 1e9, pause / 1e6
        at = slowest_slot(self.gc_slowest, start_s, dur_ms)
        if at is not None:
            self.gc_slowest[at] = {"start_s": start_s, "dur_ms": dur_ms,
                                   "generation": gen}

    # -------------------------------------------------------- heartbeat
    def _beat(self) -> None:
        period = int(HEARTBEAT_S * 1e9)
        beats = self._beats
        while True:
            due = self._due_ns = time.perf_counter_ns() + period
            time.sleep(HEARTBEAT_S)
            woke = time.perf_counter_ns()
            beats[self.beats % BEATS_KEPT] = (due, woke)
            self.beats += 1
            start_s, dur_ms = due / 1e9, max(0, woke - due) / 1e6
            at = slowest_slot(self.lag_slowest, start_s, dur_ms)
            if at is not None:
                self.lag_slowest[at] = {"start_s": start_s,
                                        "dur_ms": dur_ms}

    def lag_ns(self, start_ns: int, end_ns: int) -> int:
        """The longest stretch of [start_ns, end_ns] during which a beat
        was due and not yet taken, the beat asleep now included."""
        now = time.perf_counter_ns()
        spans = [b for b in list(self._beats) if b is not None]
        due = self._due_ns
        if due is not None:
            spans.append((due, now))
        worst = 0
        for due, woke in spans:
            worst = max(worst, min(woke, end_ns) - max(due, start_ns))
        return worst

    # ------------------------------------------------------------ read
    def during(self, start_ns: int, end_ns: int,
               gc_ns: Optional[int] = None, usage=None) -> dict:
        """What the process did inside [start_ns, end_ns], given
        `gc_ns` and `thread_usage()` as read at its start (None: not
        read): `gc_ms` of collector pauses, `gc_gen` the highest
        generation collected (None: none), `lag_ms` the heartbeat's
        worst delay, and the calling thread's voluntary and involuntary
        context switches and major faults (None where not read)."""
        gens = [g for g in range(3) if self.gc_stop_ns[g] >= start_ns]
        now = thread_usage() if usage is not None else None
        return {
            "gc_ms": None if gc_ns is None else (self.gc_ns - gc_ns) / 1e6,
            "gc_gen": max(gens) if gens else None,
            "lag_ms": self.lag_ns(start_ns, end_ns) / 1e6,
            "vcsw": None if now is None else now.ru_nvcsw - usage.ru_nvcsw,
            "ivcsw": (None if now is None
                      else now.ru_nivcsw - usage.ru_nivcsw),
            "majflt": (None if now is None
                       else now.ru_majflt - usage.ru_majflt),
        }

    def snapshot(self) -> dict:
        return {
            "gc": {"seconds": self.gc_ns / 1e9,
                   "count": sum(self.gc_count),
                   "by_gen": {str(g): {"seconds": self.gc_ns_by_gen[g] / 1e9,
                                       "count": self.gc_count[g]}
                              for g in range(3)},
                   "slowest": slowest(self.gc_slowest)},
            "lag": {"slowest": slowest(self.lag_slowest),
                    "beats": self.beats,
                    "period_ms": HEARTBEAT_S * 1e3},
        }


_monitor: Optional[HostMonitor] = None
_start_lock = threading.Lock()


def start_host_monitor() -> HostMonitor:
    """The process's monitor, started on the first call."""
    global _monitor
    with _start_lock:
        if _monitor is None:
            monitor = HostMonitor()
            monitor.start()
            _monitor = monitor
        return _monitor
