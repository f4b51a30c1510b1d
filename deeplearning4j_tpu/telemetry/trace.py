"""Span-based tracing: one primitive, three places a span can land.

`span("train_step")` wraps a host-side region and clocks it on
`time.perf_counter_ns` (monotonic, the clock `time.perf_counter` reads).
What a closed span leaves behind depends on what is listening:

- **A `SpanRecord`**, while `start_tracing()` is active (the CLI's
  `--trace PATH`): name, start, duration, thread, an identifier and the
  identifier of the span that caused it (the enclosing span on the
  thread, or `parent_id=` for work caused from another thread), plus the
  keyword arguments, `request=` among them, so that the spans of one
  request can be joined. Records sit in a bounded in-memory buffer and
  are written out at the end as Chrome trace JSON (`chrome://tracing`,
  Perfetto), nesting rebuilt by the viewer from containment per `tid`.
- **The profiler's own trace.** A span that runs also enters a
  `jax.profiler.TraceAnnotation` of the same name. A TraceMe does nothing
  while no profiler session is live; inside a `jax.profiler` window,
  whoever opened it (`ProfilerListener`, the benchmark's traced run), the
  program's spans sit on the device trace's own clock beside PjRt's, and
  an idle gap of the device can be put down to the phase that covers it.
- **Phase totals**, for a span given `phases=`: seconds and count by
  span name, always on. These are counters like any other of the
  registry (`PhaseTotals` keeps them in one histogram family), so
  `/metrics` has them with no tracer and no profiler. For the names the
  totals call `cpu_names`, the span also reads the thread's own CPU
  clock (`time.thread_time_ns`) at both ends: wall time less CPU time is
  the time the thread did not run, waiting or not scheduled.

`slowest_slot` keeps "the longest of each interval" in a ring of a few
slots: the longest scheduler pass, collector pause or heartbeat delay of
each of the last minute's intervals, however long start-up's were.

A span with no `phases=` runs only while tracing is on: until then it
is an object and two attribute checks, cheap enough for the fit and
checkpoint loops. A span with `phases=` always runs: two clock reads, one
histogram observation and one TraceMe.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, NamedTuple, Optional

__all__ = [
    "SpanRecord", "Tracer", "PhaseTotals", "span", "start_tracing",
    "stop_tracing", "active_tracer", "chrome_trace", "save_chrome_trace",
    "slowest_slot", "slowest", "SLOWEST_KEPT", "SLOWEST_INTERVAL_S",
]

#: a ring of "the longest of each interval" holds SLOWEST_KEPT intervals
#: of SLOWEST_INTERVAL_S seconds: about the last minute
SLOWEST_KEPT = 8
SLOWEST_INTERVAL_S = 8


class SpanRecord(NamedTuple):
    """One closed span. Times are perf_counter nanoseconds; `parent_id`
    is None for a root."""

    name: str
    start_ns: int
    dur_ns: int
    thread_id: int
    args: dict
    span_id: int
    parent_id: Optional[int]


class Tracer:
    """Bounded span buffer + per-thread nesting state."""

    def __init__(self, max_spans: int = 100_000):
        self.max_spans = int(max_spans)
        self._spans = deque(maxlen=self.max_spans)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._ids = itertools.count(1)

    # ------------------------------------------------------------ record
    def _stack(self) -> List[int]:
        """Identifiers of the spans open on this thread, outermost
        first."""
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def record(self, rec: SpanRecord) -> None:
        with self._lock:
            self._spans.append(rec)

    def add(self, name: str, start_ns: int, end_ns: int,
            parent_id: Optional[int] = None,
            thread_id: Optional[int] = None, **args) -> int:
        """Record a span whose two ends were stamped elsewhere (the
        stages of a request's life, known only when it finishes).
        Returns its identifier, for its children's `parent_id`."""
        span_id = next(self._ids)
        self.record(SpanRecord(
            name, int(start_ns), max(0, int(end_ns) - int(start_ns)),
            threading.get_ident() if thread_id is None else thread_id,
            args, span_id, parent_id))
        return span_id

    def spans(self) -> List[SpanRecord]:
        with self._lock:
            return list(self._spans)

    # ------------------------------------------------------------ export
    def chrome_trace(self) -> dict:
        """Chrome trace format dict: "X" (complete) events, microsecond
        timestamps. Nesting is reconstructed by the viewer from
        timestamp containment per tid; `span_id` and `parent_id` ride in
        args for programmatic consumers."""
        pid = os.getpid()
        events = []
        for s in self.spans():
            args = dict(s.args)
            args.update(span_id=s.span_id, parent_id=s.parent_id)
            events.append({
                "name": s.name,
                "ph": "X",
                "ts": s.start_ns / 1e3,
                "dur": s.dur_ns / 1e3,
                "pid": pid,
                "tid": s.thread_id,
                "args": args,
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


class PhaseTotals:
    """Seconds and count of the spans one owner closes, by span name.

    The totals live in one histogram family of the registry (label
    `phase`, plus the owner's labels), so a scrape has them; `pass_ns`
    holds the nanoseconds since the owner last called `begin_pass()`,
    which is how a scheduler says what one of its passes was made of.
    The spans named in `cpu_names` also count the thread's CPU seconds,
    into the counter family `cpu_family` and `pass_cpu_ns`.
    One thread closes an owner's spans; any thread may read."""

    def __init__(self, family, names: Iterable[str], cpu_family=None,
                 cpu_names: Iterable[str] = (), **labels):
        self._cells = {n: family.labels(phase=n, **labels) for n in names}
        self.pass_ns: Dict[str, int] = dict.fromkeys(self._cells, 0)
        self.cpu = frozenset(cpu_names)
        self._cpu_cells = {n: cpu_family.labels(phase=n, **labels)
                           for n in self.cpu}
        self.pass_cpu_ns: Dict[str, int] = dict.fromkeys(self.cpu, 0)

    def begin_pass(self) -> None:
        for ns in (self.pass_ns, self.pass_cpu_ns):
            for name in ns:
                ns[name] = 0

    def add(self, name: str, dur_ns: int,
            cpu_ns: Optional[int] = None) -> None:
        self._cells[name].observe(dur_ns * 1e-9)
        self.pass_ns[name] += dur_ns
        if cpu_ns is not None:
            self._cpu_cells[name].inc(cpu_ns * 1e-9)
            self.pass_cpu_ns[name] += cpu_ns

    def totals(self) -> Dict[str, dict]:
        out = {n: {"seconds": c.sum, "count": c.count}
               for n, c in self._cells.items()}
        for n, c in self._cpu_cells.items():
            out[n]["cpu_seconds"] = c.value
        return out


def slowest_slot(ring: list, start_s: float, dur_ms: float,
                 interval_s: float = SLOWEST_INTERVAL_S) -> Optional[int]:
    """The slot of `ring` in which to keep an event that started at
    `start_s` (seconds) and lasted `dur_ms`, or None where the slot
    already holds a longer event of the same interval. Slot k holds
    interval number k mod len(ring), so an interval that comes round
    replaces the one it laps. Entries are dicts with `start_s` and
    `dur_ms`. Takes no lock and allocates nothing: the collector's
    callback calls it."""
    interval = int(start_s // interval_s)
    at = interval % len(ring)
    kept = ring[at]
    if (kept is not None and kept["dur_ms"] >= dur_ms
            and int(kept["start_s"] // interval_s) == interval):
        return None
    return at


def slowest(ring: list) -> List[dict]:
    """The entries a ring holds, oldest first."""
    return sorted((e for e in ring if e is not None),
                  key=lambda e: e["start_s"])


_active: Optional[Tracer] = None
_annotation = None


def _trace_annotation():
    """`jax.profiler.TraceAnnotation`, imported at a span's first run
    and not with this module: telemetry is imported by processes that
    never start a back end."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


def start_tracing(max_spans: int = 100_000) -> Tracer:
    """Install (and return) the process tracer. Idempotent-ish: a second
    call replaces the tracer (fresh buffer)."""
    global _active
    _active = Tracer(max_spans=max_spans)
    return _active


def stop_tracing() -> Optional[Tracer]:
    """Stop recording; returns the tracer (buffer intact) for export."""
    global _active
    t, _active = _active, None
    return t


def active_tracer() -> Optional[Tracer]:
    return _active


class span:  # noqa: N801 - a context manager called like a function
    """Time a host-side region: `with span("stage", epoch=i): ...`.

    `phases=` names the `PhaseTotals` that count this span whether or
    not anybody traces; `parent_id=` names the span that caused this one
    where that is not the enclosing span of the thread. `args` may be
    added to until the span closes (`with span(...) as s: s.args["n"] =
    n`); `start_ns` and `dur_ns` can be read once it has, and `cpu_ns`,
    the thread's CPU time inside the span, where `phases` names it among
    its `cpu_names` (None otherwise)."""

    __slots__ = ("name", "args", "phases", "parent_id", "span_id",
                 "start_ns", "dur_ns", "cpu_ns", "_tracer", "_stack",
                 "_ann")

    def __init__(self, name: str, phases: Optional[PhaseTotals] = None,
                 parent_id: Optional[int] = None, **args):
        self.name = name
        self.args = args
        self.phases = phases
        self.parent_id = parent_id
        self.span_id = None
        self.start_ns = self.dur_ns = 0
        self.cpu_ns = None
        self._tracer = None
        self._ann = None

    def __enter__(self) -> "span":
        tracer = self._tracer = _active
        if tracer is None and self.phases is None:
            return self
        if tracer is not None:
            stack = self._stack = tracer._stack()
            if self.parent_id is None and stack:
                self.parent_id = stack[-1]
            self.span_id = next(tracer._ids)
            stack.append(self.span_id)
        ann = self._ann = _trace_annotation()(self.name)
        ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        # the CPU clock is read inside the wall clock's two reads
        if self.phases is not None and self.name in self.phases.cpu:
            self.cpu_ns = time.thread_time_ns()
        return self

    def __exit__(self, *exc) -> None:
        ann = self._ann
        if ann is None:
            return
        if self.cpu_ns is not None:
            self.cpu_ns = time.thread_time_ns() - self.cpu_ns
        self.dur_ns = time.perf_counter_ns() - self.start_ns
        ann.__exit__(*exc)
        if self.phases is not None:
            self.phases.add(self.name, self.dur_ns, self.cpu_ns)
        tracer = self._tracer
        if tracer is not None:
            self._stack.pop()
            tracer.record(SpanRecord(
                self.name, self.start_ns, self.dur_ns,
                threading.get_ident(), self.args, self.span_id,
                self.parent_id))


def chrome_trace() -> dict:
    """Chrome trace of the active tracer ({} when tracing is off)."""
    return _active.chrome_trace() if _active else {"traceEvents": []}


def save_chrome_trace(path: str) -> Optional[str]:
    """Write the active tracer's Chrome trace; None when tracing is
    off."""
    return _active.save(path) if _active else None
