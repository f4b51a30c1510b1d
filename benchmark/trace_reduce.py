"""From a profiler trace to numbers: the benchmark's own reduction.

A trace is read into a neutral form,

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

by `load_xplane` (an `.xplane.pb`, through `jax.profiler.ProfileData`)
or `load_json` (the recorded and the hand-made traces under
`benchmark/testdata/`), and `reduce` works on that form alone:

- the window is the host span named `WINDOW` that the harness holds open
  while it traces; device events are clipped to it;
- busy is the union of the intervals in which an operation ran on a
  device (its "XLA Ops" line), averaged over the device planes; idle
  share is 1 - busy / window;
- kernel and program time is summed per name (`op_name`);
- each idle gap of 50 us or more is put down to the innermost host span
  that covers its middle; shorter ones are summed under one name.
"""

from __future__ import annotations

import json
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SHORT_GAP_NS = 50_000
SHORT_GAPS = "gaps_under_50_us_between_ops"
UNATTRIBUTED = "unattributed:no_host_span_covers_it"


# ------------------------------------------------------------- loading
def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") \
        and "CUSTOM" not in plane_name.upper()


# ------------------------------------------------------------ intervals
def union_ns(intervals: List[Tuple[int, int]]) -> int:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: List[Tuple[int, int]], start: int, end: int
            ) -> List[Tuple[int, int]]:
    """The idle stretches of [start, end) that no interval covers."""
    out, at = [], start
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, end)))
        at = max(at, e)
        if at >= end:
            break
    if at < end:
        out.append((at, end))
    return [(s, e) for s, e in out if e > s]


def clip(events, start: int, end: int) -> List[Tuple[str, int, int]]:
    """(name, start, end) of the events that overlap the window, cut to
    it."""
    out = []
    for name, s, d in events:
        e = s + d
        if e <= start or s >= end:
            continue
        out.append((name, max(s, start), min(e, end)))
    return out


_SERIAL = re.compile(r"[.:](\d+)$")
_HLO = re.compile(r"^%?([\w.\-]+) = \(*(\w+)\[([\d,]*)\]")


def op_name(raw: str) -> str:
    """A stable name for an operation. The device's events carry the
    HLO's text (`%copy.12 = bf16[2561,16,16,128]{...} copy(...)`): keep
    the target without the compiler's serial number, and for anything
    but a custom call (a kernel, which has a name of its own) the type
    and shape of its first result: `copy_bf16_2561_16_16_128_`,
    `paged_decode_attention`. Any other name loses only a serial."""
    raw = raw.strip()
    m = _HLO.match(raw)
    if not m:
        return _SERIAL.sub("", raw.lstrip("%"))
    base = _SERIAL.sub("", m.group(1))
    if " custom-call(" in raw:
        return base
    dims = m.group(3).replace(",", "_")
    return f"{base}_{m.group(2)}_{dims}_"


# ------------------------------------------------------------- reduction
def find_window(trace: dict, marker: str = WINDOW
                ) -> Optional[Tuple[int, int]]:
    for plane in trace["planes"]:
        if is_device(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name == marker:
                    return s, s + d
    return None


def host_spans(trace: dict, start: int, end: int, marker: str = WINDOW):
    spans = []
    for plane in trace["planes"]:
        if is_device(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, s, e in clip(line["events"], start, end):
                if name != marker:
                    spans.append((name, s, e))
    return spans


def attribute(gaps, spans) -> Dict[str, int]:
    """Idle nanoseconds by what the host was doing."""
    out: Dict[str, int] = defaultdict(int)
    for s, e in gaps:
        if e - s < SHORT_GAP_NS:
            out[SHORT_GAPS] += e - s
            continue
        mid = (s + e) // 2
        best = None
        for name, hs, he in spans:
            if hs <= mid < he and (best is None or he - hs < best[1]):
                best = (name, he - hs)
        out[op_name(best[0]) if best else UNATTRIBUTED] += e - s
    return dict(out)


def reduce(trace: dict, marker: str = WINDOW) -> Optional[dict]:
    """The numbers of one traced window, or None where the trace holds
    no window or no device plane."""
    window = find_window(trace, marker)
    devices = [p for p in trace["planes"] if is_device(p["name"])]
    if window is None or not devices:
        return None
    start, end = window
    busy, op_ns, op_n = [], defaultdict(int), defaultdict(int)
    mod_ns, mod_n = defaultdict(int), defaultdict(int)
    gap_ns: Dict[str, int] = defaultdict(int)
    spans = host_spans(trace, start, end, marker)
    for plane in devices:
        ops = []
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                ops += clip(line["events"], start, end)
            elif line["name"] == MODULES_LINE:
                for name, s, e in clip(line["events"], start, end):
                    mod_ns[op_name(name)] += e - s
                    mod_n[op_name(name)] += 1
        for name, s, e in ops:
            op_ns[op_name(name)] += e - s
            op_n[op_name(name)] += 1
        iv = [(s, e) for _n, s, e in ops]
        busy.append(union_ns(iv))
        for k, v in attribute(gaps_ns(iv, start, end), spans).items():
            gap_ns[k] += v
    n = len(devices)

    def top(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"window_s": (end - start) / 1e9,
            "busy_s": sum(busy) / n / 1e9,
            "devices": n,
            "op_s": {k: v / n / 1e9 for k, v in op_ns.items()},
            "op_n": {k: v / n for k, v in op_n.items()},
            "module_s": {k: v / n / 1e9 for k, v in mod_ns.items()},
            "module_n": {k: v / n for k, v in mod_n.items()},
            "device_ops": top(op_ns),
            "idle_gaps": top(gap_ns)}


def matching(table: Dict[str, float], needle: str) -> float:
    """Sum of the entries whose name contains `needle`."""
    return sum(v for k, v in table.items() if needle in k)
