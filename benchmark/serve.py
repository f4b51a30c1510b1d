"""The serving drivers: a closed loop of clients and an open loop of
arrivals, both through `InferenceEngine.generate_stream`.

One process, no HTTP server and no child: the window drives
`generate_stream` -> `DecodeLoop.submit` -> paged cache -> flash prefill
-> paged decode kernel. Set-up makes the weights on the device from the
seed, has the cell's family build the engine, and executes once every
prefill group the cell's schedule can reach (the family's
`warm_requests`), so that nothing is compiled or loaded inside the
window; `programs_in_window` counts what was all the same.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import List, Optional

import numpy as np

from benchmark import schedule, weights
from benchmark.manifest import Cell

#: a client gives up on a token after this long: the request has failed
TOKEN_TIMEOUT_S = 120.0
#: after the close, wait this long for answers that are due
LATE_S = 60.0
PROMPT_STREAM, WARM_STREAM = 0, 2


# ------------------------------------------------------------- compiles
class ProgramCounter:
    """Programs that JAX compiled or loaded from its persistent cache,
    counted from JAX's own monitoring events: whatever reaches the
    compiler, the program's small eager scatters included."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.count += 1

    def _on_duration(self, name: str, _secs: float, **_kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.count += 1


def seconds_in_dispatch(loop) -> float:
    """Sum of the scheduler's own histogram of one dispatch, device
    round trip included (`dl4j_decode_step_seconds`). Its growth over
    the window says whether a stall sat inside a dispatch or on the host
    between two."""
    from deeplearning4j_tpu import telemetry

    return telemetry.get_registry().histogram(
        "dl4j_decode_step_seconds").labels(loop=loop.label).sum


def loop_programs(loop) -> int:
    """The scheduler's own program counts, from the program."""
    from deeplearning4j_tpu.utils import jaxenv

    frag = loop.plan_fragment()
    return (max(loop.prefill_programs(), 0)
            + max(loop.decode_step_programs(), 0)
            + len(frag["prefill"]) + len(frag["prefill_ctx"])
            + jaxenv.compile_cache_entries())


# ---------------------------------------------------------------- set-up
def build_engine(cell: Cell, seed: int):
    """Weights on the device from the seed, then the engine the cell's
    configuration states, as its family builds it."""
    params = weights.make_params(seed, cell.family, cell.config)
    return cell.family.build_engine(cell.config, params), params


def warm(engine, cell: Cell, seed: int, seconds: float) -> int:
    """Execute once, on throw-away requests, every program the schedule
    can reach: the family's groups of requests, one after the other.
    The requests stay out of the prefix cache. Returns the count of
    groups."""
    vocab = cell.family.sizes(cell.config)["vocab_size"]
    todo = cell.family.warm_requests(cell.config, cell.traffic, seconds)
    for i, (n, plen) in enumerate(todo):
        prompts = [weights.token_ids(seed, WARM_STREAM, i * 64 + r, plen,
                                     vocab)
                   for r in range(n)]
        for s in engine.decode_loop.submit_many(prompts, 2,
                                                prefix_cache=False):
            s.result(timeout=TOKEN_TIMEOUT_S * 5)
    return len(todo)


# ---------------------------------------------------------------- window
def _record(req: schedule.Request, due: float) -> dict:
    return {"index": req.index, "client": req.client, "due": due,
            "sent": None, "first": None, "times": [], "tokens": [],
            "prompt_len": req.prompt_len, "max_tokens": req.output_len,
            "finish": None, "failed": None, "prompt": None,
            "stream": None}


def _consume(rec: dict, stream) -> None:
    """Stamp every token as the client gets it."""
    try:
        for tok in stream.tokens(timeout=TOKEN_TIMEOUT_S):
            rec["times"].append(time.perf_counter())
            rec["tokens"].append(int(tok))
        rec["finish"] = stream.finish_reason
    except Exception as e:  # noqa: BLE001 - a failed request is data
        rec["failed"] = repr(e)
        rec["finish"] = "error"
    if rec["times"]:
        rec["first"] = rec["times"][0]


def _send(engine, rec: dict) -> None:
    import jax

    with jax.profiler.TraceAnnotation("bench.generate_stream"):
        rec["sent"] = time.perf_counter()
        try:
            rec["stream"] = engine.generate_stream(rec["prompt"],
                                                   rec["max_tokens"])
        except Exception as e:  # noqa: BLE001 - refused counts as failed
            rec["failed"] = repr(e)
            rec["finish"] = "refused"


def _prompt(cell: Cell, seed: int, req: schedule.Request) -> np.ndarray:
    return weights.token_ids(seed, PROMPT_STREAM, req.index,
                             req.prompt_len,
                             cell.family.sizes(cell.config)["vocab_size"])


class Traffic:
    """The load of one run: threads that send and stamp, and their
    records. `start()` returns the loop's zero on the host clock."""

    def __init__(self, engine, cell: Cell, seed: int, seconds: float):
        self.engine, self.cell, self.seed = engine, cell, seed
        self.seconds = seconds
        self.records: List[dict] = []
        self.stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._lock = threading.Lock()
        self.t0: Optional[float] = None

    def start(self) -> float:
        t = self.cell.traffic
        if t["driver"] == "serve_closed":
            self.t0 = time.perf_counter()
            for row in schedule.closed_loop(t):
                self._spawn(self._client, row)
        else:
            reqs = schedule.open_loop(t, self.seconds)
            prompts = [_prompt(self.cell, self.seed, r) for r in reqs]
            self.t0 = time.perf_counter()
            recs = [_record(r, self.t0 + r.offset_s) for r in reqs]
            for rec, prompt in zip(recs, prompts):
                rec["prompt"] = prompt
            self.records = recs
            self._spawn(self._arrivals, recs)
        return self.t0

    def _spawn(self, fn, *args) -> None:
        th = threading.Thread(target=fn, args=args, daemon=True)
        self._threads.append(th)
        th.start()

    def _client(self, row) -> None:
        """A closed-loop client: its next request when the last ends."""
        for req in row:
            if self.stop.is_set():
                return
            rec = _record(req, time.perf_counter())
            rec["prompt"] = _prompt(self.cell, self.seed, req)
            with self._lock:
                self.records.append(rec)
            _send(self.engine, rec)
            if rec["stream"] is None:
                return
            _consume(rec, rec["stream"])

    def _arrivals(self, recs) -> None:
        """The open loop: each request at its due time, late or not."""
        for rec in recs:
            wait = rec["due"] - time.perf_counter()
            if wait > 0 and self.stop.wait(wait):
                return
            if self.stop.is_set():
                return
            _send(self.engine, rec)
            if rec["stream"] is not None:
                self._spawn(_consume, rec, rec["stream"])

    def finish(self, start: float, end: float) -> None:
        """After the close: wait for the answers due in the window (one
        that comes late is late, not wrong), then cancel the rest."""
        deadline = time.perf_counter() + LATE_S
        if self.cell.traffic["driver"] == "serve_open":
            for rec in list(self.records):
                if not start <= rec["due"] < end:
                    continue
                while (rec["finish"] is None and rec["sent"] is not None
                       and time.perf_counter() < deadline):
                    time.sleep(0.05)
        self.stop.set()
        with self._lock:
            recs = list(self.records)
        for rec in recs:
            if rec["stream"] is not None and rec["finish"] is None:
                rec["stream"].cancel()
        for th in list(self._threads):
            th.join(timeout=TOKEN_TIMEOUT_S)
        with self._lock:
            self.records = [r for r in self.records
                            if r["sent"] is not None]


def sleep_until(t: float) -> None:
    wait = t - time.perf_counter()
    if wait > 0:
        time.sleep(wait)


def run_window(engine, cell: Cell, seed: int, seconds: float,
               programs: ProgramCounter, tracer=None,
               on_start=lambda: None) -> dict:
    """The fixed warm-up of the same loop, then the window. Returns the
    records and the program's counts at both ends."""
    loop = engine.decode_loop
    traffic = Traffic(engine, cell, seed, seconds)
    gc.collect()
    gc.freeze()
    t0 = traffic.start()
    start = t0 + float(cell.traffic["warmup_s"])
    end = start + seconds
    sleep_until(start)
    on_start()
    snap0, count0, progs0 = loop.snapshot(), programs.count, \
        loop_programs(loop)
    in_dispatch0 = seconds_in_dispatch(loop)
    trace = None
    if tracer is not None:
        spec = cell.traffic["trace"]
        sleep_until(start + min(float(spec["start_s"]), seconds / 4))
        trace = tracer.record(
            min(float(spec["seconds"]), seconds / 2), loop.snapshot)
    sleep_until(end)
    snap1, count1, progs1 = loop.snapshot(), programs.count, \
        loop_programs(loop)
    in_dispatch1 = seconds_in_dispatch(loop)
    traffic.finish(start, end)
    gc.unfreeze()
    gaps = [1e3 * (b - a) for r in traffic.records
            for a, b in zip(r["times"], r["times"][1:]) if start <= b < end]
    ttft = [1e3 * (r["first"] - r["due"]) for r in traffic.records
            if start <= r["due"] < end and r["first"] is not None]
    return {"window": (start, end), "requests": traffic.records,
            "snap0": snap0, "snap1": snap1, "trace": trace,
            "programs_in_window": max(count1 - count0, progs1 - progs0),
            "diagnosis": {
                "jax_programs_in_window": count1 - count0,
                "loop_programs_in_window": progs1 - progs0,
                "dispatches": snap1["dispatches"] - snap0["dispatches"],
                "tokens_streamed": (snap1["tokens_streamed"]
                                    - snap0["tokens_streamed"]),
                "prefill_tokens": (snap1["prefill_tokens"]
                                   - snap0["prefill_tokens"]),
                "requests_admitted": (snap1["requests"]
                                      - snap0["requests"]),
                "prefill_groups": sorted(
                    tuple(g) for g in loop.plan_fragment()["prefill"]),
                "tokens_in_window": sum(
                    1 for r in traffic.records for t in r["times"]
                    if start <= t < end),
                "prefill_group_sizes_in_window": _group_sizes(
                    traffic.records, start, end),
                "itl_top5_ms": [round(g, 2) for g in
                                sorted(gaps, reverse=True)[:5]],
                "itl_ladder_ms": _ladder(
                    gaps, (50, 90, 95, 96, 97, 98, 99, 99.5, 99.9)),
                "ttft_ladder_ms": _ladder(ttft, (50, 75, 90, 95, 99)),
                "in_dispatch_s": round(in_dispatch1 - in_dispatch0, 4),
                "queued_at_start": snap0["queued"],
                "queued_at_end": snap1["queued"],
                "ttft_p50_ms_by_half": _ttft_by_half(
                    traffic.records, start, end),
            }}


def _ladder(values, qs) -> dict:
    from benchmark import stats

    return {str(q): round(stats.percentile(values, q), 3)
            for q in qs if values}


def _group_sizes(records, start: float, end: float) -> dict:
    """How many requests each prefill group of the window held, read
    from the outside: first tokens that reach their clients within a
    millisecond of each other came from one flush of one pass."""
    firsts = sorted(r["first"] for r in records
                    if r["first"] is not None and start <= r["first"] < end)
    sizes: dict = {}
    run, last = 0, None
    for t in firsts + [float("inf")]:
        if last is not None and t - last > 1e-3:
            sizes[run] = sizes.get(run, 0) + 1
            run = 0
        run, last = run + 1, t
    return {str(k): v for k, v in sorted(sizes.items())}


def _ttft_by_half(records, start: float, end: float) -> list:
    """Median time to first token of the requests due in each half of
    the window: a queue that grows shows as a second half worse than the
    first (what the sweep for the knee reads)."""
    from benchmark import stats

    mid = (start + end) / 2
    return [stats.percentile(
        [1e3 * (r["first"] - r["due"]) for r in records
         if lo <= r["due"] < hi and r["first"] is not None], 50)
        for lo, hi in ((start, mid), (mid, end))]


def attempted_failed(ctx: dict) -> tuple:
    """Requests the window sent or had due, and those of them that
    failed, were refused, or never gave a token."""
    start, end = ctx["window"]
    mine = [r for r in ctx["requests"] if start <= r["due"] < end
            or (r["times"] and start <= r["times"][-1] < end)]
    bad = [r for r in mine if r["failed"] or r["finish"] in
           ("error", "refused") or (r["first"] is None
                                    and r["finish"] != "cancelled")]
    return len(mine), len(bad)
