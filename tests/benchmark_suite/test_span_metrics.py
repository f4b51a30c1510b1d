"""The readers of the scheduler's own spans and counters, on a trace
and on records built by hand: each returns the hand-counted value, and
None where the program gives it nothing to read (as the program before
these metrics does)."""

import os

import pytest

from benchmark import manifest, trace_reduce as tr

DATA = os.path.join(manifest.ROOT, "benchmark", "testdata")
US = 1e-6


def spans_trace():
    """Built by hand, kept as data. One device, window 0..1000 us, three
    decode steps 0-300, 400-600, 700-900. The scheduler's line holds one
    `decode.tick` a step; the first gap (300-400) lies in its
    `decode.emit`, the second (600-700) in a `decode.d2h` that encloses
    PjRt's `np.asarray(jax.Array)`, and over the third (900-1000) the
    host holds no span at all."""
    return tr.load_json(os.path.join(DATA, "decode_spans.json"))["trace"]


def read(name, ctx):
    return manifest.load_reader(name)(ctx)


class Stream:
    def __init__(self, **life):
        self._life = life

    def timeline(self):
        return dict(request_id=0, **self._life)


def request(due, life=None, failed=None, times=(), stream="life"):
    if stream == "life":
        stream = Stream(**life) if life else None
    return {"due": due, "failed": failed, "times": list(times),
            "stream": stream}


def snap(dispatches, tick_s, d2h_s, prefill_passes, slow=()):
    return {"dispatches": dispatches, "prefill_passes": prefill_passes,
            "phases": {"decode.tick": {"seconds": tick_s, "count": 1},
                       "decode.d2h": {"seconds": d2h_s, "count": 1}},
            "slow_ticks": list(slow)}


def test_gaps_go_to_the_scheduler_s_phase_and_an_inner_span_still_wins():
    out = tr.reduce(spans_trace())
    assert out["window_s"] == pytest.approx(1000 * US)
    assert out["busy_s"] == pytest.approx(700 * US)
    gaps = dict(out["idle_gaps"])
    assert gaps["decode.emit"] == pytest.approx(100 * US)
    assert "decode.tick" not in gaps and "decode.d2h" not in gaps
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(100 * US)
    assert gaps[tr.UNATTRIBUTED] == pytest.approx(100 * US)
    assert sum(gaps.values()) + out["busy_s"] == \
        pytest.approx(out["window_s"])
    assert tr.matching(out["module_n"], "step_fn") == 3


def test_unattributed_share_of_the_idle_time():
    ctx = {"trace": tr.reduce(spans_trace())}
    assert read("serve_idle_unattributed_share", ctx) == \
        pytest.approx(100.0 / 3)
    # every gap named: the share is 0, not absent
    named = dict(ctx["trace"], idle_gaps=[["decode.emit", 300 * US]])
    assert read("serve_idle_unattributed_share", {"trace": named}) == 0.0
    # what the program before this one gives: half the idle time
    before = dict(ctx["trace"], idle_gaps=[[tr.UNATTRIBUTED, 150 * US],
                                           ["np.asarray", 150 * US]])
    assert read("serve_idle_unattributed_share", {"trace": before}) == \
        pytest.approx(50.0)
    assert read("serve_idle_unattributed_share", {}) is None
    assert read("serve_idle_unattributed_share", {"trace": None}) is None
    busy = dict(ctx["trace"], busy_s=ctx["trace"]["window_s"])
    assert read("serve_idle_unattributed_share", {"trace": busy}) is None


def lives_ctx():
    """Eleven requests due in a window of 0..10 s, one before it. Queue
    waits 10, 20, ..., 100 ms, then one that was never admitted; all
    admitted ones got their first token 50 ms after admission but the
    last, 250 ms."""
    reqs = [request(-1.0, dict(submitted=-1.0, admitted=-0.5,
                               first_token=-0.4, finished=0.2))]
    for i in range(1, 11):
        t = float(i) / 2
        hold = 0.25 if i == 10 else 0.05
        reqs.append(request(t, dict(
            submitted=t, admitted=t + i / 100.0,
            first_token=t + i / 100.0 + hold, finished=t + 1.0),
            times=[t + 1.0]))
    reqs.append(request(9.0, dict(submitted=9.0, admitted=None,
                                  first_token=None, finished=None)))
    return {"window": (0.0, 10.0), "requests": reqs}


def test_queue_wait_and_admit_to_first_percentiles():
    ctx = lives_ctx()
    # eleven samples; the 90th percentile is the tenth: 100 ms of queue
    # (the eleventh, never admitted, is the worst: the window's 10 s)
    assert read("sched_queue_wait_p90_ms", ctx) == pytest.approx(100.0)
    assert read("sched_admit_to_first_p90_ms", ctx) == pytest.approx(250.0)
    # one more that failed: two samples of twelve are the worst, and the
    # 90th percentile lies between the tenth and the eleventh
    ctx["requests"].append(request(9.5, failed="boom", stream=None))
    assert read("sched_queue_wait_p90_ms", ctx) == \
        pytest.approx(100.0 + 0.9 * (10_000.0 - 100.0))
    # the worst is the time the run waited: a token after the close
    ctx["requests"][1]["times"] = [12.0]
    assert read("sched_admit_to_first_p90_ms", ctx) == \
        pytest.approx(250.0 + 0.9 * (12_000.0 - 250.0))


def test_lives_absent_read_as_nothing():
    ctx = lives_ctx()
    for r in ctx["requests"]:
        r["stream"] = object()  # a stream of a program without stamps
    assert read("sched_queue_wait_p90_ms", ctx) is None
    assert read("sched_admit_to_first_p90_ms", ctx) is None
    empty = {"window": (0.0, 10.0), "requests": []}
    assert read("sched_queue_wait_p90_ms", empty) is None
    assert read("sched_admit_to_first_p90_ms", empty) is None


def test_host_milliseconds_a_dispatch_and_prefill_share():
    ctx = {"window": (100.0, 151.0),
           "snap0": snap(1000, 70.0, 60.0, 40),
           "snap1": snap(1500, 108.0, 95.5, 65)}
    # 38 s of passes less 35.5 s waiting on the device, 500 dispatches
    assert read("sched_host_ms_per_dispatch", ctx) == pytest.approx(5.0)
    assert read("sched_prefill_dispatch_share", ctx) == pytest.approx(5.0)
    still = dict(ctx, snap1=ctx["snap0"])
    assert read("sched_host_ms_per_dispatch", still) is None
    assert read("sched_prefill_dispatch_share", still) is None
    old = {"window": (0.0, 1.0), "snap0": {"dispatches": 1},
           "snap1": {"dispatches": 9}}
    assert read("sched_host_ms_per_dispatch", old) is None
    assert read("sched_prefill_dispatch_share", old) is None
    assert read("sched_host_ms_per_dispatch", {"window": (0.0, 1.0)}) is None
    assert read("sched_prefill_dispatch_share", {"window": (0.0, 1.0)}) \
        is None


def test_longest_pass_that_started_in_the_window():
    slow = [{"start_s": 90.0, "dur_ms": 4000.0, "phases": {}},
            {"start_s": 101.0, "dur_ms": 95.0, "phases": {}},
            {"start_s": 120.0, "dur_ms": 2015.7,
             "phases": {"decode.d2h": 2000.0}},
            {"start_s": 151.0, "dur_ms": 9000.0, "phases": {}}]
    ctx = {"window": (100.0, 151.0), "snap1": snap(1, 0, 0, 0, slow)}
    assert read("sched_tick_max_ms", ctx) == 2015.7
    ctx["snap1"]["slow_ticks"] = slow[:1] + slow[3:]
    assert read("sched_tick_max_ms", ctx) is None  # all older or later
    assert read("sched_tick_max_ms", {"window": (0.0, 1.0),
                                      "snap1": {"dispatches": 3}}) is None
    assert read("sched_tick_max_ms", {"window": (0.0, 1.0)}) is None


@pytest.mark.parametrize("cell, metrics", [
    ("cgpt13b-decode-sat", {"sched_host_ms_per_dispatch",
                            "sched_prefill_dispatch_share",
                            "sched_tick_max_ms",
                            "serve_idle_unattributed_share"}),
    ("cgpt13b-prompt-p80", {"sched_queue_wait_p90_ms",
                            "sched_admit_to_first_p90_ms",
                            "sched_host_ms_per_dispatch",
                            "sched_prefill_dispatch_share",
                            "sched_tick_max_ms",
                            "serve_idle_unattributed_share"}),
])
def test_the_serving_cells_list_the_new_metrics_last(cell, metrics):
    names = [m["name"] for m in manifest.load_cell(cell).per_layer]
    assert set(names[-len(metrics):]) == metrics
    for train in ("gpt2m-train-t1024", "gpt2m-train-t128"):
        assert not metrics & {m["name"] for m in
                              manifest.load_cell(train).per_layer}
