"""Percentile, whole-window rate and worst-for-failed arithmetic on
hand-made samples, through the metric readers themselves."""

import math

import pytest

from benchmark import manifest, stats


def reader(name):
    return manifest.load_reader(name)


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 99, 99.01),
    (list(range(1, 101)), 90, 90.1),
    ([7], 99, 7.0),
    ([], 50, None),
    ([1, 2, math.inf], 99, math.inf),
])
def test_percentile(values, q, want):
    got = stats.percentile(values, q)
    assert got == want or got == pytest.approx(want)


def test_rate_is_over_the_whole_window():
    assert stats.rate(510, 51) == 10.0
    with pytest.raises(ValueError):
        stats.rate(1, 0)


def request(due, first_after, n, gap, failed=False):
    times = [] if failed else [due + first_after + i * gap for i in range(n)]
    return {"due": due, "sent": due + 0.001, "times": times,
            "first": times[0] if times else None, "failed":
            "boom" if failed else None, "finish":
            "error" if failed else "max_tokens", "prompt_len": 8,
            "tokens": [1] * len(times)}


def ctx_of(requests, start=0.0, end=10.0):
    return {"window": (start, end), "requests": requests,
            "seconds": end - start}


def test_tokens_and_gaps_count_inside_the_window_only():
    r = request(-1.0, 0.5, 30, 0.1)     # tokens from -0.5 to 2.4
    ctx = ctx_of([r], 0.0, 2.0)
    inside = [t for t in r["times"] if 0.0 <= t < 2.0]
    assert reader("out_tok_s")(ctx) == pytest.approx(len(inside) / 2.0)
    assert reader("itl_p50_ms")(ctx) == pytest.approx(100.0)


def test_a_stall_moves_throughput_and_the_tail():
    steady = [request(0.0, 0.1, 99, 0.1) for _ in range(4)]
    stalled = []
    for _ in range(4):
        r = request(0.0, 0.1, 99, 0.1)
        # three slow steps of 0.7 s after the 50th token of every
        # request: 3% of its gaps, two seconds of its window
        r["times"] = [t + 0.6 * min(max(i - 49, 0), 3)
                      for i, t in enumerate(r["times"])]
        stalled.append(r)
    a, b = ctx_of(steady), ctx_of(stalled)
    assert reader("out_tok_s")(b) < 0.85 * reader("out_tok_s")(a)
    for tail in ("itl_p98_ms", "itl_p99_ms"):
        assert reader(tail)(a) == pytest.approx(100.0)
        assert reader(tail)(b) > 150.0
    assert reader("itl_p50_ms")(b) == pytest.approx(100.0)


def test_failed_or_silent_requests_count_as_the_worst():
    good = [request(float(i), 0.2, 5, 0.05) for i in range(9)]
    bad = request(5.5, 0.2, 5, 0.05, failed=True)
    ctx = ctx_of(good + [bad])
    p90 = reader("ttft_p90_ms")(ctx)
    assert p90 > 200.0 + 1.0           # the failure sits in the tail
    assert reader("ttft_p50_ms")(ctx) == pytest.approx(200.0)
    assert stats.ttft_samples([bad], 0, 10) == [math.inf]
    # not due in the window: not counted at all
    assert stats.ttft_samples([request(11.0, 0.2, 5, 0.05)], 0, 10) == []


def test_train_rate_and_mfu_use_all_the_time():
    cell = manifest.load_cell("gpt2m-train-t1024")
    ctx = {"train": {"steps": 100, "tokens_per_step": 8192,
                     "elapsed": 20.0, "seq_len": 1024, "rows": 8},
           "config": cell.config, "family": cell.family, "itemsize": 2,
           "peak": manifest.load_peak("TPU v5 lite")}
    assert reader("train_tok_s")(ctx) == pytest.approx(40960.0)
    mfu = reader("train_mfu")(ctx)
    assert 40.0 < mfu < 50.0
    ctx["train"]["elapsed"] = 40.0     # the same steps, idle included
    assert reader("train_mfu")(ctx) == pytest.approx(mfu / 2)


def test_a_reader_with_nothing_to_read_returns_nothing():
    ctx = ctx_of([])
    ctx["trace"] = None
    for name in ("decode_step_dev_ms", "decode_mfu", "prefill_mfu",
                 "paged_decode_attention_roofline",
                 "prefill_flash_fwd_roofline", "serve_dev_idle_share",
                 "train_flash_fwd_roofline", "train_step_dev_ms",
                 "itl_p98_ms", "itl_p99_ms", "ttft_p90_ms",
                 "kv_peak_page_share"):
        assert reader(name)(ctx) is None, name
