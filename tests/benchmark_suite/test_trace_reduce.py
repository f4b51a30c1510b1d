"""The trace reduction on a hand-made trace and on a small trace
recorded on the chip (`benchmark/testdata/`): busy union, idle share,
per-name kernel time, gap attribution to host spans."""

import json
import os

import pytest

from benchmark import manifest, trace_reduce as tr

DATA = os.path.join(manifest.ROOT, "benchmark", "testdata")
US = 1_000


def synthetic():
    """The trace built by hand, kept as data. One device, window 0..1000
    us. Ops: a 0-100, b 50-200 (overlaps a), kernel k twice 300-400 and
    600-700, one op straddling the window's end 950-1100, one wholly
    outside. Host: a span `wait_io` over 200-300, `dispatch` over
    400-600 with `inner` 450-550 nested, nothing over 700-950."""
    return tr.load_json(os.path.join(DATA, "synthetic.json"))["trace"]


def test_union_and_gaps():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert tr.union_ns([]) == 0
    assert tr.gaps_ns([(0, 10), (5, 20), (30, 40)], 0, 50) == \
        [(20, 30), (40, 50)]
    assert tr.gaps_ns([], 0, 5) == [(0, 5)]
    assert tr.gaps_ns([(0, 5)], 0, 5) == []


def test_op_names_keep_shapes_and_drop_serials():
    assert tr.op_name("%fusion.12 = bf16[16,2048]{1,0} fusion(%p)") == \
        "fusion_bf16_16_2048_"
    assert tr.op_name(
        "%copy.3 = bf16[2561,16,16,128]{3,2,1,0:T(8,128)(2,1)} copy(%x)") \
        == "copy_bf16_2561_16_16_128_"
    assert tr.op_name(
        "%paged_decode_attention.48 = bf16[16,16,16,128]{3,2,1,0:T(8,128)"
        "(2,1)S(1)} custom-call(s32[16,128]{1,0} %copy-done.2)") == \
        "paged_decode_attention"
    assert tr.op_name(
        "%slice-start.1 = ((bf16[2048,2048]{1,0:T(8,128)(2,1)}), "
        "bf16[512,2048]{1,0}) slice-start(%p)") == \
        "slice-start_bf16_2048_2048_"
    assert tr.op_name("paged_decode_attention") == "paged_decode_attention"
    assert tr.op_name("flash_fwd.7") == "flash_fwd"
    assert tr.op_name("jit_step_fn(123)") == "jit_step_fn(123)"


def test_synthetic_trace_reduces_to_the_hand_count():
    out = tr.reduce(synthetic())
    assert out["window_s"] == pytest.approx(1000e-6)
    # busy: 0-200, 300-400, 600-700, 950-1000 = 450 us
    assert out["busy_s"] == pytest.approx(450e-6)
    assert out["op_s"]["k"] == pytest.approx(200e-6)
    assert out["op_n"]["k"] == 2
    assert out["op_s"]["a_bf16_4_8_"] == pytest.approx(100e-6)
    assert out["op_s"]["tail"] == pytest.approx(50e-6)   # clipped
    assert "outside" not in out["op_s"]
    assert tr.matching(out["module_s"], "step_fn") == pytest.approx(300e-6)
    assert tr.matching(out["module_n"], "step_fn") == 2
    assert tr.matching(out["module_s"], "prefill_fn") == \
        pytest.approx(100e-6)
    gaps = dict(out["idle_gaps"])
    # 200-300 under wait_io; 400-600: its middle (500) lies in `inner`,
    # the innermost span; 700-950 under nothing
    assert gaps["wait_io"] == pytest.approx(100e-6)
    assert gaps["inner"] == pytest.approx(200e-6)
    assert gaps[tr.UNATTRIBUTED] == pytest.approx(250e-6)
    assert sum(gaps.values()) + out["busy_s"] == \
        pytest.approx(out["window_s"])
    assert out["device_ops"][0][0] == "k"
    assert len(out["device_ops"]) <= 10


def test_short_gaps_are_summed_under_one_name():
    t = synthetic()
    t["planes"][0]["lines"][0]["events"] = [
        ["x", i * 100 * US, 90 * US] for i in range(10)]
    out = tr.reduce(t)
    gaps = dict(out["idle_gaps"])
    assert gaps[tr.SHORT_GAPS] == pytest.approx(100e-6)
    assert out["busy_s"] == pytest.approx(900e-6)


def test_no_window_or_no_device_reads_nothing():
    t = synthetic()
    t["planes"][1]["lines"][0]["events"] = []
    assert tr.reduce(t) is None
    assert tr.reduce({"planes": synthetic()["planes"][1:]}) is None


def test_two_devices_are_averaged():
    t = synthetic()
    second = json.loads(json.dumps(t["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][0]["events"] = []
    t["planes"].append(second)
    out = tr.reduce(t)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(225e-6)


RECORDED = os.path.join(DATA, "decode_sat_v5e.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="the recorded trace is not in this checkout")
def test_recorded_chip_trace():
    """A stretch of `cgpt13b-decode-sat` recorded on a TPU v5e in PR 24
    and cut down to the window; `expect` in the file is what a reading
    by hand of the same events gives."""
    with open(RECORDED) as f:
        rec = json.load(f)
    out = tr.reduce(rec["trace"])
    want = rec["expect"]
    assert out["window_s"] == pytest.approx(want["window_s"])
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert 0 < out["busy_s"] <= out["window_s"]
    for name, secs in want["op_s"].items():
        assert out["op_s"][name] == pytest.approx(secs, rel=1e-6)
    assert tr.matching(out["op_n"], "paged_decode_attention") == \
        want["paged_calls"]
    assert tr.matching(out["module_n"], "step_fn") == want["decode_steps"]
    total = sum(v for _k, v in out["idle_gaps"])
    assert total <= out["window_s"] - out["busy_s"] + 1e-9
