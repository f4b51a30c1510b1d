"""`benchmark/flops.py` against hand counts for both configurations."""

import json
import os

import pytest

from benchmark import flops, manifest

PEAK = manifest.load_peak("TPU v5 lite")


def shape(name):
    """(sizes the benchmark computes with, the configuration file)."""
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == name)
    with open(os.path.join(manifest.ROOT, entry["file"])) as f:
        raw = json.load(f)
    return manifest.shape_of(raw), raw


@pytest.mark.parametrize("name,total,matmul", [
    # embed + pos + ln_f + 24 x (4 d^2 + 2 d f + f + d + 4 d)
    ("cerebras-gpt-1.3b",
     50257 * 2048 + 2048 * 2048 + 2 * 2048
     + 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192 + 8192 + 2048 + 4 * 2048),
     24 * (4 * 2048 ** 2 + 2 * 2048 * 8192) + 50257 * 2048),
    ("gpt2-medium",
     50257 * 1024 + 1024 * 1024 + 2 * 1024
     + 24 * (4 * 1024 ** 2 + 2 * 1024 * 4096 + 4096 + 1024 + 4 * 1024),
     24 * (4 * 1024 ** 2 + 2 * 1024 * 4096) + 50257 * 1024),
])
def test_parameter_counts(name, total, matmul):
    s, raw = shape(name)
    assert flops.params_total(s) == total == \
        raw["reckoned_bytes"]["parameters"]
    assert flops.params_matmul(s) == matmul
    assert raw["reckoned_bytes"]["weights_bf16"] == 2 * total


def test_cerebras_numbers_by_hand():
    s, raw = shape("cerebras-gpt-1.3b")
    assert flops.params_total(s) == 1_315_526_656
    # one decoded token at context 1024: 2 x 1.311 G weights it
    # multiplies, plus 4 x 24 x 2048 x 1024 for attention
    assert flops.decode_token_flops(s, 1024) == \
        2 * 1_310_885_888 + 201_326_592
    # K and V of one token, all layers, bf16
    assert 2 * 24 * 2048 * 2 == raw["reckoned_bytes"]["kv_bytes_per_token"]
    assert raw["reckoned_bytes"]["kv_pool"] == 2560 * 16 * 196608
    # a 1024-token prompt: body matmuls, head once, causal attention
    body = 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192)
    assert flops.prefill_flops(s, 1024) == \
        2 * body * 1024 + 2 * 50257 * 2048 \
        + 4 * 24 * 2048 * (1024 * 1025 // 2)
    # a decode step over 16 slots at 1024 keys each moves the weights
    # and 16 x 1024 x 196,608 B of cache: 5.85 GB, 7.1 ms at 819 GB/s
    byts = flops.decode_step_bytes(s, [1024] * 16, 2)
    assert byts == 2 * 1_315_526_656 + 16 * 1024 * 196608
    assert byts / PEAK["hbm_bytes_per_s"] == pytest.approx(7.146e-3, rel=1e-3)


def test_gpt2_medium_train_token_by_hand():
    s, _ = shape("gpt2-medium")
    fwd = 2 * 353_453_056 + 4 * 24 * 1024 * 1025 / 2
    assert flops.train_flops_token(s, 1024) == int(3 * fwd)
    # 6 N and a little: 2.27 GF a token; 48k tokens/s is 55% of 197 TF/s
    per_tok = flops.train_flops_token(s, 1024)
    assert per_tok == pytest.approx(2.272e9, rel=2e-3)
    assert 48_000 * per_tok / PEAK["bf16_flops_per_s"] == \
        pytest.approx(0.5535, rel=2e-3)
    # at T128 attention all but vanishes
    assert flops.train_flops_token(s, 128) == pytest.approx(
        6 * 353_453_056, rel=0.01)


def test_kernel_work_by_hand():
    s, _ = shape("cerebras-gpt-1.3b")
    # paged decode, one layer, two slots at 17 and 32 keys, page 16:
    # two pages each of K and V, 16 heads x 128, bf16, plus q and o
    w = flops.paged_decode_attention_work(s, [17, 32], 16, 2)
    assert w["bytes"] == 2 * 4 * 16 * 2048 * 2 + 2 * 2 * 2048 * 2
    assert w["flops"] == 4 * 2048 * (17 + 32)
    assert flops.least_seconds(w, PEAK) == w["bytes"] / 819e9  # bandwidth
    # flash forward, one row of 1024: compute-bound
    f = flops.flash_fwd_work(s, 1, 1024, 2)
    assert f["flops"] == 4 * 2048 * (1024 * 1025 // 2)
    assert f["bytes"] == 4 * 1024 * 2048 * 2
    assert flops.least_seconds(f, PEAK) == f["flops"] / 197e12
    m, _ = shape("gpt2-medium")
    b = flops.flash_bwd_work(m, 8, 1024, 2)
    assert b["flops"] == 10 * 1024 * (1024 * 1025 // 2) * 8
    assert b["bytes"] == 13 * 8 * 1024 * 1024 * 2
