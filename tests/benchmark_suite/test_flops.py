"""The GPT-2 family's answers (`benchmark/families/gpt2.py`) against
hand counts for both configurations, and the roofline of
`benchmark/flops.py`."""

import json
import os

import numpy as np
import pytest

from benchmark import flops, manifest

PEAK = manifest.load_peak("TPU v5 lite")


def family(name):
    """(the family, a reader's record of a bf16 run of the
    configuration, the configuration file)."""
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == name)
    with open(os.path.join(manifest.ROOT, entry["file"])) as f:
        raw = json.load(f)
    return manifest.load_family(raw), {"config": raw, "itemsize": 2}, raw


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
    fam, _, raw = family(name)
    assert fam.params_total(raw) == total == \
        raw["reckoned_bytes"]["parameters"]
    assert fam.params_matmul(raw) == matmul
    assert raw["reckoned_bytes"]["weights_bf16"] == 2 * total


@pytest.mark.parametrize("name,want", [
    ("cerebras-gpt-1.3b", {"vocab_size": 50257, "d_model": 2048,
                           "n_heads": 16, "n_layers": 24, "d_ff": 8192,
                           "max_len": 2048}),
    ("gpt2-medium", {"vocab_size": 50257, "d_model": 1024, "n_heads": 16,
                     "n_layers": 24, "d_ff": 4096, "max_len": 1024}),
])
def test_the_family_s_answers_are_what_the_harness_computed_before(
        name, want):
    """Sizes, tree, counts and programs of the GPT-2 family equal what
    `manifest.shape_of`, `weights.leaf_shapes`, `flops.*` and
    `schedule.warm_groups` gave before they moved (the numbers of
    `reckoned_bytes` in each file)."""
    fam, ctx, raw = family(name)
    assert fam.sizes(raw) == want
    d, f, v, t = (want[k] for k in ("d_model", "d_ff", "vocab_size",
                                    "max_len"))
    tree = fam.param_shapes(raw)
    assert sorted(tree) == ["blocks", "embed", "ln_f", "pos"]
    assert tree["embed"] == (v, d) and tree["pos"] == (t, d)
    assert len(tree["blocks"]) == 24
    assert tree["blocks"][0] == tree["blocks"][23] == {
        "ln1": {"g": (d,), "b": (d,)}, "Wq": (d, d), "Wk": (d, d),
        "Wv": (d, d), "Wo": (d, d), "ln2": {"g": (d,), "b": (d,)},
        "W1": (d, f), "b1": (f,), "W2": (f, d), "b2": (d,)}
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))[0]
    count = sum(int(np.prod(shape)) for _, shape in leaves)
    assert count == raw["reckoned_bytes"]["parameters"]
    gains = [jax.tree_util.keystr(p) for p, _ in leaves
             if fam.is_gain(jax.tree_util.keystr(p))]
    assert len(gains) == 2 * 24 + 1 and all("ln" in g for g in gains)
    body = 24 * (4 * d * d + 2 * d * f)
    assert fam.decode_token_flops(ctx, 1000) == \
        2 * (body + v * d) + 4 * 24 * d * 1000
    assert fam.prefill_flops(ctx, 300) == 2 * body * 300 + 2 * v * d \
        + 4 * 24 * d * (300 * 301 // 2)
    assert fam.train_flops_token(ctx, 128) == int(
        3 * (2 * (body + v * d) + 4 * 24 * d * 129 / 2))
    assert fam.decode_step_bytes(ctx, [700.9]) == \
        2 * count + 2 * 24 * d * 2 * 700
    for works in (fam.flash_fwd_work(ctx, 8, 1024),
                  fam.flash_bwd_work(ctx, 8, 1024)):
        assert len(works) == 24 and all(w == works[0] for w in works)
    assert callable(fam.reference().logits)
    if "serving" in raw:
        assert fam.prompt_buckets(t, 16) == (16, 32, 64, 128, 256, 512,
                                             1024, 2048)
        with open(os.path.join(manifest.ROOT, "benchmark", "traffic",
                               "doc-p80.json")) as fh:
            todo = fam.warm_requests(raw, json.load(fh), 51)
        # 16 group sizes at the smallest bucket touched, then the other
        # (bb, tb) groups; the top bucket's prompts leave room to decode
        assert len(todo) == 31 and todo[:16] == [
            (n, 256) for n in range(1, 17)]
        assert sorted(set(p for _, p in todo)) == [256, 512, 1024, 2046]


def test_cerebras_numbers_by_hand():
    fam, ctx, raw = family("cerebras-gpt-1.3b")
    assert fam.params_total(raw) == 1_315_526_656
    # one decoded token at context 1024: 2 x 1.311 G weights it
    # multiplies, plus 4 x 24 x 2048 x 1024 for attention
    assert fam.decode_token_flops(ctx, 1024) == \
        2 * 1_310_885_888 + 201_326_592
    # K and V of one token, all layers, bf16
    assert 2 * 24 * 2048 * 2 == raw["reckoned_bytes"]["kv_bytes_per_token"]
    assert raw["reckoned_bytes"]["kv_pool"] == 2560 * 16 * 196608
    # a 1024-token prompt: body matmuls, head once, causal attention
    body = 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192)
    assert fam.prefill_flops(ctx, 1024) == \
        2 * body * 1024 + 2 * 50257 * 2048 \
        + 4 * 24 * 2048 * (1024 * 1025 // 2)
    # a decode step over 16 slots at 1024 keys each moves the weights
    # and 16 x 1024 x 196,608 B of cache: 5.85 GB, 7.1 ms at 819 GB/s
    byts = fam.decode_step_bytes(ctx, [1024] * 16)
    assert byts == 2 * 1_315_526_656 + 16 * 1024 * 196608
    assert byts / PEAK["hbm_bytes_per_s"] == pytest.approx(7.146e-3, rel=1e-3)


def test_gpt2_medium_train_token_by_hand():
    fam, ctx, _ = family("gpt2-medium")
    fwd = 2 * 353_453_056 + 4 * 24 * 1024 * 1025 / 2
    assert fam.train_flops_token(ctx, 1024) == int(3 * fwd)
    # 6 N and a little: 2.27 GF a token; 48k tokens/s is 55% of 197 TF/s
    per_tok = fam.train_flops_token(ctx, 1024)
    assert per_tok == pytest.approx(2.272e9, rel=2e-3)
    assert 48_000 * per_tok / PEAK["bf16_flops_per_s"] == \
        pytest.approx(0.5535, rel=2e-3)
    # at T128 attention all but vanishes
    assert fam.train_flops_token(ctx, 128) == pytest.approx(
        6 * 353_453_056, rel=0.01)


def test_kernel_work_by_hand():
    fam, ctx, _ = family("cerebras-gpt-1.3b")
    # paged decode, one layer, two slots at 17 and 32 keys, page 16:
    # two pages each of K and V, 16 heads x 128, bf16, plus q and o
    works = fam.paged_decode_attention_work(ctx, [17, 32])
    assert len(works) == 24
    w = works[0]
    assert w["bytes"] == 2 * 4 * 16 * 2048 * 2 + 2 * 2 * 2048 * 2
    assert w["flops"] == 4 * 2048 * (17 + 32)
    assert flops.least_seconds(w, PEAK) == w["bytes"] / 819e9  # bandwidth
    # flash forward, one row of 1024: compute-bound
    f = fam.flash_fwd_work(ctx, 1, 1024)[0]
    assert f["flops"] == 4 * 2048 * (1024 * 1025 // 2)
    assert f["bytes"] == 4 * 1024 * 2048 * 2
    assert flops.least_seconds(f, PEAK) == f["flops"] / 197e12
    # 24 calls of a pass, 48 counted in the trace: two passes
    assert flops.least_seconds_for([f] * 24, 48, PEAK) == \
        pytest.approx(48 * f["flops"] / 197e12)
    m, mctx, _ = family("gpt2-medium")
    b = m.flash_bwd_work(mctx, 8, 1024)[0]
    assert b["flops"] == 10 * 1024 * (1024 * 1025 // 2) * 8
    assert b["bytes"] == 13 * 8 * 1024 * 1024 * 2
