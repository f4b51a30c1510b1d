"""The `cohere2_moe` family's files: its counts against the
configuration's `reckoned_bytes` and against hand numbers, its cell at
the tests' small size run by the harness to a `correct` line with the
fp8 control not correct, and the eight new readers on hand-made
snapshots and a hand-made trace."""

import json

import pytest

from benchmark import manifest, run
from tests.benchmark_suite import tiny, tiny_moe

CELL = "cmdaplus-ep8-agent-long"
SEED = 2 ** 31 + 2905


@pytest.fixture(scope="module")
def real():
    return manifest.load_cell(CELL)


def _ctx(cell, **more):
    return dict({"config": cell.config, "family": cell.family,
                 "itemsize": 2, "traffic": cell.traffic}, **more)


# ------------------------------------------------------------ the files
def test_the_cell_loads_by_name_with_its_family_and_its_share(real):
    assert real.config["family"] == "cohere2_moe" and real.chips == 1
    s = real.family.sizes(real.config)
    assert (s["d_model"], s["n_heads"], s["n_kv_heads"], s["head_dim"],
            s["d_ff"], s["window"], s["k"], s["n_shared"]) == \
        (4096, 128, 8, 128, 4096, 4096, 8, 4)      # widths as published
    assert s["kinds"] == ("window", "window", "window", "full")
    assert (s["n_held"], s["router_width"], s["vocab_size"],
            s["max_len"]) == (16, 128, 32768, 8192)
    # (its file, source, reference and list of cuts:
    # test_every_configuration.py, one case a configuration)
    names = {m["name"] for m in real.per_layer}
    assert {"decode_step_mfu", "decode_mfu", "decode_hbm_share",
            "paged_decode_attention_roofline", "prefill_pass_dev_ms",
            "prefill_pass_mfu", "prefill_pass_flash_roofline",
            "moe_expert_roofline", "moe_expert_load_max_over_mean",
            "moe_held_pair_share", "kv_window_pages_per_slot_peak",
            "sched_release_window_ms_per_dispatch"} <= names
    assert {m["name"] for m in real.end_to_end} == \
        {"out_tok_s", "itl_p98_ms", "setup_s"}
    # the three prefill metrics the benchmark has move `ttft_p90_ms`,
    # which this cell does not report: it joins none of them
    assert not {"prefill_mfu", "prefill_flash_fwd_roofline",
                "prefill_dev_ms_per_ktok"} & names


def test_the_warm_set_covers_every_group_the_schedule_can_form(real):
    """What test_schedule.py checks of a mix, with this family's own
    buckets and the bound on a pass's prefill: the closed loop's 32
    clients start at once, but a pass claims one row of the 8,192
    bucket, so (1, 8192) is the one prefill group there can be."""
    from benchmark import schedule

    srv = real.config["serving"]
    buckets = real.family.prompt_buckets(8192, srv["page_size"])
    assert buckets == (128, 256, 512, 1024, 2048, 4096, 8192)
    plan = schedule.warm_groups(real.traffic, 51, srv["slots"], buckets)
    assert plan["buckets"] == [8192] and max(plan["sizes"]) == 32
    assert srv["prefill_tokens_per_pass"] // 8192 == 1
    assert real.family.warm_requests(real.config, real.traffic, 51) == \
        [(1, 8190)]
    # and the program's buckets are these
    from deeplearning4j_tpu.serving.paged_kv import prompt_buckets

    assert prompt_buckets(real.family.model_config(real.config),
                          srv["page_size"]) == buckets


def test_the_tree_and_the_counts_against_reckoned_bytes(real):
    fam, cfg = real.family, real.config
    reck = cfg["reckoned_bytes"]
    shapes = fam.param_shapes(cfg)

    def count(tree):
        if isinstance(tree, tuple):
            n = 1
            for d in tree:
                n *= d
            return n
        vals = tree.values() if isinstance(tree, dict) else tree
        return sum(count(v) for v in vals)

    assert count(shapes) == fam.params_total(cfg) == reck["parameters"]
    assert 2 * reck["parameters"] == reck["weights_bf16"]
    p = fam.layer_params(cfg)
    assert p["attention"] + p["shared"] + p["router"] + p["gain"] == \
        reck["parameters_a_layer_outside_routed_experts"]
    assert p["expert"] == reck["parameters_a_routed_expert"]
    # by hand: Wq, Wo 4096 x 16384; Wk, Wv 4096 x 1024; 4 x 3 x 4096^2
    assert p["attention"] == 2 * 4096 * 16384 + 2 * 4096 * 1024
    assert p["shared"] == 12 * 4096 ** 2 and p["expert"] == 3 * 4096 ** 2
    assert shapes["blocks"][0]["experts"]["gate"] == (16, 4096, 4096)
    assert shapes["blocks"][0]["router"] == (4096, 128)
    assert "pos" not in shapes
    ctx = _ctx(real)
    assert fam.kv_bytes_token_layer(ctx) == \
        reck["kv_bytes_per_token_per_layer"] == 2 * 8 * 128 * 2
    srv = cfg["serving"]
    page = fam.kv_bytes_token_layer(ctx) * srv["page_size"]
    assert (srv["kv_pages"] + 1) * page == reck["kv_pool_full_kind"]
    assert 3 * (srv["window_pages"] + 1) * page == \
        reck["kv_pool_window_kind"]
    assert reck["kv_pool"] == reck["kv_pool_full_kind"] \
        + reck["kv_pool_window_kind"]
    assert srv["kv_pages"] == 32 * 8192 // 128
    assert srv["window_pages"] == 32 * (4096 // 128 + 1)


def test_the_counts_against_hand_numbers(real):
    fam = real.family
    ctx = _ctx(real)
    outside = 2 * 4 * (142606336 + 201326592 + 524288)
    head = 2 * 32768 * 4096
    # no counters in ctx: a uniform router, 8 x 16 / 128 = 1 pair a layer
    routed = 4 * 2 * 50331648
    assert fam.decode_token_flops(ctx, 7700) == pytest.approx(
        outside + routed + head
        + 4 * 128 * 128 * (3 * 4096 + 7700))      # windows cap at 4096
    assert fam.decode_token_flops(ctx, 100) == pytest.approx(
        outside + routed + head + 4 * 128 * 128 * 4 * 100)
    # the program's counters in ctx: 0.9 pairs a token a layer, 13.5 of
    # 16 experts touched a layer a step
    moe0 = {"tokens": 0, "pairs": 0, "decode_tokens": 0,
            "decode_pairs": 0, "decode_steps": 0, "experts_touched": 0}
    moe1 = {"tokens": 9000, "pairs": 31000, "decode_tokens": 1000,
            "decode_pairs": 3600, "decode_steps": 40,
            "experts_touched": 40 * 54}
    counted = _ctx(real, snap0={"moe": moe0}, snap1={"moe": moe1})
    assert fam.decode_token_flops(counted, 100) == pytest.approx(
        outside + 3.6 * 2 * 50331648 + head + 4 * 128 * 128 * 4 * 100)
    assert fam.held_pairs_per_token(counted, decode=False) == \
        pytest.approx((31000 - 3600) / 8000)
    weights = 2 * (4 * 344461312 + 32768 * 4096 + 4096)
    assert fam.decode_step_bytes(counted, [7700, 5000]) == pytest.approx(
        weights + 54 * 2 * 50331648
        + 4096 * (7700 + 5000 + 3 * 4096 * 2))
    assert fam.decode_step_bytes(ctx, [10]) == pytest.approx(
        weights + 64 * 2 * 50331648 + 4096 * 40)
    # a prompt of 7168: full layer T(T+1)/2 pairs; window layers W(W+1)/2
    # + (T - W) W
    full = 7168 * 7169 // 2
    win = 4096 * 4097 // 2 + (7168 - 4096) * 4096
    assert fam.causal_pairs("window", 7168, 4096) == win
    assert fam.prefill_flops(ctx, 7168) == pytest.approx(
        (outside + routed) * 7168 + head
        + 4 * 128 * 128 * (full + 3 * win))
    works = fam.flash_fwd_work(ctx, 1, 7168)
    assert [w["flops"] for w in works] == \
        [4 * 128 * 128 * win] * 3 + [4 * 128 * 128 * full]
    assert works[0]["bytes"] == 7168 * (2 * 128 + 2 * 8) * 128 * 2
    # the paged kernel: whole pages, and only those a window still sees
    calls = fam.paged_decode_attention_work(ctx, [7700, 130])
    assert len(calls) == 4
    # cursor 7699: first visible 3604 -> pages 28..60 = 33; all 61 full
    assert fam.visible_pages("window", 7700, 4096, 128) == 33
    assert fam.visible_pages("full", 7700, 4096, 128) == 61
    page = 4096 * 128
    q_out = 2 * 2 * 128 * 128 * 2
    assert calls[0]["bytes"] == (33 + 2) * page + q_out
    assert calls[3]["bytes"] == (61 + 2) * page + q_out
    assert calls[0]["flops"] == 4 * 128 * 128 * (4096 + 130)
    assert calls[3]["flops"] == 4 * 128 * 128 * (7700 + 130)
    work = fam.moe_expert_work(ctx, 1000, 50)
    assert work["flops"] == 6 * 4096 * 4096 * 1000
    assert work["bytes"] == 50 * 3 * 4096 * 4096 * 2 \
        + 1000 * (4096 * 2 + 5 * 4096 * 2 + 4096 * 4)


def test_nothing_trains_and_says_so(real):
    for fn in (real.family.make_train_step, real.family.train_flops_token,
               real.family.reference().loss_and_grad):
        with pytest.raises(NotImplementedError, match="trains nothing"):
            fn(real.config, None)
    with pytest.raises(ValueError, match="prefix_cache"):
        real.family.build_engine(
            dict(real.config, serving=dict(real.config["serving"],
                                           prefix_cache=True)), None)


def test_the_reference_imports_nothing_of_the_program(real):
    with open(real.family.reference().__file__) as f:
        text = f.read()
    assert "import deeplearning4j_tpu" not in text
    assert "from deeplearning4j_tpu" not in text


# ------------------------------------------------- the cell, at a small size
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tiny.build(str(tmp_path_factory.mktemp("moe")))
    tiny_moe.add(root)
    return root


@pytest.fixture(scope="module")
def tiny_line(tiny_root):
    cell = manifest.load_cell(tiny_moe.CELL, tiny_root)
    return run.execute(cell, SEED, 1.0, False, require_chip=False)


def test_the_cell_runs_to_a_correct_line(tiny_root, tiny_line):
    line = tiny_line
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["numbers"]["tokens_compared"] >= 20
    assert set(line["metrics"]) == {"setup_s", "out_tok_s", "itl_p98_ms"}
    assert line["detail"]["prefill_groups"] == [(1, 32)]
    assert line["detail"]["jax_programs_in_window"] == 0
    json.dumps(line)


def test_the_fp8_control_is_not_correct(tiny_root, tiny_line):
    """The reference in the program's place, through the comparison
    that decides `correct`, by the kind of limit the real cell has (a
    count of tokens off the float32 reference's best): in float32 it
    is exact, in fp8 it is not correct, over as many requests as the
    cell's own run compares."""
    from benchmark import check

    cell = manifest.load_cell(tiny_moe.CELL, tiny_root)
    assert set(cell.limits) == {"tokens_off_best"} == \
        set(manifest.load_cell(CELL).limits)
    n = cell.traffic["check_requests"]
    assert tiny_line["numbers"]["requests_compared"] == n
    assert tiny_line["compared"]["tokens_off_best"]["value"] == 0
    sample = tiny.greedy_sample(cell, SEED, n, 21, 14)
    numbers = check.serve_numbers(cell, SEED, sample, ("fp8",))
    assert numbers["tokens_off_best"] == 0
    assert check.verdict(numbers, cell.limits)["correct"] is True
    low = numbers["control_fp8_tokens_off_best"]
    assert low >= 2 * cell.limits["tokens_off_best"], numbers
    assert check.verdict({"tokens_off_best": low},
                         cell.limits)["correct"] is False


# ------------------------------------------------------ the new readers
def _snap(pairs, tokens, decode_tokens, decode_pairs, steps, touched,
          dispatches, release_s, prefill_tokens):
    return {"dispatches": dispatches, "prefill_tokens": prefill_tokens,
            "phases": {"decode.release_window": {"seconds": release_s,
                                                 "count": dispatches},
                       "decode.tick": {"seconds": 1.0, "count": 1}},
            "pages_by_kind": {"window": {"pages_per_slot_peak": 33},
                              "full": {}},
            "moe": {"pairs_by_layer_expert": pairs,
                    "pairs": sum(map(sum, pairs)), "tokens": tokens,
                    "experts_per_token": 8, "decode_tokens": decode_tokens,
                    "decode_pairs": decode_pairs, "decode_steps": steps,
                    "experts_touched": touched}}


def test_the_new_readers_on_hand_made_snapshots_and_a_trace(real):
    snap0 = _snap([[0, 0], [0, 0]], 0, 0, 0, 0, 0, 100, 0.0, 0)
    # 2 layers x 2 held experts for the snapshot's sake
    snap1 = _snap([[30, 10], [20, 20]], 40, 10, 18, 5, 12, 150, 0.1,
                  2 * 7168)
    ctx = _ctx(real, snap0=snap0, snap1=snap1, window=(0.0, 1.0),
               peak=manifest.load_peak("TPU v5 lite"),
               trace={"busy_s": 1.0, "window_s": 2.0, "host": (0.0, 1.0),
                      "snap0": snap0, "snap1": snap1,
                      "op_s": {"gmm": 0.01, "gmm.7": 0.01, "fusion": 9.0,
                               "flash_fwd": 0.06, "flash_fwd.3": 0.04},
                      "op_n": {}, "module_s": {"jit_prefill_fn": 0.6},
                      "module_n": {"jit_prefill_fn": 2.0}},
               requests=[{"prompt_len": 7168, "first": 0.4},
                         {"prompt_len": 7168, "first": 0.9},
                         {"prompt_len": 7168, "first": 1.5}])
    entries = [m for m in real.per_layer
               if m["workloads"] == [CELL]]
    assert len(entries) == 8
    got = {k: v["value"] for k, v in
           run.read_metrics(entries, ctx, real.root).items()}
    assert got["moe_expert_load_max_over_mean"] == pytest.approx(30 / 20)
    # 80 pairs of 40 tokens x 2 layers x 8 chosen
    assert got["moe_held_pair_share"] == pytest.approx(100 * 80 / 640)
    assert got["kv_window_pages_per_slot_peak"] == 33
    assert got["sched_release_window_ms_per_dispatch"] == \
        pytest.approx(1e3 * 0.1 / 50)
    # 80 pairs; 12 experts touched by decode steps and 2 prefills x 4
    # layers x 16 held; bytes bound: 140 x 100.7 MB over 819 GB/s
    work = real.family.moe_expert_work(ctx, 80, 12 + 2 * 4 * 16)
    least = max(work["flops"] / ctx["peak"]["bf16_flops_per_s"],
                work["bytes"] / ctx["peak"]["hbm_bytes_per_s"])
    assert got["moe_expert_roofline"] == pytest.approx(100 * least / 0.02)
    assert got["prefill_pass_dev_ms"] == pytest.approx(300.0)
    # two prompts of 7,168 got their first token while the trace ran:
    # the family's operations over the prefill programs' time x peak,
    # and the flash kernel's least time (compute bound) over its own
    fam, peak = real.family, ctx["peak"]["bf16_flops_per_s"]
    assert got["prefill_pass_mfu"] == pytest.approx(
        100 * 2 * fam.prefill_flops(ctx, 7168) / (0.6 * peak))
    flash = sum(w["flops"] for w in fam.flash_fwd_work(ctx, 1, 7168))
    assert got["prefill_pass_flash_roofline"] == pytest.approx(
        100 * 2 * flash / peak / 0.1)
    assert 40 < got["prefill_pass_mfu"] < 70
    assert 40 < got["prefill_pass_flash_roofline"] < 70
    # a program that has none of what this PR adds (the parent): the
    # five readers of its counters and span find nothing, and none
    # raises; the three of the prefill program read any program's
    bare = dict(ctx, snap0={"dispatches": 1, "phases": {}},
                snap1={"dispatches": 2, "phases": {}},
                trace=dict(ctx["trace"],
                           snap0={"prefill_tokens": 0},
                           snap1={"prefill_tokens": 2 * 7168}))
    assert set(run.read_metrics(entries, bare, real.root)) == {
        "prefill_pass_dev_ms", "prefill_pass_mfu",
        "prefill_pass_flash_roofline"}
