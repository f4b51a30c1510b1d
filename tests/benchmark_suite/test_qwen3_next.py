"""The `qwen3_next` family's files: the configuration's widths against
the published ones and its cuts against `reckoned_bytes`, its counts
against hand numbers, its cell at the tests' small size run by the
harness to a `correct` line with the fp8 control not correct, and the
four new readers on hand-made snapshots and a hand-made trace."""

import json

import pytest

from benchmark import manifest, run
from tests.benchmark_suite import tiny, tiny_hybrid

CELL = "qwen3next-ep8-agent-long"
SEED = 2 ** 31 + 3505


@pytest.fixture(scope="module")
def real():
    return manifest.load_cell(CELL)


def _ctx(cell, **more):
    return dict({"config": cell.config, "family": cell.family,
                 "itemsize": 2, "traffic": cell.traffic}, **more)


# ------------------------------------------------------------ the files
def test_the_cell_loads_with_its_family_its_widths_and_its_share(real):
    assert real.config["family"] == "qwen3_next" and real.chips == 1
    s = real.family.sizes(real.config)
    assert (s["d_model"], s["n_heads"], s["n_kv_heads"], s["head_dim"],
            s["rotary_dim"], s["d_ff"], s["d_shared"], s["k"],
            s["lin_k_heads"], s["lin_v_heads"], s["lin_k_dim"],
            s["lin_v_dim"], s["conv_kernel"]) == \
        (2048, 16, 2, 256, 64, 512, 512, 10, 16, 32, 128, 128, 4)
    assert s["kinds"] == ("linear", "linear", "linear", "full") * 3
    assert (s["n_held"], s["router_width"], s["vocab_size"],
            s["max_len"]) == (64, 512, 18992, 8192)
    names = {m["name"] for m in real.per_layer}
    assert {"decode_step_mfu", "decode_mfu", "decode_hbm_share",
            "paged_decode_attention_roofline", "kv_peak_page_share",
            "serve_hbm_peak_gb", "gdn_update_dev_ms",
            "gdn_update_roofline", "gdn_scan_roofline",
            "cache_state_gb"} <= names
    assert {m["name"] for m in real.end_to_end} == \
        {"out_tok_s", "itl_p98_ms", "setup_s"}
    # the eight metrics ep8 alone lists stay ep8's (PERF.md section 7)
    assert not {"moe_expert_roofline", "prefill_pass_dev_ms",
                "prefill_pass_mfu"} & names
    cfg = real.family.model_config(real.config)
    assert cfg.conv_channels == 8192 and cfg.rope_theta == 1e7
    assert (cfg.router_score, cfg.shared_combine) == ("softmax",
                                                      "sigmoid_gate")


def test_every_published_number_stands_and_every_cut_is_listed(real):
    """Against the catalog row's own `config` (copied here, the guide's
    file is not the repository's): every number as published but the
    four cuts, which `published` holds with their reasons."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "vocab_size": 151936}
    cfg = real.config
    cuts = {"num_hidden_layers": 12, "num_experts": 64,
            "vocab_size": 18992, "max_position_embeddings": 8192}
    for key, value in published.items():
        assert cfg[key] == cuts.get(key, value), key
    assert cfg["published"] == {k: published[k] for k in cuts}
    assert sorted(cfg["reduced_why"]) == sorted(cuts)
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "qwen3-next-80b-a3b-ep8")
    assert sorted(entry["reduced"]) == sorted(cuts)
    assert cfg["model_type"] == "qwen3_next" and cfg["norm_topk_prob"]
    assert cfg["tie_word_embeddings"] is False
    # the floors: whole periods and four layers, 8 experts, an eighth
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    assert cfg["num_experts"] * 8 == 512 and 18992 * 8 == 151936
    for key in ("deployment", "serving_why", "assumed", "departures"):
        assert cfg[key], key


def test_the_warm_set_covers_every_group_the_schedule_can_form(real):
    """The closed loop's 64 clients start at once, but a pass claims one
    row of the 8,192 bucket, so (1, 8192) is the one prefill group there
    can be; and no client runs out of requests inside a run."""
    from benchmark import schedule

    srv = real.config["serving"]
    buckets = real.family.prompt_buckets(8192, srv["page_size"])
    assert buckets == (128, 256, 512, 1024, 2048, 4096, 8192)
    plan = schedule.warm_groups(real.traffic, 51, srv["slots"], buckets)
    assert plan["buckets"] == [8192] and max(plan["sizes"]) == 64
    assert real.family.warm_requests(real.config, real.traffic, 51) == \
        [(1, 8190)]
    from deeplearning4j_tpu.serving.paged_kv import prompt_buckets

    assert prompt_buckets(real.family.model_config(real.config),
                          srv["page_size"]) == buckets
    rows = schedule.closed_loop(real.traffic)
    assert len(rows) == 64 and all(len(r) == 16 for r in rows)
    # at 10 ms a token a client needs ~8,100 tokens for warm-up and
    # window; its 16 requests hold at least 11,000
    assert min(sum(r.output_len for r in row) for row in rows) > 11000
    assert all(r.prompt_len + r.output_len <= 8192
               for row in rows for r in row)


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

    assert count(shapes) == fam.params_total(cfg) == reck["parameters"] \
        == 2929374400
    assert 2 * reck["parameters"] == reck["weights_bf16"]
    p = fam.layer_params(cfg)
    # by hand (ISSUE 35): 2048 x 12,288 + 2048 x 64 + 8,192 x 4 + 4096 x
    # 2048 + 32 + 32 + 128; 2048 x 8,192 + 2 x 2048 x 512 + 4096 x 2048 +
    # 512; 2048 x 512 + 3 x 2048 x 512 + 2,048 + 4,096
    assert p["linear"] == reck["parameters_a_linear_mixer"] == 33718464
    assert p["full"] == reck["parameters_a_full_mixer"] == 27263488
    assert p["router"] + p["shared"] + p["gains"] == 4200448 == \
        reck["parameters_a_layer_outside_mixer_and_routed_experts"]
    assert p["expert"] == reck["parameters_a_routed_expert"] == 3145728
    assert shapes["blocks"][0]["W_qkvz"] == (2048, 12288)
    assert shapes["blocks"][0]["conv"] == (4, 8192)
    assert shapes["blocks"][3]["Wq"] == (2048, 8192)
    assert shapes["blocks"][3]["experts"]["gate"] == (64, 2048, 512)
    assert shapes["blocks"][0]["router"] == (2048, 512)
    assert shapes["head"] == (2048, 18992) and "pos" not in shapes
    assert "Wq" not in shapes["blocks"][0]
    assert "W_qkvz" not in shapes["blocks"][3]
    ctx = _ctx(real)
    assert fam.kv_bytes_token_layer(ctx) == \
        reck["kv_bytes_per_token_per_layer"] == 2 * 2 * 256 * 2
    srv = cfg["serving"]
    assert srv["kv_pages"] == 64 * 8192 // 128
    assert 3 * (srv["kv_pages"] + 1) * 2048 * 128 == reck["kv_pool"]
    assert fam.state_bytes_slot_layer(ctx) == 32 * 128 * 128 * 4 \
        + 3 * 8192 * 2 == reck["state_bytes_per_slot_per_layer"]
    assert reck["state"] == 64 * 9 * 2146304
    # the whole of it: 64% of the chip
    total = reck["weights_bf16"] + reck["kv_pool"] + reck["state"]
    assert 0.63 < total / 16e9 < 0.66
    # and the program's cache says the same of its state
    from deeplearning4j_tpu.serving.paged_kv import state_bytes_per_slot

    assert state_bytes_per_slot(fam.model_config(cfg)) == 2146304


def test_the_counts_against_hand_numbers(real):
    fam = real.family
    ctx = _ctx(real)
    mixers = 9 * 33718464 + 3 * 27263488
    every = 12 * (2048 * 512 + 3 * 2048 * 512 + 2048)
    head = 2 * 18992 * 2048
    # no counters in ctx: a uniform router, 10 x 64 / 512 pairs a layer
    routed = 12 * 1.25 * 2 * 3145728
    recur = 9 * 32 * 6 * 128 * 128
    assert fam.decode_token_flops(ctx, 7700) == pytest.approx(
        2 * (mixers + every) + routed + head + 3 * 4 * 16 * 256 * 7700
        + recur)
    full = 7168 * 7169 // 2
    assert fam.prefill_flops(ctx, 7168) == pytest.approx(
        (2 * (mixers + every) + routed + recur) * 7168 + head
        + 3 * 4 * 16 * 256 * full)
    # the program's counters: 46 of 64 experts a layer touched a step
    moe0 = {"tokens": 0, "pairs": 0, "decode_tokens": 0,
            "decode_pairs": 0, "decode_steps": 0, "experts_touched": 0}
    moe1 = {"tokens": 9000, "pairs": 130000, "decode_tokens": 6400,
            "decode_pairs": 96000, "decode_steps": 100,
            "experts_touched": 100 * 12 * 46}
    counted = _ctx(real, snap0={"moe": moe0}, snap1={"moe": moe1})
    assert fam.held_pairs_per_token(counted, decode=True) == 15.0
    outside = mixers + 12 * 4200448 + 18992 * 2048 + 2048
    assert fam.decode_step_bytes(counted, [7700, 5000]) == pytest.approx(
        2 * (outside + 12 * 46 * 3145728)
        + 2048 * 3 * (7700 + 5000) + 2 * 2 * 9 * 2146304)
    works = fam.flash_fwd_work(ctx, 1, 7168)
    assert [w["flops"] for w in works] == [4 * 16 * 256 * full] * 3
    assert works[0]["bytes"] == 7168 * (2 * 16 + 2 * 2) * 256 * 2
    calls = fam.paged_decode_attention_work(ctx, [7700, 130])
    assert len(calls) == 3
    page = 2048 * 128
    assert calls[0]["bytes"] == (61 + 2) * page + 2 * 2 * 16 * 256 * 2
    assert calls[0]["flops"] == 4 * 16 * 256 * (7700 + 130)
    # the scan: 11.5 M operations a value head and chunk of 64, the
    # ISSUE's 5.8 M a token a layer; 24.8 KB a token
    scan = fam.gdn_scan_work(ctx, 1, 7168)
    a_token = 32 * (64 * (6 * 128 + 4 * 128) + 6 * 128 * 128)
    assert a_token == 5767168 and scan["flops"] == 7168 * a_token
    assert scan["bytes"] == 7168 * ((2 * 16 + 2 * 32) * 128 * 2
                                    + 2 * 32 * 4) + 32 * 128 * 128 * 4
    assert 220 < scan["flops"] / scan["bytes"] < 240      # on the ridge
    upd = fam.gdn_update_work(ctx, 64)
    assert upd["flops"] == 64 * 32 * 6 * 128 * 128
    assert upd["bytes"] == 64 * (2 * 32 * 128 * 128 * 4
                                 + (2 * 32 + 32) * 128 * 2 + 32 * 128 * 4)
    work = fam.moe_expert_work(ctx, 1000, 50)
    assert work["flops"] == 6 * 2048 * 512 * 1000
    assert fam.MOE_EXPERT_OPS == ("gmm",)


def test_nothing_trains_and_what_is_off_stays_off(real):
    for fn in (real.family.make_train_step, real.family.train_flops_token,
               real.family.reference().loss_and_grad):
        with pytest.raises(NotImplementedError, match="trains nothing"):
            fn(real.config, None)
    for key, on in (("prefix_cache", True), ("speculation", 2),
                    ("horizon", 2)):
        with pytest.raises(ValueError, match=key):
            real.family.build_engine(
                dict(real.config, serving=dict(real.config["serving"],
                                               **{key: on})), None)


def test_the_reference_imports_nothing_of_the_program(real):
    with open(real.family.reference().__file__) as f:
        text = f.read()
    assert "import deeplearning4j_tpu" not in text
    assert "from deeplearning4j_tpu" not in text
    assert "lax.scan(step" in text          # token by token, no chunks


# ------------------------------------------------- the cell, at a small size
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tiny.build(str(tmp_path_factory.mktemp("hybrid")))
    tiny_hybrid.add(root)
    return root


@pytest.fixture(scope="module")
def tiny_line(tiny_root):
    cell = manifest.load_cell(tiny_hybrid.CELL, tiny_root)
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
    """The reference in the program's place, by the kind of limit the
    real cell has. Read at this size (CPU, four seeds): the bf16 program
    0-2 of 173 served tokens off the float32 reference's best, the bf16
    reference in its place 0, 0, 2, 1 of 224, the fp8 control 12, 10, 9,
    11."""
    from benchmark import check

    cell = manifest.load_cell(tiny_hybrid.CELL, tiny_root)
    assert set(cell.limits) == {"tokens_off_best"} == \
        set(manifest.load_cell(CELL).limits)
    n = cell.traffic["check_requests"]
    assert tiny_line["numbers"]["requests_compared"] == n
    assert tiny_line["compared"]["tokens_off_best"]["value"] <= 4
    sample = tiny.greedy_sample(cell, SEED, n, 21, 14)
    numbers = check.serve_numbers(cell, SEED, sample, ("fp8",))
    assert numbers["tokens_off_best"] == 0
    assert check.verdict(numbers, cell.limits)["correct"] is True
    low = numbers["control_fp8_tokens_off_best"]
    assert low >= 2 * cell.limits["tokens_off_best"], numbers
    assert check.verdict({"tokens_off_best": low},
                         cell.limits)["correct"] is False


# ------------------------------------------------------ the new readers
def test_the_new_readers_on_hand_made_snapshots_and_a_trace(real):
    state = {"bytes": 1236271104, "bytes_per_slot": 19316736, "layers": 9,
             "slots_live": 64}
    snap0 = {"dispatches": 100, "prefill_tokens": 0, "state": state}
    snap1 = {"dispatches": 150, "prefill_tokens": 2 * 7168,
             "state": state}
    # 50 dispatches of 64 tokens: requests whose tokens 1.. fall in the
    # traced second; two prompts got their first token in it
    requests = [{"prompt_len": 7168, "first": -1.0,
                 "times": [-1.0] + [0.01 + 0.0199 * i for i in range(50)]}
                for _ in range(64)]
    requests += [{"prompt_len": 7168, "first": 0.4, "times": [0.4]},
                 {"prompt_len": 7168, "first": 0.9, "times": [0.9]},
                 {"prompt_len": 7168, "first": 1.5, "times": [1.5]}]
    ctx = _ctx(real, snap0=snap0, snap1=snap1, window=(0.0, 1.0),
               peak=manifest.load_peak("TPU v5 lite"),
               trace={"busy_s": 1.0, "window_s": 1.0, "host": (0.0, 1.0),
                      "snap0": snap0, "snap1": snap1,
                      "op_s": {"gdn_update": 0.2, "gdn_update.3": 0.1,
                               "gdn_scan": 0.09, "fusion": 9.0},
                      "op_n": {"gdn_update": 300.0, "gdn_update.3": 150.0,
                               "gdn_scan": 18.0},
                      "module_s": {}, "module_n": {}},
               requests=requests)
    entries = [m for m in real.per_layer if m["workloads"] == [CELL]]
    assert [m["name"] for m in entries] == [
        "gdn_update_dev_ms", "gdn_update_roofline", "gdn_scan_roofline",
        "cache_state_gb"]
    assert {m["layer"] for m in entries[:3]} == {
        "linear-attention layer models/hybrid_transformer.py"}
    assert entries[3]["layer"] == "cache serving/paged_kv.py"
    got = {k: v["value"] for k, v in
           run.read_metrics(entries, ctx, real.root).items()}
    assert got["cache_state_gb"] == pytest.approx(1.236271104)
    # 0.3 s of the update over 50 dispatches: 6 ms a dispatch, 9 calls
    assert got["gdn_update_dev_ms"] == pytest.approx(6.0)
    # 450 calls of 64 live slots: the state twice and the rows, 819 GB/s
    work = real.family.gdn_update_work(ctx, 64.0)
    least = 450 * work["bytes"] / 819e9
    assert got["gdn_update_roofline"] == pytest.approx(100 * least / 0.3)
    assert 45 < got["gdn_update_roofline"] < 55
    # 18 calls (two prefills x nine layers) of 7,168 tokens
    scan = real.family.gdn_scan_work(ctx, 1, 7168.0)
    least = 18 * max(scan["flops"] / 197e12, scan["bytes"] / 819e9)
    assert got["gdn_scan_roofline"] == pytest.approx(100 * least / 0.09)
    assert 3 < got["gdn_scan_roofline"] < 6
    # a program that has none of what this PR adds (the parent), or a
    # cell of another family: the readers find nothing and none raises
    bare = dict(ctx, snap0={"dispatches": 1}, snap1={"dispatches": 2},
                trace=dict(ctx["trace"], op_s={"fusion": 1.0}, op_n={}))
    assert run.read_metrics(entries, bare, real.root) == {}
    other = manifest.load_cell("cmdaplus-ep8-agent-long")
    assert run.read_metrics(entries, dict(ctx, family=other.family),
                            real.root) == {
        "cache_state_gb": {"value": pytest.approx(1.236271104),
                           "unit": "GB"}}
