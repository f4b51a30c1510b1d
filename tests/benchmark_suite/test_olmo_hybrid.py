"""The `olmo_hybrid` family's files: the configuration's widths against
the published ones and its cuts against `reckoned_bytes`, its counts
against hand numbers, its cell at the tests' small size run by the
harness to a `correct` line with the fp8 control not correct, the six
new readers on hand-made snapshots and a hand-made trace, and the warm
set against every program the schedule can reach."""

import json

import pytest

from benchmark import manifest, run, schedule
from tests.benchmark_suite import tiny, tiny_olmo

CELL = "olmohyb7b-docs-chunked"
SEED = 2 ** 31 + 3707
NEW = ["prefill_chunk_mfu", "prefill_chunk_dev_ms_per_ktok",
       "prefill_scan_carried_roofline", "prefill_ctx_flash_roofline",
       "decode_state_update_roofline", "sched_prefill_carried_chunk_share"]


@pytest.fixture(scope="module")
def real():
    return manifest.load_cell(CELL)


def _ctx(cell, **more):
    return dict({"config": cell.config, "family": cell.family,
                 "itemsize": 2, "traffic": cell.traffic}, **more)


# ------------------------------------------------------------ the files
def test_the_cell_loads_with_its_family_and_its_widths(real):
    assert real.config["family"] == "olmo_hybrid" and real.chips == 1
    s = real.family.sizes(real.config)
    assert (s["d_model"], s["n_heads"], s["n_kv_heads"], s["head_dim"],
            s["d_ff"], s["lin_k_heads"], s["lin_v_heads"], s["lin_k_dim"],
            s["lin_v_dim"], s["conv_kernel"], s["neg_eigval"]) == \
        (3840, 30, 30, 128, 11008, 30, 30, 96, 192, 4, True)
    assert s["kinds"] == ("linear", "linear", "linear", "full") * 3
    assert (s["vocab_size"], s["max_len"]) == (100352, 16384)
    names = [m["name"] for m in real.per_layer]
    assert names[-6:] == NEW
    assert {"decode_step_mfu", "decode_mfu", "decode_hbm_share",
            "paged_decode_attention_roofline", "kv_peak_page_share",
            "serve_hbm_peak_gb", "serve_dev_idle_share"} <= set(names)
    assert {m["name"] for m in real.end_to_end} == \
        {"out_tok_s", "itl_p98_ms", "setup_s"}
    # the lists pinned to q3n and to ep8 alone stay theirs (PERF.md 7)
    assert not {"gdn_update_roofline", "gdn_scan_roofline",
                "cache_state_gb", "moe_expert_roofline",
                "prefill_pass_mfu"} & set(names)
    cfg = real.family.model_config(real.config)
    assert (cfg.norm_place, cfg.qk_norm, cfg.attn_gate, cfg.rotary_dim,
            cfg.allow_neg_eigval, cfg.n_experts, cfg.n_held) == \
        ("post", "width", False, 0, True, 0, 0)
    assert cfg.conv_channels == 11520 and cfg.rms_eps == 1e-6
    traffic = real.traffic
    assert (traffic["clients"], traffic["requests_per_client"],
            traffic["warmup_s"], traffic["check_requests"],
            traffic["stagger_first"]) == (16, 48, 15, 16, True)
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                     "sigma": 0.6, "min": 1024,
                                     "max": 15360}
    assert traffic["output_len"]["values"] == [32, 64, 96, 128, 160]
    assert traffic["trace"] == {"start_s": 8, "seconds": 6}


def test_every_published_number_stands_and_every_cut_is_listed(real):
    """Against the catalog row's own `config` (copied here, the guide's
    file is not the repository's): every number as published but the
    three cuts, which `published` holds with their reasons."""
    period = ["linear_attention"] * 3 + ["full_attention"]
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": period * 8, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    cfg = real.config
    cuts = {"num_hidden_layers": 12, "layer_types": period * 3,
            "max_position_embeddings": 16384}
    for key, value in published.items():
        assert cfg[key] == cuts.get(key, value), key
    assert cfg["layer_types"] == published["layer_types"][:12]
    assert sorted(cfg["published"]) == sorted(cfg["reduced_why"]) == \
        sorted(cuts)
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["published"]["max_position_embeddings"] == 65536
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "olmo-hybrid-7b-l12")
    assert sorted(entry["reduced"]) == sorted(cuts)
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    # no width among the cuts; whole periods; the vocabulary whole
    assert not [k for k in cuts if k.endswith(("_dim", "_size", "_heads"))]
    assert cfg["num_hidden_layers"] % 4 == 0
    assert sorted(cfg["assumed"]) == [
        "column_order", "float32", "gated_delta_rule", "head_dim",
        "no_rotation", "norm_placement", "serving", "weights"]
    for key in ("deployment", "serving_why", "departures"):
        assert cfg[key], key


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
        == 3268268508
    assert 2 * reck["parameters"] == reck["weights_bf16"] == 6536537016
    p = fam.layer_params(cfg)
    # by hand (ISSUE 37): 3840 x 17,280 + 3840 x 60 + 4 x 11,520 + 5760 x
    # 3840 + 60 + 192; 4 x 3840 x 3840 + 2 x 3840; 3 x 3840 x 11,008
    assert p["linear"] == reck["parameters_a_linear_mixer"] == 88750332
    assert p["full"] == reck["parameters_a_full_mixer"] == 58990080
    assert p["ff"] == reck["parameters_a_feed_forward"] == 126812160
    assert reck["parameters_embedding_and_head"] == 770703360
    assert shapes["blocks"][0]["W_qkvz"] == (3840, 17280)
    assert shapes["blocks"][0]["W_ba"] == (3840, 60)
    assert shapes["blocks"][0]["conv"] == (4, 11520)
    assert shapes["blocks"][0]["norm"] == {"g": (192,)}
    assert shapes["blocks"][3]["Wq"] == (3840, 3840)       # no gate
    assert shapes["blocks"][3]["q_norm"] == {"g": (3840,)}  # whole width
    assert shapes["blocks"][3]["W_gate"] == (3840, 11008)
    assert "router" not in shapes["blocks"][0]
    assert shapes["head"] == (3840, 100352) and "pos" not in shapes
    ctx = _ctx(real)
    assert fam.kv_bytes_token_layer(ctx) == 15360 == \
        reck["kv_bytes_per_token_per_layer"]
    assert reck["kv_bytes_per_token"] == 46080
    srv = cfg["serving"]
    assert (srv["slots"], srv["page_size"], srv["kv_pages"],
            srv["prefill_tokens_per_pass"]) == (16, 128, 1024, 4096)
    assert 3 * (srv["kv_pages"] + 1) * 15360 * 128 == reck["kv_pool"]
    assert fam.state_bytes_slot_layer(ctx) == 2211840 + 69120 == \
        reck["state_bytes_per_slot_per_layer"] \
        + reck["kept_columns_bytes_per_slot_per_layer"]
    assert reck["state"] == 16 * 9 * 2280960
    total = reck["weights_bf16"] + reck["kv_pool"] + reck["state"]
    assert total == reck["weights_pool_and_state"]
    assert 0.80 < total / 16e9 < 0.82
    from deeplearning4j_tpu.serving.paged_kv import (prompt_buckets,
                                                     state_bytes_per_slot)

    model = fam.model_config(cfg)
    assert state_bytes_per_slot(model) == 2280960
    assert prompt_buckets(model, 128) == fam.prompt_buckets(16384, 128)


def test_the_counts_against_hand_numbers(real):
    fam = real.family
    ctx = _ctx(real)
    body = 2 * (9 * 88750332 + 3 * 58990080 + 12 * 126812160)
    head = 2 * 100352 * 3840
    recur = 9 * 30 * 6 * 96 * 192
    scores = 3 * 4 * 30 * 128
    assert fam.decode_token_flops(ctx, 5000) == body + head \
        + scores * 5000 + recur
    # a prompt in one piece, and in three: the sum is the whole prompt's
    assert fam.pieces_of(real.config, 3000) == [(0, 3000)]
    assert fam.pieces_of(real.config, 9000) == [(0, 4096), (4096, 4096),
                                                (8192, 808)]
    for n in (3000, 9000, 15360):
        assert fam.prefill_flops(ctx, n) == (body + recur) * n + head \
            + scores * (n * (n + 1) // 2)
    # the second piece of the 9,000: 4,096 queries that see 4,096 keys of
    # context and their own causal half; no head
    assert fam.piece_flops(ctx, 4096, 4096, False) == \
        (body + recur) * 4096 + scores * (4096 * 4096 + 4096 * 4097 // 2)
    flash = fam.ctx_flash_work(ctx, 8192, 808)
    assert flash["flops"] == 4 * 30 * 128 * (8192 * 808 + 808 * 809 // 2)
    assert flash["bytes"] == (2 * 808 * 30 + 2 * 9000 * 30) * 128 * 2
    works = fam.flash_fwd_work(ctx, 1, 4096)
    assert len(works) == 3 and works[0]["bytes"] == 4096 * 120 * 128 * 2
    # the scan at the PUBLISHED widths, whatever the kernel pads to:
    # 30 heads x (64 x (6 x 96 + 4 x 192) + 6 x 96 x 192) a token
    a_token = 30 * (64 * (6 * 96 + 4 * 192) + 6 * 96 * 192)
    assert a_token == 5898240
    cold = fam.gdn_scan_work(ctx, 1, 4096)
    kept = fam.gdn_scan_work(ctx, 1, 4096, carried=True)
    assert cold["flops"] == kept["flops"] == 4096 * a_token
    rows = 4096 * ((2 * 30 * 96 + 2 * 30 * 192) * 2 + 2 * 30 * 4)
    assert cold["bytes"] == rows + 2211840
    assert kept["bytes"] == rows + 2 * 2211840      # the state read too
    upd = fam.gdn_update_work(ctx, 14)
    assert upd["flops"] == 14 * 30 * 6 * 96 * 192
    assert upd["bytes"] == 14 * (2 * 2211840 + (2 * 30 * 96 + 30 * 192)
                                 * 2 + 30 * 192 * 4)
    weights = 3268268508 - 100352 * 3840
    assert fam.decode_step_bytes(ctx, [5000, 9000]) == 2 * weights \
        + 15360 * 3 * 14000 + 2 * 2 * 9 * 2280960
    calls = fam.paged_decode_attention_work(ctx, [5000, 130])
    assert len(calls) == 3
    assert calls[0]["bytes"] == (40 + 2) * 15360 * 128 \
        + 2 * 2 * 30 * 128 * 2
    assert calls[0]["flops"] == 4 * 30 * 128 * 5130


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
    assert "precision=HIGHEST" in text and "bfloat16)" not in text


# ------------------------------------- what the warm-up has to reach
def test_the_warm_set_covers_every_program_the_schedule_can_reach(real):
    """The accepted `test_schedule.py` case builds every mix's warm set
    from GPT-2's buckets for 2,048 positions (PERF.md 7 (b)); this is the
    same check with the family's own buckets and the bound on a pass.
    Every pass of the schedule is reckoned: the rows of whole prompts
    and first pieces a pass can claim under the bound, and the bucket of
    every later piece."""
    fam, cfg, traffic = real.family, real.config, real.traffic
    srv = cfg["serving"]
    piece = srv["prefill_tokens_per_pass"]
    buckets = fam.prompt_buckets(16384, srv["page_size"])
    assert buckets == (128, 256, 512, 1024, 2048, 4096, 8192, 16384)
    plan = fam.reachable_programs(cfg, traffic, 51)
    rows = schedule.closed_loop(traffic)
    assert len(rows) == 16 and all(len(r) == 48 for r in rows)
    assert all(1024 <= r.prompt_len <= 15360
               and r.prompt_len + r.output_len <= 16384
               for row in rows for r in row)
    cold, carried = set(), set()
    for row in rows:
        for r in row:
            cuts = fam.pieces_of(cfg, r.prompt_len)
            tb = schedule.bucket_of(cuts[0][1], buckets)
            # beside it a pass can hold whatever else the bound admits
            for n in range(1, max(1, piece // tb) + 1):
                cold.add((n, tb))
            for _at, n in cuts[1:]:
                carried.add(schedule.bucket_of(
                    max(n, piece // fam.LAST_PIECE_FLOOR), buckets))
    assert cold <= set(plan["cold"]) and carried <= set(plan["carried"])
    assert plan["carried"] == [1024, 2048, 4096]
    assert len(plan["cold"]) == 7          # 4 + 2 + 1 under 4,096
    warm = fam.warm_requests(cfg, traffic, 51)
    assert warm[:7] == [(n, tb) for n, tb in plan["cold"]]
    assert warm[7:] == [(1, 5120), (1, 6144), (1, 8192), (1, 15360)]
    # the pieces the warm prompts are cut into run every carried bucket
    warmed = {schedule.bucket_of(max(n, piece // 4), buckets)
              for _, plen in warm for _at, n in
              fam.pieces_of(cfg, plen)[1:]}
    assert warmed == set(plan["carried"])
    # no client runs out: 48 requests hold 4,466 output tokens and more,
    # a run serves a client ~13 requests
    assert min(sum(r.output_len for r in row) for row in rows) >= 4466
    # half the prompts need two to four pieces, all but the first on a
    # kept state
    n_pieces = [len(fam.pieces_of(cfg, r.prompt_len))
                for row in rows for r in row]
    assert 0.45 < sum(n > 1 for n in n_pieces) / len(n_pieces) < 0.55
    assert max(n_pieces) == 4


# ------------------------------------------------- the cell, at a small size
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tiny.build(str(tmp_path_factory.mktemp("olmo")))
    tiny_olmo.add(root)
    return root


@pytest.fixture(scope="module")
def tiny_line(tiny_root):
    cell = manifest.load_cell(tiny_olmo.CELL, tiny_root)
    return run.execute(cell, SEED, 1.0, False, require_chip=False)


def test_the_cell_runs_to_a_correct_line(tiny_root, tiny_line):
    line = tiny_line
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["numbers"]["tokens_compared"] >= 20
    assert set(line["metrics"]) == {"setup_s", "out_tok_s", "itl_p98_ms"}
    assert line["detail"]["jax_programs_in_window"] == 0
    # prompts of 8-110 tokens under a bound of 32: pieces were served
    assert (1, 32) in line["detail"]["prefill_groups"]
    json.dumps(line)


def test_the_fp8_control_is_not_correct(tiny_root, tiny_line):
    """The reference in the program's place, by the kind of limit the
    real cell has (a count of served tokens off the float32 reference's
    best)."""
    from benchmark import check

    cell = manifest.load_cell(tiny_olmo.CELL, tiny_root)
    assert set(cell.limits) == {"tokens_off_best"} == \
        set(manifest.load_cell(CELL).limits)
    n = cell.traffic["check_requests"]
    assert tiny_line["numbers"]["requests_compared"] == n
    assert tiny_line["compared"]["tokens_off_best"]["value"] <= 4
    sample = tiny.greedy_sample(cell, SEED, n, 40, 8)
    numbers = check.serve_numbers(cell, SEED, sample, ("fp8",))
    assert numbers["tokens_off_best"] == 0
    assert check.verdict(numbers, cell.limits)["correct"] is True
    low = numbers["control_fp8_tokens_off_best"]
    assert low >= 2 * cell.limits["tokens_off_best"], numbers
    assert check.verdict({"tokens_off_best": low},
                         cell.limits)["correct"] is False


# ------------------------------------------------------ the new readers
def test_the_new_readers_on_hand_made_snapshots_and_a_trace(real):
    """One traced second: two prompts of 9,000 got their first token in
    it (three pieces each, two on a kept state), 14 streams decoded 50
    steps."""
    def snap(dispatches, tokens, first, carried):
        return {"dispatches": dispatches, "prefill_tokens": tokens,
                "prefill_chunks": {"first": first, "carried": carried,
                                   "tokens": tokens}}

    snap0, snap1 = snap(100, 0, 0, 0), snap(150, 18000, 2, 4)
    requests = [{"prompt_len": 5000, "first": -1.0,
                 "times": [-1.0] + [0.01 + 0.0199 * i for i in range(50)]}
                for _ in range(14)]
    requests += [{"prompt_len": 9000, "first": 0.4, "times": [0.4]},
                 {"prompt_len": 9000, "first": 0.9, "times": [0.9]},
                 {"prompt_len": 9000, "first": 1.5, "times": [1.5]}]
    ctx = _ctx(real, snap0=snap0, snap1=snap1, window=(0.0, 1.0),
               peak=manifest.load_peak("TPU v5 lite"),
               trace={"busy_s": 1.0, "window_s": 1.0, "host": (0.0, 1.0),
                      "snap0": snap0, "snap1": snap1,
                      "op_s": {"gdn_update": 0.05, "gdn_scan": 0.12,
                               "prefill_ctx_flash": 0.03, "fusion": 9.0},
                      "op_n": {"gdn_update": 450.0, "gdn_scan": 54.0,
                               "prefill_ctx_flash": 12.0},
                      "module_s": {"jit_prefill_fn": 0.45,
                                   "jit_prefill_chunk_fn": 0.55,
                                   "jit_step_fn": 0.9},
                      "module_n": {"jit_prefill_fn": 2.0,
                                   "jit_prefill_chunk_fn": 4.0,
                                   "jit_step_fn": 50.0}},
               requests=requests)
    entries = [m for m in real.per_layer if m["workloads"] == [CELL]]
    assert [m["name"] for m in entries] == NEW
    assert [m["moves"] for m in entries] == [
        "out_tok_s", "itl_p98_ms", "out_tok_s", "itl_p98_ms", "out_tok_s",
        "itl_p98_ms"]
    layers = {m["name"]: m["layer"] for m in
              manifest.load_manifest()["per_layer"]}
    assert layers["prefill_chunk_mfu"] == layers["prefill_mfu"]
    assert layers["prefill_scan_carried_roofline"] == \
        layers["decode_state_update_roofline"] == \
        layers["gdn_scan_roofline"]
    assert layers["prefill_ctx_flash_roofline"] == \
        layers["prefill_flash_fwd_roofline"]
    assert layers["sched_prefill_carried_chunk_share"] == \
        layers["sched_tok_per_dispatch"]
    got = {k: v["value"] for k, v in
           run.read_metrics(entries, ctx, real.root).items()}
    assert sorted(got) == sorted(NEW)
    fam = real.family
    # two prompts of 9,000 over one second of every prefill program
    ops = 2 * fam.prefill_flops(ctx, 9000)
    assert got["prefill_chunk_mfu"] == pytest.approx(
        100 * ops / (1.0 * 197e12))
    assert 45 < got["prefill_chunk_mfu"] < 50
    assert got["prefill_chunk_dev_ms_per_ktok"] == pytest.approx(
        1000 / 18.0)
    # 54 calls = 6 programs x 9 layers of 3,000 tokens, 4 of 6 carried
    cold = fam.gdn_scan_work(ctx, 1, 3000.0)
    kept = fam.gdn_scan_work(ctx, 1, 3000.0, carried=True)
    def least(work):
        return max(work["flops"] / 197e12, work["bytes"] / 819e9)

    # 169 operations a byte at 96 x 192, under the chip's 240: the
    # scan's least time is its bytes', and a kept state adds to them
    assert least(kept) == kept["bytes"] / 819e9 > least(cold)
    assert got["prefill_scan_carried_roofline"] == pytest.approx(
        100 * 54 * (4 / 6 * least(kept) + 2 / 6 * least(cold)) / 0.12)
    assert 5 < got["prefill_scan_carried_roofline"] < 7
    # 12 calls = 4 pieces x 3 layers: (4,096 on 4,096) and (808 on 8,192)
    mean = (least(fam.ctx_flash_work(ctx, 4096, 4096))
            + least(fam.ctx_flash_work(ctx, 8192, 808))) / 2
    assert got["prefill_ctx_flash_roofline"] == pytest.approx(
        100 * 12 * mean / 0.03)
    assert got["prefill_ctx_flash_roofline"] < 100
    # 450 calls of 14 live slots: the state twice and the rows
    work = fam.gdn_update_work(ctx, 14.0)
    assert got["decode_state_update_roofline"] == pytest.approx(
        100 * 450 * work["bytes"] / 819e9 / 0.05)
    assert 60 < got["decode_state_update_roofline"] < 100
    assert got["sched_prefill_carried_chunk_share"] == pytest.approx(
        100 * 4 / 6)
    # a program that has none of what this PR adds (the parent): the
    # readers find nothing and none raises
    old = {"dispatches": 1, "prefill_tokens": 0}
    bare = dict(ctx, snap0=old, snap1=dict(old, dispatches=2),
                trace=dict(ctx["trace"], snap0=old, snap1=old,
                           op_s={"fusion": 1.0}, op_n={}, module_s={},
                           module_n={}))
    assert run.read_metrics(entries, bare, real.root) == {}
    # and a cell of another family reads none of the five that ask the
    # family for a count it does not have
    other = manifest.load_cell("cgpt13b-decode-sat")
    assert set(run.read_metrics(
        entries, dict(ctx, family=other.family, config=other.config),
        real.root)) == {"sched_prefill_carried_chunk_share"}
