"""The `lfm2_moe` family's files: the configuration's numbers against
the published ones and its cuts against `reckoned_bytes`, its six
answers and its counts against hand numbers, the cell's metric lists
(joined, never pinned), its cell at the tests' small size run by the
harness to a `correct` line that a wrong sample cannot read, and the
warm set against every program the schedule can reach."""

import json

import pytest

from benchmark import manifest, run, schedule
from tests.benchmark_suite import tiny, tiny_lfm2

CELL = "lfm2moe-agent-mid-sat64"
SEED = 2 ** 31 + 4101
#: the lists of the accepted cells that this cell joins: the 18 that
#: sat, ep8, q3n and olm all report
JOINED = ["itl_p50_ms", "itl_p95_ms", "itl_p99_ms",
          "sched_tok_per_dispatch", "sched_dispatch_ms_mean",
          "sched_programs_in_window", "kv_peak_page_share",
          "decode_step_dev_ms", "decode_mfu", "decode_hbm_share",
          "decode_step_mfu", "paged_decode_attention_roofline",
          "serve_dev_idle_share", "serve_hbm_peak_gb",
          "sched_host_ms_per_dispatch", "sched_prefill_dispatch_share",
          "sched_tick_max_ms", "serve_idle_unattributed_share"]
#: the catalog row's `config` (the guide's file is not the
#: repository's): every number as published
PERIOD = ["full_attention", "conv", "conv", "conv"]
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + PERIOD * 9 + ["full_attention",
                                                    "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}


@pytest.fixture(scope="module")
def real():
    return manifest.load_cell(CELL)


def _ctx(cell, **more):
    return dict({"config": cell.config, "family": cell.family,
                 "itemsize": 2, "traffic": cell.traffic}, **more)


# ------------------------------------------------------------ the files
def test_every_published_number_stands_and_every_cut_is_listed(real):
    cfg = real.config
    assert PUBLISHED["layer_types"].count("conv") == 30
    cuts = {"num_hidden_layers": 10,
            "layer_types": PUBLISHED["layer_types"][:10],
            "max_position_embeddings": 8192}
    for key, value in PUBLISHED.items():
        assert cfg[key] == cuts.get(key, value), key
    assert sorted(cfg["published"]) == sorted(cfg["reduced_why"]) == \
        sorted(cuts)
    for key in cuts:
        assert cfg["published"][key] == PUBLISHED[key], key
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "lfm2-24b-a2b-l10")
    assert sorted(entry["reduced"]) == sorted(cuts)
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    # the stage: both dense layers, two whole periods after them, 8 of
    # the 38 layers that follow the dense ones; every expert and the
    # whole vocabulary
    assert cfg["layer_types"] == ["conv", "conv"] + PERIOD * 2
    assert cfg["num_experts"] == 64 and cfg["vocab_size"] == 65536
    assert not [k for k in cuts if k.endswith(("_dim", "_size", "_heads"))]
    assert sorted(cfg["assumed"]) == [
        "conv_state", "expert_bias", "head_dim", "in_proj_order",
        "intermediate_size", "norms", "rotary", "router", "serving",
        "tied_head", "weights"]
    for key in ("deployment", "serving_why", "departures"):
        assert cfg[key], key
    assert "four pipeline stages of 10 layers" in cfg["deployment"]


def test_the_cell_loads_with_its_family_and_its_widths(real):
    assert real.config["family"] == "lfm2_moe" and real.chips == 1
    s = real.family.sizes(real.config)
    assert (s["d_model"], s["n_heads"], s["n_kv_heads"], s["head_dim"],
            s["d_ff"], s["d_dense"], s["n_experts"], s["k"],
            s["conv_kernel"]) == (2048, 32, 8, 64, 1536, 11776, 64, 4, 3)
    assert (s["n_conv"], s["n_full"], s["n_dense"], s["n_moe"]) == \
        (8, 2, 2, 8)
    cfg = real.family.model_config(real.config)
    assert (cfg.norm_place, cfg.qk_norm, cfg.attn_gate, cfg.rotary_dim,
            cfg.rope_theta, cfg.rms_eps) == ("pre", "head", False, 64,
                                              1e6, 1e-5)
    assert (cfg.n_experts, cfg.n_held, cfg.held_first, cfg.n_shared,
            cfg.experts_per_token, cfg.router_score, cfg.router_bias,
            cfg.tied_head) == (64, 64, 0, 0, 4, "sigmoid", True, True)
    assert (cfg.n_dense_layers, cfg.d_ff_dense, cfg.conv_kernel) == \
        (2, 11776, 3)
    assert [cfg.dense_at(i) for i in range(10)] == [True] * 2 + [False] * 8
    assert cfg.slot_state == {"conv": {"conv": ((2 * 2048,), cfg.dtype)}}
    traffic = real.traffic
    assert (traffic["clients"], traffic["requests_per_client"],
            traffic["warmup_s"], traffic["check_requests"],
            traffic["stagger_first"]) == (64, 16, 15, 8, True)
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                     "sigma": 0.5, "min": 1024,
                                     "max": 4096}
    assert traffic["output_len"]["values"] == [512, 640, 768, 896, 1024]
    assert traffic["trace"] == {"start_s": 8, "seconds": 6}


def test_the_cell_joins_the_lists_it_fits_and_no_pinned_one(real):
    """The 18 per-layer metrics that sat, ep8, q3n and olm all list are
    among the cell's, found by name: a later cell, or a later metric
    the cell joins, moves nothing here."""
    m = manifest.load_manifest()
    assert set(JOINED) <= {x["name"] for x in real.per_layer}
    assert {"out_tok_s", "itl_p98_ms", "setup_s"} <= \
        {x["name"] for x in real.end_to_end}
    for name in JOINED:
        entry = next(x for x in m["per_layer"] if x["name"] == name)
        assert CELL in entry["workloads"], name
    for name in ("out_tok_s", "itl_p98_ms"):
        entry = next(x for x in m["end_to_end"] if x["name"] == name)
        assert CELL in entry["workloads"], name
    work = next(w for w in m["workloads"] if w["name"] == CELL)
    assert work["chips"] == 1 and work["config"] == "lfm2-24b-a2b-l10"
    assert len(work["why"]) <= 200
    # an mfu for each end-to-end metric it reports
    moves = {x["moves"] for x in real.per_layer if "mfu" in x["name"]}
    assert {"out_tok_s", "itl_p98_ms"} <= moves


# ------------------------------------------------- the six answers
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

    # by hand: a conv mixer 4 x 2048^2 + 3 x 2048, attention
    # 2 x 2048^2 + 2 x 2048 x 512 + 128, the dense 3 x 2048 x 11,776, an
    # expert 3 x 2048 x 1536; the stage's 5,267,090,176 with the head tied
    assert count(shapes) == fam.params_total(cfg) == reck["parameters"] \
        == 5267090176
    p = fam.layer_params(cfg)
    assert p["conv"] == reck["parameters_a_conv_mixer"] == 16783360
    assert p["full"] == reck["parameters_an_attention_mixer"] == 10485888
    assert p["dense"] == reck["parameters_a_dense_feed_forward"] \
        == 72351744
    assert p["expert"] == reck["parameters_an_expert"] == 9437184
    assert 64 * p["expert"] == reck["parameters_experts_a_layer"] \
        == 603979776
    assert reck["parameters_published_tied"] == 23843661440
    assert reck["parameters_published_untied"] == 23977879168
    assert 2 * reck["parameters"] == reck["weights_bf16"] == 10534180352
    blocks = shapes["blocks"]
    assert blocks[0]["W_in"] == (2048, 6144) and blocks[0]["conv"] == \
        (3, 2048)
    assert blocks[0]["W_gate"] == (2048, 11776) and "router" not in \
        blocks[0]
    assert blocks[2]["Wq"] == (2048, 2048) and blocks[2]["Wk"] == \
        (2048, 512)
    assert blocks[2]["q_norm"] == {"g": (64,)}
    assert blocks[2]["experts"]["gate"] == (64, 2048, 1536)
    assert blocks[2]["expert_bias"] == (64,) and "shared" not in blocks[2]
    assert "head" not in shapes                     # tied
    assert not fam.is_gain("['blocks'][2]['expert_bias']")
    ctx = _ctx(real)
    assert fam.kv_bytes_token_layer(ctx) == 2048 == \
        reck["kv_bytes_per_token_per_layer"]
    srv = cfg["serving"]
    assert (srv["slots"], srv["page_size"], srv["kv_pages"],
            srv["prefill_tokens_per_pass"]) == (64, 128, 2560, 4096)
    assert srv["kv_pages"] * 128 == 64 * 5120
    assert 2 * (srv["kv_pages"] + 1) * 2048 * 128 == reck["kv_pool"]
    assert fam.state_bytes_slot_layer(ctx) == 8192 == \
        reck["state_bytes_per_slot_per_layer"]
    assert reck["state"] == 64 * 8 * 8192
    total = reck["weights_bf16"] + reck["kv_pool"] + reck["state"]
    assert total == reck["total"] and 0.73 < total / 16e9 < 0.75
    from deeplearning4j_tpu.serving.paged_kv import (pool_bytes,
                                                     prompt_buckets,
                                                     state_bytes_per_slot)

    model = fam.model_config(cfg)
    assert state_bytes_per_slot(model, "conv") == 8192
    assert state_bytes_per_slot(model) == 0          # no linear layer
    assert pool_bytes(model, {"full": 2560}, 128) == reck["kv_pool"]
    assert prompt_buckets(model, 128) == fam.prompt_buckets(8192, 128)


def test_the_counts_against_hand_numbers(real):
    fam = real.family
    ctx = _ctx(real)
    router = 2049 * 64
    body = 2 * (8 * 16783360 + 2 * 10485888 + 2 * 72351744 + 8 * router)
    experts = 2 * 9437184 * 8 * 4                 # k a layer, no counter
    head = 2 * 65536 * 2048
    scores = 2 * 4 * 32 * 64
    assert fam.decode_token_flops(ctx, 3000) == body + experts + head \
        + scores * 3000
    assert fam.prefill_flops(ctx, 2048) == (body + experts) * 2048 \
        + head + scores * (2048 * 2049 // 2)
    # ~1.2 GFLOP a token outside attention
    assert 1.15e9 < body + experts < 1.25e9
    # a step: everything outside the experts once, every expert once
    # where no counter says otherwise, K/V of the keys, the columns of
    # two live slots read and written in 8 layers
    outside = 8 * 16783360 + 2 * 10485888 + 2 * 72351744 + 8 * router \
        + 10 * 4096 + 65536 * 2048 + 2048
    assert fam.decode_step_bytes(ctx, [3000, 5000]) == \
        2 * (outside + 8 * 64 * 9437184) + 2048 * 2 * 8000 \
        + 2 * 2 * 8 * 8192
    # with the program's counters: 2 steps that touched 1,000 (layer,
    # expert) weights, 128 decoded tokens of 4,096 pairs
    moe = {"tokens": 0, "pairs": 0, "decode_tokens": 0, "decode_pairs": 0,
           "decode_steps": 0, "experts_touched": 0}
    counted = _ctx(real, snap0={"moe": moe}, snap1={"moe": dict(
        moe, tokens=128, pairs=4096, decode_tokens=128, decode_pairs=4096,
        decode_steps=2, experts_touched=1000)})
    assert fam.experts_touched_per_step(counted) == 500
    assert fam.pairs_per_token(counted, decode=True) == 32
    assert fam.decode_step_bytes(counted, [3000]) == \
        2 * (outside + 500 * 9437184) + 2048 * 2 * 3000 + 2 * 8 * 8192
    calls = fam.paged_decode_attention_work(ctx, [3000, 130])
    assert len(calls) == 2
    assert calls[0]["bytes"] == (24 + 2) * 2048 * 128 + 2 * 2 * 32 * 64 * 2
    assert calls[0]["flops"] == 4 * 32 * 64 * 3130
    works = fam.flash_fwd_work(ctx, 2, 2048)
    assert len(works) == 2
    assert works[0]["bytes"] == 2 * 2048 * (2 * 32 + 2 * 8) * 64 * 2
    assert works[0]["flops"] == 4 * 32 * 64 * 2 * (2048 * 2049 // 2)


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
    # the router the program has: normalised, biased, scaled by 1
    for key, value in (("use_expert_bias", False), ("norm_topk_prob", False),
                       ("routed_scaling_factor", 2.5)):
        with pytest.raises(ValueError, match="router"):
            real.family.model_config(dict(real.config, **{key: value}))


def test_the_reference_imports_nothing_of_the_program(real):
    with open(real.family.reference().__file__) as f:
        text = f.read()
    assert "import deeplearning4j_tpu" not in text
    assert "from deeplearning4j_tpu" not in text
    # the convolution as the three-tap sum, every product at HIGHEST
    assert "w[j] * ext[j:j + t]" in text
    assert "precision=HIGHEST" in text and "bfloat16)" not in text


# ------------------------------------- what the warm-up has to reach
def test_the_warm_set_covers_every_program_the_schedule_can_reach(real):
    """The accepted `test_schedule.py` case builds every mix's warm set
    from GPT-2's buckets for 2,048 positions (PERF.md 7 (b)); this is the
    same check with the family's own buckets and the bound on a pass:
    every (rows, bucket) a pass can claim of the prompts the schedule
    sends, and no piece (no prompt is longer than the bound)."""
    fam, cfg, traffic = real.family, real.config, real.traffic
    srv = cfg["serving"]
    bound = srv["prefill_tokens_per_pass"]
    buckets = fam.prompt_buckets(8192, srv["page_size"])
    assert buckets == (128, 256, 512, 1024, 2048, 4096, 8192)
    rows = schedule.closed_loop(traffic)
    assert len(rows) == 64 and all(len(r) == 16 for r in rows)
    assert all(1024 <= r.prompt_len <= 4096
               and r.prompt_len + r.output_len <= 5120
               for row in rows for r in row)
    reach = set()
    for row in rows:
        for r in row:
            tb = schedule.bucket_of(r.prompt_len, buckets)
            for n in range(1, bound // tb + 1):
                reach.add((schedule.pow2_at_least(n), tb))
    warm = fam.warm_requests(cfg, traffic, 51)
    assert warm == [(1, 1024), (2, 1024), (3, 1024), (4, 1024),
                    (1, 2048), (2, 2048), (1, 4096)]
    warmed = {(schedule.pow2_at_least(n), schedule.bucket_of(n_tok,
                                                             buckets))
              for n, n_tok in warm}
    assert reach == warmed
    # no client runs out: 16 requests hold 11,000 output tokens and
    # more, a run serves a client ~4
    assert min(sum(r.output_len for r in row) for row in rows) >= 11000
    too_long = dict(traffic, prompt_len=dict(traffic["prompt_len"],
                                             max=5000))
    with pytest.raises(ValueError, match="pieces"):
        fam.warm_requests(cfg, too_long, 51)


# ------------------------------------------------- the cell, at a small size
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tiny.build(str(tmp_path_factory.mktemp("lfm2")))
    tiny_lfm2.add(root)
    return root


@pytest.fixture(scope="module")
def tiny_line(tiny_root):
    cell = manifest.load_cell(tiny_lfm2.CELL, tiny_root)
    return run.execute(cell, SEED, 1.0, False, require_chip=False)


def test_the_cell_runs_to_a_correct_line(tiny_root, tiny_line):
    line = tiny_line
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["numbers"]["tokens_compared"] >= 20
    assert set(line["metrics"]) == {"setup_s", "out_tok_s", "itl_p98_ms"}
    assert line["detail"]["jax_programs_in_window"] == 0
    json.dumps(line)


def test_the_limit_refuses_tokens_that_are_not_the_reference_s(
        tiny_root, tiny_line):
    """By the kind of limit the real cell has (a count of served tokens
    off the float32 reference's best): a sample the reference decodes
    greedily reads 0, the same sample with every served token moved to
    its neighbour is refused. (At these widths the tied head makes the
    model repeat a prompt's last token, whatever the precision, so the
    fp8 control cannot fail here; `tests/test_lfm2_moe.py` makes the
    precision's case on louder weights.)"""
    import numpy as np

    from benchmark import check

    cell = manifest.load_cell(tiny_lfm2.CELL, tiny_root)
    assert set(cell.limits) == {"tokens_off_best"} == \
        set(manifest.load_cell(CELL).limits)
    n = cell.traffic["check_requests"]
    assert tiny_line["numbers"]["requests_compared"] == n
    assert tiny_line["compared"]["tokens_off_best"]["value"] <= 4
    sample = tiny.greedy_sample(cell, SEED, n, 24, 8)
    numbers = check.serve_numbers(cell, SEED, sample)
    assert numbers["tokens_off_best"] == 0
    assert check.verdict(numbers, cell.limits)["correct"] is True
    moved = [dict(r, tokens=list((np.asarray(r["tokens"]) + 1) % 97))
             for r in sample]
    wrong = check.serve_numbers(cell, SEED, moved)
    assert wrong["tokens_off_best"] >= n        # each first token at least
    assert check.verdict(wrong, cell.limits)["correct"] is False
