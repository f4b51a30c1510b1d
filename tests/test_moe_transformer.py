"""The second block (`models/moe_transformer.py`) and its cache of two
kinds (`serving/paged_kinds.py`, `DecodeLoop`) at a small size on the
CPU: d 64, 8 query heads over 2 K/V heads of 16, window 8, pages of 4,
layers `s, s, s, f`, 16 experts of which 4 are held, 4 chosen, 2 shared.

The plain reference is the benchmark's (`benchmark/reference/
cohere2_moe.py`, which imports nothing of the program), told the same
share by the same configuration file."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import cohere2_moe as family
from deeplearning4j_tpu.telemetry import exposition
from deeplearning4j_tpu.models import moe_transformer as moe
from deeplearning4j_tpu.serving import decode_loop as dl
from deeplearning4j_tpu.serving import paged_kinds as pk
from tests.benchmark_suite import tiny_moe

PS, WINDOW, SEED = 4, 8, 2 ** 31 + 29
TRASH = 20          # pools of 20 pages of each kind; page 20 is trash


def _config(dtype="float32", **over):
    return dict(tiny_moe.CONFIG, dtype=dtype, **over)


def _model(config, **over):
    return family.model_config(config)._replace(**over)


def _params(config):
    return weights.make_params(SEED, family, config)


def _tokens(n, seed=1):
    return np.random.RandomState(seed).randint(0, 97, (n,)).astype(np.int32)


def _through_the_cache(cfg, params, toks, plen, release=True,
                       kernel="gather"):
    """Teacher-forced logits of positions plen-1 .. len(toks)-1: prefill
    of the first `plen` tokens, then one decode step a token, the window
    kind's table taking the trash page for pages that fell out where
    `release` (and only the window's pages written by the prefill)."""
    t = len(toks)
    n_p = 16
    pool = pk.init_pool(cfg, {"full": TRASH, "window": TRASH}, PS)
    tb = -(-plen // PS) * PS
    padded = np.zeros((1, tb), np.int32)
    padded[0, :plen] = toks[:plen]
    lo = max(0, plen - WINDOW + 1) // PS if release else 0
    ids_f = np.full((1, tb // PS), TRASH, np.int32)
    ids_f[0, :-(-plen // PS)] = np.arange(-(-plen // PS))
    ids_w = np.full((1, tb // PS), TRASH, np.int32)
    ids_w[0, lo:-(-plen // PS)] = np.arange(lo, -(-plen // PS))
    lg, pool, pairs = jax.jit(lambda *a: pk.prefill(*a, cfg))(
        params, jnp.asarray(padded), jnp.asarray([plen]), pool,
        {"full": jnp.asarray(ids_f), "window": jnp.asarray(ids_w)})
    out, counted = [np.asarray(lg[0])], [np.asarray(pairs)]
    tab_f = np.arange(n_p, dtype=np.int32)[None, :]
    tab_w = np.full((1, n_p), TRASH, np.int32)
    tab_w[0, lo:] = np.arange(lo, n_p)
    step = jax.jit(lambda *a: pk.decode_step(*a, cfg, kernel))
    for pos in range(plen, t):
        if release:
            tab_w[0, :max(0, pos - WINDOW + 1) // PS] = TRASH
        lg, pool, pairs = step(
            params, jnp.asarray(toks[pos:pos + 1]), pool,
            {"full": jnp.asarray(tab_f), "window": jnp.asarray(tab_w)},
            jnp.asarray([pos]), jnp.asarray([True]))
        out.append(np.asarray(lg[0]))
        counted.append(np.asarray(pairs))
    return np.stack(out), counted


# ------------------------------------------- (a) cache against reference
@pytest.mark.parametrize("dtype,tol", [
    ("float32", 1e-4),
    # bfloat16 holds 8 bits: logits of ~0.05 to 0.3 computed through four
    # layers of bf16 products differ from the f32 reference by a few
    # thousandths; 0.02 is six times the largest gap seen and a tenth of
    # the spread of the logits
    ("bfloat16", 2e-2)])
def test_prefill_then_decode_equals_the_reference_s_forward(dtype, tol):
    config = _config(dtype)
    cfg, params = _model(config), _params(config)
    toks, plen = _tokens(40), 21           # contexts to 5 x the window
    got, _ = _through_the_cache(cfg, params, toks, plen)
    want = np.asarray(family.reference().logits(
        config, params, jnp.asarray(toks[None, :]), plen - 1, len(toks)))[0]
    assert got.shape == want.shape == (len(toks) - plen + 1, 97)
    assert np.abs(got - want).max() < tol
    # and the uncached forward of the program is the same function
    whole = np.asarray(moe.logits(params, jnp.asarray(toks[None]), cfg))[0]
    assert np.abs(whole[plen - 1:] - want).max() < tol


# --------------------------------------------------- (b) the shares add up
def test_the_shares_and_the_shared_experts_once_add_up_to_the_layer():
    config = _config(num_experts=16, held_experts_first=0)
    whole = _model(config)
    p = _params(config)["blocks"][0]
    h = jax.random.normal(jax.random.PRNGKey(3), (24, 64), jnp.float32)
    full, pairs_full = moe.expert_layer(p, h, whole)
    sh = p["shared"]
    shared = sum((jax.nn.silu(h @ sh["gate"][j]) * (h @ sh["up"][j]))
                 @ sh["down"][j] for j in range(2)) / 2
    total, pairs = 0, []
    for r in range(4):
        share = whole._replace(n_held=4, held_first=4 * r)
        part = dict(p, experts=jax.tree_util.tree_map(
            lambda a: a[4 * r:4 * r + 4], p["experts"]))
        out, n = moe.expert_layer(part, h, share)
        total = total + (out - shared)          # this share's routed sum
        pairs.append(np.asarray(n))
    assert np.abs(np.asarray(total + shared - full)).max() < 1e-5
    # every pair fell on exactly one share: 24 tokens x 4 choices
    assert np.concatenate(pairs).tolist() == np.asarray(pairs_full).tolist()
    assert int(np.concatenate(pairs).sum()) == 24 * 4
    # and the benchmark's reference, told the whole layer, agrees
    ref = family.reference()
    want = ref._experts(p, h, ref.what_is_held(config), "f32")
    assert np.abs(np.asarray(full - want)).max() < 1e-5


def test_padding_rows_route_nowhere_and_count_nowhere():
    cfg = _model(_config())
    p = _params(_config())["blocks"][0]
    h = jax.random.normal(jax.random.PRNGKey(4), (8, 64), jnp.float32)
    valid = jnp.arange(8) < 5
    out, pairs = moe.expert_layer(p, h, cfg, valid)
    alone, pairs_alone = moe.expert_layer(p, h[:5], cfg)
    assert np.asarray(pairs).tolist() == np.asarray(pairs_alone).tolist()
    assert np.abs(np.asarray(out[:5] - alone)).max() < 1e-6


def test_grouped_matmul_kernel_equals_ragged_dot_in_interpret_mode():
    lhs = jax.random.normal(jax.random.PRNGKey(5), (256, 128), jnp.float32)
    rhs = jax.random.normal(jax.random.PRNGKey(6), (4, 128, 128),
                            jnp.float32)
    sizes = jnp.asarray([40, 0, 77, 13], jnp.int32)
    got = moe.grouped_matmul(lhs, rhs, sizes, jnp.float32, interpret=True)
    want = moe.grouped_matmul(lhs, rhs, sizes, jnp.float32)
    assert np.abs(np.asarray(got - want))[:130].max() < 1e-3


# ------------------------------------------------------ (c) window pages
def test_released_window_pages_change_no_logit():
    config = _config()
    cfg, params = _model(config), _params(config)
    toks = _tokens(40, seed=2)
    returned, _ = _through_the_cache(cfg, params, toks, 21, release=True)
    kept, _ = _through_the_cache(cfg, params, toks, 21, release=False)
    assert np.array_equal(returned, kept)


def _loop(cfg, params, **kw):
    kw.setdefault("slots", 3)
    return dl.DecodeLoop(params, cfg, page_size=PS, prefix_cache=False,
                         start=False, **kw)


def test_a_slot_never_holds_more_than_a_window_of_pages_and_full_grows():
    config = _config()
    cfg, params = _model(config), _params(config)
    loop = _loop(cfg, params)
    stream = loop.submit(_tokens(21), 30)
    full_seen, window_seen = [], []
    for _ in range(200):
        if stream.done:
            break
        loop.tick()
        kinds = loop.snapshot()["pages_by_kind"]
        full_seen.append(kinds["full"]["pages_in_use"])
        window_seen.append(kinds["window"]["pages_in_use"])
    assert stream.finish_reason == "max_tokens"
    snap = loop.snapshot()
    win = snap["pages_by_kind"]["window"]
    assert win["table_pages"] == WINDOW // PS + 1 == 3
    assert max(window_seen[:-1]) <= 3 and win["pages_per_slot_peak"] <= 3
    assert max(full_seen) == -(-50 // PS)          # 21 + 29 written keys
    assert full_seen[:-1] == sorted(full_seen[:-1])  # the full kind grows
    # pages 3..12 were claimed (0..2 had left the window at admission)
    assert win["released"] >= 10 - 3
    assert win["pages_in_use"] == snap["pages_in_use"] == 0
    # layers weigh the sums: one full layer, three window layers
    assert snap["pages_total"] == 3 * 16 + 3 * 9   # 3 slots
    assert snap["peak_pages_in_use"] == max(
        f + 3 * w for f, w in zip(full_seen, window_seen))


def test_served_tokens_are_the_reference_s_best_and_equal_with_no_return(
        monkeypatch):
    config = _config()
    cfg, params = _model(config), _params(config)
    prompts = [_tokens(n, seed=n) for n in (21, 9, 30, 5)]
    budgets = [20, 12, 25, 30]

    def serve():
        loop = _loop(cfg, params, n_pages=64, window_pages=64)
        streams = loop.submit_many(prompts, budgets)
        loop.run_until_idle()
        return [s.result() for s in streams]

    got = serve()
    monkeypatch.setattr(dl._WindowPages, "release_before",
                        lambda self, slot, cursor: 0)
    assert serve() == got
    ref = family.reference()
    for prompt, out in zip(prompts, got):
        seq = np.concatenate([prompt, out])
        lg = np.asarray(ref.logits(config, params,
                                   jnp.asarray(seq[None, :-1]),
                                   len(prompt) - 1, len(seq) - 1))[0]
        picked = lg[np.arange(len(out)), out]
        assert (lg.max(-1) - picked).max() < 1e-4


def test_a_stall_for_window_pages_ends_when_a_request_retires():
    config = _config()
    cfg, params = _model(config), _params(config)
    # 2 slots want 3 window pages each; 4 in the pool
    loop = _loop(cfg, params, slots=2, window_pages=4)
    streams = loop.submit_many([_tokens(10), _tokens(10, seed=7)], 12)
    loop.run_until_idle()
    assert [s.finish_reason for s in streams] == ["max_tokens"] * 2
    assert loop.snapshot()["admission_waits"] > 0


@pytest.mark.parametrize("kw,word", [
    (dict(prefix_cache=True), "prefix sharing"),
    (dict(prefix_cache=False, speculation=2), "speculation"),
    (dict(prefix_cache=False, horizon=2), "horizon"),
    (dict(prefix_cache=False, role="prefill"), "/kv/export")])
def test_what_two_kinds_cannot_do_is_an_error_by_name(kw, word):
    config = _config()
    with pytest.raises(ValueError, match=word):
        dl.DecodeLoop(_params(config), _model(config), page_size=PS,
                      start=False, **kw)


# ------------------------------------------------- (e) the bound on a pass
@pytest.mark.parametrize("bound,per_pass", [(32, 1), (64, 2), (None, 3)])
def test_a_pass_prefills_no_more_than_its_bound(bound, per_pass):
    config = _config()
    cfg, params = _model(config), _params(config)
    loop = _loop(cfg, params, prefill_tokens_per_pass=bound)
    streams = loop.submit_many([_tokens(21, seed=i) for i in range(3)], 4)
    loop.tick()
    snap = loop.snapshot()
    assert snap["occupied_slots"] == per_pass      # 21 tokens: bucket 32
    assert snap["queued"] == 3 - per_pass
    loop.run_until_idle()
    assert all(s.finish_reason == "max_tokens" for s in streams)
    groups = {tuple(g) for g in loop.plan_fragment()["prefill"]}
    assert groups == {(per_pass if per_pass != 3 else 4, 32)} | (
        {(1, 32)} if per_pass == 2 else set())


def test_the_bound_is_checked_at_construction():
    config = _config()
    with pytest.raises(ValueError, match="prefill_tokens_per_pass"):
        _loop(_model(config), _params(config), prefill_tokens_per_pass=0)


# --------------------------------------------- (f) counters and the span
def test_counters_of_pages_by_kind_and_of_pairs_and_the_release_span():
    config = _config()
    cfg, params = _model(config), _params(config)
    loop = _loop(cfg, params, name="moe-counters")
    streams = loop.submit_many([_tokens(21), _tokens(9, seed=3)], [20, 12])
    loop.run_until_idle()
    assert all(s.finish_reason == "max_tokens" for s in streams)
    snap = loop.snapshot()
    moe_snap = snap["moe"]
    # every real token went through the router once a program
    assert moe_snap["tokens"] == 21 + 9 + moe_snap["decode_tokens"]
    assert moe_snap["decode_tokens"] == 19 + 11
    by = np.asarray(moe_snap["pairs_by_layer_expert"])
    assert by.shape == (4, 4) and by.sum() == moe_snap["pairs"]
    assert 0 < moe_snap["decode_pairs"] <= moe_snap["pairs"]
    assert moe_snap["experts_touched"] <= 16 * moe_snap["decode_steps"]
    # pairs a token a layer: 4 chosen of 16, 4 held -> about 1
    assert 0.5 < moe_snap["pairs"] / (4 * moe_snap["tokens"]) < 1.6
    # the rows the movement went through: a layer's pairs rounded up to
    # whole grid steps of 128, so at this size a step a layer a program
    # that held a pair, and never fewer rows than pairs
    programs = moe_snap["decode_steps"] + 2
    assert moe_snap["pairs"] <= moe_snap["rows_moved"] <= 4 * 128 * programs
    assert moe_snap["rows_moved"] % 128 == 0
    # a pass releases before it prepares a step: once a dispatch, and
    # once more in the last pass, which found no slot to advance and
    # only read the step in flight
    rel = snap["phases"]["decode.release_window"]
    assert rel["count"] == snap["dispatches"] + 1 > 1
    released = snap["pages_by_kind"]["window"]["released"]
    assert released > 0
    text = exposition.render_prometheus()
    lab = 'loop="moe-counters"'
    assert f"dl4j_kv_window_pages_released_total{{{lab}}} {released}" \
        in text
    assert f'dl4j_kv_pages_total_by_kind{{kind="window",{lab}}} 9' in text
    assert f'dl4j_kv_pages_in_use_by_kind{{kind="full",{lab}}} 0' in text
    assert f"dl4j_moe_tokens_total{{{lab}}} {moe_snap['tokens']}" in text
    assert (f"dl4j_moe_experts_touched_total{{{lab}}} "
            f"{moe_snap['experts_touched']}") in text
    assert f'dl4j_moe_pairs_total{{expert="0",layer="0",{lab}}}' in text
    assert (f"dl4j_moe_rows_moved_total{{{lab}}} "
            f"{moe_snap['rows_moved']}") in text
    # the one-kind families keep their shape
    assert f"dl4j_kv_pages_total{{{lab}}} 48" in text
    # the paged kernel's block by kind: this loop gathers, so none
    assert snap["paged_block_pages"] == {"full": 0, "window": 0}
    assert f'dl4j_paged_kernel_block_pages{{kind="window",{lab}}} 0' \
        in text


def test_paged_kernel_block_by_kind_follows_each_kind_s_columns():
    """At a head size that engages the block (pages of 16, 2 K/V heads
    of 128 in float32, 16 KiB of K a page): the full kind sweeps 64
    columns 32 a block, the window kind `window_table_pages` = 5
    columns in one block of 5."""
    from deeplearning4j_tpu.attention.paged_pallas import block_pages

    cfg = _model(_config(), head_dim=128, window=50, max_len=1024,
                 interpret=True)
    params = moe.init_moe_params(jax.random.PRNGKey(0), cfg)
    with dl.DecodeLoop(params, cfg, slots=2, page_size=16,
                       prefix_cache=False, kernel="pallas", start=False,
                       name="moe-blocks") as loop:
        assert loop.snapshot()["paged_block_pages"] == {
            "full": block_pages(16, cfg.n_kv_heads, 128, cfg.dtype, 64),
            "window": 5}
        assert loop.snapshot()["paged_block_pages"]["full"] == 32
    text = exposition.render_prometheus()
    assert ('dl4j_paged_kernel_block_pages{kind="full",'
            'loop="moe-blocks"} 32') in text
    assert ('dl4j_paged_kernel_block_pages{kind="window",'
            'loop="moe-blocks"} 5') in text


def test_rope_turns_pairs_and_keeps_norms():
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 5, 2, 16))
    pos = jnp.asarray([[0, 1, 2, 7, 100]])
    y = moe.rope(x, pos, 50000.0)
    assert np.allclose(np.asarray(y[0, 0]), np.asarray(x[0, 0]), atol=1e-6)
    assert np.allclose(np.linalg.norm(y, axis=-1),
                       np.linalg.norm(x, axis=-1), atol=1e-5)
    # pair i of position p turns by p * theta^(-2i/hd)
    ang = 7 * 50000.0 ** (-2 * 3 / 16)
    a, b = np.asarray(x[0, 3, 1, 6:8])
    want = [a * np.cos(ang) - b * np.sin(ang),
            b * np.cos(ang) + a * np.sin(ang)]
    assert np.allclose(np.asarray(y[0, 3, 1, 6:8]), want, atol=1e-5)


# ------------------------------------ (e) how the pairs' rows travel (PR 36)
ROWS_CFG = dict(vocab_size=8, d_model=64, n_heads=2, n_kv_heads=1,
                head_dim=16, d_ff=32, layer_kinds=("full",), window=4,
                n_experts=16, experts_per_token=4, n_shared=2, n_held=4,
                held_first=4, interpret=True)


def _one_hot_layer(p, h, cfg, valid):
    """The layer with no sorted order at all: every held expert over
    every token, a one-hot sum under the router's weights. The same
    types as the program: products in h's type accumulated in float32,
    the weights and the sum over a token's pairs in float32."""
    f32 = jnp.float32
    scores = moe.ROUTER_SCORES[cfg.router_score](jnp.dot(
        h.astype(f32), p["router"].astype(f32),
        precision=jax.lax.Precision.HIGHEST))
    top, chosen = jax.lax.top_k(scores, cfg.experts_per_token)
    weight = top / jnp.sum(top, axis=-1, keepdims=True)
    out, held_pairs = jnp.zeros(h.shape, f32), []
    for e in range(cfg.n_held):
        hot = (chosen == cfg.held_first + e) & valid[:, None]
        held_pairs.append(hot)
        ex = p["experts"]
        g = jnp.dot(h, ex["gate"][e], preferred_element_type=f32)
        u = jnp.dot(h, ex["up"][e], preferred_element_type=f32)
        act = (jax.nn.silu(g.astype(h.dtype).astype(f32))
               * u.astype(h.dtype).astype(f32)).astype(h.dtype)
        y = jnp.dot(act, ex["down"][e], preferred_element_type=f32)
        out = out + jnp.sum(weight * hot, axis=-1)[:, None] * y
    shared, _ = moe.expert_layer(p, h, cfg, jnp.zeros_like(valid))
    return out + shared, np.asarray(jnp.stack(held_pairs))  # (held, t, k)


def _routed_case(case, t, dtype):
    """(h, router, valid) that puts the pairs where the case wants them.
    Column 0 of h pulls a token's choices onto the held experts (+) or
    off them (-); column 1 pulls one held expert in alone."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    h = jax.random.normal(k1, (t, 64), jnp.float32)
    router = 0.02 * jax.random.normal(k2, (64, 16), jnp.float32)
    held = (jnp.arange(16) >= 4) & (jnp.arange(16) < 8)
    router = router.at[0].set(jnp.where(held, 1.0, -1.0))
    router = router.at[1].set(jnp.where(jnp.arange(16) == 5, 3.0, 0.0))
    valid = jnp.ones((t,), bool)
    pull = {"none_held": -8.0, "beyond_one_chunk": 8.0}.get(case, 0.0)
    h = h.at[:, 0].set(pull).at[:, 1].set(0.0)
    if case == "zero_one_and_k":
        # thirds: all k on held experts, none, exactly one (expert 5)
        kind = jnp.arange(t) % 3
        h = h.at[:, 0].set(jnp.where(kind == 0, 8.0, -8.0))
        h = h.at[:, 1].set(jnp.where(kind == 2, 8.0, 0.0))
    if case == "valid_masks_rows":
        valid = (jnp.arange(t) % 5 != 0) & (jnp.arange(t) < t - 9)
    return h.astype(dtype), router.astype(dtype), valid


@pytest.mark.parametrize("score,combine", [("sigmoid", "average"),
                                           ("softmax", "sigmoid_gate")])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-3)])
@pytest.mark.parametrize("case,t", [
    ("none_held", 128), ("one_chunk", 128), ("beyond_one_chunk", 256),
    ("zero_one_and_k", 128), ("valid_masks_rows", 128)])
def test_the_rows_travel_as_the_one_hot_sum_says(case, t, dtype, tol,
                                                 score, combine):
    """`expert_layer` through the two kernels of `models/moe_rows.py`
    (interpret mode) against the one-hot sum: no pair held, the pairs of
    one chunk, every pair held so that the sorted pairs take more than
    one chunk, tokens with 0, 1 and k pairs, padding rows; both families'
    score and shared-expert rules; float32 at 1e-5, and bfloat16 at a
    few of its roundings of results of ~0.01 (the products are the same
    ones, in another order)."""
    cfg = moe.MoEConfig(**ROWS_CFG, dtype=dtype, router_score=score,
                        shared_combine=combine).check()
    p = moe.init_moe_params(jax.random.PRNGKey(2), cfg)["blocks"][0]
    h, router, valid = _routed_case(case, t, dtype)
    p = dict(p, router=router, shared_gate=(0.02 * jax.random.normal(
        jax.random.PRNGKey(5), (64, 2), jnp.float32)).astype(dtype))
    got, pairs = jax.jit(
        lambda p, h, valid: moe.expert_layer(p, h, cfg, valid))(p, h, valid)
    want, hot = _one_hot_layer(p, h, cfg, valid)
    assert np.asarray(pairs).tolist() == hot.sum(axis=(1, 2)).tolist()
    assert np.abs(np.asarray(got - want)).max() < tol
    by_token = hot.sum(axis=(0, 2))                    # held pairs a token
    most, chunk = moe._pair_chunk(t, cfg)
    if case == "none_held":
        assert by_token.sum() == 0
    elif case == "beyond_one_chunk":
        assert by_token.sum() == t * 4 == most > chunk
    elif case == "zero_one_and_k":
        assert set(by_token.tolist()) == {0, 1, 4}
    elif case == "valid_masks_rows":
        assert by_token[~np.asarray(valid)].sum() == 0 < by_token.sum()
    else:
        assert 0 < by_token.sum() <= chunk < most


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rows_in_copies_the_pairs_rows_and_no_row_past_their_step(dtype):
    """`rows_in` alone: the first n rows are the tokens' rows bit for
    bit, whatever n is against the grid step; `rows_moved` is n rounded
    up to whole steps."""
    from deeplearning4j_tpu.models import moe_rows

    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(40, 64), dtype)
    tok = jnp.asarray(rng.randint(0, 40, (512,)), jnp.int32)
    for n in (0, 1, 127, 128, 129, 512):
        got = moe_rows.rows_in(h, tok, jnp.int32(n), interpret=True)
        assert got.shape == (512, 64) and got.dtype == h.dtype
        assert np.array_equal(np.asarray(got[:n], np.float32),
                              np.asarray(h[tok[:n]], np.float32))
        assert moe_rows.rows_moved(n) == -(-n // 128) * 128
    assert moe_rows.rows_moved(np.asarray([700, 0])).tolist() == [768, 0]


def test_rows_out_sums_each_token_s_pairs_in_float32():
    """`rows_out` alone, kernel and plain form, against a float64 loop:
    tokens with no pair get zeros, more pairs than copies in flight."""
    from deeplearning4j_tpu.models import moe_rows

    rng = np.random.RandomState(1)
    y = jnp.asarray(rng.randn(256, 64), jnp.float32)
    for share in (0.0, 0.2, 1.0):
        pos = rng.randint(0, 256, (256, 3))
        pos[rng.rand(256, 3) >= share] = -1
        w = rng.rand(256, 3).astype(np.float32)
        want = np.zeros((256, 64))
        for a, b in zip(*np.nonzero(pos >= 0)):
            want[a] += np.float64(w[a, b]) * np.asarray(y[pos[a, b]],
                                                        np.float64)
        for interpret in (True, False):
            got = moe_rows.rows_out(y, jnp.asarray(pos, jnp.int32),
                                    jnp.asarray(w), interpret=interpret)
            assert np.abs(np.asarray(got) - want).max() < 1e-5
