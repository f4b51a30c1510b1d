"""The `olmo_hybrid` family through the program at a small size on the
CPU, at widths that keep the published shape's awkwardness (`dk` 24,
`dv` 48, 3 heads: a state that is not square, heads that are no
multiple of 8): the one block told where its norms stand against the
plain reference, the cache's lanes against the reference's full
forward, a prompt prefilled whole against the same prompt in pieces,
the scan from a kept state against the recurrence, and the scheduler's
piece a pass.

Every comparison is float32 against float32, and each tolerance is
stated where it is used with what was read. The same comparisons with
the program in bfloat16 read 5e-3 to 3e-2 (its rounding is 2^-8 of
values near 1), which every tolerance here refuses by a factor of ten
and more: `test_bfloat16_in_float32_s_place_fails` holds that."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import olmo_hybrid as family
from benchmark.families import qwen3_next
from deeplearning4j_tpu.attention import gdn_pallas as gdn
from deeplearning4j_tpu.attention.flash_pallas import flash_attention_ctx
from deeplearning4j_tpu.models import hybrid_transformer as hybrid
from deeplearning4j_tpu.serving import decode_loop as dl
from deeplearning4j_tpu.serving import paged_kinds as pk
from tests.benchmark_suite import tiny_hybrid, tiny_olmo

PS, SEED, SLOTS, PAGES = 8, 2 ** 31 + 37, 3, 40


def _config(periods=1, dtype="float32"):
    return tiny_olmo.config(periods, dtype)


def _tokens(n, seed=1):
    return np.random.RandomState(seed).randint(0, 97, (n,)).astype(np.int32)


def _loud(params):
    """The blocks' matrices scaled by 8: at N(0, 0.02) the tiny model
    repeats one token for ever, and a stale state or a dropped piece
    would serve the same tokens as a sound one."""
    return dict(params, blocks=jax.tree_util.tree_map(
        lambda a: a * 8 if a.ndim >= 2 else a, params["blocks"]))


def _model(config, loud=True, **over):
    params = weights.make_params(SEED, family, config)
    return (family.model_config(config)._replace(**over),
            _loud(params) if loud else params)


# ------------------------------------------------------------- the block
@pytest.mark.parametrize("periods", [1, 2])
def test_the_uncached_forward_is_the_reference(periods):
    """4 and 8 layers over 100 tokens, every position's logits. 2e-4 of
    logits of sd 0.14 (one period) and 0.2 (two): the chunked scan sums
    in another order than the recurrence; read 1.3e-5 and 3e-5."""
    config = _config(periods)
    cfg, params = _model(config)
    assert cfg.layer_kinds == ("linear",) * 3 + ("full",) \
        if periods == 1 else len(cfg.layer_kinds) == 8
    assert (cfg.norm_place, cfg.qk_norm, cfg.attn_gate, cfg.rotary_dim,
            cfg.allow_neg_eigval, cfg.n_experts) == \
        ("post", "width", False, 0, True, 0)
    toks = _tokens(100)
    got = hybrid.logits(params, jnp.asarray(toks[None]), cfg)[0]
    want = family.reference().logits(config, params,
                                     jnp.asarray(toks[None]), 0, 100)[0]
    assert float(jnp.std(want)) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4)


def test_what_the_block_is_told_changes_what_it_computes():
    """One block: each of the things this family tells it moves the
    logits by far more than the tolerance above (a switch that did
    nothing would pass every other test)."""
    config = _config()
    cfg, params = _model(config)
    toks = jnp.asarray(_tokens(40)[None])
    base = hybrid.logits(params, toks, cfg)

    def moved(**over):
        other = hybrid.logits(params, toks, cfg._replace(**over))
        return float(jnp.max(jnp.abs(other - base)))

    assert moved(norm_place="pre") > 1e-2
    assert moved(allow_neg_eigval=False) > 1e-3
    with pytest.raises(ValueError, match="norm_place"):
        cfg._replace(norm_place="both").check()
    with pytest.raises(ValueError, match="dense feed-forward"):
        cfg._replace(n_held=2).check()
    # and qwen3_next's configuration says what it always said
    q3n = qwen3_next.model_config(tiny_hybrid.CONFIG)
    assert (q3n.norm_place, q3n.qk_norm, q3n.attn_gate,
            q3n.allow_neg_eigval) == ("pre", "head", True, False)
    assert q3n.n_experts == 16 and q3n.rotary_dim == 8


# ---------------------------------------------------- the scan's carry
def _recurrence(q, k, v, g, beta, s0):
    """(o (B, H, T, dv), S (B, H, dk, dv)) by the definition, from
    `s0`."""
    def one_head(q, k, v, g, beta, s0):
        def step(s, now):
            q, k, v, g, b = now
            s = s * jnp.exp(g)
            s = s + jnp.outer(k, b * (v - s.T @ k))
            return s, s.T @ q
        s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
        return o, s
    with jax.default_matmul_precision("highest"):
        return jax.vmap(jax.vmap(one_head))(q, k, v, g, beta, s0)


def _case(t, decay=0.997, dk=24, dv=48, heads=3, rows=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (rows, heads, t, dk))
    k = jax.random.normal(ks[1], (rows, heads, t, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (rows, heads, t, dv))
    g = jnp.log(decay) * jax.random.uniform(
        ks[3], (rows, heads, t), minval=0.5, maxval=1.5)
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(
        ks[4], (rows, heads, t)))
    s0 = jax.random.normal(ks[5], (rows, heads, dk, dv))
    return q, k, v, g, beta, s0


@pytest.mark.pallas
@pytest.mark.parametrize("interpret", [False, True],
                         ids=["plain", "kernel-interpreted"])
def test_the_scan_from_a_kept_state_is_the_recurrence(interpret):
    """256 tokens at a decay near 0.997 a token, beta drawn over (0, 2)
    (a third of the draws above 1.5), from a state of sd 1 that the
    decay keeps to the end, at 24 x 48 a head and 3 heads. 3e-4 of
    outputs of sd 0.6 and a state of sd 1.6; read 4e-5 and 6e-5. The
    same scan from zero differs by 0.4: the state handed in is used."""
    q, k, v, g, beta, s0 = _case(256)
    assert float(jnp.mean(beta > 1.5)) > 0.25
    want_o, want_s = _recurrence(q, k, v, g, beta, s0)
    got_o, got_s = gdn.gdn_scan(q, k, v, g, beta, state=s0,
                                interpret=interpret)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=3e-4)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=3e-4)
    cold_o, _ = gdn.gdn_scan(q, k, v, g, beta, interpret=interpret)
    assert float(jnp.max(jnp.abs(cold_o - want_o))) > 0.1


@pytest.mark.pallas
def test_a_write_strength_of_two_turns_a_direction_round():
    """`I - beta k k^T` with beta = 2 has the eigenvalue -1 along k: a
    state that remembers `old` for k comes out remembering `-old` once
    a token writes v = 0 at k with the whole strength. One real token,
    no decay, by hand; then the scan and the update agree with the
    recurrence over 64 tokens of beta = 2 (3e-4 as above)."""
    dk, dv = 24, 48
    k = jnp.zeros((dk,)).at[3].set(1.0)
    old = (1.0 + jnp.arange(dv, dtype=jnp.float32)) / dv
    k64 = jnp.broadcast_to(k, (1, 1, 64, dk))
    zeros = jnp.zeros((1, 1, 64))
    _, s = gdn.gdn_scan(k64 / dk ** 0.5, k64, jnp.zeros((1, 1, 64, dv)),
                        zeros, zeros.at[0, 0, 0].set(2.0),
                        state=jnp.outer(k, old)[None, None],
                        interpret=True)
    np.testing.assert_allclose(np.asarray(s[0, 0, 3]), np.asarray(-old),
                               atol=1e-6)
    q, k, v, g, _, s0 = _case(64, rows=1)
    beta = jnp.full(g.shape, 2.0)
    want_o, want_s = _recurrence(q, k, v, g, beta, s0)
    got_o, got_s = gdn.gdn_scan(q, k, v, g, beta, state=s0,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=3e-4)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=3e-4)
    o1, s1 = gdn.gdn_update(s0, q[:, :, 0], k[:, :, 0], v[:, :, 0],
                            g[:, :, 0], beta[:, :, 0], interpret=True)
    step_o, step_s = _recurrence(q[:, :, :1], k[:, :, :1], v[:, :, :1],
                                 g[:, :, :1], beta[:, :, :1], s0)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(step_o[:, :, 0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(step_s),
                               atol=1e-5)


@pytest.mark.pallas
def test_flash_with_a_query_offset_is_the_masked_softmax():
    """A piece of 128 queries at positions offset + i over 384 keys, 3
    heads of 128 with a K/V head each, two rows at different offsets,
    through the kernel (interpreted): against the dense masked softmax.
    2e-5 of outputs of sd 0.3 in float32 (read 2e-6); keys past a row's
    last query are poisoned with NaN-free but huge values and change
    nothing, which a mask by the STATIC offset would not survive."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 3, 128, 128))
    k = jax.random.normal(ks[1], (2, 3, 384, 128))
    v = jax.random.normal(ks[2], (2, 3, 384, 128))
    offset = jnp.asarray([128, 0], jnp.int32)
    far = jnp.arange(384)[None, None, :, None] >= \
        (offset[:, None, None, None] + 128)
    k, v = jnp.where(far, 1e4, k), jnp.where(far, 1e4, v)
    got = flash_attention_ctx(q, k, v, offset, q_tile=128, block_k=128,
                              interpret=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") / jnp.sqrt(128.0)
    seen = jnp.arange(384)[None, None, None, :] <= (
        offset[:, None, None, None] + jnp.arange(128)[None, None, :, None])
    want = jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v,
                      precision="highest")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


# ----------------------------------------------- the cache's lanes
def _pool(cfg):
    return pk.init_pool(cfg, {"full": PAGES}, PS, slots=SLOTS)


def _piece(cfg, params, pool, toks, at, upto, slot, tb, kernel):
    """Tokens [at, upto) of `toks` into `slot`, padded to `tb`: the cold
    prefill where at == 0, else a piece on what the slot keeps."""
    n = upto - at
    padded = np.zeros((2, tb), np.int32)       # row 1 is a padding row
    padded[0, :n] = toks[at:upto]
    ids = np.full((2, tb // PS), PAGES, np.int32)
    ids[0, :-(-n // PS)] = at // PS + np.arange(-(-n // PS))
    page_ids = {"full": jnp.asarray(ids),
                "linear": jnp.asarray([slot, SLOTS], jnp.int32)}
    lens = jnp.asarray([n, 1])
    if at == 0:
        lg, pool, aux = pk.prefill(params, jnp.asarray(padded), lens, pool,
                                   page_ids, cfg)
    else:
        ctab = np.full((2, 16), PAGES, np.int32)
        ctab[0, :at // PS] = np.arange(at // PS)
        lg, pool, aux = pk.prefill_ctx(
            params, jnp.asarray(padded), lens, pool, page_ids,
            {"full": jnp.asarray(ctab)}, jnp.asarray([at, 0]), cfg,
            kernel=kernel)
    assert aux == ()
    return lg[0], pool


def _in_pieces(cfg, params, toks, cuts, slot=1, kernel="gather"):
    pool, lg = _pool(cfg), None
    for at, upto in zip([0] + cuts, cuts + [len(toks)]):
        tb = -(-(upto - at) // 32) * 32          # a padded tail
        lg, pool = _piece(cfg, params, pool, toks, at, upto, slot, tb,
                          kernel)
    return lg, pool


def _decode(cfg, params, pool, toks, plen, slot, kernel="gather"):
    out = []
    table = np.full((SLOTS, 16), PAGES, np.int32)
    table[slot] = np.arange(16)
    active = np.zeros((SLOTS,), bool)
    active[slot] = True
    step = jax.jit(lambda *a: pk.decode_step(*a, cfg, kernel=kernel))
    for pos in range(plen, len(toks)):
        tokens = np.zeros((SLOTS,), np.int32)
        tokens[slot] = toks[pos]
        lengths = np.zeros((SLOTS,), np.int32)
        lengths[slot] = pos
        lg, pool, _ = step(params, jnp.asarray(tokens), pool,
                           {"full": jnp.asarray(table)},
                           jnp.asarray(lengths), jnp.asarray(active))
        out.append(np.asarray(lg[slot]))
    return np.stack(out), pool


@pytest.mark.pallas
@pytest.mark.parametrize("kernel,interpret", [("gather", False),
                                              ("pallas", True)])
def test_prefill_then_decode_is_the_reference_s_forward(kernel, interpret):
    """85 tokens through the paged prefill (a row of 96 beside a padding
    row), then 14 decode steps in slot 1 of 3: every position's logits
    are the reference's full forward over the same 99 tokens. 3e-4 of a
    scale of 0.14: scan, update and paged read each sum in another order
    than the recurrence (read 2e-5 to 5e-5)."""
    config = _config()
    cfg, params = _model(config, interpret=interpret)
    toks = _tokens(99)
    first, pool = _in_pieces(cfg, params, toks[:85], [], kernel=kernel)
    rest, pool = _decode(cfg, params, pool, toks, 85, 1, kernel)
    want = family.reference().logits(config, params,
                                     jnp.asarray(toks[None]), 84, 99)[0]
    np.testing.assert_allclose(np.concatenate([first[None], rest]),
                               np.asarray(want), atol=3e-4)
    for layer in pool.layers[:3]:
        for name in ("state", "conv"):
            assert not np.asarray(layer[name])[[0, 2]].any(), name
        assert np.asarray(layer["state"])[1].any()


@pytest.mark.pallas
@pytest.mark.parametrize("kernel", ["gather", "pallas"])
@pytest.mark.parametrize("cuts", [[64], [32, 64], [32, 64, 96]],
                         ids=["2", "3", "4"])
def test_a_prompt_in_pieces_is_the_prompt_whole(cuts, kernel):
    """107 tokens prefilled whole (a row of 128) and in 2, 3 and 4
    pieces that start on a page boundary, the last with a padded tail
    (11 or 43 real tokens in a row of 32 or 64): the last position's
    logits, every slot's state and kept columns, and the logits of 6
    decode steps after either. 2e-4: a piece starts its scan from a
    float32 state where the whole prompt carries it inside the kernel,
    and attends over pages where the whole prompt attends over rows
    (read 1e-5 to 4e-5 on logits of sd 0.14, 2e-5 on states of sd
    0.3). Every kernel through the interpreter (the whole prompt's row
    of 128 is the flash kernel's); the lane is the context's read."""
    cfg, params, toks, whole, pool_w, after_w, want = _whole(kernel)
    parts, pool_p = _in_pieces(cfg, params, toks[:107], list(cuts),
                               kernel=kernel)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(want[0]),
                               atol=3e-4)
    for lw, lp in zip(pool_w.layers[:3], pool_p.layers[:3]):
        for name in ("state", "conv"):
            assert np.asarray(lw[name])[1].any()
            np.testing.assert_allclose(np.asarray(lp[name]),
                                       np.asarray(lw[name]), atol=2e-4)
    after_p, _ = _decode(cfg, params, pool_p, toks, 107, 1, kernel)
    np.testing.assert_allclose(after_p, after_w, atol=2e-4)
    np.testing.assert_allclose(after_p, np.asarray(want[1:]), atol=3e-4)


@functools.lru_cache(maxsize=None)
def _whole(kernel):
    """The 107 tokens prefilled whole and decoded on, once a lane."""
    config = _config()
    cfg, params = _model(config, interpret=True)
    toks = _tokens(113, seed=5)
    whole, pool_w = _in_pieces(cfg, params, toks[:107], [], kernel=kernel)
    after_w, _ = _decode(cfg, params, pool_w, toks, 107, 1, kernel)
    want = family.reference().logits(config, params,
                                     jnp.asarray(toks[None]), 106, 113)[0]
    return cfg, params, toks, whole, pool_w, after_w, want


def test_bfloat16_in_float32_s_place_fails():
    """The tolerances above are tight enough to tell: the program in
    bfloat16 (weights and activations) against the float32 reference
    misses 3e-4 by a factor of ten and more (read 2e-2)."""
    config = _config()
    cfg32, params = _model(config)
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    cfg = cfg32._replace(dtype=jnp.bfloat16)
    toks = jnp.asarray(_tokens(100)[None])
    want = family.reference().logits(config, params, toks, 0, 100)[0]
    got = jax.jit(lambda p, t: hybrid.logits(p, t, cfg))(low, toks)[0]
    assert float(jnp.max(jnp.abs(got - want))) > 3e-3


# ------------------------------------------------------ the scheduler
def _loop(cfg, params, **kw):
    args = dict(slots=SLOTS, page_size=PS, n_pages=64, prefix_cache=False,
                prefill_tokens_per_pass=32, kernel="gather", start=False)
    return dl.DecodeLoop(params, cfg, **dict(args, **kw))


def _greedy(config, params, prompt, n):
    """`n` tokens the reference decodes greedily after `prompt`. The
    sequence is padded to 128 (one shape, one compile): the reference
    is causal, so what follows a position does not reach it."""
    seq = list(prompt)
    for _ in range(n):
        padded = np.zeros((1, 128), np.int32)
        padded[0, :len(seq)] = seq
        lg = family.reference().logits(config, params, jnp.asarray(padded),
                                       len(seq) - 1, len(seq))
        seq.append(int(jnp.argmax(lg[0, 0])))
    return seq[len(prompt):]


def test_a_long_prompt_is_prefilled_a_piece_a_pass():
    """90 tokens under a bound of 32: three passes of a piece (32, 32,
    then 26 in the 32 bucket), one program for the first and ONE for
    the two on a kept state whatever their context, a decode step of
    the stream that is running between two pieces, and both requests
    served the tokens the reference decodes greedily. The running
    stream's tokens are those it is served alone."""
    config = _config()
    cfg, params = _model(config)
    short, long_ = _tokens(20, seed=2), _tokens(90, seed=3)
    alone = _loop(cfg, params)
    want_short = alone.submit(short, 9, prefix_cache=False)
    alone.run_until_idle()
    loop = _loop(cfg, params)
    a = loop.submit(short, 9, prefix_cache=False)
    loop.tick()                                   # a's prefill
    b = loop.submit(long_, 5, prefix_cache=False)
    seen = []
    for _ in range(3):
        before = loop.snapshot()
        loop.tick()
        after = loop.snapshot()
        seen.append((after["prefill_tokens"] - before["prefill_tokens"],
                     after["dispatches"] - before["dispatches"]))
    # a piece and a step of the running stream in every pass
    assert seen == [(32, 1), (32, 1), (26, 1)]
    assert loop.snapshot()["prefill_chunks"] == {
        "first": 1, "carried": 2, "tokens": 90}
    loop.run_until_idle()
    assert a.result() == want_short.result() == \
        _greedy(config, params, short, 9)
    assert b.result() == _greedy(config, params, long_, 5)
    assert len(set(b.result())) > 2               # loud enough to tell
    frag = loop.plan_fragment()
    assert frag["prefill"] == [[1, 32]]
    assert frag["prefill_chunk"] == [[1, 16, 32]] and \
        frag["prefill_ctx"] == []
    snap = loop.snapshot()
    assert snap["prefill_programs"] == 2 and \
        snap["decode_step_programs"] == 1
    assert snap["prefill_tokens"] == 110 and snap["pages_in_use"] == 0


def test_a_last_piece_takes_a_bucket_of_a_quarter_piece_or_more():
    """A bound of 64: a last piece of 3 tokens rides the 16 bucket (a
    quarter of a piece), not the 8 one, so the pieces on a kept state
    are three programs at most (16, 32, 64); a cancelled prompt gives
    its pages back between two pieces."""
    config = _config()
    cfg, params = _model(config)
    loop = _loop(cfg, params, prefill_tokens_per_pass=64)
    s = loop.submit(_tokens(67, seed=4), 3, prefix_cache=False)
    loop.run_until_idle()
    assert s.result() == _greedy(config, params, _tokens(67, seed=4), 3)
    assert loop.plan_fragment()["prefill_chunk"] == [[1, 16, 16]]
    gone = loop.submit(_tokens(120, seed=6), 3, prefix_cache=False)
    loop.tick()
    assert loop.snapshot()["pages_in_use"] == 15
    gone.cancel()
    loop.run_until_idle()
    assert gone.finish_reason == "cancelled"
    assert loop.snapshot()["pages_in_use"] == 0
    assert loop.snapshot()["prefill_chunks"]["carried"] == 1


def test_the_span_says_what_a_piece_starts_from():
    from deeplearning4j_tpu import telemetry

    config = _config()
    cfg, params = _model(config)
    loop = _loop(cfg, params)
    tracer = telemetry.start_tracing()
    try:
        loop.submit(_tokens(50, seed=7), 2, prefix_cache=False)
        loop.run_until_idle()
    finally:
        telemetry.stop_tracing()
    spans = [e["args"] for e in tracer.chrome_trace()["traceEvents"]
             if e["name"] == "decode.prefill_dispatch"]
    assert [(s["ctx"], s["carried"], s["tokens"], s["tb"])
            for s in spans] == [(0, False, 32, 32), (16, True, 18, 32)]


def test_prompts_within_the_bound_stay_one_pass():
    """`qwen3_next`'s and `cohere2_moe`'s cells: 7,168 tokens in the
    8,192 bucket under a bound of 8,192 is one pass, as it was; a model
    with a window kind, or a loop that shares pages by content, has no
    pieces whatever the bound."""
    from benchmark import manifest

    for name, one in (("qwen3next-ep8-agent-long", [(1, 8190)]),
                      ("cmdaplus-ep8-agent-long", [(1, 8190)])):
        cell = manifest.load_cell(name)
        srv = cell.config["serving"]
        assert srv["prefill_tokens_per_pass"] == 8192 >= \
            cell.traffic["prompt_len"]["value"]
        assert cell.family.warm_requests(cell.config, cell.traffic,
                                         51) == one
    q3n = qwen3_next.model_config(dict(tiny_hybrid.CONFIG,
                                       dtype="float32"))
    params = weights.make_params(SEED, qwen3_next,
                                 dict(tiny_hybrid.CONFIG, dtype="float32"))
    loop = dl.DecodeLoop(params, q3n, slots=2, page_size=4, n_pages=32,
                         prefix_cache=False, prefill_tokens_per_pass=32,
                         kernel="gather", start=False)
    assert loop._piece == 32
    s = loop.submit(_tokens(21), 3, prefix_cache=False)
    loop.run_until_idle()
    assert len(s.result()) == 3
    assert loop.snapshot()["prefill_chunks"] == {
        "first": 0, "carried": 0, "tokens": 0}
    assert loop.plan_fragment()["prefill_chunk"] == []
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig, init_transformer_params)

    gpt = TransformerConfig(vocab_size=97, d_model=32, n_heads=2,
                            n_layers=1, d_ff=64, max_len=64)
    shared = dl.DecodeLoop(
        init_transformer_params(jax.random.PRNGKey(0), gpt), gpt, slots=2,
        page_size=4, prefill_tokens_per_pass=16, start=False)
    assert shared._piece is None                  # prefix cache on
