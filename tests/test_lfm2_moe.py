"""The `lfm2_moe` family through the program at a small size on the
CPU (`tests/benchmark_suite/tiny_lfm2.py`: d 64, 4 query heads over 2
K/V heads of 16, 8 experts of which 2 are chosen, one dense layer, then
one or two periods `full, conv, conv, conv`): the one block told that
its layers are conv and full, that the first is dense, that the router
has a selection bias and the head is tied, against the plain reference;
the cache's lanes against the reference's full forward; a prompt
prefilled in pieces on the kept columns against the same prompt whole;
the expert layer alone; and what the other families compile, unmoved.

Every comparison is float32 against float32, on weights whose blocks'
matrices are scaled by 8 (at N(0, 0.02) the tiny model repeats a
prompt's last token through its tied head, and a stale column or a
wrong choice would serve the same tokens as a sound one). Each
tolerance is stated where it is used with what was read. The program
in bfloat16 reads 1e-2 and more against the same reference, which
every tolerance here refuses by a factor of ten and more
(`test_bfloat16_in_float32_s_place_fails`)."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import lfm2_moe as family
from deeplearning4j_tpu.models import hybrid_transformer as hybrid
from deeplearning4j_tpu.models import moe_transformer as moe
from deeplearning4j_tpu.serving import decode_loop as dl
from deeplearning4j_tpu.serving import paged_kinds as pk
from tests.benchmark_suite import tiny_lfm2

PS, SEED, SLOTS, PAGES = 8, 2 ** 31 + 41, 3, 40


def _tokens(n, seed=1):
    return np.random.RandomState(seed).randint(0, 97, (n,)).astype(np.int32)


def _loud(params):
    return dict(params, blocks=jax.tree_util.tree_map(
        lambda a: a * 8 if a.ndim >= 2 else a, params["blocks"]))


def _model(periods=1, **over):
    config = tiny_lfm2.config(periods, "float32")
    params = _loud(weights.make_params(SEED, family, config))
    return config, family.model_config(config)._replace(**over), params


def _reference(config, params, toks, first, last):
    return np.asarray(family.reference().logits(
        config, params, jnp.asarray(toks[None]), first, last)[0])


# ------------------------------------------------------------- the block
@pytest.mark.parametrize("periods", [1, 2])
def test_the_uncached_forward_is_the_reference(periods):
    """5 and 9 layers over 60 tokens, every position's logits. 1e-4 of
    logits of sd 0.16 (the tied head: rows of the embedding, N(0, 0.02)
    over 64): products and sums in another order (read 1e-6)."""
    config, cfg, params = _model(periods)
    assert cfg.layer_kinds == ("conv",) + ("full", "conv", "conv",
                                           "conv") * periods
    toks = _tokens(60)
    got = np.asarray(hybrid.logits(params, jnp.asarray(toks[None]),
                                   cfg)[0])
    want = _reference(config, params, toks, 0, 60)
    assert want.std() > 0.1
    # not an echo of the input: the model says something of its own
    assert (want.argmax(-1) != toks).mean() > 0.5
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_what_the_block_is_told_changes_what_it_computes():
    """Each thing this family tells the block moves the logits by far
    more than the tolerance above (a switch that did nothing would pass
    every other test), and the kinds and sizes it refuses."""
    config, cfg, params = _model()
    toks = jnp.asarray(_tokens(40)[None])
    base = hybrid.logits(params, toks, cfg)

    def moved(p=params, **over):
        other = hybrid.logits(p, toks, cfg._replace(**over))
        return float(jnp.max(jnp.abs(other - base)))

    untied = dict(params, head=params["embed"].T)
    assert moved(p=untied, tied_head=False) == 0.0
    assert moved(router_bias=False) > 1e-2
    assert moved(rotary_dim=0) > 1e-2
    with pytest.raises(ValueError, match="layer_kinds"):
        cfg._replace(layer_kinds=("conv", "window")).check()
    with pytest.raises(ValueError, match="dense"):
        cfg._replace(n_dense_layers=9).check()


def test_the_convolution_keeps_the_last_two_real_columns_and_no_more():
    """The three-tap sum over kept columns: a sequence in one call, in
    two calls on the columns the first kept, and in a row padded past
    its real length (the padding holds other values and moves neither
    the output of the real rows nor the columns kept) are one and the
    same, bit for bit; and the columns kept are the last two REAL
    columns of u, not the row's last two."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    u = jax.random.normal(ks[0], (2, 21, 64))
    w = jax.random.normal(ks[1], (3, 64))
    whole, kept = hybrid.conv_mix(u, w)
    assert kept["conv"].shape == (2, 128)
    np.testing.assert_array_equal(np.asarray(kept["conv"]).reshape(2, 2, 64),
                                  np.asarray(u[:, -2:]))
    want = w[0] * jnp.pad(u, ((0, 0), (2, 0), (0, 0)))[:, :21] \
        + w[1] * jnp.pad(u, ((0, 0), (1, 0), (0, 0)))[:, :21] + w[2] * u
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               atol=1e-6)
    first, k1 = hybrid.conv_mix(u[:, :13], w)
    second, k2 = hybrid.conv_mix(u[:, 13:], w, prev=k1["conv"])
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([first, second], 1)), np.asarray(whole))
    np.testing.assert_array_equal(np.asarray(k2["conv"]),
                                  np.asarray(kept["conv"]))
    padded = jnp.concatenate([u, 9.0 + jax.random.normal(ks[2], (2, 11,
                                                                   64))], 1)
    out, kp = hybrid.conv_mix(padded, w, true_len=jnp.array([21, 17]))
    np.testing.assert_array_equal(np.asarray(out[0, :21]),
                                  np.asarray(whole[0]))
    np.testing.assert_array_equal(np.asarray(kp["conv"][0]),
                                  np.asarray(kept["conv"][0]))
    np.testing.assert_array_equal(np.asarray(kp["conv"][1]).reshape(2, 64),
                                  np.asarray(u[1, 15:17]))


def test_the_factored_convolution_is_the_linear_layer_s_former_one():
    """`causal_conv` with SiLU is what the gated delta rule's convolution
    computed inline before it was factored out, in the same order and
    types, bit for bit: the columns concatenated behind what the slot
    kept, a float32 sum of the taps, SiLU, the input's type."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    u = jax.random.normal(ks[0], (2, 9, 48)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[1], (4, 48)).astype(jnp.bfloat16)
    prev = jax.random.normal(ks[2], (2, 3 * 48)).astype(jnp.bfloat16)
    true_len = jnp.array([9, 5])

    def former(prev):
        ext = jnp.concatenate([prev.reshape(2, 3, 48), u], axis=1)
        acc = sum(ext[:, j:j + 9].astype(jnp.float32)
                  * w.astype(jnp.float32)[j] for j in range(4))
        rows = true_len[:, None] + jnp.arange(3)[None, :]
        return (jax.nn.silu(acc).astype(u.dtype),
                jnp.take_along_axis(ext, rows[:, :, None], axis=1))

    for p in (prev, jnp.zeros_like(prev)):
        got = hybrid.causal_conv(u, w, jax.nn.silu, prev=p,
                                 true_len=true_len)
        for a, b in zip(got, former(p)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


# ---------------------------------------------------- the expert layer
def _rows(n=40, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, 64))


def test_the_all_held_expert_layer_is_the_reference_s_whole_layer():
    """Every expert held: the routed sum IS the layer (no shared expert,
    nothing left to another chip). 40 rows, 8 experts, 2 chosen: 2e-5 of
    outputs of sd ~0.4 (read 3e-7); every expert had a pair."""
    config, cfg, params = _model()
    p = params["blocks"][1]
    h = _rows()
    got, pairs = moe.expert_layer(p, h, cfg)
    want = family.reference().expert_layer(config, p, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)
    assert int(pairs.sum()) == 40 * 2 and (np.asarray(pairs) > 0).all()


def test_a_large_selection_bias_changes_the_choice_not_the_weights():
    """A bias of +30 on experts 5 and 6 puts every token on them; the
    weights stay the sigmoid SCORES of 5 and 6, normalised over their
    sum + 1e-6, whatever the bias: the layer is those two experts
    weighted so, by hand, and the reference's (2e-5 as above)."""
    config, cfg, params = _model()
    p = dict(params["blocks"][1])
    p["expert_bias"] = jnp.zeros((8,)).at[5].set(30.0).at[6].set(30.0)
    h = _rows()
    got, pairs = moe.expert_layer(p, h, cfg)
    assert np.asarray(pairs).tolist() == [0] * 5 + [40, 40, 0]
    s = jax.nn.sigmoid(jnp.dot(h, p["router"], precision="highest"))
    w = s[:, 5:7] / (s[:, 5:7].sum(-1, keepdims=True) + 1e-6)

    def expert(e):
        ex = p["experts"]
        with jax.default_matmul_precision("highest"):
            return (jax.nn.silu(h @ ex["gate"][e]) * (h @ ex["up"][e])) \
                @ ex["down"][e]

    by_hand = w[:, :1] * expert(5) + w[:, 1:] * expert(6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(by_hand),
                               atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(family.reference().expert_layer(config, p, h)),
        atol=2e-5)
    # the same scores with no bias choose otherwise
    _, unbiased = moe.expert_layer(dict(p, expert_bias=jnp.zeros((8,))), h,
                                   cfg)
    assert np.asarray(unbiased)[[0, 1, 2, 3, 4, 7]].sum() > 0


# ----------------------------------------------- the cache's lanes
def _pool(cfg):
    return pk.init_pool(cfg, {"full": PAGES}, PS, slots=SLOTS)


@functools.lru_cache(maxsize=None)
def _lane(name, cfg, kernel="gather"):
    """One jitted lane a configuration: each shape compiles once for
    every test."""
    if name == "prefill":
        return jax.jit(lambda *a: pk.prefill(*a, cfg))
    return jax.jit(lambda *a: getattr(pk, name)(*a, cfg, kernel=kernel))


def _piece(cfg, params, pool, toks, at, upto, slot, tb, kernel,
           pad_with=0):
    """Tokens [at, upto) of `toks` into `slot`, padded to `tb` (with
    `pad_with` ids): the cold prefill where at == 0, else a piece on
    what the slot keeps."""
    n = upto - at
    padded = np.full((2, tb), pad_with, np.int32)   # row 1 is padding
    padded[0, :n] = toks[at:upto]
    ids = np.full((2, tb // PS), PAGES, np.int32)
    ids[0, :-(-n // PS)] = at // PS + np.arange(-(-n // PS))
    page_ids = {"full": jnp.asarray(ids),
                "conv": jnp.asarray([slot, SLOTS], jnp.int32)}
    lens = jnp.asarray([n, 1])
    if at == 0:
        lg, pool, aux = _lane("prefill", cfg)(
            params, jnp.asarray(padded), lens, pool, page_ids)
    else:
        ctab = np.full((2, 16), PAGES, np.int32)
        ctab[0, :at // PS] = np.arange(at // PS)
        lg, pool, aux = _lane("prefill_ctx", cfg, kernel)(
            params, jnp.asarray(padded), lens, pool, page_ids,
            {"full": jnp.asarray(ctab)}, jnp.asarray([at, 0]))
    # one row of pairs a layer, none in the dense one
    assert aux.shape == (cfg.n_layers, 8) and not np.asarray(aux)[0].any()
    return lg[0], pool


def _in_pieces(cfg, params, toks, cuts, slot=1, kernel="gather", **kw):
    pool, lg = _pool(cfg), None
    for at, upto in zip([0] + cuts, cuts + [len(toks)]):
        tb = -(-(upto - at) // 16) * 16            # a padded tail
        lg, pool = _piece(cfg, params, pool, toks, at, upto, slot, tb,
                          kernel, **kw)
    return lg, pool


def _decode(cfg, params, pool, toks, plen, slot, kernel="gather"):
    out = []
    table = np.full((SLOTS, 16), PAGES, np.int32)
    table[slot] = np.arange(16)
    active = np.zeros((SLOTS,), bool)
    active[slot] = True
    step = _lane("decode_step", cfg, kernel)
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


def _conv_columns(pool):
    return [np.asarray(layer["conv"]) for layer in pool.layers
            if "conv" in layer]


@pytest.mark.pallas
@pytest.mark.parametrize("kernel,interpret", [("gather", False),
                                              ("pallas", True)])
def test_prefill_then_decode_is_the_reference_s_forward(kernel, interpret):
    """45 tokens through the paged prefill (a row of 48 beside a padding
    row), then 10 decode steps in slot 1 of 3: every position's logits
    are the reference's full forward over the same 55 tokens. 1e-4 of
    logits of sd 0.16 (read 1e-6); the other slots' columns stay
    zero."""
    config, cfg, params = _model(interpret=interpret)
    toks = _tokens(55, seed=2)
    first, pool = _in_pieces(cfg, params, toks[:45], [], kernel=kernel)
    rest, pool = _decode(cfg, params, pool, toks, 45, 1, kernel)
    want = _reference(config, params, toks, 44, 55)
    np.testing.assert_allclose(np.concatenate([first[None], rest]), want,
                               atol=1e-4)
    for cols in _conv_columns(pool):
        assert not cols[[0, 2]].any() and cols[1].any()


@pytest.mark.parametrize("cuts", [[32], [16, 48]], ids=["2", "3"])
def test_a_prompt_in_pieces_is_the_prompt_whole(cuts):
    """61 tokens prefilled whole (a row of 64) and in 2 and 3 pieces
    that start on a page boundary, the last with a padded tail: the
    last position's logits and the slot's kept columns, then 5 decode
    steps after either. 1e-4 (read 2e-6 on logits, 1e-6 on columns): a
    piece attends over pages where the whole prompt attends over rows,
    and its convolution starts from the columns the slot kept."""
    config, cfg, params = _model()
    toks = _tokens(66, seed=5)
    whole, pool_w = _in_pieces(cfg, params, toks[:61], [])
    parts, pool_p = _in_pieces(cfg, params, toks[:61], list(cuts))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=1e-4)
    for cw, cp in zip(_conv_columns(pool_w), _conv_columns(pool_p)):
        assert cw[1].any()
        np.testing.assert_allclose(cp, cw, atol=1e-4)
    after_w, _ = _decode(cfg, params, pool_w, toks, 61, 1)
    after_p, _ = _decode(cfg, params, pool_p, toks, 61, 1)
    np.testing.assert_allclose(after_p, after_w, atol=1e-4)
    np.testing.assert_allclose(after_p, _reference(config, params, toks,
                                                   61, 66), atol=1e-4)


def test_a_padded_tail_moves_no_kept_column():
    """The same 21 tokens in a row of 32 whose padding is ids 0 and in
    one whose padding is ids 96: the kept columns are the same bit for
    bit, and the same as the 21 tokens' own last two columns in a row of
    24 (1e-5 of columns of sd ~0.5: another program, whose earlier
    layers round otherwise; read 3e-6)."""
    _, cfg, params = _model()
    toks = _tokens(21, seed=7)
    pools = [_in_pieces(cfg, params, toks, [], pad_with=v)[1]
             for v in (0, 96)]
    a, b = (_conv_columns(p) for p in pools)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    exact = _conv_columns(_piece(cfg, params, _pool(cfg), toks, 0, 21, 1,
                                 24, "gather")[1])
    for x, y in zip(a, exact):
        np.testing.assert_allclose(x, y, atol=1e-5)


def test_bfloat16_in_float32_s_place_fails():
    """The tolerances above are tight enough to tell: the program in
    bfloat16 (weights and activations) against the float32 reference
    misses 1e-4 by a factor of ten and more (read ~5e-2)."""
    config, cfg32, params = _model()
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    cfg = cfg32._replace(dtype=jnp.bfloat16)
    toks = _tokens(60)
    want = _reference(config, params, toks, 0, 60)
    got = jax.jit(lambda p, t: hybrid.logits(p, t, cfg))(
        low, jnp.asarray(toks[None]))[0]
    assert float(np.max(np.abs(np.asarray(got) - want))) > 1e-3


# ------------------------------------------------------ the scheduler
def test_the_loop_serves_the_reference_s_tokens_and_counts_its_kinds():
    """Two requests through `DecodeLoop`, one prompt longer than the
    bound (prefilled in pieces on the kept columns): both are served the
    tokens the reference decodes greedily; the conv kind's bytes stand
    beside the pages, and the expert counters count the 4 expert layers
    and none of the dense one."""
    config, cfg, params = _model()
    loop = dl.DecodeLoop(params, cfg, slots=SLOTS, page_size=PS,
                         n_pages=64, prefix_cache=False,
                         prefill_tokens_per_pass=32, kernel="gather",
                         start=False)
    prompts = [_tokens(20, seed=11), _tokens(45, seed=12)]
    streams = [loop.submit(p, 6, prefix_cache=False) for p in prompts]
    loop.run_until_idle()
    for p, s in zip(prompts, streams):
        seq = list(p)
        for _ in range(6):
            padded = np.zeros((64,), np.int32)
            padded[:len(seq)] = seq
            lg = _reference(config, params, padded, len(seq) - 1, len(seq))
            seq.append(int(lg[0].argmax()))
        assert s.result() == seq[len(p):]
    snap = loop.snapshot()
    assert snap["prefill_chunks"]["carried"] == 1
    state = snap["state"]
    assert snap["state_by_kind"] == {
        "conv": {"bytes": SLOTS * 4 * 2 * 64 * 4,
                 "bytes_per_slot": 4 * 2 * 64 * 4, "layers": 4}}
    assert state["bytes"] == loop.state_bytes() == loop.state_bytes("conv")
    assert loop.state_bytes("linear") == 0
    moe_snap = snap["moe"]
    pairs = np.asarray(moe_snap["pairs_by_layer_expert"])
    assert pairs.shape == (5, 8) and not pairs[0].any()
    assert pairs[1:].sum() == moe_snap["pairs"] == \
        4 * 2 * moe_snap["tokens"]
    assert moe_snap["experts_touched"] > 0


def test_the_conv_kind_is_refused_by_name_where_a_linear_kind_is():
    _, cfg, _ = _model()
    for asked, word in ((dict(prefix_cache=True), "prefix sharing"),
                        (dict(speculation=2), "speculation"),
                        (dict(horizon=2), "horizon"),
                        (dict(role=dl.ROLE_PREFILL), "prefill-role")):
        args = dict(dict(prefix_cache=False, speculation=0, horizon=1,
                         role=dl.ROLE_UNIFIED), **asked)
        with pytest.raises(ValueError, match=word) as e:
            dl.DecodeLoop._check_refusals(cfg, **args)
        assert "conv layers" in str(e.value)
    with pytest.raises(NotImplementedError, match="conv kind"):
        pk._no_linear(pk.KIND_CONV, "the widened verify step")


def test_the_program_names_its_new_parts():
    """`short_conv` over the conv mixer, `dense_ff` over the leading
    dense layer and `moe_router` over the biased choice, in the decode
    step's program."""
    _, cfg, params = _model()
    pool = _pool(cfg)
    table = {"full": jnp.zeros((SLOTS, 16), jnp.int32)}
    text = jax.jit(lambda *a: pk.decode_step(*a, cfg)).lower(
        params, jnp.zeros((SLOTS,), jnp.int32), pool, table,
        jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS,), bool)
    ).as_text(debug_info=True)
    for scope in ("short_conv", "dense_ff", "moe_router", "moe_experts"):
        assert scope in text, scope


# -------------------------------------- what the other families compile
#: digests of the lowered programs (StableHLO, no debug information) of
#: the other families' tiny configurations, as the tree compiled them
#: before the conv kind, the dense layers, the selection bias and the
#: tied head came in: none of it may reach their programs
UNMOVED = {
    "q3n.logits": "c3cbfc94a4611dcc",
    "q3n.prefill": "263cee6081cbceba",
    "q3n.step.gather": "bc6182ebdc6b3686",
    "olm.logits": "b2b9e46f9e9b2bf7",
    "olm.prefill": "9870779f0f0a80c4",
    "olm.step.gather": "d7925b48e80c41b4",
    "ep8.logits": "149a46314cf1e5ee",
    "ep8.prefill": "f34d39111508110a",
    "ep8.step.gather": "5cbaa63143408e46",
}


def _other_programs():
    """(name, lowered text) of the uncached logits, the paged prefill
    and the dense lane of the decode step of a `qwen3_next`-like, an
    `olmo_hybrid`-like and a `cohere2_moe`-like tiny configuration."""
    def hyb(**kw):
        base = dict(vocab_size=97, d_model=64, n_heads=4, n_kv_heads=2,
                    head_dim=16, d_ff=32,
                    layer_kinds=("linear", "linear", "linear", "full"),
                    n_experts=16, experts_per_token=4, n_shared=1,
                    n_held=4, held_first=4, lin_k_heads=2, lin_v_heads=4,
                    lin_k_dim=8, lin_v_dim=8, conv_kernel=4, rotary_dim=8,
                    max_len=64, dtype=jnp.bfloat16, interpret=True)
        return hybrid.HybridConfig(**dict(base, **kw)), \
            hybrid.init_hybrid_params
    cfgs = {"q3n": hyb(),
            "olm": hyb(n_experts=0, experts_per_token=0, n_shared=0,
                       n_held=0, held_first=0, norm_place="post",
                       attn_gate=False, qk_norm="width", rotary_dim=0,
                       allow_neg_eigval=True),
            "ep8": (moe.MoEConfig(
                vocab_size=97, d_model=64, n_heads=4, n_kv_heads=2,
                head_dim=16, d_ff=32,
                layer_kinds=("window", "window", "window", "full"),
                window=8, n_experts=16, experts_per_token=4, n_shared=2,
                n_held=4, held_first=4, max_len=64, dtype=jnp.bfloat16,
                interpret=True), moe.init_moe_params)}
    out = []
    for name, (cfg, init) in cfgs.items():
        model = moe if name == "ep8" else hybrid
        params = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
        toks = jax.ShapeDtypeStruct((2, 16), jnp.int32)
        out.append((name + ".logits", jax.jit(
            lambda p, t: model.logits(p, t, cfg)).lower(params, toks)))
        pool = jax.eval_shape(lambda: pk.init_pool(
            cfg, {k: 16 for k in pk.kinds_of(cfg)}, 4, slots=4))
        ids = {k: jax.ShapeDtypeStruct((2, 4), jnp.int32)
               for k in pk.kinds_of(cfg)}
        if name != "ep8":
            ids["linear"] = jax.ShapeDtypeStruct((2,), jnp.int32)
        vec = jax.ShapeDtypeStruct((2,), jnp.int32)
        out.append((name + ".prefill", jax.jit(
            lambda p, t, n, q, i: pk.prefill(p, t, n, q, i, cfg)).lower(
                params, toks, vec, pool, ids)))
        tables = {k: jax.ShapeDtypeStruct((4, 16), jnp.int32)
                  for k in pk.kinds_of(cfg)}
        s4 = jax.ShapeDtypeStruct((4,), jnp.int32)
        out.append((f"{name}.step.gather", jax.jit(
            lambda p, t, q, tb, n, a: pk.decode_step(
                p, t, q, tb, n, a, cfg, kernel="gather")).lower(
            params, s4, pool, tables, s4,
            jax.ShapeDtypeStruct((4,), bool))))
    return [(name, lowered.as_text()) for name, lowered in out]


def test_the_other_families_keep_their_programs():
    got = {name: hashlib.sha256(text.encode()).hexdigest()[:16]
           for name, text in _other_programs()}
    assert got == UNMOVED
