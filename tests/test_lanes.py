"""Every lane of every model against the model's own uncached forward.

A model has one block and one forward (`models.model_of(cfg)`); the
caches are that forward under their `attend` callbacks. So each lane —
the dense masked read in place of the flash kernel, the contiguous cache
(`kv_cache.py`), the paged prefill and decode step on the gather lane
and on the paged kernel (interpreted), the widened verify step at W = 1
and W = 3, and the prefill of a tail over cached prefix pages
(`paged_kinds.py`) — has to give, teacher-forced over the same tokens,
the next-token logits of the uncached forward: for the GPT-2 block, for
the block with grouped heads and an expert layer whose layers are all
full, and for the one with window layers as well. Tolerances are the
ones tests/test_paged_decode.py (1e-5) and tests/test_moe_transformer.py
(1e-4) hold their model to.

And one `DecodeLoop` for each family over the same prompts: the
`snapshot()` values a reader of either has always seen.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.attention.blockwise import masked_attention
from deeplearning4j_tpu.models import model_of
from deeplearning4j_tpu.models import moe_transformer as moe
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_transformer_params,
                                                   visible)
from deeplearning4j_tpu.serving import kv_cache, paged_kinds
from deeplearning4j_tpu.serving.decode_loop import DecodeLoop

PS, N_P, T, PLEN, CTX = 4, 16, 40, 21, 12     # pages of 4; 12 cached


def _moe(kinds):
    return moe.MoEConfig(
        vocab_size=17, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        d_ff=32, layer_kinds=kinds, window=10, n_experts=8,
        experts_per_token=2, n_shared=1, n_held=4, held_first=2,
        max_len=64).check()


MODELS = {
    "gpt2": (TransformerConfig(vocab_size=17, d_model=32, n_heads=2,
                               n_layers=2, d_ff=64, max_len=64), 1e-5),
    "moe-full": (_moe(("full", "full")), 1e-4),
    "moe-window-full": (_moe(("window", "window", "full")), 1e-4),
}
TOKENS = np.random.RandomState(5).randint(0, 17, (T,)).astype(np.int32)


def _params(cfg):
    init = (moe.init_moe_params if model_of(cfg) is moe
            else init_transformer_params)
    return init(jax.random.PRNGKey(0), cfg)


def _uncached(cfg, params, attend=None):
    """(T, vocab): the model's forward and head over the whole row, under
    its flash callback or the one given."""
    model = model_of(cfg)
    if attend is None:
        return np.asarray((moe.logits if model is moe else
                           model.transformer_logits)(
            params, jnp.asarray(TOKENS[None]), cfg))[0]
    x, _, _ = model.forward(params, jnp.asarray(TOKENS[None]),
                            jnp.arange(T), cfg, attend)
    return np.asarray(model.head(params, x, cfg))[0]


# ---------------------------------------------------------- the lanes:
# each returns logits (T - PLEN + 1, vocab) of positions PLEN-1 .. T-1
def dense(cfg, params):
    pos = jnp.arange(T)
    return _uncached(cfg, params, lambda _l, kind, q, k, v: (
        masked_attention(q, k, v, visible(cfg, kind, pos, pos)),
        None))[PLEN - 1:]


def contiguous(cfg, params):
    lg, cache = kv_cache.prefill(params, jnp.asarray(TOKENS[None, :PLEN]),
                                 kv_cache.init_cache(cfg, 1), cfg)
    out = [lg[0]]
    for pos in range(PLEN, T):
        lg, cache = kv_cache.decode_step(
            params, jnp.asarray(TOKENS[pos:pos + 1]), cache, cfg)
        out.append(lg[0])
    return np.stack(out)


def _paged_state(cfg):
    """A pool of N_P pages a kind and slot 0's tables, every logical
    page its own (nothing released: a window layer's mask alone hides
    what left its window)."""
    kinds = paged_kinds.kinds_of(cfg)
    pool = paged_kinds.init_pool(cfg, dict.fromkeys(kinds, N_P), PS)
    tables = {k: jnp.arange(N_P, dtype=jnp.int32)[None] for k in kinds}
    return pool, tables


def _page_ids(cfg, first, n_real, n_ids):
    """(1, n_ids) a kind: logical pages first .. first + n_real, then
    the trash page."""
    ids = np.full((1, n_ids), N_P, np.int32)
    ids[0, :n_real] = np.arange(first, first + n_real)
    return {k: jnp.asarray(ids) for k in paged_kinds.kinds_of(cfg)}


def _paged_prefill(cfg, params, pool, upto=PLEN):
    tb = -(-upto // PS) * PS
    padded = np.zeros((1, tb), np.int32)
    padded[0, :upto] = TOKENS[:upto]
    lg, pool, _ = paged_kinds.prefill(
        params, jnp.asarray(padded), jnp.asarray([upto]), pool,
        _page_ids(cfg, 0, tb // PS, tb // PS), cfg)
    return lg[0], pool


def _decode_from(cfg, params, pool, tables, kernel, first_logits):
    out = [first_logits]
    for pos in range(PLEN, T):
        lg, pool, _ = paged_kinds.decode_step(
            params, jnp.asarray(TOKENS[pos:pos + 1]), pool, tables,
            jnp.asarray([pos]), jnp.asarray([True]), cfg, kernel=kernel)
        out.append(lg[0])
    return np.stack(out)


def paged(cfg, params, kernel):
    cfg = cfg._replace(interpret=kernel == "pallas")
    pool, tables = _paged_state(cfg)
    lg, pool = _paged_prefill(cfg, params, pool)
    return _decode_from(cfg, params, pool, tables, kernel, lg)


def verify(cfg, params, width, kernel):
    cfg = cfg._replace(interpret=kernel == "pallas")
    pool, tables = _paged_state(cfg)
    lg, pool = _paged_prefill(cfg, params, pool)
    out = [lg[None]]
    for pos in range(PLEN, T, width):
        real = min(width, T - pos)
        row = np.zeros((1, width), np.int32)
        row[0, :real] = TOKENS[pos:pos + real]
        lg, pool, _ = paged_kinds.verify_step(
            params, jnp.asarray(row), pool, tables, jnp.asarray([pos]),
            jnp.asarray([real]), cfg, kernel=kernel)
        out.append(lg[0, :real])
    return np.concatenate(out)


def prefill_ctx(cfg, params):
    """Tokens [0, CTX) by a plain prefill, [CTX, PLEN) as a tail over
    those pages (the table padded with the trash page to a power of
    two), then decode."""
    pool, tables = _paged_state(cfg)
    _, pool = _paged_prefill(cfg, params, pool, upto=CTX)
    tail = PLEN - CTX
    tb = -(-tail // PS) * PS
    padded = np.zeros((1, tb), np.int32)
    padded[0, :tail] = TOKENS[CTX:PLEN]
    ctx = np.full((1, 4), N_P, np.int32)
    ctx[0, :CTX // PS] = np.arange(CTX // PS)
    lg, pool, _ = paged_kinds.prefill_ctx(
        params, jnp.asarray(padded), jnp.asarray([tail]), pool,
        _page_ids(cfg, CTX // PS, tb // PS, tb // PS),
        {k: jnp.asarray(ctx) for k in tables}, jnp.asarray([CTX]), cfg)
    return _decode_from(cfg, params, pool, tables, "gather", lg[0])


LANES = {
    "dense": dense,
    "contiguous": contiguous,
    "paged-gather": lambda c, p: paged(c, p, "gather"),
    "paged-pallas": lambda c, p: paged(c, p, "pallas"),
    "verify-w1": lambda c, p: verify(c, p, 1, "gather"),
    "verify-w3": lambda c, p: verify(c, p, 3, "gather"),
    "verify-w3-pallas": lambda c, p: verify(c, p, 3, "pallas"),
    "prefill-ctx": prefill_ctx,
}


@pytest.mark.parametrize("lane", sorted(LANES))
@pytest.mark.parametrize("model", sorted(MODELS))
def test_lane_gives_the_uncached_forward_s_logits(model, lane):
    cfg, tol = MODELS[model]
    params = _params(cfg)
    want = _uncached(cfg, params)[PLEN - 1:]
    got = np.asarray(LANES[lane](cfg, params), np.float32)
    assert got.shape == want.shape == (T - PLEN + 1, 17)
    assert np.abs(got - want).max() < tol


# ------------------------------------------------ one loop for a family
PROMPTS = [TOKENS[:21], TOKENS[3:12], TOKENS[7:36]]

#: what `snapshot()` read at the commit before the fold (PR 31's tree)
#: for these prompts, 6 tokens each, 3 slots, pages of 4: plain pages
#: where there is one kind (16 a slot; 6 + 3 + 8 for the prompts and 3
#: for their first decoded pages), sums weighted by the kind's layers
#: where there are two (2 window layers of 12 pages, 1 full of 48)
SNAPSHOTS = {
    "gpt2": dict(pages_total=48, peak_pages_in_use=20,
                 paged_block_pages=0, tokens_streamed=18, dispatches=5),
    "moe-window-full": dict(
        pages_total=2 * 12 + 48, peak_pages_in_use=2 * 12 + 20,
        paged_block_pages={"full": 0, "window": 0}, tokens_streamed=18,
        dispatches=5),
}


@pytest.mark.parametrize("model", sorted(SNAPSHOTS))
def test_snapshot_reads_what_it_read_before_the_fold(model):
    cfg, _ = MODELS[model]
    with DecodeLoop(_params(cfg), cfg, slots=3, page_size=PS,
                    prefix_cache=False, start=False,
                    name=f"lanes-{model}") as loop:
        streams = loop.submit_many(PROMPTS, 6)
        loop.run_until_idle()
        snap = loop.snapshot()
    assert all(len(s.result(0)) == 6 for s in streams)
    assert {k: snap[k] for k in SNAPSHOTS[model]} == SNAPSHOTS[model]
    assert snap["pages_in_use"] == 0
    assert ("moe" in snap) == (cfg.n_held > 0)
