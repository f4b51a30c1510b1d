"""The two attention kernels with grouped K/V heads and a window, in
interpret mode: the paged decode kernel with a first visible position
per slot against a dense float64 reference and against the gather path
of the decode step; the flash forward with groups and a window against
`attention/blockwise.py`. And what they were is what they are: with as
many K/V heads as query heads and nothing windowed the new arguments
change no bit. Since PR 31 pages that are whole tiles are swept a block
of several a step: the same cases at a page size that engages it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.attention.blockwise import blockwise_attention
from deeplearning4j_tpu.attention.flash_pallas import flash_attention
from deeplearning4j_tpu.attention.paged_pallas import (block_pages,
                                                       paged_attention)

pytestmark = pytest.mark.pallas


def _paged_case(hq, hkv, dtype=jnp.float32, seed=0):
    s, ps, hd, n_p, pages = 3, 8, 32, 6, 20
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (s, hq, hd), dtype)
    k = jax.random.normal(ks[1], (pages + 1, hkv, ps, hd), dtype)
    v = jax.random.normal(ks[2], (pages + 1, hkv, ps, hd), dtype)
    table = np.random.RandomState(seed).permutation(pages)[:s * n_p] \
        .reshape(s, n_p).astype(np.int32)
    lengths = np.asarray([5, 29, 47], np.int32)
    return q, k, v, table, lengths, ps


def _dense(q, k, v, table, lengths, first, ps):
    """float64, key by key; a cursor past the table sees the table."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    s, hq, hd = q.shape
    group = hq // k.shape[1]
    out = np.zeros((s, hq, hd))
    for i in range(s):
        pos = np.arange(first[i],
                        min(lengths[i], table.shape[1] * ps - 1) + 1)
        kk = k[table[i, pos // ps], :, pos % ps]       # (keys, Hkv, hd)
        vv = v[table[i, pos // ps], :, pos % ps]
        for n in range(hq):
            sc = kk[:, n // group] @ q[i, n] / np.sqrt(hd)
            w = np.exp(sc - sc.max())
            out[i, n] = (w / w.sum()) @ vv[:, n // group]
    return out


@pytest.mark.parametrize("hq,hkv", [(8, 2), (16, 1), (4, 4)])
def test_paged_kernel_grouped_heads_and_a_first_position(hq, hkv):
    q, k, v, table, lengths, ps = _paged_case(hq, hkv)
    window = 16
    first = np.maximum(lengths - window + 1, 0).astype(np.int32)
    # pages before the first visible one were given back: trash
    released = table.copy()
    for i in range(3):
        released[i, :first[i] // ps] = 20
    got = paged_attention(q, k, v, jnp.asarray(released),
                          jnp.asarray(lengths), first=jnp.asarray(first),
                          window_pages=window // ps + 1, interpret=True)
    want = _dense(q, k, v, table, lengths, first, ps)
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    # no first position: every key up to the cursor
    got = paged_attention(q, k, v, jnp.asarray(table), jnp.asarray(lengths),
                          interpret=True)
    want = _dense(q, k, v, table, lengths, np.zeros(3, np.int32), ps)
    assert np.abs(np.asarray(got) - want).max() < 1e-5


@pytest.mark.parametrize("window", [150, 40, None])
@pytest.mark.parametrize("hq,hkv", [(8, 2), (16, 1), (4, 4)])
def test_paged_kernel_blocks_of_pages_grouped_and_windowed(hq, hkv, window):
    """Pages of 16 that hold 64 KiB of K (K/V heads x head size 1,024
    in float32): 8 pages a block. A window of 150 keys straddles 11
    columns (no multiple of the block) from a first position in the
    middle of a page and of the table's blocks; one of 40 keys
    straddles 4, fewer than a block holds."""
    s, ps, hd, n_p, pages = 4, 16, 1024 // hkv, 20, 80
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (s, hq, hd))
    k = jax.random.normal(ks[1], (pages + 1, hkv, ps, hd))
    v = jax.random.normal(ks[2], (pages + 1, hkv, ps, hd))
    table = np.random.RandomState(3).permutation(pages).reshape(s, n_p) \
        .astype(np.int32)
    lengths = np.asarray([5, 160, 299, 319], np.int32)
    if window is None:
        assert block_pages(ps, hkv, hd, jnp.float32, n_p) == 8
        got = paged_attention(q, k, v, jnp.asarray(table),
                              jnp.asarray(lengths), interpret=True)
        want = _dense(q, k, v, table, lengths, np.zeros(s, np.int32), ps)
        assert np.abs(np.asarray(got) - want).max() < 1e-5
        return
    columns = -(-(window - 1) // ps) + 1
    assert block_pages(ps, hkv, hd, jnp.float32, columns) == min(8, columns)
    first = np.maximum(lengths - window + 1, 0).astype(np.int32)
    # pages before the first visible one were given back, those past
    # the cursor's never granted: trash
    held = np.full_like(table, pages)
    for i in range(s):
        lo, hi = first[i] // ps, lengths[i] // ps
        held[i, lo:hi + 1] = table[i, lo:hi + 1]
    got = paged_attention(q, k, v, jnp.asarray(held), jnp.asarray(lengths),
                          first=jnp.asarray(first), window_pages=columns,
                          interpret=True)
    want = _dense(q, k, v, table, lengths, first, ps)
    assert np.abs(np.asarray(got) - want).max() < 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_at_today_s_shapes_is_bit_for_bit(dtype):
    q, k, v, table, lengths, ps = _paged_case(4, 4, dtype)
    today = paged_attention(q, k, v, jnp.asarray(table),
                            jnp.asarray(lengths), interpret=True)
    # the windowed sweep from position 0 over every column is the same
    # sum in the same order
    swept = paged_attention(q, k, v, jnp.asarray(table),
                            jnp.asarray(lengths),
                            first=jnp.zeros((3,), jnp.int32),
                            window_pages=table.shape[1], interpret=True)
    assert np.array_equal(np.asarray(today), np.asarray(swept))
    # grouped rows that happen to be one a head
    one = paged_attention(q[:, :, None, :].reshape(3, 4, -1), k, v,
                          jnp.asarray(table), jnp.asarray(lengths),
                          interpret=True)
    assert np.array_equal(np.asarray(today), np.asarray(one))


def test_decode_step_kernel_lane_equals_the_gather_lane():
    from tests import test_moe_transformer as t

    config = t._config()
    cfg, params = t._model(config), t._params(config)
    toks = t._tokens(34, seed=5)
    gather, pairs_g = t._through_the_cache(cfg, params, toks, 21)
    kernel, pairs_k = t._through_the_cache(
        cfg._replace(interpret=True), params, toks, 21, kernel="pallas")
    assert np.abs(gather - kernel).max() < 1e-5
    assert all(np.array_equal(a, b) for a, b in zip(pairs_g, pairs_k))


def _flash_case(hq, hkv, t=256, hd=64, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    return (jax.random.normal(ks[0], (2, hq, t, hd), dtype),
            jax.random.normal(ks[1], (2, hkv, t, hd), dtype),
            jax.random.normal(ks[2], (2, hkv, t, hd), dtype))


@pytest.mark.parametrize("window", [None, 1, 100, 128, 200])
@pytest.mark.parametrize("hq,hkv", [(4, 1), (4, 4)])
def test_flash_groups_and_a_window_against_blockwise(hq, hkv, window):
    q, k, v = _flash_case(hq, hkv)
    got = flash_attention(q, k, v, True, 128, 128, True, window)
    rep = hq // hkv
    want = blockwise_attention(q, jnp.repeat(k, rep, 1),
                               jnp.repeat(v, rep, 1), causal=True,
                               window=window)
    assert np.abs(np.asarray(got - want)).max() < 2e-5


def test_flash_falls_back_to_blockwise_with_groups_and_a_window():
    q, k, v = _flash_case(4, 2, t=40, hd=16)     # no 128-aligned tile
    got = flash_attention(q, k, v, True, window=8)
    want = blockwise_attention(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                               causal=True, window=8)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_at_today_s_shapes_is_bit_for_bit(dtype):
    q, k, v = _flash_case(4, 4, dtype=dtype)
    today = flash_attention(q, k, v, True, 128, 128, True)
    # a window that holds every key masks and skips nothing more
    assert np.array_equal(np.asarray(today), np.asarray(
        flash_attention(q, k, v, True, 128, 128, True, 256)))
    # K/V heads shared by no one are groups of one
    q2, k2, v2 = _flash_case(4, 2, dtype=dtype)
    grouped = flash_attention(q2, k2, v2, True, 128, 128, True)
    repeated = flash_attention(q2, jnp.repeat(k2, 2, 1),
                               jnp.repeat(v2, 2, 1), True, 128, 128, True)
    assert np.array_equal(np.asarray(grouped), np.asarray(repeated))


def test_groups_and_windows_have_no_backward_and_say_so():
    q, k, v = _flash_case(4, 2)
    with pytest.raises(NotImplementedError, match="no trainer"):
        jax.grad(lambda q: flash_attention(q, k, v, True, 128, 128,
                                           True).sum())(q)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, False, window=8)
    with pytest.raises(ValueError, match="dividing"):
        flash_attention(q, k[:, :1].repeat(3, 1), v[:, :1].repeat(3, 1),
                        True)
