"""Paged K/V for a model whose layers are of two kinds: the device side.

`models/moe_transformer.py` has "full" layers, which keep every key of a
sequence, and "window" layers, which only ever read the last `window`
keys. One pool of pages for both would make the window layers hold what
they never read again. Here each KIND of layer has its own pool size
and its own page table; all layers of one kind share page ids (page p
of a kind is row p of every pool of that kind), as all layers of the
one-kind cache (`paged_kv.py`) do:

- `init_pool(cfg, pages, page_size)`: one `{"k", "v"}` of shape
  `(pages[kind] + 1, n_kv_heads, page_size, head_dim)` a layer; the
  last page of each is that kind's trash page.
- tables are a dict by kind of `(S, pages_per_slot)` int32, logical
  page -> page of that kind. A window layer's table holds the trash
  page for logical pages whose last key has left the window
  (`DecodeLoop` returns those pages to the kind's free list in the pass
  in which they fall out), and the step is told nothing more: the first
  visible position follows from the cursor, `max(0, pos - window + 1)`.
- `prefill` and `decode_step` are the model's one block under two
  `attend` callbacks: whole-page scatter then flash attention (grouped
  heads, window), and `_write_rows` then the paged kernel (grouped
  heads, a first position) or the dense gather. A prompt longer than
  the window writes, in the window layers, only the pages that still
  hold a key the first decoded token can see: the others' ids are the
  trash page's.

What this cache cannot do is an error by name where it is asked for
(`DecodeLoop`): prefix sharing, speculation, a horizon above 1 and page
export all assume one kind of page.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.attention.blockwise import NEG_INF
from deeplearning4j_tpu.attention.paged_pallas import paged_attention
from deeplearning4j_tpu.models import moe_transformer as moe
from deeplearning4j_tpu.models.moe_transformer import (KIND_FULL,
                                                       KIND_WINDOW,
                                                       MoEConfig)
from deeplearning4j_tpu.serving.paged_kv import PagedKVPool, _write_rows

__all__ = ["kinds_of", "layers_of", "window_table_pages", "first_visible",
           "init_pool", "pool_bytes", "page_bytes", "prefill",
           "decode_step"]


def kinds_of(cfg: MoEConfig):
    """The kinds this model has, full first."""
    return tuple(k for k in (KIND_FULL, KIND_WINDOW)
                 if k in cfg.layer_kinds)


def layers_of(cfg: MoEConfig) -> Dict[str, int]:
    return {k: cfg.layer_kinds.count(k) for k in kinds_of(cfg)}


def window_table_pages(cfg: MoEConfig, page_size: int) -> int:
    """The most table columns a window can straddle: `window` keys that
    end anywhere in a page."""
    return -(-(cfg.window - 1) // page_size) + 1


def first_visible(pos, window: int):
    """First position a query at `pos` sees in a window layer (the
    query's own position counts among the `window`)."""
    return jnp.maximum(pos - window + 1, 0)


def init_pool(cfg: MoEConfig, pages: Dict[str, int],
              page_size: int) -> PagedKVPool:
    layers = []
    for kind in cfg.layer_kinds:
        shape = (int(pages[kind]) + 1, cfg.n_kv_heads, page_size,
                 cfg.head_dim)
        layers.append({"k": jnp.zeros(shape, cfg.dtype),
                       "v": jnp.zeros(shape, cfg.dtype)})
    return PagedKVPool(tuple(layers))


def page_bytes(cfg: MoEConfig, page_size: int) -> int:
    """K and V of one page of one layer."""
    return (2 * cfg.n_kv_heads * page_size * cfg.head_dim
            * jnp.dtype(cfg.dtype).itemsize)


def pool_bytes(cfg: MoEConfig, pages: Dict[str, int],
               page_size: int) -> int:
    """HBM the pools pin, trash pages included."""
    return sum((int(pages[k]) + 1) * page_bytes(cfg, page_size)
               for k in cfg.layer_kinds)


def prefill(params, tokens, true_len, pool: PagedKVPool,
            page_ids: Dict[str, jax.Array], cfg: MoEConfig):
    """A batch of padded prompts (B, Tb) through every block in one
    dispatch. `page_ids[kind]` (B, Tb / page_size) names, by kind, the
    page each page-sized run of a row's K/V goes to: the trash page for
    runs past the row's real pages, for padding rows, and in a window
    layer for runs no later query can see. Returns (logits (B, vocab)
    at each row's last real position, the pool, pairs (layers, n_held)
    of the real tokens)."""
    b, tb = tokens.shape
    ps = pool.page_size
    positions = jnp.broadcast_to(jnp.arange(tb), (b, tb))
    valid = positions < true_len[:, None]
    flat = {kind: ids.reshape(-1) for kind, ids in page_ids.items()}

    def pages(arr, like):
        # (B, Tb, Hkv, hd) -> (B * Tb/ps pages, Hkv, ps, hd)
        a = arr.astype(like.dtype).reshape(b, tb // ps, ps,
                                           cfg.n_kv_heads, cfg.head_dim)
        return a.transpose(0, 1, 3, 2, 4).reshape(
            b * (tb // ps), cfg.n_kv_heads, ps, cfg.head_dim)

    def attend(layer, kind, q, k, v):
        held = pool.layers[layer]
        new = {"k": held["k"].at[flat[kind]].set(pages(k, held["k"])),
               "v": held["v"].at[flat[kind]].set(pages(v, held["v"]))}
        return moe.causal_attention(cfg, kind, q, k, v), new

    x, layers, pairs = moe.forward(params, tokens, positions, cfg, attend,
                                   valid)
    idx = jnp.broadcast_to((true_len - 1)[:, None, None],
                           (b, 1, cfg.d_model))
    last_x = jnp.take_along_axis(x, idx, axis=1)[:, 0, :]
    return moe.head(params, last_x, cfg), PagedKVPool(layers), pairs


def decode_step(params, tokens, pool: PagedKVPool,
                tables: Dict[str, jax.Array], lengths, active,
                cfg: MoEConfig, kernel: str = "gather"):
    """One decode step over S slots, as `paged_kv.paged_decode_step`
    is for the one-kind cache: write each active slot's K/V row at its
    cursor through its kind's table (`_write_rows`, so the donated pools
    keep their layout and are updated in place), attend over what the
    layer's kind lets the cursor see, return (logits (S, vocab), the
    pool, pairs (layers, n_held) of the active slots' tokens)."""
    if kernel not in ("gather", "pallas"):
        raise ValueError(f"kernel must be 'gather' or 'pallas' here, "
                         f"got {kernel!r}")
    s = tokens.shape[0]
    ps = pool.page_size
    pos = lengths
    rows = jnp.arange(s)
    group = cfg.n_heads // cfg.n_kv_heads
    dest, n_cols = {}, {}
    for kind, table in tables.items():
        n_p = table.shape[1]
        trash = pool.layers[cfg.layer_kinds.index(kind)]["k"].shape[0] - 1
        dest[kind] = jnp.where(
            active & (pos // ps < n_p),
            table[rows, jnp.minimum(pos // ps, n_p - 1)], trash)
        n_cols[kind] = n_p
    offset = pos % ps
    first = first_visible(pos, cfg.window)
    scale = 1.0 / jnp.sqrt(jnp.float32(cfg.head_dim))

    def attend(layer, kind, q, k, v):
        held = pool.layers[layer]
        ks = _write_rows(held["k"], dest[kind], offset, k[:, 0])
        vs = _write_rows(held["v"], dest[kind], offset, v[:, 0])
        table = tables[kind]
        windowed = kind == KIND_WINDOW
        if kernel == "pallas":
            att = paged_attention(
                q[:, 0], ks, vs, table, lengths,
                first=first if windowed else None,
                window_pages=window_table_pages(cfg, ps),
                interpret=cfg.interpret)
        else:
            span = n_cols[kind] * ps
            kg = ks[table].transpose(0, 2, 1, 3, 4).reshape(
                s, cfg.n_kv_heads, span, cfg.head_dim)
            vg = vs[table].transpose(0, 2, 1, 3, 4).reshape(
                s, cfg.n_kv_heads, span, cfg.head_dim)
            qg = q[:, 0].reshape(s, cfg.n_kv_heads, group, cfg.head_dim)
            sc = jnp.einsum("shgd,shkd->shgk", qg.astype(jnp.float32),
                            kg.astype(jnp.float32)) * scale
            k_pos = jnp.arange(span)[None, :]
            mask = k_pos <= pos[:, None]
            if windowed:
                mask = mask & (k_pos >= first[:, None])
            sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
            att = jnp.einsum("shgk,shkd->shgd",
                             jax.nn.softmax(sc, axis=-1),
                             vg.astype(jnp.float32))
            att = att.reshape(s, cfg.n_heads, cfg.head_dim)
        return att[:, None], {"k": ks, "v": vs}

    x, layers, pairs = moe.forward(
        params, tokens[:, None], pos[:, None], cfg, attend,
        active[:, None])
    return moe.head(params, x[:, 0], cfg), PagedKVPool(layers), pairs
