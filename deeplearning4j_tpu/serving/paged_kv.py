"""Paged KV cache: block-pool K/V storage for continuous-batching decode.

The contiguous `KVCache` (kv_cache.py) reserves `(B, H, max_len, hd)`
per request — HBM for the worst case, not for the tokens actually
written, and one slow request holds its whole batch's reservation until
the batch finishes. This module is what is the CACHE's of the other
design: a shared **block pool** of fixed-size pages (the PagedAttention
design carried into the repo's portable O(1)-cache decode, PAPERS.md
arXiv:2603.09555), its sizes, its byte accounting and the single-page
operations sharing and shipping need. What a model's block does with the
pool — the prefills and the steps — is `paged_kinds.py`.

- per layer, one `(n_pages + 1, n_kv_heads, page_size, head_dim)` pool
  for K and one for V. The LAST page is the **trash page**: masked slots
  (inactive / paused) direct their writes there so the scatter in the
  compiled step never needs a data-dependent shape. The host allocator
  never hands the trash page out.
- a per-slot **page table** `(S, pages_per_slot)` of pool indices maps a
  slot's logical positions `[0, max_len)` onto physical pages.
  Unallocated entries hold the trash index so gathers are always valid
  (their positions are masked out of attention by the slot's length).

KV memory therefore scales with tokens actually written: a slot holds
`ceil(tokens / page_size)` pages, pages return to the pool the moment a
request completes, and admission is a free-page check instead of a
whole-`max_len` reservation (`serving/decode_loop.py` owns that
accounting; `paged_kv_bytes` is the envelope). Prefill programs are one
per prompt-length bucket (buckets are page multiples, `prompt_buckets`).
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Tuple

import jax.numpy as jnp

from deeplearning4j_tpu.models.transformer import KIND_LINEAR, SLOT_KINDS

__all__ = ["PagedKVPool", "init_pool", "page_bytes", "pool_bytes",
           "slot_kinds", "state_bytes_per_slot",
           "init_paged_pool", "paged_kv_bytes",
           "pages_per_slot", "pages_for_tokens", "prompt_buckets",
           "copy_page", "extract_page", "install_page"]


class PagedKVPool(NamedTuple):
    """What the cache holds a layer. `layers`: tuple (one per block). A
    layer that keeps keys holds {"k", "v"} arrays of shape (n_pages + 1,
    n_kv_heads, page_size, head_dim), n_pages the layer's kind's; the
    last page is the trash page for masked writes. A layer of a kind
    held by slot (`linear`, `conv`) holds no pages: its arrays are
    indexed by SLOT, `cfg.slot_state[kind]` says which (linear:
    `{"state": (slots, Hv, dk, dv) float32, "conv": (slots, columns
    kept)}`; conv: `{"conv": (slots, columns kept)}`). `page_size`, `n_pages`
    and `trash_page` are those of the first layer that has pages: the
    pool's, where there is one kind of page."""

    layers: Tuple[Any, ...]

    @property
    def _paged(self):
        return next(layer for layer in self.layers if "k" in layer)

    @property
    def page_size(self) -> int:
        return self._paged["k"].shape[2]

    @property
    def n_pages(self) -> int:
        """Usable pages (the trash page is excluded)."""
        return self._paged["k"].shape[0] - 1

    @property
    def trash_page(self) -> int:
        return self._paged["k"].shape[0] - 1


def pages_per_slot(cfg, page_size: int) -> int:
    """Page-table width: pages covering the model's full window."""
    return -(-cfg.max_len // page_size)


def pages_for_tokens(n_tokens: int, page_size: int) -> int:
    """Physical pages holding `n_tokens` written positions."""
    return -(-n_tokens // page_size)


def prompt_buckets(cfg, page_size: int) -> Tuple[int, ...]:
    """Prefill prompt-length buckets: page-multiple powers of two up to
    the full window, so ragged prompts compile a handful of prefill
    programs, ever (the DeviceFeed ladder idea applied to T)."""
    top = pages_per_slot(cfg, page_size) * page_size
    buckets, b = [], page_size
    while b < top:
        buckets.append(b)
        b *= 2
    buckets.append(top)
    return tuple(buckets)


def slot_kinds(cfg) -> Dict[str, int]:
    """The kinds this model holds by SLOT, no pages, and the layers of
    each (empty for a model whose layers all keep keys)."""
    return {k: cfg.layer_kinds.count(k) for k in SLOT_KINDS
            if k in cfg.layer_kinds}


def init_pool(cfg, pages: Dict[str, int], page_size: int,
              slots: int = 0) -> PagedKVPool:
    """Allocate the block pools: `pages[kind]` usable pages and the
    trash page for every layer of that kind, and for every layer of a
    kind held by slot (which has no entry in `pages`) its arrays, a row
    a slot of `slots`. Pool HBM is fixed at construction — per-request
    cost is page-table bookkeeping, not allocation."""
    layers = []
    for kind in cfg.layer_kinds:
        if kind not in pages:
            layers.append({name: jnp.zeros((int(slots),) + shape, dtype)
                           for name, (shape, dtype)
                           in cfg.slot_state[kind].items()})
            continue
        shape = (int(pages[kind]) + 1, cfg.n_kv_heads, page_size,
                 cfg.head_dim)
        layers.append({"k": jnp.zeros(shape, cfg.dtype),
                       "v": jnp.zeros(shape, cfg.dtype)})
    return PagedKVPool(tuple(layers))


def state_bytes_per_slot(cfg, kind: str = KIND_LINEAR) -> int:
    """What ONE slot holds in ONE layer of `kind`, a kind held by slot:
    a linear layer's recurrent state and kept columns, a conv layer's
    kept columns. 0 for a model that has no such layer."""
    entry = getattr(cfg, "slot_state", {}).get(kind)
    if not entry:
        return 0
    return sum(math.prod(shape) * jnp.dtype(dtype).itemsize
               for shape, dtype in entry.values())


def page_bytes(cfg, page_size: int) -> int:
    """K and V of one page of one layer."""
    return (2 * cfg.n_kv_heads * page_size * cfg.head_dim
            * jnp.dtype(cfg.dtype).itemsize)


def pool_bytes(cfg, pages: Dict[str, int], page_size: int) -> int:
    """HBM the pools pin (trash pages included) — the serving memory
    envelope. Unlike the contiguous `kv_cache_bytes(cfg, B)` this is
    independent of concurrency: occupancy (pages in use / pages) is the
    load signal, exported as dl4j_kv_pages_{total,in_use}."""
    return sum((int(pages[k]) + 1) * page_bytes(cfg, page_size)
               for k in cfg.layer_kinds if k in pages)


def _same_for_every_kind(cfg, n_pages: int, page_size: int):
    if n_pages < 1:
        raise ValueError(f"n_pages must be >= 1, got {n_pages}")
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    return dict.fromkeys((k for k in cfg.layer_kinds
                          if k not in SLOT_KINDS), n_pages)


def init_paged_pool(cfg, n_pages: int, page_size: int) -> PagedKVPool:
    """`init_pool` with `n_pages` for every kind of layer: the whole
    pool of a model whose layers are of one kind."""
    return init_pool(cfg, _same_for_every_kind(cfg, n_pages, page_size),
                     page_size)


def paged_kv_bytes(cfg, n_pages: int, page_size: int) -> int:
    """`pool_bytes` with `n_pages` for every kind of layer."""
    return pool_bytes(cfg, _same_for_every_kind(cfg, n_pages, page_size),
                      page_size)


def copy_page(pool: PagedKVPool, src, dst) -> PagedKVPool:
    """Copy-on-write fork helper: duplicate ONE physical page (every
    layer's K and V rows) from pool index `src` into `dst`. `src`/`dst`
    are traced int32 scalars, so the jitted caller compiles exactly one
    program for every fork the server ever performs — the only compiled
    surface prefix sharing adds (decode_loop.DecodeLoop)."""
    layers = tuple({"k": layer["k"].at[dst].set(layer["k"][src]),
                    "v": layer["v"].at[dst].set(layer["v"][src])}
                   for layer in pool.layers)
    return PagedKVPool(layers)


def extract_page(pool: PagedKVPool, page: int):
    """Host-side copy of ONE physical page across every layer — the
    fleet KV plane's export read (serving/fleetkv.py). Returns a list
    of (k, v) numpy arrays of shape (n_heads, page_size, head_dim),
    one pair per layer. Pure reads on the immutable pool arrays: a
    concurrent pool swap in the decode loop cannot tear a page whose
    content is pinned (CoW writers fork elsewhere)."""
    import numpy as np

    return [(np.asarray(layer["k"][page]), np.asarray(layer["v"][page]))
            for layer in pool.layers]


def install_page(pool: PagedKVPool, page: int, chunk) -> PagedKVPool:
    """Write one shipped page's K/V rows (`chunk[l] = (k, v)` per
    layer, the `extract_page` shape) into pool index `page`. Eager
    single-page scatters — constant shapes, so XLA caches one program
    per dtype regardless of how many pages ever ship, and nothing here
    touches the decode loop's jitted program set."""
    if len(chunk) != len(pool.layers):
        raise ValueError(
            f"shipped page has {len(chunk)} layers, pool has "
            f"{len(pool.layers)}")
    want = pool.layers[0]["k"].shape[1:]
    layers = []
    for layer, (k, v) in zip(pool.layers, chunk):
        if tuple(k.shape) != tuple(want) or tuple(v.shape) != tuple(want):
            raise ValueError(
                f"shipped page shape {tuple(k.shape)} != pool page "
                f"shape {tuple(want)}")
        layers.append({"k": layer["k"].at[page].set(k),
                       "v": layer["v"].at[page].set(v)})
    return PagedKVPool(tuple(layers))
