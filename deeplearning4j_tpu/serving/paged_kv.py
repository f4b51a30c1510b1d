"""Paged KV cache: block-pool K/V storage for continuous-batching decode.

The contiguous `KVCache` (kv_cache.py) reserves `(B, H, max_len, hd)`
per request — HBM for the worst case, not for the tokens actually
written, and one slow request holds its whole batch's reservation until
the batch finishes. This module stores KV in a shared **block pool** of
fixed-size pages (the PagedAttention design carried into the repo's
portable O(1)-cache decode, PAPERS.md arXiv:2603.09555):

- per layer, one `(n_pages + 1, n_heads, page_size, head_dim)` pool for
  K and one for V. The LAST page is the **trash page**: masked slots
  (inactive / paused) direct their writes there so the scatter in the
  compiled step never needs a data-dependent shape. The host allocator
  never hands the trash page out.
- the pool never changes layout inside a compiled program: it arrives
  donated, the step writes its new rows into it in place
  (`_write_rows`: one scatter that indexes page, head and offset), the
  paged kernel reads it as it stands and the output aliases the input.
  A write that leaves the head dimension as a window between the page
  and the offset index costs two copies of the whole pool per layer
  for K and for V each on the TPU (tests/test_paged_step_layout.py).
- a per-slot **page table** `(S, pages_per_slot)` of pool indices maps a
  slot's logical positions `[0, max_len)` onto physical pages.
  Unallocated entries hold the trash index so gathers are always valid
  (their positions are masked out of attention by the slot's length).

KV memory therefore scales with tokens actually written: a slot holds
`ceil(tokens / page_size)` pages, pages return to the pool the moment a
request completes, and admission is a free-page check instead of a
whole-`max_len` reservation (`serving/decode_loop.py` owns that
accounting; `paged_kv_bytes` is the envelope).

Shapes in both compiled entry points are fixed for the life of the
server: `paged_decode_step` is ONE program over S slots (page table,
lengths and the active mask are traced arrays — requests join and leave
without recompiling), `paged_prefill` compiles one program per
prompt-length bucket (buckets are page multiples, `prompt_buckets`).

Parity: positions beyond a slot's length are masked to NEG_INF before
the softmax, so `exp` underflows to exactly 0 and garbage in unwritten
page tails contributes exactly 0 — the paged step is the contiguous
`decode_step` to float tolerance (tests/test_paged_decode.py pins 1e-5
teacher-forced).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.attention.blockwise import NEG_INF
from deeplearning4j_tpu.attention.flash_pallas import flash_attention
from deeplearning4j_tpu.attention.paged_pallas import paged_attention
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   _layer_norm)
from deeplearning4j_tpu.serving.kv_cache import _ffn, _heads

__all__ = ["PagedKVPool", "init_paged_pool", "paged_kv_bytes",
           "pages_per_slot", "pages_for_tokens", "prompt_buckets",
           "paged_prefill", "paged_prefill_ctx", "paged_decode_step",
           "paged_verify_step", "copy_page", "extract_page",
           "install_page", "decode_read_bytes"]


class PagedKVPool(NamedTuple):
    """Per-block K/V page pools. `layers`: tuple (one per transformer
    block) of {"k", "v"} arrays of shape (n_pages + 1, n_heads,
    page_size, head_dim); index `n_pages` (the last page) is the trash
    page for masked writes."""

    layers: Tuple[Any, ...]

    @property
    def page_size(self) -> int:
        return self.layers[0]["k"].shape[2]

    @property
    def n_pages(self) -> int:
        """Usable pages (the trash page is excluded)."""
        return self.layers[0]["k"].shape[0] - 1

    @property
    def trash_page(self) -> int:
        return self.layers[0]["k"].shape[0] - 1


def pages_per_slot(cfg: TransformerConfig, page_size: int) -> int:
    """Page-table width: pages covering the model's full window."""
    return -(-cfg.max_len // page_size)


def pages_for_tokens(n_tokens: int, page_size: int) -> int:
    """Physical pages holding `n_tokens` written positions."""
    return -(-n_tokens // page_size)


def prompt_buckets(cfg: TransformerConfig, page_size: int
                   ) -> Tuple[int, ...]:
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


def init_paged_pool(cfg: TransformerConfig, n_pages: int,
                    page_size: int) -> PagedKVPool:
    """Allocate the block pool (`n_pages` usable + 1 trash page per
    layer). Pool HBM is fixed at construction — per-request cost is
    page-table bookkeeping, not allocation."""
    if n_pages < 1:
        raise ValueError(f"n_pages must be >= 1, got {n_pages}")
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    hd = cfg.d_model // cfg.n_heads
    shape = (n_pages + 1, cfg.n_heads, page_size, hd)
    layers = tuple({"k": jnp.zeros(shape, cfg.dtype),
                    "v": jnp.zeros(shape, cfg.dtype)}
                   for _ in range(cfg.n_layers))
    return PagedKVPool(layers)


def paged_kv_bytes(cfg: TransformerConfig, n_pages: int,
                   page_size: int) -> int:
    """HBM the whole pool pins (including the trash page) — the serving
    memory envelope. Unlike the contiguous `kv_cache_bytes(cfg, B)` this
    is independent of concurrency: occupancy (pages in use / n_pages)
    is the load signal, exported as dl4j_kv_pages_{total,in_use}."""
    if n_pages < 1:
        raise ValueError(f"n_pages must be >= 1, got {n_pages}")
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return (2 * cfg.n_layers * (n_pages + 1) * page_size
            * cfg.d_model * itemsize)


def paged_prefill(params, tokens, true_len, pool: PagedKVPool,
                  page_ids, cfg: TransformerConfig):
    """Run a BATCH of padded prompts (B, Tb) through every block in one
    dispatch, scattering each row's K/V into the pool pages listed in
    its `page_ids` row (shape (B, Tb/page_size); entries past a row's
    real pages — and every entry of a padding row — hold the trash
    index). `true_len` is (B,); returns (logits (B, vocab), each row at
    its own position `true_len - 1`, updated pool).

    Batching matters: an admission burst (N queued prompts hitting
    freed slots between decode steps) costs one compiled call instead
    of N — the scheduler pads B up to a small pow2 ladder so program
    count stays bounded (DecodeLoop._admit).

    Same math as the contiguous `prefill` — causal flash attention means
    positions < true_len never see the zero-padding, and the padding's
    garbage K/V lands either in the real last page's tail (masked out of
    decode by the slot length) or on the trash page."""
    b, tb = tokens.shape
    ps = pool.page_size
    # the page-multiple bucket can overshoot max_len (e.g. max_len=100,
    # page_size=16 -> top bucket 112): clamp the position ids so the
    # overshoot rows (pure padding, causally invisible to real
    # positions) reuse the last embedding instead of reading OOB
    pos_ids = jnp.minimum(jnp.arange(tb), cfg.max_len - 1)
    x = params["embed"][tokens] + params["pos"][pos_ids]
    flat_ids = page_ids.reshape(-1)                    # (B * Tb/ps,)
    new_layers = []
    for p, layer in zip(params["blocks"], pool.layers):
        h = _layer_norm(p["ln1"], x)
        q = _heads(h, p["Wq"], cfg)
        k = _heads(h, p["Wk"], cfg)
        v = _heads(h, p["Wv"], cfg)
        att = flash_attention(q, k, v, True, interpret=cfg.interpret)
        att = att.transpose(0, 2, 1, 3).reshape(b, tb, cfg.d_model)
        x = x + att @ p["Wo"]
        x = _ffn(p, x)
        # (B, H, Tb, hd) -> (B * Tb/ps pages, H, ps, hd) page scatter
        def pages(arr, like):
            a = arr.astype(like.dtype)
            a = a.reshape(b, cfg.n_heads, tb // ps, ps, -1)
            return a.transpose(0, 2, 1, 3, 4).reshape(
                b * (tb // ps), cfg.n_heads, ps, -1)
        new_layers.append({
            "k": layer["k"].at[flat_ids].set(pages(k, layer["k"])),
            "v": layer["v"].at[flat_ids].set(pages(v, layer["v"])),
        })
    x = _layer_norm(params["ln_f"], x)
    # gather each row's LAST REAL position before the vocab projection —
    # (B, d) @ (d, vocab) instead of a (B, Tb, vocab) matmul
    idx = jnp.broadcast_to((true_len - 1)[:, None, None],
                           (b, 1, cfg.d_model))
    last_x = jnp.take_along_axis(x, idx, axis=1)[:, 0, :]
    return last_x @ params["embed"].T, PagedKVPool(tuple(new_layers))


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


def paged_prefill_ctx(params, tokens, true_len, pool: PagedKVPool,
                      page_ids, ctx_table, ctx_len,
                      cfg: TransformerConfig):
    """Prefill a batch of prompt TAILS whose prefix K/V already sits in
    pool pages (the prefix-cache warm path): row b's tokens are prompt
    positions `[ctx_len[b], ctx_len[b] + true_len[b])`, its cached
    prefix occupies the pages in `ctx_table[b]` (trash-padded, masked by
    `ctx_len`), and its tail K/V scatters into `page_ids[b]` exactly
    like `paged_prefill`. Returns (logits (B, vocab) at each row's last
    real tail position, updated pool).

    Tails always start on a page boundary (the admission path only
    reuses FULL cached chunks), so the whole-page scatter reshape is
    unchanged. Attention is the decode step's exact masked softmax in
    f32 over [gathered prefix pages ‖ tail], not the flash kernel —
    tail queries see every real prefix position plus the causal window
    of the tail itself; masked lanes underflow to exactly 0 so trash /
    page-tail garbage contributes exactly 0. Shared prefix pages are
    only READ — sharing stays host-side bookkeeping."""
    b, tb = tokens.shape
    ps = pool.page_size
    hd = cfg.d_model // cfg.n_heads
    w_ctx = ctx_table.shape[1] * ps
    pos_ids = jnp.minimum(ctx_len[:, None] + jnp.arange(tb),
                          cfg.max_len - 1)
    x = params["embed"][tokens] + params["pos"][pos_ids]
    flat_ids = page_ids.reshape(-1)
    # prefix cols real below ctx_len; tail cols causal within the tail
    m_ctx = jnp.arange(w_ctx)[None, :] < ctx_len[:, None]      # (B, Wc)
    m_self = (jnp.arange(tb)[None, :] <= jnp.arange(tb)[:, None])
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    new_layers = []
    for p, layer in zip(params["blocks"], pool.layers):
        h = _layer_norm(p["ln1"], x)
        q = _heads(h, p["Wq"], cfg)                   # (B, H, Tb, hd)
        k = _heads(h, p["Wk"], cfg)
        v = _heads(h, p["Wv"], cfg)
        # gather the cached prefix: (B, Pc, H, ps, hd) -> (B, H, Wc, hd)
        kc = layer["k"][ctx_table].transpose(0, 2, 1, 3, 4).reshape(
            b, cfg.n_heads, w_ctx, hd)
        vc = layer["v"][ctx_table].transpose(0, 2, 1, 3, 4).reshape(
            b, cfg.n_heads, w_ctx, hd)
        qf = q.astype(jnp.float32)
        sc_ctx = jnp.einsum("bhqd,bhkd->bhqk", qf,
                            kc.astype(jnp.float32)) * scale
        sc_self = jnp.einsum("bhqd,bhkd->bhqk", qf,
                             k.astype(jnp.float32)) * scale
        sc = jnp.concatenate([
            jnp.where(m_ctx[:, None, None, :], sc_ctx, NEG_INF),
            jnp.where(m_self[None, None, :, :], sc_self, NEG_INF),
        ], axis=-1)
        wts = jax.nn.softmax(sc, axis=-1)
        vf = jnp.concatenate([vc.astype(jnp.float32),
                              v.astype(jnp.float32)], axis=2)
        att = jnp.einsum("bhqk,bhkd->bhqd", wts, vf)
        att = att.astype(x.dtype).transpose(0, 2, 1, 3).reshape(
            b, tb, cfg.d_model)
        x = x + att @ p["Wo"]
        x = _ffn(p, x)

        # (B, H, Tb, hd) -> (B * Tb/ps pages, H, ps, hd) page scatter,
        # identical to paged_prefill's
        def pages(arr, like):
            a = arr.astype(like.dtype)
            a = a.reshape(b, cfg.n_heads, tb // ps, ps, -1)
            return a.transpose(0, 2, 1, 3, 4).reshape(
                b * (tb // ps), cfg.n_heads, ps, -1)
        new_layers.append({
            "k": layer["k"].at[flat_ids].set(pages(k, layer["k"])),
            "v": layer["v"].at[flat_ids].set(pages(v, layer["v"])),
        })
    x = _layer_norm(params["ln_f"], x)
    idx = jnp.broadcast_to((true_len - 1)[:, None, None],
                           (b, 1, cfg.d_model))
    last_x = jnp.take_along_axis(x, idx, axis=1)[:, 0, :]
    return last_x @ params["embed"].T, PagedKVPool(tuple(new_layers))


def decode_read_bytes(pool: PagedKVPool, lengths, table_width: int, *,
                      dense: bool = False) -> int:
    """Host-side accounting: KV bytes ONE decode token step must read
    for attention, summed over slots. Default (`dense=False`) is the
    streamed-kernel figure — K+V for each slot's written pages only,
    `min(floor(pos / page_size) + 1, table_width)` pages at cursor
    `pos` (exactly the pages `paged_attention`'s sweep fetches, a page
    or a block of pages a step, the trash-page read of an idle slot
    included). `dense=True` is the
    dense-gather figure: every slot touches its FULL page-table
    reservation (`S × table_width` pages) regardless of how little was
    written. The ratio of the two is the kernel's traffic win, exported
    per dispatch as dl4j_decode_kv_read_bytes{path="kernel"|"gather"}
    (decode_loop; docs/OBSERVABILITY.md)."""
    layer = pool.layers[0]["k"]
    ps = pool.page_size
    page_bytes = (layer.shape[1] * ps * layer.shape[3]
                  * jnp.dtype(layer.dtype).itemsize)
    if dense:
        pages = len(lengths) * int(table_width)
    else:
        pages = sum(min(int(pos) // ps + 1, int(table_width))
                    for pos in lengths)
    return 2 * len(pool.layers) * page_bytes * int(pages)


def _write_rows(arr, dest, offset, rows):
    """Write one `head_dim` row per (..., head) into pool array `arr`
    (n_pages + 1, H, page_size, hd): `dest` and `offset` (any shape
    `idx`, the physical page and the offset inside it) name where
    `rows` (`idx` + (H, hd)) go. The decode and the verify step's only
    write.

    The scatter indexes EVERY major dimension (page, head, offset) and
    leaves the `head_dim` row as its only window. Written as
    `arr.at[dest, :, offset, :]` the head dimension is a window between
    two indexed dimensions, and the TPU compiler then gives the
    scatter's operand the layout {3,1,2,0} where the donated pool and
    the paged kernel hold {3,2,1,0}: two layout changes of the WHOLE
    pool per layer for K and for V each, 96 copies of 168 MB a step at
    the served widths (PERF.md section 6, PR 27). In this form the
    pool keeps its layout and is updated in place.
    tests/test_paged_step_layout.py compiles both steps for a v5e and
    fails on any pool-shaped copy. Duplicate destinations (inactive
    slots colliding on the trash page) stay legal: no `unique_indices`
    promise is made."""
    heads = jnp.arange(arr.shape[1])
    return arr.at[dest[..., None], heads, offset[..., None], :].set(
        rows.astype(arr.dtype))


def paged_verify_step(params, tokens, pool: PagedKVPool, page_table,
                      lengths, widths, cfg: TransformerConfig,
                      kernel: str = "gather"):
    """The WIDENED decode step speculative verify rides: `tokens` is
    (S, W) — row s's column j is the token whose K/V belongs at cursor
    `lengths[s] + j` (column 0 is the slot's ordinary pending token,
    columns 1..W-1 the drafter's proposals). `widths` (S,) int32 is how
    many columns of each row are real (0 = idle slot; 1 = plain
    non-speculative step riding along). Returns
    (logits (S, W, vocab), updated pool).

    All real positions write K/V through the page table in one
    dispatch (columns past a row's width write to the trash page, same
    contract as `paged_decode_step`'s inactive slots; the write is
    `_write_rows`, indexed by page, head and offset so that the donated
    pool keeps its layout and is updated in place) and every query
    attends causally — column j sees positions <= lengths[s] + j, so
    draft K/V written "in the future" of a query is masked exactly like
    unwritten page-tail garbage. logits[s, j] is therefore the target
    model's next-token distribution after the prefix extended by
    proposals 1..j — the verify/accept rule's ground truth. Rejected
    columns leave garbage at positions past the rolled-back cursor:
    always masked (key position > every later query's cursor is
    impossible — the cursor only moves forward over freshly-written
    positions), then overwritten before ever becoming visible.

    `kernel` mirrors `paged_decode_step`: "gather" runs one widened
    masked-softmax over the dense window; "pallas" reuses the
    single-query streamed kernel once per column (KV reads are
    inherently O(W x written pages) either way — speculation's win is
    amortizing the weight sweep and dispatch, not the KV reads)."""
    if kernel not in ("gather", "pallas"):
        raise ValueError(
            f"kernel must be 'gather' or 'pallas' here (resolve 'auto' "
            f"via attention.paged_pallas.resolve_decode_kernel), "
            f"got {kernel!r}")
    s, w = tokens.shape
    d = cfg.d_model
    hd = d // cfg.n_heads
    ps = pool.page_size
    trash = pool.trash_page
    n_p = page_table.shape[1]
    window = n_p * ps
    pos = lengths[:, None] + jnp.arange(w)[None, :]        # (S, W)
    valid = jnp.arange(w)[None, :] < widths[:, None]       # (S, W)
    # physical destination per (slot, column); invalid columns and
    # cursors at/past the window write to trash (paged_decode_step's
    # exact rule, widened)
    dest = jnp.where(
        valid & (pos // ps < n_p),
        jnp.take_along_axis(page_table, jnp.minimum(pos // ps, n_p - 1),
                            axis=1),
        trash)
    offset = pos % ps
    pos_ids = jnp.minimum(pos, cfg.max_len - 1)
    x = params["embed"][tokens] + params["pos"][pos_ids]   # (S, W, d)
    # per-query causal mask over the logical window: column j sees
    # key positions <= lengths + j
    mask = jnp.arange(window)[None, None, :] <= pos[:, :, None]
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    new_layers = []
    for p, layer in zip(params["blocks"], pool.layers):
        h = _layer_norm(p["ln1"], x)
        q = _heads(h, p["Wq"], cfg)                    # (S, H, W, hd)
        k_new = _heads(h, p["Wk"], cfg)
        v_new = _heads(h, p["Wv"], cfg)
        # rows are (S, W, H, hd), one per (slot, column, head)
        ks = _write_rows(layer["k"], dest, offset,
                         k_new.transpose(0, 2, 1, 3))
        vs = _write_rows(layer["v"], dest, offset,
                         v_new.transpose(0, 2, 1, 3))
        if kernel == "pallas":
            # one streamed single-query pass per column, each at its
            # own cursor — garbage lanes (invalid columns) stay finite
            # and are never read by the host
            cols = []
            for j in range(w):
                lj = jnp.minimum(lengths + j, window - 1)
                cols.append(paged_attention(
                    q[:, :, j, :], ks, vs, page_table, lj,
                    interpret=cfg.interpret))
            att = jnp.stack(cols, axis=2)              # (S, H, W, hd)
            att = att.astype(x.dtype).transpose(0, 2, 1, 3).reshape(
                s, w, d)
        else:
            kg = ks[page_table].transpose(0, 2, 1, 3, 4).reshape(
                s, cfg.n_heads, window, hd)
            vg = vs[page_table].transpose(0, 2, 1, 3, 4).reshape(
                s, cfg.n_heads, window, hd)
            sc = jnp.einsum("shqd,shkd->shqk", q.astype(jnp.float32),
                            kg.astype(jnp.float32)) * scale
            sc = jnp.where(mask[:, None, :, :], sc, NEG_INF)
            wts = jax.nn.softmax(sc, axis=-1)
            att = jnp.einsum("shqk,shkd->shqd", wts,
                             vg.astype(jnp.float32))
            att = att.astype(x.dtype).transpose(0, 2, 1, 3).reshape(
                s, w, d)
        x = x + att @ p["Wo"]
        x = _ffn(p, x)
        new_layers.append({"k": ks, "v": vs})
    x = _layer_norm(params["ln_f"], x)
    logits = x @ params["embed"].T                     # (S, W, vocab)
    return logits, PagedKVPool(tuple(new_layers))


def paged_decode_step(params, tokens, pool: PagedKVPool, page_table,
                      lengths, active, cfg: TransformerConfig,
                      kernel: str = "gather"):
    """One decode step over S slots: embed `tokens` (S,), write each
    active slot's K/V at its own cursor (`lengths`) through the page
    table, attend over the slot's pages, return
    (logits (S, vocab), updated pool).

    Everything ragged is a traced ARRAY, never a shape: page_table
    (S, P) int32, lengths (S,) int32, active (S,) bool — so requests
    join and leave at token boundaries under ONE compiled program for
    the life of the server. Inactive slots write to the trash page and
    their logits are garbage the host ignores; lengths advance on the
    host side only for slots that ran. The write is `_write_rows`: it
    indexes page, head and offset, which keeps the donated pool in the
    layout the paged kernel reads, updated in place.

    `kernel` picks the attention read: "gather" materializes each
    slot's dense `(S, H, window, hd)` K/V window (O(S × max_len) HBM
    traffic per step); "pallas" streams only the written pages from the
    pool through `attention.paged_pallas.paged_attention` (same masked
    softmax to 1e-5; `cfg.interpret` runs it on CPU). Callers resolve
    "auto" BEFORE jitting with `resolve_decode_kernel` — the knob is a
    compile-time constant, not a traced value."""
    if kernel not in ("gather", "pallas"):
        raise ValueError(
            f"kernel must be 'gather' or 'pallas' here (resolve 'auto' "
            f"via attention.paged_pallas.resolve_decode_kernel), "
            f"got {kernel!r}")
    s = tokens.shape[0]
    d = cfg.d_model
    hd = d // cfg.n_heads
    ps = pool.page_size
    trash = pool.trash_page
    n_p = page_table.shape[1]
    window = n_p * ps
    pos = lengths                                          # (S,)
    rows = jnp.arange(s)
    # physical destination of the incoming token's K/V; a cursor at or
    # past the window (pos // ps == n_p) writes to trash instead of
    # clamping into the slot's LAST real page
    dest = jnp.where(active & (pos // ps < n_p),
                     page_table[rows, jnp.minimum(pos // ps, n_p - 1)],
                     trash)
    offset = pos % ps
    # clamp the position-embedding lookup exactly like paged_prefill:
    # a slot whose cursor reached the window edge must reuse the last
    # embedding, not read past the (max_len, d) table
    pos_ids = jnp.minimum(pos, cfg.max_len - 1)
    x = (params["embed"][tokens] + params["pos"][pos_ids])[:, None, :]
    mask = jnp.arange(window)[None, :] <= pos[:, None]     # (S, window)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    new_layers = []
    for p, layer in zip(params["blocks"], pool.layers):
        h = _layer_norm(p["ln1"], x)
        q = _heads(h, p["Wq"], cfg)                        # (S, H, 1, hd)
        k_new = _heads(h, p["Wk"], cfg)[:, :, 0, :]        # (S, H, hd)
        v_new = _heads(h, p["Wv"], cfg)[:, :, 0, :]
        ks = _write_rows(layer["k"], dest, offset, k_new)
        vs = _write_rows(layer["v"], dest, offset, v_new)
        if kernel == "pallas":
            # stream the written pages straight from the pool — no
            # dense window; masking/trash/window-edge handled in-kernel
            att = paged_attention(q[:, :, 0, :], ks, vs, page_table,
                                  lengths, interpret=cfg.interpret)
            att = att.astype(x.dtype).reshape(s, 1, d)
        else:
            # gather each slot's pages into its logical window:
            # (S, P, H, ps, hd) -> (S, H, P*ps, hd)
            kg = ks[page_table].transpose(0, 2, 1, 3, 4).reshape(
                s, cfg.n_heads, window, hd)
            vg = vs[page_table].transpose(0, 2, 1, 3, 4).reshape(
                s, cfg.n_heads, window, hd)
            # exact masked softmax in f32 (the contiguous decode_step
            # math; masked lanes underflow to exactly 0, so page-tail
            # garbage contributes exactly 0)
            sc = jnp.einsum("shqd,shkd->shqk", q.astype(jnp.float32),
                            kg.astype(jnp.float32)) * scale
            sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
            w = jax.nn.softmax(sc, axis=-1)
            att = jnp.einsum("shqk,shkd->shqd", w,
                             vg.astype(jnp.float32))
            att = att.astype(x.dtype).transpose(0, 2, 1, 3).reshape(
                s, 1, d)
        x = x + att @ p["Wo"]
        x = _ffn(p, x)
        new_layers.append({"k": ks, "v": vs})
    x = _layer_norm(params["ln_f"], x)
    logits = x[:, 0, :] @ params["embed"].T
    return logits, PagedKVPool(tuple(new_layers))
