"""The device side of the paged cache: every model's forward under the
cache's `attend` callbacks.

A model's layers are of one or two KINDS (`cfg.layer_kinds`): "full"
layers keep every key of a sequence, "window" layers only ever read the
last `cfg.window`. One pool of pages for both would make the window
layers hold what they never read again, so each kind has its own pool
size and its own page table; all layers of one kind share page ids
(page p of a kind is row p of every pool of that kind). A model whose
layers are all full (`models/transformer.py`) is the case of one kind:

- `paged_kv.init_pool(cfg, pages, page_size)`: one `{"k", "v"}` of
  shape `(pages[kind] + 1, n_kv_heads, page_size, head_dim)` a layer;
  the last page of each is that kind's trash page, where masked writes
  go.
- tables and page ids are a dict by kind: a table is `(S,
  pages_per_slot)` int32, logical page -> page of that kind. A window
  layer's table holds the trash page for logical pages whose last key
  has left the window (`DecodeLoop` returns those pages to the kind's
  free list in the pass in which they fall out), and the step is told
  nothing more: the first visible position follows from the cursor,
  `max(0, pos - window + 1)`.
- `prefill`, `prefill_ctx`, `decode_step` and `verify_step` are the
  model's one forward (`models.model_of(cfg)`) under four `attend`
  callbacks, which differ in what attention reads and where K/V is
  written and in nothing else: whole-page scatter then the flash kernel;
  whole-page scatter then a dense read of [gathered prefix pages ‖
  tail] or, for a piece of a prompt that is prefilled in pieces, the
  flash kernel with a query offset over the row's pages up to the
  piece's end; `_write_rows` at the cursor then the paged kernel or the
  dense gather; `_write_rows` at W columns then the same two reads a
  column.
  Each returns, after the logits and the pool, what the model's layers
  count (`aux`: the pairs by held expert, or `()`).

Two more kinds keep no pages at all (`SLOT_KINDS`). A `linear` layer
(`models/hybrid_transformer.py`) holds a recurrent state and the last
few pre-convolution columns a SEQUENCE, however long, a `conv` layer
only the last few columns of its short convolution: their arrays are
indexed by SLOT, `(slots, ...)`, donated and updated in place like the
pools. The block hands its callback the columns (and a linear layer's
gates) and gets the mixer's rows back; the lanes call the model's one
`slot_mix` with what the cache holds: `prefill` a row's real length
(what the row leaves after its last REAL token goes to
`page_ids[kind][row]`, the row's slot; a padding row names a slot past
the last and is dropped),
`prefill_ctx` the slot's kept columns and state AND the row's real
length (a later piece of a prompt prefilled in pieces: read from the
row's slot, scanned on, written back), `decode_step` the slot's kept
columns and state (an inactive slot's update is masked: g = beta = 0
leave its state bit for bit, and its kept columns are kept as they
were). They need no table, no grant and no trash row. `verify_step` is
not written for them, nor is anything that would start from the state
at a position the slot has left behind (`DecodeLoop._check_refusals`
keeps them away).

Shapes are fixed for the life of a server: a step is ONE program over S
slots (tables, lengths and the active mask are traced arrays: requests
join and leave without recompiling), a prefill one program a bucket of
prompt lengths. The pool never changes layout inside a program: it
arrives donated, the step writes its rows into it in place
(`_write_rows`), the paged kernel reads it as it stands and the output
aliases the input. Positions a query may not see are masked to NEG_INF
before the softmax, so whatever lies in page tails and on the trash
page counts for exactly nothing: every lane is the uncached forward to
float tolerance (tests/test_lanes.py).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.attention.blockwise import masked_attention
from deeplearning4j_tpu.attention.flash_pallas import flash_attention_ctx
from deeplearning4j_tpu.attention.paged_pallas import paged_attention
from deeplearning4j_tpu.models import model_of
from deeplearning4j_tpu.models.transformer import (KIND_CONV, KIND_FULL,
                                                   KIND_LINEAR,
                                                   KIND_WINDOW, SLOT_KINDS,
                                                   causal_attention,
                                                   visible)
from deeplearning4j_tpu.serving.paged_kv import (PagedKVPool,  # noqa: F401
                                                 init_pool, page_bytes,
                                                 pool_bytes, slot_kinds,
                                                 state_bytes_per_slot)

__all__ = ["KIND_FULL", "KIND_WINDOW", "KIND_LINEAR", "KIND_CONV",
           "SLOT_KINDS", "kinds_of", "slot_kinds",
           "layers_of", "window_table_pages", "first_visible", "init_pool",
           "pool_bytes", "page_bytes", "state_bytes_per_slot", "prefill",
           "prefill_ctx", "decode_step", "verify_step"]


def kinds_of(cfg):
    """The kinds of PAGE this model has, full first (the kinds held by
    slot keep no pages and are not among them)."""
    return tuple(k for k in (KIND_FULL, KIND_WINDOW)
                 if k in cfg.layer_kinds)


def layers_of(cfg) -> Dict[str, int]:
    return {k: cfg.layer_kinds.count(k) for k in kinds_of(cfg)}


def window_table_pages(cfg, page_size: int) -> Optional[int]:
    """The most table columns a window can straddle: `window` keys that
    end anywhere in a page. None where no layer has a window."""
    if cfg.window is None:
        return None
    return -(-(cfg.window - 1) // page_size) + 1


def first_visible(pos, window: int):
    """First position a query at `pos` sees in a window layer (the
    query's own position counts among the `window`)."""
    return jnp.maximum(pos - window + 1, 0)


# ------------------------------------------------- the cache's writes
def _write_pages(held, flat_ids, k, v):
    """A prefill's write: k, v (B, Hkv, Tb, hd) cut into page-sized
    runs, run r of the batch going whole to page `flat_ids[r]` (B * Tb /
    page_size of them, the trash page for a run nothing will read)."""
    b, h, tb, hd = k.shape
    ps = held["k"].shape[2]

    def pages(arr, like):
        a = arr.astype(like.dtype).reshape(b, h, tb // ps, ps, hd)
        return a.transpose(0, 2, 1, 3, 4).reshape(b * (tb // ps), h, ps,
                                                  hd)

    return {"k": held["k"].at[flat_ids].set(pages(k, held["k"])),
            "v": held["v"].at[flat_ids].set(pages(v, held["v"]))}


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


def _write_slots(held, slots, entry):
    """A prefill's write for a kind held by slot: row r of each array
    of `entry` (state, kept columns) to slot `slots[r]`; a padding row
    names a slot past the last and is dropped."""
    return {name: held[name].at[slots].set(rows.astype(held[name].dtype),
                                           mode="drop")
            for name, rows in entry.items()}


def _row_dest(pool: PagedKVPool, cfg, tables, pos, live):
    """By kind, the physical page the row at cursor `pos` goes to: the
    table's, or the kind's trash page where the row is not `live` or
    its cursor is at or past the table's end (never clamped into the
    slot's last real page). `pos` and `live` are (S,) or (S, W)."""
    ps = pool.page_size
    dest = {}
    for kind, table in tables.items():
        n_p = table.shape[1]
        trash = pool.layers[cfg.layer_kinds.index(kind)]["k"].shape[0] - 1
        column = jnp.minimum(pos // ps, n_p - 1)
        page = (table[jnp.arange(table.shape[0]), column] if pos.ndim == 1
                else jnp.take_along_axis(table, column, axis=1))
        dest[kind] = jnp.where(live & (pos // ps < n_p), page, trash)
    return dest


# -------------------------------------------------- the cache's reads
def _gathered(arr, table):
    """A slot's pages side by side, its logical window: pool array
    (n_pages + 1, H, page_size, hd) through table (S, P) -> (S, H, P *
    page_size, hd). The dense read's O(S x table) traffic."""
    s, n_p = table.shape
    _, h, ps, hd = arr.shape
    return arr[table].transpose(0, 2, 1, 3, 4).reshape(s, h, n_p * ps, hd)


def _gather_read(cfg, kind: str, q, ks, vs, table, pos):
    """The dense lane of a step: q (S, Hq, W, hd), column j at cursor
    `pos[s, j]`, over the slot's whole logical window."""
    k_pos = jnp.arange(table.shape[1] * ks.shape[2])
    return masked_attention(q, _gathered(ks, table), _gathered(vs, table),
                            visible(cfg, kind, pos, k_pos))


def _check_kernel(kernel: str) -> None:
    if kernel not in ("gather", "pallas"):
        raise ValueError(
            f"kernel must be 'gather' or 'pallas' here (resolve 'auto' "
            f"via attention.paged_pallas.resolve_decode_kernel), "
            f"got {kernel!r}")


def _no_linear(kind: str, what: str) -> None:
    if kind in SLOT_KINDS:
        raise NotImplementedError(
            f"{what} is not written for a layer of the {kind} kind: it "
            f"would need the state or kept columns at a position the "
            f"slot has left behind, and the cache keeps only the newest "
            f"(snapshots of state at a page boundary are not written)")


def _last_logits(model, params, x, true_len, cfg):
    """Each row's LAST REAL position through the head: (B, d) @ (d,
    vocab), not a (B, Tb, vocab) product."""
    b, _, d = x.shape
    idx = jnp.broadcast_to((true_len - 1)[:, None, None], (b, 1, d))
    return model.head(params, jnp.take_along_axis(x, idx, axis=1)[:, 0, :],
                      cfg)


# ------------------------------------------------------------ the lanes
def prefill(params, tokens, true_len, pool: PagedKVPool,
            page_ids: Dict[str, jax.Array], cfg):
    """A batch of padded prompts (B, Tb) through every block in one
    dispatch (an admission burst costs one compiled call, not one a
    prompt). `page_ids[kind]` (B, Tb / page_size) names, by kind, the
    page each page-sized run of a row's K/V goes to: the trash page for
    runs past the row's real pages, for padding rows, and in a window
    layer for runs no later query can see. Causal flash attention means
    positions < true_len never see the padding, whose K/V lands in the
    last real page's tail (masked out of decode by the slot's length)
    or on the trash page. Returns (logits (B, vocab) at each row's last
    real position, the pool, aux of the real tokens)."""
    model = model_of(cfg)
    tb = tokens.shape[1]
    positions = jnp.arange(tb)
    valid = positions < true_len[:, None]
    flat = {kind: ids.reshape(-1) for kind, ids in page_ids.items()}

    def attend(layer, kind, q, k, v):
        held = pool.layers[layer]
        if kind in SLOT_KINDS:
            o, entry = model.slot_mix(cfg, kind, q, k, v, true_len=true_len)
            return o, _write_slots(held, flat[kind], entry)
        att = causal_attention(cfg, kind, q, k, v)
        return att, _write_pages(held, flat[kind], k, v)

    x, layers, aux = model.forward(params, tokens, positions, cfg, attend,
                                   valid)
    return (_last_logits(model, params, x, true_len, cfg),
            PagedKVPool(layers), aux)


def _logical_table(ctx_table, page_ids, ctx_len, ps: int):
    """A row's pages in logical order with no gap: the `ctx_len // ps`
    pages of `ctx_table` (B, cb) that hold its context, then the pages
    its piece goes to, `page_ids` (B, n). (B, cb + n); the columns past
    the piece's end repeat the last, and no query sees them."""
    cb, n = ctx_table.shape[1], page_ids.shape[1]
    held = (ctx_len // ps)[:, None]
    j = jnp.arange(cb + n)[None, :]
    src = jnp.minimum(jnp.where(j < held, j, cb + j - held), cb + n - 1)
    return jnp.take_along_axis(
        jnp.concatenate([ctx_table, page_ids], axis=1), src, axis=1)


def prefill_ctx(params, tokens, true_len, pool: PagedKVPool,
                page_ids: Dict[str, jax.Array],
                ctx_tables: Dict[str, jax.Array], ctx_len, cfg,
                kernel: str = "gather"):
    """Prefill a batch of prompt TAILS whose prefix K/V already sits in
    pool pages (the prefix cache's warm path, and every piece after the
    first of a prompt that is prefilled in pieces): row b's tokens are
    prompt positions `[ctx_len[b], ctx_len[b] + true_len[b])`, its
    context occupies the pages in `ctx_tables[kind][b]` (trash-padded,
    masked by `ctx_len`), and its tail K/V goes to `page_ids` exactly as
    in `prefill` (tails start on a page boundary: admission only reuses
    FULL cached chunks, and a piece is a whole number of pages). Pages
    of the context are only READ.

    `kernel` picks the full kind's read. "gather" is the dense read
    over [gathered context pages ‖ tail]: a (Tb, context + Tb) array of
    scores a head, for the short tails of the prefix cache. "pallas"
    writes the tail first and runs `flash_attention_ctx` over the row's
    pages in logical order, context and tail, query row i seeing the
    keys up to `ctx_len + i`: no array of scores, blocks past a tile's
    last query neither computed nor fetched, ONE program whatever the
    context's length (`cfg.interpret` runs it on the CPU). A window
    layer takes the dense read in either lane.

    A layer held by slot reads the kept columns (and a linear layer the
    state) of the row's slot (`page_ids[kind]`), runs the tail on top
    of them and writes them back as they stand after the row's last
    REAL token.
    Returns what `prefill` returns."""
    _check_kernel(kernel)
    model = model_of(cfg)
    b, tb = tokens.shape
    ps = pool.page_size
    positions = ctx_len[:, None] + jnp.arange(tb)               # (B, Tb)
    valid = jnp.arange(tb) < true_len[:, None]
    flat = {kind: ids.reshape(-1) for kind, ids in page_ids.items()}

    def attend(layer, kind, q, k, v):
        held = pool.layers[layer]
        if kind in SLOT_KINDS:
            at = flat[kind]
            o, entry = model.slot_mix(
                cfg, kind, q, k, v, prev=held["conv"][at],
                state=held["state"][at] if kind == KIND_LINEAR else None,
                true_len=true_len)
            return o, _write_slots(held, at, entry)
        if kernel == "pallas" and kind == KIND_FULL:
            held = _write_pages(held, flat[kind], k, v)
            table = _logical_table(ctx_tables[kind], page_ids[kind],
                                   ctx_len, ps)
            with jax.named_scope("prefill_ctx_flash"):
                att = flash_attention_ctx(
                    q, _gathered(held["k"], table),
                    _gathered(held["v"], table), ctx_len,
                    interpret=cfg.interpret)
            return att, held
        table = ctx_tables[kind]
        ctx_pos = jnp.broadcast_to(jnp.arange(table.shape[1] * ps),
                                   (b, table.shape[1] * ps))
        # prefix columns are real below ctx_len; the tail is causal
        k_pos = jnp.concatenate([ctx_pos, positions], axis=1)
        real = jnp.concatenate([ctx_pos < ctx_len[:, None],
                                jnp.ones((b, tb), bool)], axis=1)
        att = masked_attention(
            q, jnp.concatenate([_gathered(held["k"], table), k], axis=2),
            jnp.concatenate([_gathered(held["v"], table), v], axis=2),
            visible(cfg, kind, positions, k_pos) & real[:, None, :])
        return att, _write_pages(held, flat[kind], k, v)

    x, layers, aux = model.forward(params, tokens, positions, cfg, attend,
                                   valid)
    return (_last_logits(model, params, x, true_len, cfg),
            PagedKVPool(layers), aux)


def decode_step(params, tokens, pool: PagedKVPool,
                tables: Dict[str, jax.Array], lengths, active, cfg,
                kernel: str = "gather"):
    """One decode step over S slots: embed `tokens` (S,), write each
    active slot's K/V row at its cursor (`lengths`) through its kind's
    table (`_write_rows`, so the donated pools keep their layout and
    are updated in place), attend over what the layer's kind lets the
    cursor see, return (logits (S, vocab), the pool, aux of the active
    slots' tokens). Inactive slots write to the trash page and their
    logits are garbage the host ignores.

    `kernel` picks the read: "gather" materializes each slot's dense
    window (O(S x table) traffic a step); "pallas" streams only the
    written pages from the pool through `paged_attention` (grouped
    heads and a first visible position are its arguments; `cfg.interpret`
    runs it on the CPU). Callers resolve "auto" BEFORE jitting
    (`resolve_decode_kernel`): the lane is a constant of the program."""
    _check_kernel(kernel)
    model = model_of(cfg)
    ps = pool.page_size
    pos = lengths
    dest = _row_dest(pool, cfg, tables, pos, active)
    offset = pos % ps
    first = (first_visible(pos, cfg.window) if KIND_WINDOW in tables
             else None)

    def attend(layer, kind, q, k, v):
        held = pool.layers[layer]
        if kind in SLOT_KINDS:
            if kind == KIND_LINEAR:
                live = active[:, None, None]
                k = tuple(jnp.where(live, gate, 0.0) for gate in k)
            o, entry = model.slot_mix(cfg, kind, q, k, v, prev=held["conv"],
                                      state=held.get("state"))
            entry["conv"] = jnp.where(active[:, None], entry["conv"],
                                      held["conv"])
            return o, entry
        ks = _write_rows(held["k"], dest[kind], offset, k[:, :, 0])
        vs = _write_rows(held["v"], dest[kind], offset, v[:, :, 0])
        table = tables[kind]
        if kernel == "pallas":
            att = paged_attention(
                q[:, :, 0], ks, vs, table, lengths,
                first=first if kind == KIND_WINDOW else None,
                window_pages=window_table_pages(cfg, ps),
                interpret=cfg.interpret)[:, :, None]
        else:
            att = _gather_read(cfg, kind, q, ks, vs, table, pos[:, None])
        return att, {"k": ks, "v": vs}

    x, layers, aux = model.forward(params, tokens[:, None], pos[:, None],
                                   cfg, attend, active[:, None])
    return model.head(params, x[:, 0], cfg), PagedKVPool(layers), aux


def verify_step(params, tokens, pool: PagedKVPool,
                tables: Dict[str, jax.Array], lengths, widths, cfg,
                kernel: str = "gather"):
    """The WIDENED decode step speculative verify rides: `tokens` is
    (S, W), row s's column j the token whose K/V belongs at cursor
    `lengths[s] + j` (column 0 is the slot's pending token, columns
    1..W-1 the drafter's proposals). `widths` (S,) int32 is how many
    columns of each row are real (0 = idle slot; 1 = a plain step
    riding along). Returns (logits (S, W, vocab), the pool, aux of the
    real columns).

    All real positions write K/V through the tables in one dispatch
    (columns past a row's width go to the trash page) and every query
    attends causally: column j sees positions <= lengths[s] + j, so
    draft K/V written "in the future" of a query is masked exactly like
    unwritten page-tail garbage, and logits[s, j] is the model's
    next-token distribution after the prefix extended by proposals
    1..j. Rejected columns leave garbage past the rolled-back cursor:
    always masked (the cursor only moves forward over freshly written
    positions), then overwritten before ever becoming visible.

    "pallas" reuses the single-query streamed kernel once a column
    (K/V reads are O(W x written pages) either way: speculation's win
    is the weight sweep and the dispatch, not the K/V reads)."""
    _check_kernel(kernel)
    model = model_of(cfg)
    w = tokens.shape[1]
    ps = pool.page_size
    pos = lengths[:, None] + jnp.arange(w)[None, :]            # (S, W)
    valid = jnp.arange(w)[None, :] < widths[:, None]
    dest = _row_dest(pool, cfg, tables, pos, valid)
    offset = pos % ps

    def attend(layer, kind, q, k, v):
        _no_linear(kind, "the widened verify step")
        held = pool.layers[layer]
        # rows are (S, W, H, hd), one per (slot, column, head)
        ks = _write_rows(held["k"], dest[kind], offset,
                         k.transpose(0, 2, 1, 3))
        vs = _write_rows(held["v"], dest[kind], offset,
                         v.transpose(0, 2, 1, 3))
        table = tables[kind]
        if kernel == "pallas":
            # each column at its own cursor; garbage lanes (columns
            # that are not real) stay finite and are never read
            cols = []
            for j in range(w):
                at = jnp.minimum(lengths + j, table.shape[1] * ps - 1)
                cols.append(paged_attention(
                    q[:, :, j], ks, vs, table, at,
                    first=(first_visible(at, cfg.window)
                           if kind == KIND_WINDOW else None),
                    window_pages=window_table_pages(cfg, ps),
                    interpret=cfg.interpret))
            att = jnp.stack(cols, axis=2)
        else:
            att = _gather_read(cfg, kind, q, ks, vs, table, pos)
        return att, {"k": ks, "v": vs}

    x, layers, aux = model.forward(params, tokens, pos, cfg, attend, valid)
    return model.head(params, x, cfg), PagedKVPool(layers), aux
