"""Paged-attention decode as a Pallas TPU kernel.

`paged_decode_step` (serving/paged_kv.py) historically gathered every
slot's page list into a dense `(S, H, window, hd)` K/V window each
step — per-step HBM traffic scaling with the page-table RESERVATION
(`S × max_len`), not the tokens actually written. This kernel streams
pages straight from the pool instead (the PagedAttention design,
PAPERS.md arXiv:2603.09555, on the repo's kernel-with-interpret
portability pattern from `attention/flash_pallas.py`):

- grid `(S, P)`: one slot per row, one page-table column per step. The
  page table and per-slot lengths ride `PrefetchScalarGridSpec` scalar
  prefetch, so the K/V BlockSpec index map picks the PHYSICAL page
  (`pt[s, j]`) for each grid step — the pool is the kernel operand and
  no dense window is ever materialized;
- online softmax across a slot's pages: f32 scratch (acc, m, s) carried
  over the sequential page dimension, base-2 state (`exp2`, scores
  prescaled by log2(e)/sqrt(hd)) exactly like the flash kernels;
- pages past a slot's written frontier (`j * page_size > pos`) are
  skipped with `pl.when` — no MXU work, and because unallocated page
  table entries all hold the trash index, Pallas's pipeline skips even
  the re-fetch (consecutive grid steps with identical block indices);
- lanes past the cursor inside the frontier page are masked to NEG_INF
  (underflow to exactly 0), matching the gather path's masked softmax,
  so parity with `kernel="gather"` holds at 1e-5 (tests pin it under
  ragged membership, CoW-shared pages, and the max_len window edge).

`resolve_decode_kernel` is the lane selector behind the
`kernel="pallas"|"gather"|"auto"` knob (`DecodeLoop`, `engine`,
`cli serve`): `auto` takes the kernel only on TPU inside the envelope
that has been run against the gather math on a chip, and NEVER
silently runs interpret mode off-TPU; explicit `pallas` off-TPU is an
error unless `cfg.interpret` is set (the CPU tier-1 test lane).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.attention.flash_pallas import LOG2E, NEG_INF

__all__ = ["paged_attention", "resolve_decode_kernel", "DECODE_KERNELS"]

DECODE_KERNELS = ("auto", "pallas", "gather")


def _decode_kernel(pt_ref, len_ref, *refs, page_size: int,
                   windowed: bool = False):
    """One (slot, page) grid step. `pt_ref`/`len_ref` (and `first_ref`
    where `windowed`) are the scalar-prefetch operands (the same arrays
    the BlockSpec index maps read); K/V refs already hold the PHYSICAL
    page the index map selected for this step. The query block carries
    `rows` rows a K/V head: identical copies of the slot's one query
    row where there are as many K/V heads as query heads, the query
    heads that share the K/V head where there are fewer (see
    `paged_attention`); the softmax state below is (H, rows, ·).

    `windowed`: the slot sees positions [first, pos] only, and grid
    step j stands for logical page first // page_size + j, so the sweep
    starts at the first page that still holds a visible key."""
    from jax.experimental import pallas as pl

    if windowed:
        first_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, s_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, s_ref = refs
    si = pl.program_id(0)
    j = pl.program_id(1)
    n_j = pl.num_programs(1)
    pos = len_ref[si]   # this slot's cursor: positions [0, pos] visible
    if windowed:
        first = first_ref[si]
        page = first // page_size + j

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)

    # pages wholly past the written frontier contribute exactly 0 in the
    # gather path (every lane masked): skip them here — page 0 always
    # computes (pos >= 0), so the softmax sum is never empty
    @pl.when((page if windowed else j) * page_size <= pos)
    def _tile():
        q = q_ref[0]          # (H, rows, hd)
        k = k_ref[0]          # (H, ps, hd)
        v = v_ref[0]
        hd = q.shape[-1]
        # base-2 softmax state, scores prescaled by log2(e)/sqrt(hd):
        # the transcendental is a bare exp2 (flash_pallas._kernel)
        scale2 = jnp.float32(LOG2E) / jnp.float32(hd) ** 0.5
        scores = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=_dot_precision(q.dtype)) * scale2   # (H, rows, ps)
        k_pos = (page if windowed else j) * page_size \
            + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
        mask = k_pos <= pos   # current token at `pos` IS visible
        if windowed:
            mask = jnp.logical_and(mask, k_pos >= first)
        scores = jnp.where(mask, scores, NEG_INF)
        m_prev, s_prev = m_ref[...], s_ref[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(scores - m_new)
        p = jnp.where(mask, p, 0.0)
        m_ref[...] = m_new
        s_ref[...] = s_prev * alpha + p.sum(axis=-1, keepdims=True)
        # P in V's storage dtype for the MXU dot, f32 accumulation —
        # same rounding story as the flash forward
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=_dot_precision(v.dtype))

    @pl.when(j == n_j - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(s_ref[...], 1e-30)).astype(o_ref.dtype)


def _dot_precision(dtype):
    """f32 pools ask Mosaic for a full-precision contraction (the 1e-5
    parity with the gather path's f32 softmax is pinned by tests);
    narrower dtypes take the MXU's native single pass."""
    return (jax.lax.Precision.HIGHEST
            if jnp.dtype(dtype).itemsize >= 4 else None)


def paged_attention(q, k_pool, v_pool, page_table, lengths, *,
                    first=None, window_pages=None,
                    interpret: bool = False):
    """Single-token paged attention over the block pool.

    q: (S, H, hd) — one decode query row per slot (the token being
    written this step). k_pool/v_pool: (n_pages + 1, H, page_size, hd)
    block pools, last page = trash. page_table: (S, P) int32 pool
    indices (trash-filled past each slot's allocation). lengths: (S,)
    int32 cursors — positions [0, lengths[s]] are attended (the
    incoming token's K/V must already be scattered at its cursor,
    exactly as `paged_decode_step` orders writes before attention).

    Returns (S, H, hd) in q.dtype. page_table/lengths are traced
    values: membership changes never recompile (the
    `decode_step_programs() == 1` invariant).

    The query row is replicated to one sublane tile of rows (8 for
    4-byte, 16 for 2-byte dtypes) before the call: Mosaic's matmul
    needs a free (non-contracting, non-batch) dimension on BOTH
    operands, and a bare `(H, hd) x (H, ps, hd)` matrix-vector batch
    has none on the left (it does not lower — the form this kernel had
    before it ever met the compiler). The replicas cost `rows` x the
    query/output bytes, which are ~1/page_count of the K/V bytes the
    step streams; row 0 of the result is the answer.

    Grouped K/V heads: the pools may hold fewer heads than q, Hkv
    dividing Hq. The rows of a K/V head's block are then REAL ones, the
    Hq / Hkv query heads that read it (query head n reads K/V head
    n // (Hq / Hkv)), padded with zero rows up to whole sublane tiles.

    `first` (S,) int32: a first visible position per slot beside the
    last, for layers that see a window: positions [first[s],
    lengths[s]] are attended, and the sweep covers `window_pages`
    table columns from column first[s] // page_size on (pages before it
    are never fetched, as pages past the cursor are skipped), so
    `window_pages` is at least the most columns a window can straddle.
    Without `first` and with as many K/V heads as query heads the call
    compiles to the program it always was."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, hq, hd = q.shape
    h, ps = k_pool.shape[1], k_pool.shape[2]
    n_p = page_table.shape[1]
    tile = 32 // jnp.dtype(q.dtype).itemsize   # one sublane tile
    if hq == h:
        rows = tile
        q_rows = jnp.broadcast_to(q[:, :, None, :], (s, h, rows, hd))
    else:
        if hq % h:
            raise ValueError(f"{h} K/V heads do not divide {hq} query "
                             f"heads")
        real = hq // h
        rows = -(-real // tile) * tile
        q_rows = jnp.pad(q.reshape(s, h, real, hd),
                         ((0, 0), (0, 0), (0, rows - real), (0, 0)))
    windowed = first is not None
    if windowed:
        n_j = min(n_p, int(window_pages) if window_pages else n_p)
        scalars = (page_table.astype(jnp.int32),
                   lengths.astype(jnp.int32), first.astype(jnp.int32))
        q_map = lambda si, j, pt, ln, fs: (si, 0, 0, 0)  # noqa: E731
        kv_map = lambda si, j, pt, ln, fs: (  # noqa: E731
            pt[si, jnp.minimum(fs[si] // ps + j, n_p - 1)], 0, 0, 0)
    else:
        n_j = n_p
        scalars = (page_table.astype(jnp.int32),
                   lengths.astype(jnp.int32))
        q_map = lambda si, j, pt, ln: (si, 0, 0, 0)  # noqa: E731
        kv_map = lambda si, j, pt, ln: (pt[si, j], 0, 0, 0)  # noqa: E731
    q_spec = pl.BlockSpec((1, h, rows, hd), q_map,
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, h, ps, hd), kv_map,
                           memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(s, n_j),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((h, rows, hd), jnp.float32),   # acc
            pltpu.VMEM((h, rows, 1), jnp.float32),    # running max (base-2)
            pltpu.VMEM((h, rows, 1), jnp.float32),    # running sum
        ])
    out = pl.pallas_call(
        partial(_decode_kernel, page_size=ps, windowed=windowed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, rows, hd), q.dtype),
        # slots are independent (scratch init/finalize is per-row);
        # only the page sweep carries the online-softmax state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(*scalars, q_rows, k_pool, v_pool)
    if hq == h:
        return out[:, :, 0, :]
    return out[:, :, :hq // h, :].reshape(s, hq, hd)


def resolve_decode_kernel(kernel: str, cfg, page_size: int) -> str:
    """Resolve the `kernel="pallas"|"gather"|"auto"` knob to the lane
    `paged_decode_step` actually runs — ONCE, at loop construction, so
    the decode step stays one compiled program.

    - "gather": always the dense-gather path.
    - "pallas": the kernel; off-TPU this raises unless `cfg.interpret`
      is set (tests run the kernel code path through the interpreter —
      production must never fall into that silently).
    - "auto": the kernel on TPU for hd <= 128, a <= 4-byte KV dtype
      and page_size >= 8; everything else takes the gather path.
      That envelope is what has been CHECKED on a v5e (PR 21,
      tests/test_tpu_lane.py): H8 x hd128 and H16 x hd64, f32 (1e-5
      against a float64 dense reference) and bf16 (2e-2), page sizes 8
      and 16, ragged cursors. The chip refused nothing in it. Outside
      it the kernel also compiles (hd 16..256, page sizes 1..128 were
      compiled for v5e, not run), but sub-tile pages pad every K/V
      block to a full (8|16, 128) tile and nothing there has been
      compared on a chip, so `auto` does not go there; widen it with a
      tpu-lane case, not by argument.
      Off-TPU auto is ALWAYS gather, interpret or not: interpret mode
      is a test lane, not a production fallback."""
    if kernel not in DECODE_KERNELS:
        raise ValueError(
            f"kernel must be one of {DECODE_KERNELS}, got {kernel!r}")
    on_tpu = jax.default_backend() == "tpu"
    if kernel == "gather":
        return "gather"
    if kernel == "pallas":
        if not on_tpu and not getattr(cfg, "interpret", False):
            raise ValueError(
                "kernel='pallas' needs a TPU backend; off-TPU the "
                "kernel only runs under interpret mode (set "
                "cfg.interpret=True in tests) — use kernel='gather' "
                "or 'auto' instead")
        return "pallas"
    # auto
    if not on_tpu:
        return "gather"
    # a model description that states its head size says so itself
    # (grouped heads: d_model / n_heads is not it)
    hd = getattr(cfg, "head_dim", None) or cfg.d_model // cfg.n_heads
    itemsize = jnp.dtype(cfg.dtype).itemsize
    if hd > 128 or itemsize > 4 or page_size < 8:
        return "gather"
    return "pallas"
