"""Paged-attention decode as a Pallas TPU kernel.

The decode step (`serving/paged_kinds.decode_step`) historically
gathered every slot's page list into a dense `(S, H, window, hd)` K/V
window each step — per-step HBM traffic scaling with the page-table
RESERVATION (`S × max_len`), not the tokens actually written. This
kernel streams pages straight from the pool instead (the
PagedAttention design, PAPERS.md arXiv:2603.09555, on the repo's
kernel-with-interpret portability pattern from
`attention/flash_pallas.py`):

- the sweep of a slot goes over BLOCKS of `block_pages(...)`
  consecutive page-table columns. The page table and per-slot lengths
  ride `PrefetchScalarGridSpec` scalar prefetch and pick the PHYSICAL
  page of each column — the pool is the kernel operand and no dense
  window is ever materialized. A block is as many pages as hold 128
  keys, the size at which pages of 128 tokens read 77% of the kernel's
  roofline on a v5e and pages of 16, one a step, read 24% (a grid step
  costs ~0.4 us whatever it moves, and a page of 16 x 16 x 128 bf16 is
  0.08 us of bandwidth):
  - pages of 128 tokens and more, and pages that are no whole
    (sublane, lane) tiles, are a block each: grid `(S, P)`, the K/V
    BlockSpec index map reads `pt[s, j]`, Pallas's own pipeline
    fetches (`_decode_kernel`, the program this kernel always was);
  - smaller pages are swept several a step (`_block_kernel`): grid
    `(S,)`, the pools left in HBM, each page of a block copied by a
    DMA of its own to its rows of one `(H, N * page_size, hd)` buffer,
    the next block in flight while this one computes. The scores of a
    block are one lane-dense `(H, rows, N * page_size)` tile;
- online softmax across a slot's blocks: f32 scratch (acc, m, s),
  base-2 state (`exp2`, scores prescaled by log2(e)/sqrt(hd)) exactly
  like the flash kernels;
- columns past a slot's written frontier cost nothing: the block sweep
  loops over the blocks that hold a visible key and fetches their
  written pages alone; the page sweep skips a page with `pl.when`, and
  because unallocated page table entries all hold the trash index,
  Pallas's pipeline skips even the re-fetch (consecutive grid steps
  with identical block indices);
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

__all__ = ["paged_attention", "block_pages", "resolve_decode_kernel",
           "DECODE_KERNELS"]

DECODE_KERNELS = ("auto", "pallas", "gather")


def _softmax_update(q, k, v, page, page_size, pos, first,
                    acc_ref, m_ref, s_ref, transposed: bool = False):
    """One online-softmax update of the (H, rows, .) state with the
    keys of `k`/`v` (H, keys, hd), or (H, hd, keys) where `transposed`,
    the first of them the first of logical page `page`: positions past
    `pos` (and before `first`, where it is given) are masked to NEG_INF
    and weigh exactly 0."""
    hd = q.shape[-1]
    keys_at = 2 if transposed else 1
    # base-2 softmax state, scores prescaled by log2(e)/sqrt(hd):
    # the transcendental is a bare exp2 (flash_pallas._kernel)
    scale2 = jnp.float32(LOG2E) / jnp.float32(hd) ** 0.5
    scores = jax.lax.dot_general(
        q, k, (((2,), (3 - keys_at,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=_dot_precision(q.dtype)) * scale2   # (H, rows, keys)
    k_pos = page * page_size \
        + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    mask = k_pos <= pos   # current token at `pos` IS visible
    if first is not None:
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
        p.astype(v.dtype), v, (((2,), (keys_at,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=_dot_precision(v.dtype))


def _decode_kernel(pt_ref, len_ref, *refs, page_size: int,
                   windowed: bool = False, transposed: bool = False):
    """One (slot, page) grid step: the kernel of a block of ONE page.
    `pt_ref`/`len_ref` (and `first_ref` where `windowed`) are the
    scalar-prefetch operands (the same arrays the BlockSpec index maps
    read); K/V refs already hold the PHYSICAL page the index map
    selected for this step. The query block carries `rows` rows a K/V
    head: identical copies of the slot's one query row where there are
    as many K/V heads as query heads, the query heads that share the
    K/V head where there are fewer (see `paged_attention`); the softmax
    state is (H, rows, ·).

    `windowed`: the slot sees positions [first, pos] only, and grid
    step j stands for logical page first // page_size + j, so the sweep
    starts at the first page that still holds a visible key.
    `transposed`: a page of K and V arrives as (H, hd, page_size)."""
    from jax.experimental import pallas as pl

    if windowed:
        first_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, s_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, s_ref = refs
    si = pl.program_id(0)
    j = pl.program_id(1)
    n_j = pl.num_programs(1)
    pos = len_ref[si]   # this slot's cursor: positions [0, pos] visible
    first = first_ref[si] if windowed else None
    page = first // page_size + j if windowed else j

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)

    # pages wholly past the written frontier contribute exactly 0 in the
    # gather path (every lane masked): skip them here — page 0 always
    # computes (pos >= 0), so the softmax sum is never empty
    @pl.when(page * page_size <= pos)
    def _tile():
        _softmax_update(q_ref[0], k_ref[0], v_ref[0], page, page_size,
                        pos, first, acc_ref, m_ref, s_ref, transposed)

    @pl.when(j == n_j - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(s_ref[...], 1e-30)).astype(o_ref.dtype)


def _block_kernel(pt_ref, len_ref, *refs, page_size: int, n_block: int,
                  n_sweep: int, windowed: bool = False):
    """One slot a grid step, a block of `n_block` pages a loop step.

    The pools stay in HBM (`memory_space=pl.ANY`). A block is the K
    (and V) of `n_block` consecutive table columns, each page one DMA
    from where it lies in the pool to its `page_size` rows of a
    (H, n_block * page_size, hd) buffer: the pages arrive side by side
    along the key dimension, so the scores are one lane-dense
    (H, rows, n_block * page_size) tile and the block is one
    online-softmax update; nothing is relaid in VMEM. Two buffers: the
    next block (or the next slot's first) is in flight while this one
    computes; which of the two is next is carried from slot to slot in
    `turn_ref`, so the grid is sequential ("arbitrary").

    Only columns that hold a visible key are fetched: from column
    first // page_size (0 where not `windowed`) to the cursor's, at
    most `n_sweep` of them. The loop runs over their blocks alone, so
    columns past the cursor cost neither a DMA nor a step. Rows of a
    buffer that no DMA of this block wrote hold an earlier block's
    pages or the zeros both buffers start from: finite, and masked."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if windowed:
        first_ref, *refs = refs
    (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, acc_ref, m_ref, s_ref,
     turn_ref) = refs
    si = pl.program_id(0)
    n_s = pl.num_programs(0)
    n_table = pt_ref.shape[1]
    ps = page_size

    def sweep(s):
        """First table column and count of the columns slot `s` reads
        (at least one: the softmax sum is never empty)."""
        col0 = (jnp.minimum(first_ref[s] // ps, n_table - 1)
                if windowed else 0)
        last = jnp.minimum(len_ref[s] // ps,
                           jnp.minimum(col0 + n_sweep, n_table) - 1)
        return col0, jnp.maximum(last - col0 + 1, 1)

    def block_dma(act, s, span, b, buf):
        """"start" or "wait" (`act`) the copies of block `b` of slot
        `s`, whose sweep is `span`, into buffer `buf`: K and V of each
        of the block's columns that the sweep reads."""
        col0, n_cols = span

        def page_dma(i):
            page = pt_ref[s, col0 + b * n_block + i]
            for kv, (hbm, vmem) in enumerate(((k_hbm, k_buf),
                                              (v_hbm, v_buf))):
                copy = pltpu.make_async_copy(
                    hbm.at[page], vmem.at[buf, :, pl.ds(i * ps, ps)],
                    sem.at[buf, kv])
                getattr(copy, act)()

        page_dma(0)             # a block that runs holds its first page
        for i in range(1, n_block):
            pl.when(b * n_block + i < n_cols)(partial(page_dma, i))

    @pl.when(si == 0)
    def _first_slot():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        turn_ref[0] = 0
        block_dma("start", 0, sweep(0), 0, 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    s_ref[...] = jnp.zeros_like(s_ref)
    # a cursor at the table's end sees every key of the table and
    # none past it: a buffer's rows that follow the last page fetched
    # stand for positions that no page holds
    pos = jnp.minimum(len_ref[si], n_table * ps - 1)
    first = first_ref[si] if windowed else None
    span = col0, n_cols = sweep(si)
    n_blocks = (n_cols + n_block - 1) // n_block
    turn = turn_ref[0]

    def block(b, carry):
        buf = jax.lax.rem(turn + b, 2)

        @pl.when(b + 1 < n_blocks)
        def _next_block():
            block_dma("start", si, span, b + 1, 1 - buf)

        @pl.when(jnp.logical_and(b + 1 == n_blocks, si + 1 < n_s))
        def _next_slot():
            block_dma("start", si + 1, sweep(si + 1), 0, 1 - buf)

        block_dma("wait", si, span, b, buf)
        _softmax_update(q_ref[0], k_buf[buf], v_buf[buf],
                        col0 + b * n_block, ps, pos, first,
                        acc_ref, m_ref, s_ref)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, None)
    turn_ref[0] = jax.lax.rem(turn + n_blocks, 2)
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
    exactly as `paged_kinds.decode_step` orders writes before attention).

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
    A step of the sweep covers a BLOCK of N = `block_pages(...)`
    consecutive table columns, N derived from the call's shapes and
    from nothing else: as many pages as hold `BLOCK_KEYS` = 128 keys,
    no more than `BLOCK_BYTES` of K and no more than the columns swept;
    1 for pages of 128 tokens and more and for pages that are no whole
    (sublane, lane) tiles. On a v5e, 24 calls at `cgpt13b-decode-sat`'s
    shapes (16 slots at 1,024-1,472 keys, 16 heads of 128, pages of 16,
    bf16; the bytes of their written pages take 4.86 ms at 819 GB/s)
    took 20.98 ms with one page a step, 11.69 / 7.76 / 6.22 / 6.15 ms
    with blocks of 2 / 4 / 8 / 16 pages (23 / 42 / 63 / 78 / 79% of
    that least), and with 5 of the 16 slots live 8.68 ms against 3.26
    / 2.42 / 2.15 / 2.31: 128 keys it is, 256 pay nothing more (PERF.md
    section 6, PR 31). With N = 1 the call is the program it always
    was, grid `(S, columns)` with the pool under a block spec; with
    N > 1 the grid is `(S,)`, the pools stay in HBM and the kernel
    copies each page of a block to its place in a VMEM buffer itself
    (`_block_kernel`). The sweep starts at the first column swept,
    aligned to nothing; a table width or a window that N does not
    divide ends in a block with fewer pages, and columns past the
    cursor are neither fetched nor looped over. Callers see none of
    it.

    The call is traced and lowered once a shape, not once a layer (the
    body is jitted): a decode step of 24 layers that lowered the block
    kernel 24 times took 7.5 s to lower where one page a step took 2.9,
    5-14 s of a cell's `setup_s` on the chip (PERF.md section 6, PR
    31)."""
    return _paged_attention(
        q, k_pool, v_pool, page_table, lengths, first,
        window_pages=None if window_pages is None else int(window_pages),
        interpret=bool(interpret))


@partial(jax.jit, static_argnames=("window_pages", "interpret"))
def _paged_attention(q, k_pool, v_pool, page_table, lengths, first, *,
                     window_pages, interpret):
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
    n_j = min(n_p, window_pages) if windowed and window_pages else n_p
    scalars = (page_table.astype(jnp.int32), lengths.astype(jnp.int32))
    if windowed:
        scalars += (first.astype(jnp.int32),)
    n_block = block_pages(ps, h, hd, k_pool.dtype, n_j)
    q_spec = pl.BlockSpec((1, h, rows, hd), lambda si, *_: (si, 0, 0, 0),
                          memory_space=pltpu.VMEM)
    state = [
        pltpu.VMEM((h, rows, hd), jnp.float32),   # acc
        pltpu.VMEM((h, rows, 1), jnp.float32),    # running max (base-2)
        pltpu.VMEM((h, rows, 1), jnp.float32),    # running sum
    ]
    # heads narrower than a lane tile in pages of whole lane tiles: the
    # TPU lays such a pool out with its positions minor ({2,3,1,0}, no
    # padding), and a row-major operand would copy both pools whole at
    # every call; read as it lies, each page (H, hd, page_size), it is
    # the transposed pool's row-major layout and no copy at all
    transposed = bool(hd % 128) and ps % 128 == 0
    if transposed:
        k_pool, v_pool = (jnp.swapaxes(a, 2, 3) for a in (k_pool, v_pool))
    if n_block == 1:
        # a block of one page is one contiguous piece of the pool:
        # Pallas's own pipeline fetches it by a block spec
        if windowed:
            kv_map = lambda si, j, pt, ln, fs: (  # noqa: E731
                pt[si, jnp.minimum(fs[si] // ps + j, n_p - 1)], 0, 0, 0)
        else:
            kv_map = lambda si, j, pt, ln: (pt[si, j], 0, 0, 0)  # noqa: E731
        kv_spec = pl.BlockSpec((1, *k_pool.shape[1:]), kv_map,
                               memory_space=pltpu.VMEM)
        kernel = partial(_decode_kernel, page_size=ps, windowed=windowed,
                         transposed=transposed)
        grid, scratch = (s, n_j), state
        # slots are independent (scratch init/finalize is per-row);
        # only the page sweep carries the online-softmax state
        semantics = ("parallel", "arbitrary")
    else:
        kv_spec = pl.BlockSpec(memory_space=pl.ANY)
        kernel = partial(_block_kernel, page_size=ps, n_block=n_block,
                         n_sweep=n_j, windowed=windowed)
        block = pltpu.VMEM((2, h, n_block * ps, hd), k_pool.dtype)
        grid = (s,)
        scratch = [block, block, pltpu.SemaphoreType.DMA((2, 2)), *state,
                   pltpu.SMEM((1,), jnp.int32)]
        # the buffer in flight is carried from one slot to the next
        semantics = ("arbitrary",)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec], out_specs=q_spec,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((s, h, rows, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        interpret=interpret,
        name="paged_decode_attention",
    )(*scalars, q_rows, k_pool, v_pool)
    if hq == h:
        return out[:, :, 0, :]
    return out[:, :, :hq // h, :].reshape(s, hq, hd)


#: keys a block holds: what a page of 128 tokens holds, at which the
#: kernel reads 77% of its roofline (`paged_attention`'s doc-string
#: has the sweep of 32, 64, 128 and 256 keys)
BLOCK_KEYS = 128
#: most bytes of K a block holds: K and V, two buffers each, stay
#: inside a quarter of the 16 MiB of VMEM a kernel may use
BLOCK_BYTES = 1 << 20


def block_pages(page_size: int, kv_heads: int, head_dim: int, dtype,
                n_columns: int) -> int:
    """Pages of a block of `paged_attention`'s sweep, from the shapes
    of the call alone (see its doc-string): 8 for `cerebras-gpt-1.3b`'s
    pages of 16 tokens, 1 for `command-a-plus-ep8`'s pages of 128.
    `n_columns`: the table columns a slot's sweep covers."""
    itemsize = jnp.dtype(dtype).itemsize
    if page_size % (32 // itemsize) or head_dim % 128:
        # a page whose rows are no whole (sublane, lane) tiles has no
        # aligned place inside a block (Mosaic refuses the DMA): it is
        # fetched alone, padded, as ever
        return 1
    page_bytes = kv_heads * page_size * head_dim * itemsize
    return max(1, min(BLOCK_KEYS // page_size, BLOCK_BYTES // page_bytes,
                      n_columns))


def resolve_decode_kernel(kernel: str, cfg, page_size: int) -> str:
    """Resolve the `kernel="pallas"|"gather"|"auto"` knob to the lane
    the decode step actually runs — ONCE, at loop construction, so
    the decode step stays one compiled program.

    - "gather": always the dense-gather path.
    - "pallas": the kernel; off-TPU this raises unless `cfg.interpret`
      is set (tests run the kernel code path through the interpreter —
      production must never fall into that silently).
    - "auto": the kernel on TPU for hd <= 128, and for hd 256 on
      pages of 128 tokens and more, a <= 4-byte KV dtype and page_size
      >= 8; everything else takes the gather path.
      That envelope is what has been CHECKED on a v5e (PR 21,
      tests/test_tpu_lane.py): H8 x hd128 and H16 x hd64, f32 (1e-5
      against a float64 dense reference) and bf16 (2e-2), page sizes 8
      and 16, ragged cursors (one page a step but for f32 pages of 8
      and 16 at hd128, which PR 31 ran again in blocks of 16 and 8);
      PR 31: S16 x H16 x hd128, pages of 16 over 128 columns, bf16 and
      f32, 8 pages a block, and 32 query heads over 4 K/V heads with
      a window over 33 columns (2e-2 / 1e-4 against the float64
      reference, cursors on every edge of a page, a block and the
      table); PR 35: S64 x 16 query heads over 2 K/V heads of 256 (8
      rows a K/V head, one 128 KB K page a block), pages of 128 over 64
      columns, bf16 and f32 (2e-2 / 1e-4 against the float64 reference,
      cursors on the edges of a page and the table): where the dense
      gather of 64 slots x 64 columns would move 1.1 GB a full layer a
      step. The chip refused nothing in it. Outside it the kernel
      also compiles (hd 16..256, page sizes 1..128 were compiled for
      v5e, not run), but sub-tile pages pad every K/V block to a full
      (8|16, 128) tile and nothing there has been compared on a chip,
      so `auto` does not go there; widen it with a tpu-lane case, not
      by argument.
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
    itemsize = jnp.dtype(cfg.dtype).itemsize
    if itemsize > 4 or page_size < 8:
        return "gather"
    if cfg.head_dim > 128 and not (cfg.head_dim == 256
                                   and page_size >= 128):
        return "gather"
    return "pallas"
