"""How the expert layer's rows travel between token order and the
order sorted by expert: two Pallas TPU kernels that copy rows and
touch only rows that hold a (token, expert) pair.

`rows_in` builds the operand of the grouped products: row i of the
result is `h[tok[i]]` for the `n` sorted pairs. `rows_out` brings the
products' rows back: token t's result is the sum over its pairs of
`w * y[row of the pair]`, in float32, and a token with no pair gets
zeros. Neither is a gather or a scatter of XLA's: on a TPU those went
through every row of a chunk twice as wide as the pairs, sorted the
token indices to scatter in order and copied the gathered rows once
more for the kernel that read them (PERF.md section 6, PR 36).

**A row is a slab.** Mosaic copies whole (sublane, lane) tiles, and in
a `(rows, d)` array a tile holds eight rows (sixteen of a 2-byte
type), so one row of it cannot be the source or the target of a DMA.
Both kernels therefore read their rows from a `(rows, S, d / S)` view,
S the sublane tile of the type: there a row is a slab of whole tiles,
contiguous in HBM, picked by an index on the leading dimension. The
view costs XLA one pass over the array (`h`: the tokens' rows once a
layer; `y`: the chunk's). What the grouped products read, and what the
layer returns, are plain `(rows, d)`: the kernels turn slabs into rows
in VMEM (`slab[:, s, :]` is columns `[s * d / S, (s + 1) * d / S)`).

Indices ride scalar memory (`PrefetchScalarGridSpec` for `rows_in`,
SMEM blocks a token tile for `rows_out`), the copies are
`pltpu.make_async_copy` with several in flight (the idiom of
`attention/paged_pallas.py`'s block kernel), and a grid step whose rows
hold no pair starts no copy.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["rows_in", "rows_out", "rows_moved", "ROWS"]

#: sorted rows a grid step of `rows_in` copies; tokens a grid step of
#: `rows_out` sums
ROWS = 128
#: copies `rows_out` keeps in flight
IN_FLIGHT = 16


def _tile(n: int) -> int:
    """Rows a grid step: `ROWS`, or the largest halving of it that
    divides `n` rows (a decode step has fewer than `ROWS` tokens)."""
    r = ROWS
    while n % r:
        r //= 2
    return r


def _slabs(x):
    """x (rows, d) -> (rows, S, d / S): each row a slab of whole tiles
    (S the type's sublane tile; d / S a multiple of 128 at the served
    widths)."""
    rows, d = x.shape
    s = 32 // jnp.dtype(x.dtype).itemsize
    if d % s:
        raise ValueError(f"rows of {d} do not split into {s} sublanes")
    return x.reshape(rows, s, d // s)


def rows_moved(n_pairs):
    """Rows the two kernels go through for `n_pairs` sorted pairs: the
    pairs rounded up to whole grid steps of `rows_in` (a chunk is whole
    steps, so the chunks do not show). An int or a numpy array a layer:
    the scheduler's counter `dl4j_moe_rows_moved` is its sum. Off a TPU
    the plain forms go through every row of a chunk instead."""
    return -(-n_pairs // ROWS) * ROWS


# ------------------------------------------------------------- rows in
def _rows_in_kernel(tok_ref, n_ref, h_hbm, o_ref, buf, sem):
    """Grid step i: sorted rows [i * rows, (i + 1) * rows). The slabs of
    step i + 1 are in flight while step i's are turned into rows; a
    step past the last pair does nothing (its output block is the last
    real one's, see `rows_in`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    rows = buf.shape[1]
    steps = (n_ref[0] + rows - 1) // rows
    slot = jax.lax.rem(i, 2)

    def copies(act, step, slot):
        unroll = 8 if rows % 8 == 0 else 1

        def some(r8, carry):
            for r in range(unroll):
                r = r8 * unroll + r
                copy = pltpu.make_async_copy(
                    h_hbm.at[tok_ref[step * rows + r]], buf.at[slot, r],
                    sem.at[slot])
                getattr(copy, act)()
            return carry

        jax.lax.fori_loop(0, rows // unroll, some, None)

    @pl.when(jnp.logical_and(i == 0, steps > 0))
    def _first():
        copies("start", 0, 0)

    @pl.when(i + 1 < steps)
    def _next():
        copies("start", i + 1, 1 - slot)

    @pl.when(i < steps)
    def _rows():
        copies("wait", i, slot)
        width = buf.shape[3]
        for s in range(buf.shape[2]):
            o_ref[:, s * width:(s + 1) * width] = buf[slot, :, s, :]


def rows_in(h, tok, n, *, interpret: bool = False):
    """Row i of the result is `h[tok[i]]` for i < n; rows from the end
    of the grid step that holds row n - 1 on are whatever the buffer
    held (the grouped products' group sizes leave them out).

    h (t, d); tok (m,) int32 in [0, t); n int32 scalar, at most m. On a
    TPU, or in interpret mode, the kernel `moe_rows_in`; elsewhere the
    plain gather."""
    if not (interpret or jax.default_backend() == "tpu"):
        return h[tok]
    return _rows_in(h, tok, jnp.reshape(n, (1,)).astype(jnp.int32),
                    interpret=bool(interpret))


@partial(jax.jit, static_argnames=("interpret",))
def _rows_in(h, tok, n, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, d = tok.shape[0], h.shape[1]
    rows = _tile(m)
    # behind a barrier: the view is one pass over `h` as its other
    # readers have it; without it the compiler lays the tokens' rows
    # out as slabs where they are made, and pays for it by passes over
    # each float32 array they are made from
    slabs = _slabs(jax.lax.optimization_barrier(h))

    def last_real(i, tok, n):
        # steps past the last pair keep the block of the last real
        # step: nothing is fetched for them and nothing written back
        return jnp.minimum(i, jnp.maximum((n[0] + rows - 1) // rows - 1,
                                          0)), 0

    return pl.pallas_call(
        _rows_in_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(m // rows,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((rows, d), last_real),
            scratch_shapes=[pltpu.VMEM((2, rows) + slabs.shape[1:],
                                       h.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((m, d), h.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_rows_in",
    )(tok.astype(jnp.int32), n, slabs)


# ------------------------------------------------------------ rows out
def _rows_out_kernel(line_ref, w_ref, y_hbm, o_ref, acc, stage, sem,
                     ring_tok, ring_w, count, *, stride: int):
    """One tile of tokens a grid step. A token's line of scalar memory
    says how many pairs it has and which rows they are; each pair
    starts the copy of its slab into a ring of `IN_FLIGHT` buffers, and
    the pair that held the buffer before is waited for, weighted and
    added to its token's slab of `acc` first. Then the tile is written
    once, as rows. A token with no pair costs one scalar read."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ring = stage.shape[0]
    acc[...] = jnp.zeros_like(acc)
    count[0] = 0

    def retire(slot):
        pltpu.make_async_copy(y_hbm.at[0], stage.at[slot],
                              sem.at[slot]).wait()
        tok = ring_tok[slot]
        acc[tok] = acc[tok] + ring_w[slot] * stage[slot]

    def token(tt, carry):
        line = tt * stride

        def pair(j, carry):
            held = count[0]
            slot = jax.lax.rem(held, ring)
            pl.when(held >= ring)(partial(retire, slot))
            pltpu.make_async_copy(y_hbm.at[line_ref[line + 1 + j]],
                                  stage.at[slot], sem.at[slot]).start()
            ring_tok[slot] = tt
            ring_w[slot] = w_ref[line + 1 + j]
            count[0] = held + 1
            return carry

        return jax.lax.fori_loop(0, line_ref[line], pair, carry)

    jax.lax.fori_loop(0, acc.shape[0], token, None)
    held = count[0]

    def drain(i, carry):
        retire(jax.lax.rem(i, ring))
        return carry

    jax.lax.fori_loop(jnp.maximum(held - ring, 0), held, drain, None)
    width = acc.shape[2]
    for s in range(acc.shape[1]):
        o_ref[:, s * width:(s + 1) * width] = acc[:, s, :]


def rows_out(y, pos, w, *, interpret: bool = False):
    """out[t] = sum over j with pos[t, j] >= 0 of w[t, j] * y[pos[t, j]]
    in float32, zeros where a token has none.

    y (m, d) float32; pos (t, k) int32, a row of y or -1; w (t, k)
    float32. On a TPU, or in interpret mode, the kernel `moe_rows_out`;
    elsewhere a plain gather of every (token, choice) and a masked
    sum."""
    if not (interpret or jax.default_backend() == "tpu"):
        rows = y[jnp.maximum(pos, 0)]                       # (t, k, d)
        return jnp.sum(jnp.where((pos >= 0)[..., None],
                                 w[..., None] * rows, 0.0), axis=1)
    return _rows_out(y, pos, w, interpret=bool(interpret))


@partial(jax.jit, static_argnames=("interpret",))
def _rows_out(y, pos, w, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, k = pos.shape
    d = y.shape[1]
    tokens = _tile(t)
    slabs = _slabs(y)
    # a token's line: the count of its pairs, then their rows (their
    # weights, in `w`'s lines), the pairs it lacks squeezed out. A
    # tile's lines are one block of scalar memory, whose blocks are
    # whole tiles of 1,024: `ROWS` tokens of 8 or 16 scalars each
    stride = -(-(k + 1) // 8) * 8
    held = pos >= 0
    rank = jnp.cumsum(held, axis=1) - held
    to = held[:, :, None] & (rank[:, :, None] == jnp.arange(k))

    def lines(first, of):
        packed = jnp.sum(jnp.where(to, of[:, :, None], 0), axis=1)
        return jnp.pad(jnp.concatenate([first[:, None], packed], axis=1),
                       ((0, 0), (0, stride - k - 1))).reshape(-1)

    by_tile = pl.BlockSpec((tokens * stride,), lambda i: (i,),
                           memory_space=pltpu.SMEM)
    return pl.pallas_call(
        partial(_rows_out_kernel, stride=stride),
        grid=(t // tokens,),
        in_specs=[by_tile, by_tile, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tokens, d), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tokens,) + slabs.shape[1:],
                                   jnp.float32),
                        pltpu.VMEM((IN_FLIGHT,) + slabs.shape[1:],
                                   jnp.float32),
                        pltpu.SemaphoreType.DMA((IN_FLIGHT,)),
                        pltpu.SMEM((IN_FLIGHT,), jnp.int32),
                        pltpu.SMEM((IN_FLIGHT,), jnp.float32),
                        pltpu.SMEM((1,), jnp.int32)],
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_rows_out",
    )(lines(jnp.sum(held, axis=1, dtype=jnp.int32),
            pos.astype(jnp.int32)),
      lines(jnp.zeros((t,), jnp.float32), w), slabs)
