"""The gated delta rule as two Pallas TPU kernels: the chunked scan of a
prefill (`gdn_scan`) and the one-token state update of a decode step
(`gdn_update`).

The recurrence, a value head (state `S` (dk, dv) float32, zero at the
start of a sequence; q and k already normalised, q scaled; `beta` the
write strength, in (0, 1) or, where the model lets `I - beta k k^T` have
a negative eigenvalue, in (0, 2)):

    S' = exp(g_t) S_{t-1}
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
    o_t = S_t^T q_t

`gdn_update` is that, once: grid `(slots, heads / block)`, the state
block read once and written once in place (`input_output_aliases`), the
arithmetic on the VPU in float32. k and q arrive with positions along
sublanes (`kq_t`, one (dk, 128) tile a slot and head block: lane h is
head h's k, lane `block + h` its q), because a column of the state is
scaled by them and a kernel cannot turn a row into a column for free;
a lane is spread over all lanes by a product with a one-hot matrix,
which is exact.

`gdn_scan` is the chunked form (Gated DeltaNet, arXiv:2412.06464, the
WY / UT transform): inside a chunk of C tokens everything is products,
between chunks only the state is carried (the first chunk starts from
the state the caller hands in: zero at the start of a sequence, a slot's
kept state where a prompt is prefilled a piece at a time). Neither dk
nor dv need be a multiple of the chip's 128 lanes, nor alike (96 x 192
runs as it is, a block spanning the whole of either). With `gc` the
running sum of g
inside the chunk, `L[i, j] = exp(gc_i - gc_j)` for i >= j (never above
1), `A = strictly_lower((k beta) k^T * L)` and `T = (I + A)^-1`:

    u = T (v beta);  w = T (k beta exp(gc))
    v_new = u - w S
    o = (q exp(gc)) S + lower((q k^T) * L) v_new
    S <- S exp(gc_C) + (k exp(gc_C - gc))^T v_new

`T` comes from products alone (`_unit_lower_inverse`): the diagonal
blocks of 16 by the finite Neumann product (their strictly lower part
is nilpotent of order 16, so intermediate powers grow by at most
C(15, 7) = 6,435 even where every key is the same), the blocks below
them by the exact block identity `(D + E)^-1 = (I + D^-1 E)^-1 D^-1`,
whose Neumann product has two factors. Operands of the products are in
the type the call came in (bfloat16 when serving, float32 accumulation;
in float32 every product asks for the full contraction); decays, the
inverse's sums and the state are float32.

Only the last three lines meet the state, so a chunk is two halves,
one form that the kernel and the plain path both run:

- `_wy`, the state-free half: the decay mask, `A`, `T`, `u` in float32,
  and in the call's type `[w; q exp(gc)]` stacked by rows (2C, dk) and
  `[lower((q k^T) * L); k_d^T]` (C + dk, C), `k_d = k exp(gc_C - gc)`.
  `u` stays float32 because `v_new = u - w S` is the difference of two
  terms of the value's size: rounded to bfloat16 first, it would lose
  what the one-pass chunk kept.
- `_carry`, the state half: two products, `[w; q exp(gc)] S`, then
  `[att; k_d^T] v_new`.

Both take a batch of chunks, and every product runs over the whole
batch before the next: Mosaic keeps a product's order on its MXU, so
chains written one after another serialise (PR 38: a grid step of 8
heads, each chunk's ~18 products written head after head, had a
critical path of ~15,000 cycles, ~125 of them a product's latency).
`gdn_scan` is ONE kernel: grid `(heads / block, chunks)`, the chunk
axis sequential, the state resident in VMEM in its own output block
from the first chunk to the last; a grid step runs `_wy` and then
`_carry` over its block of heads, so nothing but q, k, v, g and beta
comes in from HBM and nothing but o and the state goes out. The block
is as many heads as `_HEADS` and VMEM (`_VMEM_LIMIT`) allow, from the
widths and the type alone.

Padding must not move the state: past a row's real length the caller
passes beta = 0 and g = 0, under which a token leaves S as it is, so
the state after the last chunk is the state at the last real token.

On a TPU, or with `interpret=True` (the CPU test lane), the kernels;
elsewhere the same two halves, `_wy` over every chunk of every head at
once and `_carry` over every head under `lax.scan`, and the update as
plain products.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["gdn_scan", "gdn_update", "CHUNK"]

#: tokens of a chunk of the scan
CHUNK = 64
#: the diagonal blocks whose inverse is a finite Neumann product
_INV_BLOCK = 16
_HIGHEST = jax.lax.Precision.HIGHEST


def _prec(dtype):
    """float32 operands ask for the full contraction (tests hold the
    scan to the token-by-token recurrence); narrower ones take the
    MXU's single pass with float32 accumulation."""
    return _HIGHEST if jnp.dtype(dtype).itemsize >= 4 else None


def _dot(a, b, ca, cb, prec):
    """a . b over a's axis `ca` and b's axis `cb`, float32 accumulation;
    where both are 3-D, the leading axis is a batch of products."""
    batch = ((0,), (0,)) if a.ndim == 3 else ((), ())
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), batch),
                               preferred_element_type=jnp.float32,
                               precision=prec)


def _mm(a, b, prec):
    return _dot(a, b, a.ndim - 1, b.ndim - 2, prec)


def _mm_nt(a, b, prec):
    """a @ b^T."""
    return _dot(a, b, a.ndim - 1, b.ndim - 1, prec)


def _unit_lower_inverse(a, row, col, cd, prec):
    """(I + a)^-1 for a strictly lower (C, C) float32, or a batch of
    them (B, C, C), by products alone (the module's doc-string has the
    identities). `cd` is the type the products' operands take."""
    c = a.shape[-1]
    eye = (row == col).astype(jnp.float32)

    def mm(x, y):
        return _mm(x.astype(cd), y.astype(cd), prec)

    def neumann(m, order):
        """sum_k m^k for m nilpotent of `order`: prod (I + m^(2^j))."""
        x, p, reach = eye + m, m, 2
        while reach < order:
            p = mm(p, p)
            x = x + mm(x, p)
            reach *= 2
        return x

    if c <= _INV_BLOCK:
        return neumann(-a, c)
    shift = _INV_BLOCK.bit_length() - 1
    same = jnp.right_shift(row, shift) == jnp.right_shift(col, shift)
    d_inv = neumann(-jnp.where(same, a, 0.0), _INV_BLOCK)
    n = mm(d_inv, jnp.where(same, 0.0, a))
    return mm(neumann(-n, c // _INV_BLOCK), d_inv)


def _wy(q, k, v, gc, beta, prec):
    """The state-free half of a batch of chunks (a chunk of a head
    each): q, k (B, C, dk) and v (B, C, dv) in the call's type, gc and
    beta (B, 1, C) float32 (gc the running sum of g inside the chunk).
    Returns u (B, C, dv) float32 and, in the call's type, the operands
    that meet the state, `[w; q e^gc]` (B, 2C, dk), and those that meet
    `v_new`, `[lower(q k^T) * L; k_d^T]` (B, C + dk, C)."""
    c, cd, f32 = q.shape[1], q.dtype, jnp.float32
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def column(r):
        """(B, 1, C) -> (B, C, 1): the diagonal of the row spread over
        rows, summed along lanes."""
        return jnp.sum(jnp.where(row == col, r, 0.0), axis=-1,
                       keepdims=True)

    g_col, b_col = column(gc), column(beta)
    decay = jnp.exp(jnp.where(row >= col, g_col - gc, -1e30))  # i >= j
    kb = k.astype(f32) * b_col
    a = jnp.where(row > col, _mm_nt(kb.astype(cd), k, prec) * decay, 0.0)
    t = _unit_lower_inverse(a, row, col, cd, prec).astype(cd)
    e_gc = jnp.exp(g_col)
    u = _mm(t, (v.astype(f32) * b_col).astype(cd), prec)
    w = _mm(t, (kb * e_gc).astype(cd), prec)
    att = (_mm_nt(q, k, prec) * decay).astype(cd)
    qg = (q.astype(f32) * e_gc).astype(cd)
    g_last = jnp.min(gc, axis=-1, keepdims=True)     # g <= 0: the last
    kd_t = jnp.swapaxes(k.astype(f32) * jnp.exp(g_last - g_col), 1, 2)
    return (u, jnp.concatenate([w.astype(cd), qg], axis=1),
            jnp.concatenate([att, kd_t.astype(cd)], axis=1))


def _carry(u, wq, ak, gc, s, prec):
    """The state half of a batch of chunks, one a head, on `_wy`'s three
    outputs, gc (B, 1, C) and s (B, dk, dv) float32: two products, the
    state's and then `v_new`'s, each over the whole batch. Returns (o
    (B, C, dv) float32, the states after the chunks)."""
    c, cd = u.shape[1], wq.dtype
    ws = _mm(wq, s.astype(cd), prec)                 # [w S; q e^gc S]
    av = _mm(ak, (u - ws[:, :c]).astype(cd), prec)   # on v_new
    g_last = jnp.min(gc, axis=-1, keepdims=True)
    return ws[:, c:] + av[:, :c], s * jnp.exp(g_last) + av[:, c:]


# ------------------------------------------------------------ the scan
#: the scan's scoped VMEM (the default scope is 16 MiB, which a float32
#: step of 16 heads at 128 x 128 outgrows; a v5e has 128 MiB); a step's
#: blocks and temporaries are held to three quarters of it
_VMEM_LIMIT = 32 << 20
#: heads a grid step runs side by side, each a chain of products
_HEADS = 16


def _vmem(rows: int, cols: int, dtype) -> int:
    """Bytes of a (rows, cols) block in VMEM, padded to whole tiles."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * item


def _fit(n: int, most: int) -> int:
    """The largest divisor of n that is at most `most` (and at least 1)."""
    b = max(1, min(n, most))
    while n % b:
        b -= 1
    return b


def _scan_kernel(q_ref, k_ref, v_ref, gb_ref, s0_ref, o_ref, s_ref, *,
                 prec):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = s0_ref[...]

    gb = gb_ref[:, 0]                                    # (hb, 2, C) f32
    u, wq, ak = _wy(q_ref[:, 0], k_ref[:, 0], v_ref[:, 0], gb[:, 0:1],
                    gb[:, 1:2], prec)
    o, s = _carry(u, wq, ak, gb[:, 0:1], s_ref[...], prec)
    o_ref[:, 0] = o.astype(o_ref.dtype)
    s_ref[...] = s                      # resident over the chunk axis


@partial(jax.jit, static_argnames=("chunk", "interpret", "kernel"))
def _gdn_scan(q, k, v, g, beta, s0, *, chunk, interpret, kernel):
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    n = t // chunk
    bh = b * h
    cd, f32 = q.dtype, jnp.float32
    s0 = s0.astype(f32).reshape(bh, dk, dv)
    gc = jnp.cumsum(g.astype(f32).reshape(bh, n, chunk), axis=-1)
    gb = jnp.stack([gc, beta.astype(f32).reshape(bh, n, chunk)],
                   axis=2)                               # (BH, N, 2, C)
    q, k, v = (a.reshape(bh, n, chunk, a.shape[-1]) for a in (q, k, v))
    prec = _prec(cd)
    if not kernel:
        # every chunk's state-free half at once, then the chunks in
        # order, each over every head
        flat = gb.reshape(bh * n, 2, chunk)
        u, wq, ak = (x.reshape((bh, n) + x.shape[1:]).swapaxes(0, 1)
                     for x in _wy(*(a.reshape((bh * n,) + a.shape[2:])
                                    for a in (q, k, v)),
                                  flat[:, 0:1], flat[:, 1:2], prec))

        def step(s, x):
            o, s = _carry(*x[:3], x[3][:, 0:1], s, prec)
            return s, o
        s, o = jax.lax.scan(step, s0, (u, wq, ak, gb.swapaxes(0, 1)))
        return (o.swapaxes(0, 1).astype(v.dtype).reshape(b, h, t, dv),
                s.reshape(b, h, dk, dv))
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # a head's blocks, each double-buffered: q, k, v and gb in, o out,
    # and the state in and out (resident over the chunks); then its
    # temporaries: the inverse's (C, C) terms, u, [w S; q e^gc S],
    # [att; k_d^T] v_new and the new state in float32, and T,
    # [w; q e^gc] and [att; k_d^T] in the call's type
    head = 2 * (2 * _vmem(chunk, dk, cd) + _vmem(chunk, dv, cd)
                + _vmem(2, chunk, f32) + _vmem(chunk, dv, v.dtype)
                + 2 * _vmem(dk, dv, f32)) \
        + 4 * _vmem(chunk, chunk, f32) + _vmem(chunk, dv, f32) \
        + _vmem(2 * chunk, dv, f32) + _vmem(chunk + dk, dv, f32) \
        + _vmem(dk, dv, f32) + _vmem(chunk, chunk, cd) \
        + _vmem(2 * chunk, dk, cd) + _vmem(chunk + dk, chunk, cd)
    hb = _fit(bh, min(_HEADS, _VMEM_LIMIT * 3 // 4 // head))

    def per_chunk(*tail):
        return pl.BlockSpec((hb, 1) + tail,
                            lambda i, c: (i, c) + (0,) * len(tail),
                            memory_space=pltpu.VMEM)

    def whole_state():
        return pl.BlockSpec((hb, dk, dv), lambda i, c: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    o, s = pl.pallas_call(
        partial(_scan_kernel, prec=prec),
        grid=(bh // hb, n),
        in_specs=[per_chunk(chunk, dk), per_chunk(chunk, dk),
                  per_chunk(chunk, dv), per_chunk(2, chunk), whole_state()],
        out_specs=(per_chunk(chunk, dv), whole_state()),
        out_shape=(jax.ShapeDtypeStruct((bh, n, chunk, dv), v.dtype),
                   jax.ShapeDtypeStruct((bh, dk, dv), f32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="gdn_scan",
    )(q, k, v, gb, s0)
    return o.reshape(b, h, t, dv), s.reshape(b, h, dk, dv)


def gdn_scan(q, k, v, g, beta, *, state=None, chunk: int = CHUNK,
             interpret: bool = False):
    """The recurrence over whole sequences: q, k (B, H, T, dk) and v
    (B, H, T, dv) in one type, g (log decay, <= 0) and beta (B, H, T)
    float32, T a multiple of `chunk`; `state` (B, H, dk, dv) the state
    before the first token (None: zero, the start of a sequence).
    Returns (o (B, H, T, dv) in v's type, the state after the last
    token (B, H, dk, dv) float32). Tokens with g = 0 and beta = 0 leave
    the state as it is (padding), so with a padded tail the state
    returned is the one after the last REAL token."""
    b, h, t, dk = q.shape
    if t % chunk:
        raise ValueError(f"{t} tokens are no whole chunks of {chunk}")
    if state is None:
        state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    return _gdn_scan(q, k, v, g, beta, state, chunk=int(chunk),
                     interpret=bool(interpret),
                     kernel=bool(interpret)
                     or jax.default_backend() == "tpu")


# ---------------------------------------------------------- the update
def _update_kernel(eg_ref, beta_ref, kq_ref, v_ref, s_in, o_ref, s_out, *,
                   heads: int, total: int):
    from jax.experimental import pallas as pl

    si, bi = pl.program_id(0), pl.program_id(1)
    kq = kq_ref[0, 0]                                  # (dk, 128)
    v = v_ref[0, 0]                                    # (heads, dv)
    lane = jax.lax.broadcasted_iota(jnp.int32, (kq.shape[1], v.shape[-1]),
                                    0)

    def column(j):
        """Lane j of `kq` on every lane, (dk, dv) float32: a product
        with a one-hot matrix, exact because one operand is 0 and 1 (a
        lane slice spread by broadcast took the kernel 1.17 ms a layer
        at 64 slots on a v5e, this 0.41)."""
        return _mm(kq, (lane == j).astype(kq.dtype), _prec(kq.dtype))

    for h in range(heads):
        at = si * total + bi * heads + h
        s = s_in[0, h] * eg_ref[at]                    # (dk, dv)
        k_col, q_col = column(h), column(heads + h)
        mem = jnp.sum(s * k_col, axis=0, keepdims=True)       # (1, dv)
        delta = (v[h:h + 1].astype(jnp.float32) - mem) * beta_ref[at]
        s = s + k_col * delta
        s_out[0, h] = s
        o_ref[0, 0, h:h + 1] = jnp.sum(s * q_col, axis=0, keepdims=True)


@partial(jax.jit, static_argnames=("interpret", "kernel"))
def _gdn_update(state, q, k, v, g, beta, *, interpret, kernel):
    n, h, dk, dv = state.shape
    f32 = jnp.float32
    cd = jnp.result_type(q.dtype, k.dtype)
    q, k, v32 = q.astype(f32), k.astype(f32), v.astype(f32)
    eg, beta = jnp.exp(g.astype(f32)), beta.astype(f32)
    if not kernel:
        s = state * eg[..., None, None]
        mem = jnp.einsum("nhkv,nhk->nhv", s, k, precision=_HIGHEST)
        delta = (v32 - mem) * beta[..., None]
        s = s + k[..., :, None] * delta[..., None, :]
        return jnp.einsum("nhkv,nhk->nhv", s, q, precision=_HIGHEST), s
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hb = _fit(h, 16)
    nb = h // hb
    # v and o go in and out as (N, blocks, hb, dv), a block spanning
    # the whole of its last two dimensions: hb need then be no multiple
    # of 8 sublanes (30 heads run as two blocks of 15)
    # positions along sublanes, heads along lanes: lane j < hb is head
    # j's k, lane hb + j its q, the rest of the 128 lanes zero
    kq = jnp.concatenate([k.reshape(n, nb, hb, dk),
                          q.reshape(n, nb, hb, dk)], axis=2)
    kq_t = jnp.pad(kq.transpose(0, 1, 3, 2),
                   ((0, 0), (0, 0), (0, 0), (0, 128 - 2 * hb))).astype(cd)
    o, state = pl.pallas_call(
        partial(_update_kernel, heads=hb, total=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n, nb),
            in_specs=[
                pl.BlockSpec((1, 1, dk, 128),
                             lambda s, b, *_: (s, b, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, hb, dv),
                             lambda s, b, *_: (s, b, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, hb, dk, dv),
                             lambda s, b, *_: (s, b, 0, 0),
                             memory_space=pltpu.VMEM)],
            out_specs=(
                pl.BlockSpec((1, 1, hb, dv),
                             lambda s, b, *_: (s, b, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, hb, dk, dv),
                             lambda s, b, *_: (s, b, 0, 0),
                             memory_space=pltpu.VMEM))),
        out_shape=(jax.ShapeDtypeStruct((n, nb, hb, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)),
        # operand 4 (after the two scalar operands, kq_t and v) is the
        # state: its buffer is the new state's
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_update",
    )(eg.reshape(-1), beta.reshape(-1), kq_t, v32.reshape(n, nb, hb, dv),
      state)
    return o.reshape(n, h, dv), state


def gdn_update(state, q, k, v, g, beta, *, interpret: bool = False):
    """One token a slot: state (N, H, dk, dv) float32, q and k (N, H,
    dk), v (N, H, dv), g and beta (N, H). Returns (o (N, H, dv)
    float32, the new state, which on a TPU is the old one's buffer: the
    caller donates it). g = 0 and beta = 0 leave a slot's state as it
    is, bit for bit (an idle slot)."""
    return _gdn_update(state, q, k, v, g, beta, interpret=bool(interpret),
                       kernel=bool(interpret)
                       or jax.default_backend() == "tpu")
