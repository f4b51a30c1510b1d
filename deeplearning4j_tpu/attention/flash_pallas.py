"""Flash attention as Pallas TPU kernels, forward and backward.

Forward: grid (batch*heads, Q tiles, KV blocks) — the TPU grid is
sequential over the last dimension, so the kernel streams (block_k, d)
K/V tiles through VMEM while float32 scratch accumulators carry the
online-softmax state (acc, m, s) across KV steps for the current Q tile;
the output tile (and the per-row log-sum-exp, saved for backward) is
finalized on the last KV step. Causal tiles entirely above the diagonal
are skipped (no MXU work).

Backward: the FlashAttention recompute strategy with the saved LSE —
P = exp(S − lse) is rebuilt tile-by-tile (never materializing the full
score matrix), D = rowsum(dO ∘ O) precomputed outside. Two kernels:
dQ iterates KV blocks per Q tile; dK/dV iterates Q tiles per KV block
(each with the matching causal skip).

All dots run with bf16 operands (f32 accumulation via
preferred_element_type) — the v5e MXU's native mode; softmax state is
f32 in base-2 (exp2). Causal masking only runs on diagonal-crossing
blocks; fully-visible blocks take a mask-free branch.

Falls back to `blockwise_attention` (forward AND backward) for
tile-indivisible shapes — by design, but silently: a caller that needs
the kernels checks the program (`chip_smoke.py`'s train leg does;
docs/SERVING.md lists which prefill buckets take which lane).
Interpret mode covers CPU tests on the same kernel code path; library
code never picks it from the platform.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.attention.blockwise import blockwise_attention

NEG_INF = -1e30
LANES = 128  # Mosaic-aligned trailing dim for row vectors (lse, D)


LOG2E = 1.4426950408889634   # softmax state is kept in base-2 (exp2)
LN2 = 0.6931471805599453     # converts base-2 LSE back to natural log


def _fit_tile(t: int, tile: int):
    """Largest 128-aligned divisor of t that is <= tile.

    Returns None when no such divisor exists (ragged t — caller falls
    back to blockwise). This keeps lengths like 768 or 1536 on the
    kernel with a smaller tile instead of silently demoting them to the
    fallback when they don't divide the default tile.

    Degenerate t == 1 (a decode-shaped single-row query) returns 1: the
    tile dim is a Mosaic SUBLANE dim, which pads 1 -> 8 internally, so
    a one-row tile is legal and costs one row of padding — not a full
    q_tile of it, and not a demotion to the dense fallback. Other
    sub-128 lengths still fall back (their padding story is unmeasured
    and the prefill buckets never produce them on the kernel path)."""
    for c in range(tile - tile % 128, 0, -128):
        if c <= t and t % c == 0:
            return c
    if t == 1:
        return 1
    return None


def _causal_branches(causal: bool, qi, ki, q_tile: int, block_k: int,
                     causal_offset: int):
    """(visible, diagonal) predicates for one grid step: `visible` =
    every element of this KV block is on or below the diagonal for every
    query of the tile (mask-free branch); `diagonal` = the block crosses
    the diagonal (iota/compare/where masking required). Blocks entirely
    above the diagonal fire neither branch — the causal skip."""
    if not causal:
        return jnp.asarray(True), jnp.asarray(False)
    skip = ki * block_k > (qi + 1) * q_tile - 1 + causal_offset
    diagonal = jnp.logical_and(
        jnp.logical_not(skip),
        ki * block_k + block_k - 1 > qi * q_tile + causal_offset)
    visible = jnp.logical_and(jnp.logical_not(skip),
                              jnp.logical_not(diagonal))
    return visible, diagonal


def _window_branches(qi, ki, q_tile: int, block_k: int,
                     causal_offset: int, window: int):
    """`_causal_branches` for a causal WINDOW: query i sees key j iff
    i - window < j <= i (its own position counts). A KV block wholly
    above the diagonal or wholly left of the tile's first query's
    window fires neither branch; one that every query of the tile sees
    whole takes the mask-free branch; the rest are masked."""
    q_lo = qi * q_tile + causal_offset
    q_hi = q_lo + q_tile - 1
    k_lo = ki * block_k
    k_hi = k_lo + block_k - 1
    skip = jnp.logical_or(k_lo > q_hi, k_hi < q_lo - window + 1)
    visible = jnp.logical_and(k_hi <= q_lo, k_lo >= q_hi - window + 1)
    masked = jnp.logical_and(jnp.logical_not(skip),
                             jnp.logical_not(visible))
    return visible, masked


def _kernel(q_ref, k_ref, v_ref, o_ref, *rest, causal: bool, q_tile: int,
            block_k: int, causal_offset: int, group: int, want_lse: bool,
            window=None):
    from jax.experimental import pallas as pl

    if want_lse:
        lse_ref, acc_ref, m_ref, s_ref = rest
    else:
        lse_ref = None
        acc_ref, m_ref, s_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)

    # causal semantics: bottom-right alignment — query i sees keys up to
    # i + causal_offset, causal_offset = Tk - Tq (matches blockwise;
    # fully-masked rows output 0 like blockwise, unlike naive's
    # mean-of-V). Blocks entirely BELOW the diagonal take the mask-free
    # branch: the per-block iota/compare/where VPU work only runs on
    # diagonal-crossing blocks.
    if window is None:
        visible, diagonal = _causal_branches(
            causal, qi, ki, q_tile, block_k, causal_offset)
    else:
        visible, diagonal = _window_branches(
            qi, ki, q_tile, block_k, causal_offset, window)

    def _tile_update(masked: bool):
        # operands stay in their storage dtype (bf16): the v5e MXU runs
        # bf16 matmuls at full rate with f32 accumulation
        # (preferred_element_type) — casting to f32 first quarters MXU
        # throughput. Softmax state is f32 throughout, kept in base-2
        # (scores pre-scaled by log2(e)/sqrt(d), exp2 instead of exp) so
        # the transcendental is a bare exp2 with no hidden multiply.
        # `group` batch rows (heads) are processed per grid step as a
        # batched dot, which halves the grid-step count (`_pick_group`).
        q = q_ref[...]  # (group, q_tile, d)
        k = k_ref[...]  # (group, block_k, d)
        v = v_ref[...]
        d = q.shape[-1]
        scale2 = jnp.float32(LOG2E) / jnp.float32(d) ** 0.5
        scores = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale2
        if masked:
            q_pos = qi * q_tile + jax.lax.broadcasted_iota(
                jnp.int32, (group, q_tile, block_k), 1)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (group, q_tile, block_k), 2)
            mask = k_pos <= q_pos + causal_offset
            if window is not None:
                mask = jnp.logical_and(
                    mask, k_pos > q_pos + causal_offset - window)
            scores = jnp.where(mask, scores, NEG_INF)
        m_prev, s_prev = m_ref[...], s_ref[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(scores - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)  # fully-masked rows: m_new=NEG_INF
        m_ref[...] = m_new
        s_ref[...] = s_prev * alpha + p.sum(axis=-1, keepdims=True)
        # P is cast to V's storage dtype for the second MXU dot (standard
        # flash formulation; accumulation stays f32 so the bf16 rounding
        # of P costs ~2^-8 relative — inside bf16 output tolerance)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(visible)
    def _compute_unmasked():
        _tile_update(masked=False)

    if causal:
        @pl.when(diagonal)
        def _compute_masked():
            _tile_update(masked=True)

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(s_ref[...], 1e-30)).astype(o_ref.dtype)
        if want_lse:
            # log-sum-exp per row, saved for the backward kernels
            # (FlashAttention's L = m + log s). Fully-masked rows (s == 0)
            # get a large sentinel so exp(S - lse) underflows to exactly
            # 0. Stored lane-broadcast (group, q_tile, LANES) — Mosaic
            # block shapes need a 128-divisible trailing dim.
            s = s_ref[...]
            # m is tracked in base-2 (see _tile_update); convert to the
            # natural-log LSE the backward kernels expect: ln2·m + ln(s)
            lse = jnp.where(s > 0.0,
                            jnp.float32(LN2) * m_ref[...]
                            + jnp.log(jnp.maximum(s, 1e-30)),
                            jnp.float32(-NEG_INF))  # (group, q_tile, 1)
            lse_ref[...] = jnp.broadcast_to(lse, (*lse.shape[:-1], LANES))


def _pick_group(b: int, d: int, itemsize: int, q_tile: int,
                block_k: int, want_lse: bool) -> int:
    """Batch rows (heads) per forward grid step: 2 when the batch
    divides and the step fits VMEM, else 1.

    A (2, tile, d) batched dot halves the grid-step count, amortizing
    the per-step overhead. The byte estimate below (f32 scores +
    double-buffered q/k/v/o blocks + f32 acc scratch + the lse output
    block on the vjp path) and its 11.5M threshold were set on an older
    toolchain, where d=64 bf16 1024x1024 with lse was refused at 17.71M
    against the 16M scoped-VMEM limit. With jax 0.9.0 / libtpu 0.0.34
    every combination of group 1|2, d 64|128, f32|bf16, with and
    without lse compiles for v5e at 1024x1024 tiles (PR 21), so the
    gate is now narrower than the compiler. It is kept as it was
    because which group is FASTER at d=128 or in f32 has not been
    measured (ROADMAP S5); widening it is that item's change, not a
    correctness one. At group=1 the forward compiles and matches
    blockwise on the chip at every prefill bucket 128..2048 and the
    backward at T=1024, d 64 and 128, f32 and bf16
    (tests/test_tpu_lane.py)."""
    if b % 2 or d > 64 or itemsize > 2:
        return 1
    scores = 2 * q_tile * block_k * 4
    io = 2 * 2 * (q_tile + 2 * block_k + q_tile) * d * itemsize
    acc = 2 * q_tile * d * 4
    lse = 2 * 2 * q_tile * LANES * 4 if want_lse else 0
    return 2 if scores + io + acc + lse <= 11.5 * 1024 * 1024 else 1


def _flash_forward(q, k, v, causal: bool, q_tile: int, block_k: int,
                   interpret: bool, want_lse: bool = True, window=None):
    """q (b, Tq, d); k, v (b // kv_rows, Tk, d): `kv_rows` successive
    rows of q (the query heads of one K/V head, heads being the minor
    part of the flattened batch) read one row of k and v. With as many
    rows as q and no window this is the program it always was."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t_q, d = q.shape
    t_k = k.shape[1]
    kv_rows = b // k.shape[0]
    group = 1 if kv_rows > 1 else _pick_group(
        b, d, q.dtype.itemsize, q_tile, block_k, want_lse)
    kv_map = ((lambda bi, qi, ki: (bi, ki, 0)) if kv_rows == 1
              else (lambda bi, qi, ki: (bi // kv_rows, ki, 0)))
    grid = (b // group, t_q // q_tile, t_k // block_k)
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [pl.BlockSpec((group, q_tile, d),
                              lambda bi, qi, ki: (bi, qi, 0),
                              memory_space=pltpu.VMEM)]
    if want_lse:
        out_shape.append(
            jax.ShapeDtypeStruct((b, t_q, LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((group, q_tile, LANES),
                                      lambda bi, qi, ki: (bi, qi, 0),
                                      memory_space=pltpu.VMEM))
    res = pl.pallas_call(
        partial(_kernel, causal=causal, q_tile=q_tile, block_k=block_k,
                causal_offset=t_k - t_q, group=group, want_lse=want_lse,
                window=window),
        out_shape=tuple(out_shape) if want_lse else out_shape[0],
        grid=grid,
        in_specs=[
            pl.BlockSpec((group, q_tile, d),
                         lambda bi, qi, ki: (bi, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((group, block_k, d), kv_map,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((group, block_k, d), kv_map,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=tuple(out_specs) if want_lse else out_specs[0],
        scratch_shapes=[
            pltpu.VMEM((group, q_tile, d), jnp.float32),   # acc
            pltpu.VMEM((group, q_tile, 1), jnp.float32),   # running max
            pltpu.VMEM((group, q_tile, 1), jnp.float32),   # running sum
        ],
        # batch and Q-tile grid dims carry no cross-step state, so
        # Mosaic may treat them as parallel; only the KV accumulation
        # dim is sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return res if want_lse else (res, None)


def _kv_expanded(q, k, v):
    """k and v with as many heads as q, for the blockwise fallback of a
    grouped call: query head n reads K/V head n // (Hq / Hkv)."""
    if q.shape[:-2] == k.shape[:-2]:
        return k, v
    rep = q.shape[-3] // k.shape[-3]
    return jnp.repeat(k, rep, axis=-3), jnp.repeat(v, rep, axis=-3)


def _check_grouped(q, k, causal: bool, window) -> bool:
    """True for a call with grouped K/V heads or a window (forward
    only); raises on shapes that are neither that nor today's."""
    grouped = q.shape[:-2] != k.shape[:-2]
    if grouped and (q.ndim < 3 or q.shape[:-3] != k.shape[:-3]
                    or q.shape[-3] % k.shape[-3]):
        raise ValueError(
            f"grouped K/V heads need q (..., Hq, T, d) and k/v (..., Hkv, "
            f"T, d) with Hkv dividing Hq, got {q.shape} and {k.shape}")
    if window is not None and (not causal or window < 1):
        raise ValueError("a window is causal and at least 1 key wide")
    return grouped or window is not None


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = False, q_tile: int = 1024,
                    block_k: int = 1024, interpret: bool = False,
                    window=None):
    """Pallas flash attention. q/k/v: (batch[*heads], T, d). Tile sizes
    fit to T (largest 128-aligned divisor <= the requested tile), so
    short or oddly-sized-but-aligned sequences stay on the kernel; T
    with no 128-aligned divisor falls back to blockwise. Set
    interpret=True off-TPU.

    The 1024 defaults were tuned at (4x8)x2048x64 bf16 causal in an
    earlier round, on a set-up that no longer exists: fewer, larger
    grid steps won there (q_tile 1024 + batch-pair grouping, see
    `_pick_group`). They have not been re-tuned at head_dim 128 or on
    the current machine (ROADMAP S5); what PR 21 established is that
    they compile and match blockwise there.

    NOTE: sequence length is axis -2 (NOT axis 1 — a 4-D (B, H, T, d)
    input's axis 1 is heads; reading it as T silently routed every 4-D
    call to the blockwise fallback).

    Grouped K/V heads: k/v may hold fewer heads than q, (..., Hkv, T, d)
    against (..., Hq, T, d); query head n reads K/V head n // (Hq /
    Hkv), and the kernel's K/V index map does that without a copy.
    `window=W` (causal only): query i sees key j iff i - W < j <= i;
    KV blocks wholly outside a tile's window are skipped like those
    above the diagonal. Both are forward only (no trainer uses them
    yet: differentiating raises). With neither, the call is what it
    always was. Heads of 256 (d 16..128 is what ran before PR 35) take
    the same tiles in bfloat16, 16 query heads over 2 K/V heads at 8,192
    positions in 5.4 ms on a v5e, and a query tile of 512 in float32."""
    _check_grouped(q, k, causal, window)
    t_q, t_k = q.shape[-2], k.shape[-2]
    if q.shape[-1] > 128 and jnp.dtype(q.dtype).itemsize >= 4:
        # heads of 256 in float32: at 1024 x 1024 the kernel's blocks
        # and scores take 16.77 MB of the 16 MB a kernel may use (the
        # chip refused it, PR 35); bfloat16 fits and keeps its tiles
        q_tile = min(q_tile, 512)
    # fit tiles: largest 128-aligned divisor <= the requested tile, so
    # e.g. T=768 runs the kernel at tile 384 instead of falling back;
    # truly ragged lengths go to the blockwise fallback
    q_tile = _fit_tile(t_q, q_tile)
    block_k = _fit_tile(t_k, block_k)
    if q_tile is None or block_k is None:
        return blockwise_attention(q, *_kv_expanded(q, k, v),
                                   causal=causal, window=window)
    # primal/inference path: no lse output — skips the extra output
    # block + finalize log, which is what lets batch-pair grouping fit
    # VMEM at the 1024x1024 tiles (the vjp fwd below pays for the lse)
    out, _ = _flash_forward(q.reshape(-1, t_q, q.shape[-1]),
                            k.reshape(-1, t_k, k.shape[-1]),
                            v.reshape(-1, t_k, v.shape[-1]),
                            causal, q_tile, block_k, interpret,
                            want_lse=False, window=window)
    return out.reshape(q.shape)


# ------------------------------------------------- a query offset a row
def _ctx_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, *rest, heads: int,
                **kw):
    """`_kernel` with the causal offset read from SMEM: row `bi` of the
    flattened (batch x heads) belongs to batch row `bi // heads`."""
    from jax.experimental import pallas as pl

    _kernel(q_ref, k_ref, v_ref, o_ref, *rest,
            causal_offset=off_ref[pl.program_id(0) // heads], **kw)


def flash_attention_ctx(q, k, v, offset, q_tile: int = 1024,
                        block_k: int = 1024, interpret: bool = False):
    """Causal attention of a piece of a sequence over everything up to
    it: q (B, Hq, Tq, d) are the queries at positions `offset[b] + i`,
    k and v (B, Hkv, Tk, d) hold the keys at positions 0..Tk-1 (what
    came before the piece AND the piece itself), query row i sees key
    j iff j <= offset[b] + i. `offset` (B,) int32 is traced: one
    program serves every context length, KV blocks wholly beyond a
    tile's last query are neither computed nor fetched (their block
    index is held at the last one needed, and a block that does not
    change is not copied again), so no (Tq, Tk) array of scores is ever
    built and a short context costs what it holds. Forward only.
    Shapes with no 128-aligned tile take the dense masked read."""
    from deeplearning4j_tpu.attention.blockwise import masked_attention

    _check_grouped(q, k, True, None)
    b, hq, t_q, d = q.shape
    hkv, t_k = k.shape[1], k.shape[2]
    offset = jnp.asarray(offset, jnp.int32)
    qt, bk = _fit_tile(t_q, q_tile), _fit_tile(t_k, block_k)
    if qt is None or bk is None or t_q == 1:
        seen = jnp.arange(t_k)[None, None, :] <= (
            offset[:, None, None] + jnp.arange(t_q)[None, :, None])
        return masked_attention(q, k, v, seen).astype(q.dtype)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kv_rows = hq // hkv

    def q_map(bi, qi, ki, off):
        return bi, qi, 0

    def kv_map(bi, qi, ki, off):
        last = ((qi + 1) * qt - 1 + off[bi // hq]) // bk
        return bi // kv_rows, jnp.minimum(ki, last), 0

    out = pl.pallas_call(
        partial(_ctx_kernel, heads=hq, causal=True, q_tile=qt,
                block_k=bk, group=1, want_lse=False),
        out_shape=jax.ShapeDtypeStruct((b * hq, t_q, d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b * hq, t_q // qt, t_k // bk),
            in_specs=[
                pl.BlockSpec((1, qt, d), q_map, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bk, d), kv_map, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bk, d), kv_map, memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((1, qt, d), q_map,
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((1, qt, d), jnp.float32),
                            pltpu.VMEM((1, qt, 1), jnp.float32),
                            pltpu.VMEM((1, qt, 1), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="prefill_ctx_flash",
    )(offset, q.reshape(b * hq, t_q, d), k.reshape(b * hkv, t_k, d),
      v.reshape(b * hkv, t_k, d))
    return out.reshape(q.shape)


# --------------------------------------------------------------- backward
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref,
                   dq_acc, *, causal: bool, q_tile: int, block_k: int,
                   causal_offset: int):
    """dQ: grid (b, Tq/q_tile, Tk/block_k); accumulate over KV blocks.
    dS = P ∘ (dP − D); dQ = dS @ K · scale  (FlashAttention bwd, with
    P recomputed from the saved row log-sum-exp)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    visible, diagonal = _causal_branches(
        causal, qi, ki, q_tile, block_k, causal_offset)

    def _tile_update(masked: bool):
        # bf16 MXU operands with f32 accumulation, like the forward;
        # P recomputed in base-2 from the saved natural-log LSE. As in
        # the forward, the iota/compare/where masking only runs on
        # diagonal-crossing blocks.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]        # (q_tile,) lane-broadcast store
        dd = dd_ref[0][:, 0]          # (q_tile,) rowsum(dO ∘ O)
        d = q.shape[-1]
        scale = 1.0 / jnp.float32(d) ** 0.5
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (scale * jnp.float32(LOG2E))
        if masked:
            q_pos = qi * q_tile + jax.lax.broadcasted_iota(
                jnp.int32, (q_tile, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (q_tile, block_k), 1)
            s = jnp.where(k_pos <= q_pos + causal_offset, s, NEG_INF)
        p = jnp.exp2(s - (lse * jnp.float32(LOG2E))[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd[:, None])
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(visible)
    def _compute_unmasked():
        _tile_update(masked=False)

    if causal:
        @pl.when(diagonal)
        def _compute_masked():
            _tile_update(masked=True)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool,
                    q_tile: int, block_k: int, causal_offset: int):
    """dK/dV: grid (b, Tk/block_k, Tq/q_tile); accumulate over Q tiles.
    dV = Pᵀ @ dO; dK = dSᵀ @ Q · scale."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    visible, diagonal = _causal_branches(
        causal, qi, ki, q_tile, block_k, causal_offset)

    def _tile_update(masked: bool):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]
        dd = dd_ref[0][:, 0]
        d = q.shape[-1]
        scale = 1.0 / jnp.float32(d) ** 0.5
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (scale * jnp.float32(LOG2E))
        if masked:
            q_pos = qi * q_tile + jax.lax.broadcasted_iota(
                jnp.int32, (q_tile, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (q_tile, block_k), 1)
            s = jnp.where(k_pos <= q_pos + causal_offset, s, NEG_INF)
        p = jnp.exp2(s - (lse * jnp.float32(LOG2E))[:, None])
        pb = p.astype(do.dtype)                      # (q_tile, block_k)
        dv_acc[...] += jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd[:, None])
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(visible)
    def _compute_unmasked():
        _tile_update(masked=False)

    if causal:
        @pl.when(diagonal)
        def _compute_masked():
            _tile_update(masked=True)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal: bool, q_tile: int,
                    block_k: int, interpret: bool, lse_grad=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t_q, d = q.shape
    t_k = k.shape[1]
    dd = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                 axis=-1)  # (b, t_q): rowsum(dO ∘ O)
    if lse_grad is not None:
        # joint (out, lse) cotangent: d lse/d s_j = p_j, so the lse
        # term enters ds = p*(dp - dd) as a -g_lse shift of dd
        dd = dd - lse_grad.astype(jnp.float32)
    dd = jnp.broadcast_to(dd[..., None], (*dd.shape, LANES))

    q_spec = pl.BlockSpec((1, q_tile, d), memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, block_k, d), memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, q_tile, LANES), memory_space=pltpu.VMEM)

    def at(index_map, spec):
        return pl.BlockSpec(spec.block_shape, index_map,
                            memory_space=pltpu.VMEM)

    common = dict(causal=causal, q_tile=q_tile, block_k=block_k,
                  causal_offset=t_k - t_q)

    dq = pl.pallas_call(
        partial(_bwd_dq_kernel, **common),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(b, t_q // q_tile, t_k // block_k),
        in_specs=[
            at(lambda bi, qi, ki: (bi, qi, 0), q_spec),    # q
            at(lambda bi, qi, ki: (bi, ki, 0), k_spec),    # k
            at(lambda bi, qi, ki: (bi, ki, 0), k_spec),    # v
            at(lambda bi, qi, ki: (bi, qi, 0), q_spec),    # dO
            at(lambda bi, qi, ki: (bi, qi, 0), row_spec),  # lse
            at(lambda bi, qi, ki: (bi, qi, 0), row_spec),  # D
        ],
        out_specs=at(lambda bi, qi, ki: (bi, qi, 0), q_spec),
        scratch_shapes=[pltpu.VMEM((q_tile, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, g, lse, dd)

    dk, dv = pl.pallas_call(
        partial(_bwd_dkv_kernel, **common),
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid=(b, t_k // block_k, t_q // q_tile),
        in_specs=[
            at(lambda bi, ki, qi: (bi, qi, 0), q_spec),    # q
            at(lambda bi, ki, qi: (bi, ki, 0), k_spec),    # k
            at(lambda bi, ki, qi: (bi, ki, 0), k_spec),    # v
            at(lambda bi, ki, qi: (bi, qi, 0), q_spec),    # dO
            at(lambda bi, ki, qi: (bi, qi, 0), row_spec),  # lse
            at(lambda bi, ki, qi: (bi, qi, 0), row_spec),  # D
        ],
        out_specs=(at(lambda bi, ki, qi: (bi, ki, 0), k_spec),
                   at(lambda bi, ki, qi: (bi, ki, 0), k_spec)),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, g, lse, dd)
    return dq, dk, dv


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_with_lse(q, k, v, causal: bool = False,
                             q_tile: int = 1024, block_k: int = 1024,
                             interpret: bool = False):
    """Flash attention returning (out, lse) — lse[i] = log sum_j
    exp(s_ij) per query row (natural log, scaled scores). The building
    block for cross-shard softmax combines (ring attention's per-step
    merge, flash-decoding style splits): partial results from disjoint
    KV shards merge exactly via
    m = max(lse_a, lse_b); out = (exp(lse_a-m) out_a + exp(lse_b-m)
    out_b) / (exp(lse_a-m) + exp(lse_b-m)).

    CAVEAT: a query row that sees NO keys in its shard (causal split
    where the whole shard is in the row's future) gets out = 0 and
    lse = +1e30 — a sentinel, NOT the -inf merge identity. Substitute
    lse = -inf (and out = 0) for such shards before merging, as
    attention/ring.py's `future` branch does.

    Differentiable jointly in (out, lse): d lse / d s_j = p_j, so the
    lse cotangent folds into the existing backward as
    ds = p * (dp - (rowsum(dO*O) - g_lse)) — i.e. the dd term passed to
    the dQ/dKV kernels is shifted by -g_lse and nothing else changes.
    """
    t_q, t_k = q.shape[-2], k.shape[-2]
    qt = _fit_tile(t_q, q_tile)
    bk = _fit_tile(t_k, block_k)
    if qt is None or bk is None:
        return _blockwise_with_lse(q, k, v, causal)
    out3, lse3 = _flash_forward(q.reshape(-1, t_q, q.shape[-1]),
                                k.reshape(-1, t_k, k.shape[-1]),
                                v.reshape(-1, t_k, v.shape[-1]),
                                causal, qt, bk, interpret, want_lse=True)
    return (out3.reshape(q.shape),
            lse3[..., 0].reshape(*q.shape[:-1]))


def _blockwise_with_lse(q, k, v, causal):
    """Fallback (out, lse) for kernel-ineligible shapes: the online
    blockwise scan with its carry's lse read off — O(block) working
    set, same +1e30 sentinel for fully-masked rows as the kernel."""
    return blockwise_attention(q, k, v, causal=causal, return_lse=True)


def _fwd_with_lse(q, k, v, causal, q_tile, block_k, interpret):
    out, lse = flash_attention_with_lse(q, k, v, causal, q_tile,
                                        block_k, interpret)
    if (_fit_tile(q.shape[-2], q_tile) is None
            or _fit_tile(k.shape[-2], block_k) is None):
        # blockwise-fallback shapes: the backward re-derives everything
        # via jax.vjp — don't hold the (out, lse) activations alive
        return (out, lse), (q, k, v, None, None)
    return (out, lse), (q, k, v, out, lse)


def _bwd_with_lse(causal, q_tile, block_k, interpret, res, g):
    g_out, g_lse = g
    q, k, v, out, lse = res
    t_q, t_k = q.shape[-2], k.shape[-2]
    qt = _fit_tile(t_q, min(q_tile, 512))
    bk = _fit_tile(t_k, block_k)
    if out is None or qt is None or bk is None:
        # shapes that fell back in the forward differentiate the
        # blockwise form (including the lse output)
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _blockwise_with_lse(q_, k_, v_, causal),
            q, k, v)
        return vjp((g_out, g_lse))
    out3 = out.reshape(-1, t_q, q.shape[-1])
    lse3 = jnp.broadcast_to(
        lse.reshape(-1, t_q)[..., None], (*lse.reshape(-1, t_q).shape,
                                          LANES))
    dq, dk, dv = _flash_backward(
        q.reshape(-1, t_q, q.shape[-1]), k.reshape(-1, t_k, k.shape[-1]),
        v.reshape(-1, t_k, v.shape[-1]), out3, lse3,
        g_out.reshape(-1, t_q, q.shape[-1]), causal, qt, bk, interpret,
        lse_grad=g_lse.reshape(-1, t_q))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


flash_attention_with_lse.defvjp(_fwd_with_lse, _bwd_with_lse)


def _fwd(q, k, v, causal, q_tile, block_k, interpret, window=None):
    if _check_grouped(q, k, causal, window):
        raise NotImplementedError(
            "flash_attention with grouped K/V heads or a window has no "
            "backward: no trainer for such a block is written yet")
    t_q, t_k = q.shape[-2], k.shape[-2]
    qt = _fit_tile(t_q, q_tile)
    bk = _fit_tile(t_k, block_k)
    if qt is None or bk is None:
        # ragged: forward used the blockwise fallback — backward must too
        out = blockwise_attention(q, k, v, causal=causal)
        return out, (q, k, v, None, None)
    out3, lse = _flash_forward(q.reshape(-1, t_q, q.shape[-1]),
                               k.reshape(-1, t_k, k.shape[-1]),
                               v.reshape(-1, t_k, v.shape[-1]),
                               causal, qt, bk, interpret)
    return out3.reshape(q.shape), (q, k, v, out3, lse)


def _bwd(causal, q_tile, block_k, interpret, window, res, g):
    q, k, v, out3, lse = res
    if out3 is None:
        # blockwise-fallback forward: differentiate the blockwise form
        _, vjp = jax.vjp(
            lambda q_, k_, v_: blockwise_attention(q_, k_, v_,
                                                   causal=causal),
            q, k, v)
        return vjp(g)
    t_q, t_k = q.shape[-2], k.shape[-2]
    # the backward kernels keep four (q_tile, block_k) f32 values live
    # at once (s, p, dp, ds) — cap q_tile at 512 so they fit the ~16 MB
    # scoped VMEM budget even when the forward ran at 1024
    qt = _fit_tile(t_q, min(q_tile, 512))
    bk = _fit_tile(t_k, block_k)
    dq, dk, dv = _flash_backward(
        q.reshape(-1, t_q, q.shape[-1]), k.reshape(-1, t_k, k.shape[-1]),
        v.reshape(-1, t_k, v.shape[-1]), out3,
        lse, g.reshape(-1, t_q, q.shape[-1]), causal, qt, bk, interpret)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


flash_attention.defvjp(_fwd, _bwd)
