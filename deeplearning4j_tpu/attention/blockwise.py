"""Blockwise (flash-style) attention in pure JAX.

The online-softmax recurrence: carry (acc, row_max, row_sum) over KV
blocks; each block contributes exp(S - new_max) rescaled history. This is
the memory-efficient form XLA compiles into a scan whose working set is
one (Tq, block) tile instead of the full (Tq, Tk) score matrix.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def naive_attention(q, k, v, causal: bool = False, scale: Optional[float] = None):
    """Reference O(T^2)-memory attention (for tests and tiny inputs).
    Shapes: q (..., Tq, d), k/v (..., Tk, d)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d)
    s = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v)


def masked_attention(q, k, v, mask):
    """The caches' dense read: an exact masked softmax in f32 of q (B,
    Hq, Tq, hd) over every key of k, v (B, Hkv, Tk, hd) that `mask`
    (broadcastable to (B, Tq, Tk)) lets the query see; query head n
    reads K/V head n // (Hq / Hkv). Returns (B, Hq, Tq, hd) f32. A
    masked score underflows to exactly 0 in the softmax, so whatever
    lies in rows never written counts for exactly nothing; a query must
    see at least one key."""
    b, hq, tq, hd = q.shape
    hkv = k.shape[1]
    qg = q.reshape(b, hkv, hq // hkv, tq, hd)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (1.0 / jnp.sqrt(jnp.float32(hd)))
    s = jnp.where(jnp.asarray(mask)[..., None, None, :, :], s, NEG_INF)
    att = jnp.einsum("bhgqk,bhkd->bhgqd", jax.nn.softmax(s, axis=-1),
                     v.astype(jnp.float32))
    return att.reshape(b, hq, tq, hd)


@partial(jax.jit, static_argnames=("causal", "block_size", "q_offset",
                                   "k_offset", "return_lse", "window"))
def blockwise_attention(q, k, v, causal: bool = False,
                        block_size: int = 512,
                        q_offset: Optional[int] = None, k_offset: int = 0,
                        return_lse: bool = False,
                        window: Optional[int] = None):
    """Online-softmax attention over KV blocks.

    q: (..., Tq, d); k, v: (..., Tk, d). `q_offset`/`k_offset` are the
    global positions of the first query/key row, for callers passing
    sequence shards. Default alignment is BOTTOM-RIGHT (query i attends
    keys up to i + Tk - Tq — the KV-cache decode convention, matching
    `naive_attention`); pass q_offset explicitly for other geometries.
    Fully-masked query rows output zeros. `window=W` (with `causal`)
    narrows what query i sees to keys j with i - W < j <= i.

    `return_lse=True` additionally returns the per-row log-sum-exp of
    the scaled scores (natural log) — fully-masked rows get the +1e30
    sentinel the Pallas kernel emits — keeping the O(block) working set
    (the lse is read off the online-softmax carry, no score matrix).
    """
    orig_dtype = q.dtype
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    d = q.shape[-1]
    tq, tk = q.shape[-2], k.shape[-2]
    scale = 1.0 / jnp.sqrt(d)
    if q_offset is None:
        # bottom-right causal alignment (naive_attention's tril(k=tk-tq))
        q_offset = k_offset + tk - tq
    block = min(block_size, tk)
    n_blocks = (tk + block - 1) // block
    pad = n_blocks * block - tk
    if pad:
        kp = jnp.pad(k, [(0, 0)] * (k.ndim - 2) + [(0, pad), (0, 0)])
        vp = jnp.pad(v, [(0, 0)] * (v.ndim - 2) + [(0, pad), (0, 0)])
    else:
        kp, vp = k, v
    # (n_blocks, ..., block, d) leading scan axis
    kb = jnp.moveaxis(
        kp.reshape(*k.shape[:-2], n_blocks, block, d), -3, 0)
    vb = jnp.moveaxis(
        vp.reshape(*v.shape[:-2], n_blocks, block, d), -3, 0)

    q_pos = q_offset + jnp.arange(tq)

    def body(carry, inputs):
        acc, m, s = carry
        kb_i, vb_i, blk = inputs
        scores = jnp.einsum("...qd,...kd->...qk", q, kb_i) * scale
        k_pos = k_offset + blk * block + jnp.arange(block)
        valid = (k_pos < k_offset + tk)
        if causal:
            valid = valid[None, :] & (k_pos[None, :] <= q_pos[:, None])
            if window is not None:
                valid = valid & (k_pos[None, :] > q_pos[:, None] - window)
        else:
            valid = jnp.broadcast_to(valid[None, :],
                                     scores.shape[-2:])
        scores = jnp.where(valid, scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        # explicit valid multiply: when a row is FULLY masked, m_new stays
        # at the NEG_INF init and exp(scores - m_new) would be 1, silently
        # attending to every key — the mask zeroes those rows instead
        p = jnp.exp(scores - m_new[..., None]) * valid.astype(jnp.float32)
        s_new = s * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "...qk,...kd->...qd", p, vb_i)
        return (acc_new, m_new, s_new), None

    acc0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)
    s0 = jnp.zeros(q.shape[:-1], jnp.float32)
    (acc, m, s), _ = lax.scan(
        body, (acc0, m0, s0), (kb, vb, jnp.arange(n_blocks)))
    out = acc / jnp.maximum(s, 1e-30)[..., None]
    if not return_lse:
        return out.astype(orig_dtype)
    lse = jnp.where(s > 0.0, m + jnp.log(jnp.maximum(s, 1e-30)),
                    jnp.float32(-NEG_INF))
    return out.astype(orig_dtype), lse
