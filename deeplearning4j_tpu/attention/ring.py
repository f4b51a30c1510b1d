"""Ring attention: sequence-parallel attention over a mesh axis.

Each device holds one sequence shard of Q, K, V. K/V shards rotate
around the ring via `lax.ppermute` (nearest-neighbor ICI exchange —
bandwidth-optimal, overlappable); every device keeps the online-softmax
running state for ITS queries and folds in each visiting K/V block.
After n_devices steps every query has attended to every key. Causal
masking uses global offsets derived from the device's ring position, so
a causal ring skips nothing but masks exactly.

This is the TPU-native equivalent of Ring Attention (Liu et al.) /
context parallelism: sequence length scales linearly with the number of
devices at constant per-device memory.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map as _shard_map

from deeplearning4j_tpu.attention.blockwise import NEG_INF


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """Per-device body (inside shard_map). q/k/v: (..., T_local, d)."""
    my_idx = lax.axis_index(axis_name)
    n_dev = lax.axis_size(axis_name)   # static: the ring size
    t_local = q.shape[-2]
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(d)
    orig_dtype = q.dtype
    q32 = q.astype(jnp.float32)

    q_pos = my_idx * t_local + jnp.arange(t_local)

    def accumulate(acc, m, s, k_cur, v_cur, step):
        src_idx = (my_idx - step) % n_dev  # whose shard we hold this step
        scores = jnp.einsum(
            "...qd,...kd->...qk", q32, k_cur.astype(jnp.float32)) * scale
        if causal:
            k_pos = src_idx * t_local + jnp.arange(t_local)
            mask = k_pos[None, :] <= q_pos[:, None]
            scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        # same sentinel guards as the flash merge below: a row that has
        # seen only masked keys keeps m == m_new == NEG_INF, where the
        # unguarded exp()s read as 1 — correct today only because step 0
        # folds the (never fully masked) diagonal shard first
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        p = jnp.exp(scores - m_new[..., None])
        if causal:
            p = p * mask.astype(jnp.float32)
        s_new = s * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "...qk,...kd->...qd", p, v_cur.astype(jnp.float32))
        return acc_new, m_new, s_new

    def fold(carry, step):
        acc, m, s, k_cur, v_cur = carry
        acc, m, s = accumulate(acc, m, s, k_cur, v_cur, step)
        # rotate K/V to the next device (ring neighbor exchange over ICI)
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (acc, m, s, k_next, v_next), None

    # constant-initialized carries must carry the same device-varying axes
    # as the scanned k/v (jax vma rules). Deriving them from q32 inherits
    # the right axis set whatever the in_specs shard over (sp alone, or
    # dp x sp when batch_axis is set); XLA folds the dummy arithmetic.
    acc0 = q32 * 0.0
    row = jnp.sum(q32, axis=-1) * 0.0
    m0 = row + NEG_INF
    s0 = row
    # n_dev - 1 fold+rotate steps, then the LAST visiting shard is
    # consumed without rotating it onward — the final ppermute's output
    # was a discarded scan carry (one wasted shard-sized ICI exchange
    # of both K and V per call, plus its transpose under grad)
    (acc, m, s, k_last, v_last), _ = lax.scan(
        fold, (acc0, m0, s0, k, v), jnp.arange(n_dev - 1))
    acc, m, s = accumulate(acc, m, s, k_last, v_last,
                           jnp.asarray(n_dev - 1))
    out = acc / jnp.maximum(s, 1e-30)[..., None]
    return out.astype(orig_dtype)


def _ring_attention_local_flash(q, k, v, axis_name: str, causal: bool,
                                interpret: bool):
    """Per-device ring body with the Pallas flash kernel computing each
    visiting shard's local attention on the MXU (bf16 operands, f32
    state), merged across ring steps in log-space via the kernel's
    saved per-row lse:
        m' = max(m, lse_i); acc' = acc·e^(m-m') + out_i·e^(lse_i-m');
        s' = s·e^(m-m') + e^(lse_i-m');   out = acc/s.
    Visiting shards entirely in the causal past take the mask-free
    kernel; the self shard takes the causal kernel; future shards
    contribute nothing (their branch returns the -inf lse identity) —
    the same visible/diagonal/skip trichotomy the kernel applies to its
    own KV blocks, lifted to ring-shard granularity. Gradients flow
    through the joint (out, lse) custom vjp (the lse cotangent is a dd
    shift in the backward kernels — flash_pallas.py)."""
    from deeplearning4j_tpu.attention.flash_pallas import (
        flash_attention_with_lse)

    my_idx = lax.axis_index(axis_name)
    n_dev = lax.axis_size(axis_name)
    orig_dtype = q.dtype

    def local(k_cur, v_cur, is_causal):
        out, lse = flash_attention_with_lse(
            q, k_cur, v_cur, is_causal, interpret=interpret)
        return out.astype(jnp.float32), lse

    def accumulate(acc, m, s, k_cur, v_cur, step):
        src_idx = (my_idx - step) % n_dev

        def past(_):      # src < my: every key visible, mask-free kernel
            return local(k_cur, v_cur, False)

        def diag(_):      # src == my: standard causal within the shard
            return local(k_cur, v_cur, True)

        def future(_):    # src > my: fully masked — the merge identity
            z = jnp.zeros(q.shape, jnp.float32)
            return z, jnp.full(q.shape[:-1], NEG_INF, jnp.float32)

        if causal:
            out_i, lse_i = lax.cond(
                src_idx == my_idx, diag,
                lambda _: lax.cond(src_idx < my_idx, past, future, None),
                None)
        else:
            out_i, lse_i = past(None)
        m_new = jnp.maximum(m, lse_i)
        # explicit sentinel guards: a fully-masked shard's lse identity
        # (-1e30) merged while the carry m is still at its -1e30 init
        # would give exp(0) = 1, silently inflating the denominator.
        # Folding the diagonal shard first happens to avoid that, but
        # correctness must not depend on fold order — map the sentinel
        # to an exact 0 contribution on both sides of the merge.
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        beta = jnp.where(lse_i <= NEG_INF / 2, 0.0, jnp.exp(lse_i - m_new))
        return (acc * alpha[..., None] + out_i * beta[..., None],
                m_new, s * alpha + beta)

    def fold(carry, step):
        acc, m, s, k_cur, v_cur = carry
        acc, m, s = accumulate(acc, m, s, k_cur, v_cur, step)
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        return (acc, m, s,
                lax.ppermute(k_cur, axis_name, perm),
                lax.ppermute(v_cur, axis_name, perm)), None

    acc0 = q.astype(jnp.float32) * 0.0
    row = jnp.sum(q.astype(jnp.float32), axis=-1) * 0.0
    m0 = row + NEG_INF
    s0 = row
    # as in the einsum body: the last shard is consumed un-rotated
    (acc, m, s, k_last, v_last), _ = lax.scan(
        fold, (acc0, m0, s0, k, v), jnp.arange(n_dev - 1))
    acc, m, s = accumulate(acc, m, s, k_last, v_last,
                           jnp.asarray(n_dev - 1))
    out = acc / jnp.maximum(s, 1e-30)[..., None]
    return out.astype(orig_dtype)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = False, batch_axis: Optional[str] = None,
                   local: str = "einsum", interpret: bool = False):
    """Full attention with Q/K/V sequence-sharded over `axis`.

    q, k, v: (batch, T, d) global arrays (T divisible by the axis size).
    Returns (batch, T, d), sequence-sharded the same way. Each ring step
    processes one visiting shard (per-device shards are already
    block-sized — the ring IS the blocking).

    `local` selects the per-step local-attention engine: 'einsum' (f32
    einsums + explicit online softmax — runs anywhere) or 'flash' (the
    Pallas flash kernel per visiting shard with log-space lse merging —
    the MXU path for real TPU pods; set interpret=True off-TPU).

    `batch_axis` additionally shards the batch dimension over a second
    mesh axis — the dp×sp composition (each data-parallel replica group
    runs its own ring over the `axis` dimension of the mesh).
    """
    n_dev = mesh.shape[axis]
    t = q.shape[-2]
    if t % n_dev:
        raise ValueError(f"sequence length {t} not divisible by mesh "
                         f"axis {axis!r} size {n_dev}")
    if batch_axis is not None and q.shape[0] % mesh.shape[batch_axis]:
        raise ValueError(f"batch {q.shape[0]} not divisible by mesh "
                         f"axis {batch_axis!r} size {mesh.shape[batch_axis]}")
    if local == "flash":
        body = partial(_ring_attention_local_flash, axis_name=axis,
                       causal=causal, interpret=interpret)
    elif local == "einsum":
        body = partial(_ring_attention_local, axis_name=axis,
                       causal=causal)
    else:
        raise ValueError(f"unknown local engine {local!r}; "
                         "expected 'einsum' or 'flash'")

    spec = P(batch_axis, axis, None)
    fn = _shard_map(
        body,
        mesh=mesh,
        in_specs=(spec,) * 3,
        out_specs=spec,
        # pallas_call's out_shape structs carry no vma annotations, so
        # shard_map's varying-axes checker can't type the flash engine
        check_vma=local != "flash",
    )
    with mesh:
        return fn(q, k, v)
