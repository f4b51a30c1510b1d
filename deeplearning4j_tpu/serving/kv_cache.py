"""Preallocated KV cache: O(1)-per-token decode.

The demo `transformer.generate` recomputes the full prefix every token —
O(T) attention AND O(T) ffn/embedding work per emitted token. Serving
needs the standard two-phase shape (the "portable O(1) autoregressive
caching" design in PAPERS.md), here as the model's one forward
(`models.model_of(cfg)`) under two `attend` callbacks:

- **prefill**: one pass over the prompt (flash attention, the uncached
  forward's own read) whose callback also writes every block's K/V into
  a preallocated `(B, H, max_len, hd)` buffer;
- **decode**: one token per step — the callback writes the new
  position's k/v at the cursor (`dynamic_update_slice`) and attends over
  the buffer with a `position <= cursor` mask (`masked_attention`, the
  dense read every cache shares). Per-token work no longer grows with
  the number of generated tokens' recompute.

Shapes are fixed by `cfg.max_len`, so the whole generate loop (prefill +
`lax.scan` of decode steps) is ONE compiled program per
(batch, prompt_len, n_tokens) signature — the cursor is a traced scalar,
never a shape. Parity: `generate(cache=True)` matches the naive path to
1e-5 (tests/test_serving.py) because both run the same block; the only
difference is exact masked softmax here vs online softmax there.

Memory envelope: 2 (K and V) * n_layers * B * max_len * n_kv_heads *
head_dim elements per cache — `kv_cache_bytes` computes it;
docs/SERVING.md budgets it. The paged cache (`paged_kv.py` the pool,
`paged_kinds.py` its device side) holds pages for the tokens actually
written instead.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.attention.blockwise import masked_attention
from deeplearning4j_tpu.models import model_of
from deeplearning4j_tpu.models.transformer import (causal_attention,
                                                   visible)

__all__ = ["KVCache", "init_cache", "kv_cache_bytes", "prefill",
           "decode_step", "generate_cached"]


class KVCache(NamedTuple):
    """Per-block K/V buffers plus the write cursor.

    `layers`: tuple (one per transformer block) of {"k", "v"} arrays of
    shape (B, n_kv_heads, max_len, head_dim); positions >= `cursor` are
    unwritten zeros, masked out of every attention sweep.
    """

    layers: Tuple[Any, ...]
    cursor: jax.Array  # int32 scalar: number of filled positions


def _check_cache_args(batch_size: int, length, max_len: int) -> int:
    """Shared validation: `length=None` means the full window; an
    EXPLICIT length=0 (or negative) is rejected — the old `length or
    max_len` idiom silently allocated the full window for it, which is
    never what a caller asking for a 0-length cache meant."""
    if batch_size < 1:
        raise ValueError(f"batch_size must be >= 1, got {batch_size}")
    if length is None:
        return max_len
    if length < 1:
        raise ValueError(
            f"length must be >= 1, got {length} (omit it or pass None "
            f"for the full max_len window)")
    return length


def init_cache(cfg, batch_size: int, length: int = None) -> KVCache:
    """Empty cache for `batch_size` streams. `length` defaults to
    cfg.max_len — always allocating the full window keeps decode-step
    shapes identical across requests (one program, any prompt)."""
    length = _check_cache_args(batch_size, length, cfg.max_len)
    shape = (batch_size, cfg.n_kv_heads, length, cfg.head_dim)
    layers = tuple({"k": jnp.zeros(shape, cfg.dtype),
                    "v": jnp.zeros(shape, cfg.dtype)}
                   for _ in range(cfg.n_layers))
    return KVCache(layers, jnp.int32(0))


def kv_cache_bytes(cfg, batch_size: int, length: int = None) -> int:
    """HBM the cache pins per batch — the serving memory envelope for
    the contiguous path (the paged pool's twin is
    `paged_kv.paged_kv_bytes`, which budgets pages, not requests)."""
    length = _check_cache_args(batch_size, length, cfg.max_len)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return (2 * cfg.n_layers * batch_size * length
            * cfg.n_kv_heads * cfg.head_dim * itemsize)


def _written(held, k, v, at):
    """A layer's buffers with rows k, v (B, H, T, hd) written from
    position `at` on."""
    return {name: jax.lax.dynamic_update_slice(
        held[name], rows.astype(held[name].dtype), (0, 0, at, 0))
        for name, rows in (("k", k), ("v", v))}


def prefill(params, tokens, cache: KVCache, cfg):
    """Run the prompt (B, T0) through every block, writing K/V into the
    cache at positions [0, T0). Returns (last-position logits (B, vocab),
    cache with cursor=T0). Starts a fresh stream: any prior cache content
    is overwritten from position 0."""
    model = model_of(cfg)
    t0 = tokens.shape[1]

    def attend(layer, kind, q, k, v):
        return (causal_attention(cfg, kind, q, k, v),
                _written(cache.layers[layer], k, v, 0))

    x, layers, _ = model.forward(params, tokens, jnp.arange(t0), cfg,
                                 attend)
    return model.head(params, x[:, -1, :], cfg), KVCache(layers,
                                                         jnp.int32(t0))


def decode_step(params, token, cache: KVCache, cfg):
    """One decode step: embed `token` (B,) at position `cache.cursor`,
    attend over the cache, return (logits (B, vocab), advanced cache).
    Fixed shapes throughout — the cursor is traced, so every step of
    every request shares one compiled program."""
    model = model_of(cfg)
    cur = cache.cursor
    k_pos = jnp.arange(cache.layers[0]["k"].shape[2])

    def attend(layer, kind, q, k, v):
        # the write comes first: the cursor's own key is among the seen
        new = _written(cache.layers[layer], k, v, cur)
        att = masked_attention(q, new["k"], new["v"],
                               visible(cfg, kind, cur[None], k_pos))
        return att, new

    x, layers, _ = model.forward(params, token[:, None], cur[None], cfg,
                                 attend)
    return model.head(params, x[:, 0, :], cfg), KVCache(layers, cur + 1)


@partial(jax.jit, static_argnums=(2, 3))
def generate_cached(params, prompt, cfg, n_tokens: int):
    """Greedy decode with the KV cache: prompt (B, T0) ->
    (B, T0 + n_tokens), same contract (and same tokens, to decode-order
    tie-breaks) as the naive `transformer.generate`. One compiled
    program per (B, T0, n_tokens) signature; the decode loop is a
    `lax.scan` whose body is a single O(1) step."""
    b, t0 = prompt.shape
    # shapes and n_tokens are static here, so these guard EVERY entry
    # point (engine.generate, HTTP /generate) at trace time — without
    # them an overlong decode would silently clamp the cursor into the
    # last KV slot and emit garbage
    if n_tokens < 1:
        raise ValueError(f"n_tokens must be >= 1, got {n_tokens}")
    if t0 + n_tokens > cfg.max_len:
        raise ValueError(
            f"generation would exceed max_len ({t0} prompt + {n_tokens} "
            f"new > {cfg.max_len})")
    cache = init_cache(cfg, b)
    logits, cache = prefill(params, prompt, cache, cfg)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # token at t0

    def step(carry, _):
        cache, tok = carry
        logits, cache = decode_step(params, tok, cache, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (cache, nxt), tok

    if n_tokens == 1:
        gen = first[:, None]
    else:
        (_, last), emitted = jax.lax.scan(
            step, (cache, first), None, length=n_tokens - 1)
        gen = jnp.concatenate(
            [jnp.moveaxis(emitted, 0, 1), last[:, None]], axis=1)
    return jnp.concatenate([prompt, gen], axis=1)
