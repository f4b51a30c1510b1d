"""Causal transformer language model — beyond parity.

The reference (2014-era) predates transformers; this is the flagship
model family demonstrating the framework's pieces composing TPU-first:
the Pallas flash kernel for attention (128-aligned T and d_head >= 64
take the MXU path; other shapes fall back to blockwise automatically),
pre-LN residual blocks, one jitted + donated train step, whole-epoch
`lax.scan` training, and mesh-shardable parameters (every leaf carries
a leading- or trailing-dim structure the tp/dp shardings in
`parallel/` understand; see tests for a dp equivalence check).

Functional style (params pytree + pure apply) rather than the
MultiLayerNetwork builder: sequence models with weight tying and
per-block structure fit JAX's transform-first idiom, the same split the
LSTM module made (models/lstm.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from deeplearning4j_tpu.attention.flash_pallas import flash_attention

#: the kinds of layer a cache can be asked to hold: a "full" layer keeps
#: every key of a sequence, a "window" layer only ever reads the last
#: `cfg.window` (models/moe_transformer.py has both; this model is all
#: full)
KIND_FULL = "full"
KIND_WINDOW = "window"
KINDS = (KIND_FULL, KIND_WINDOW)
#: a layer that keeps no keys at all: a recurrent state and a few
#: columns a SEQUENCE, whatever its length (models/hybrid_transformer.py).
#: Not among `KINDS`, which are the kinds of PAGE a cache hands out
KIND_LINEAR = "linear"
#: a layer that keeps only the last few columns of a gated short
#: convolution a SEQUENCE (models/hybrid_transformer.py)
KIND_CONV = "conv"
#: the kinds a cache holds by SLOT, no pages: each names its own arrays
#: (`cfg.slot_state[kind]`)
SLOT_KINDS = (KIND_LINEAR, KIND_CONV)

#: `attend(layer, kind, q, k, v) -> (att, the cache's new state for this
#: layer)`: the one thing a block leaves to its caller. q is (B, Hq, T,
#: hd) and k, v are (B, Hkv, T, hd), heads before positions, the order
#: the kernels and the caches hold, for every model of this package. The
#: callback writes the K/V rows where its cache wants them and returns
#: what each query reads of what is visible to it, (B, Hq, T, hd). The
#: uncached forward, the contiguous cache (serving/kv_cache.py) and the
#: paged cache (serving/paged_kinds.py) are a model's one block under
#: their callbacks. A `KIND_LINEAR` layer has no K/V rows: it hands the
#: callback its pre-convolution columns (B, T, C), its two gates and the
#: convolution's weights in the three places, and gets its mixer's rows
#: (B, T, Hv, dv) and the layer's new cache entry back; a `KIND_CONV`
#: layer hands it the columns (B, T, d), None and the convolution's
#: weights, and gets the convolved columns (B, T, d) and its entry back.
Attend = Callable[[int, str, Any, Any, Any], Tuple[Any, Any]]


class TransformerConfig(NamedTuple):
    vocab_size: int
    d_model: int = 128
    n_heads: int = 2
    n_layers: int = 2
    d_ff: int = 512
    max_len: int = 256
    dtype: Any = jnp.float32
    #: interpret-mode pallas for CPU tests; ignored by the fallback
    interpret: bool = False

    # what a cache asks of any model's description, derived here: every
    # layer keeps all keys, a K/V head a query head, no expert layer
    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def n_kv_heads(self) -> int:
        return self.n_heads

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return (KIND_FULL,) * self.n_layers

    @property
    def window(self) -> None:
        return None

    @property
    def n_held(self) -> int:
        return 0


def init_transformer_params(key, cfg: TransformerConfig) -> Dict[str, Any]:
    """Embedding (tied with the output head), learned positions, and
    per-block {ln1, attn(Wq/Wk/Wv/Wo), ln2, ffn(W1/b1/W2/b2)}."""
    d, f = cfg.d_model, cfg.d_ff
    if d % cfg.n_heads:
        raise ValueError(f"d_model {d} not divisible by n_heads "
                         f"{cfg.n_heads}")
    keys = jax.random.split(key, 2 + 5 * cfg.n_layers)
    s = 0.02
    params: Dict[str, Any] = {
        "embed": s * jax.random.normal(keys[0], (cfg.vocab_size, d),
                                       cfg.dtype),
        "pos": s * jax.random.normal(keys[1], (cfg.max_len, d), cfg.dtype),
        "ln_f": {"g": jnp.ones((d,), cfg.dtype),
                 "b": jnp.zeros((d,), cfg.dtype)},
        "blocks": [],
    }
    for i in range(cfg.n_layers):
        k = keys[2 + 5 * i: 7 + 5 * i]
        params["blocks"].append({
            "ln1": {"g": jnp.ones((d,), cfg.dtype),
                    "b": jnp.zeros((d,), cfg.dtype)},
            "Wq": s * jax.random.normal(k[0], (d, d), cfg.dtype),
            "Wk": s * jax.random.normal(k[1], (d, d), cfg.dtype),
            "Wv": s * jax.random.normal(k[2], (d, d), cfg.dtype),
            "Wo": s * jax.random.normal(k[3], (d, d), cfg.dtype),
            "ln2": {"g": jnp.ones((d,), cfg.dtype),
                    "b": jnp.zeros((d,), cfg.dtype)},
            "W1": s * jax.random.normal(k[4], (d, f), cfg.dtype),
            "b1": jnp.zeros((f,), cfg.dtype),
            "W2": s * jax.random.normal(jax.random.fold_in(k[4], 1),
                                        (f, d), cfg.dtype),
            "b2": jnp.zeros((d,), cfg.dtype),
        })
    return params


def _layer_norm(p, x, eps=1e-5):
    # statistics in f32 even under bf16 params: bf16 mean/var over
    # d_model values is ~0.8%-noisy normalization every block
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return (((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
            * p["g"] + p["b"])


def _project(h, w):
    """h (B, T, d) @ w (d, n). Where a row holds one position (T == 1, a
    decode step) the product is fixed row-major before the caller splits
    it into heads: left free, the compiler meets the heads-major layout
    the attention reads by re-laying out the WEIGHT, a whole matrix
    copied a layer a step, instead of the few rows of the product
    (PERF.md section 6). Longer rows compile as they always have."""
    y = h @ w
    if h.shape[1] == 1:
        y = with_layout_constraint(
            y, Layout(major_to_minor=tuple(range(y.ndim))))
    return y


def causal_attention(cfg, kind: str, q, k, v):
    """Whole rows at once, nothing cached: q (B, Hq, T, hd) over k, v
    (B, Hkv, T, hd), causal, windowed in a window layer. The flash
    kernel (grouped heads, window; its custom vjp is the backward)
    where T is tile-aligned."""
    return flash_attention(
        q, k, v, True, interpret=cfg.interpret,
        window=cfg.window if kind == KIND_WINDOW else None)


def visible(cfg, kind: str, q_pos, k_pos):
    """The same rule as a mask (..., Tq, Tk) for the caches' dense
    reads: the query at q_pos (..., Tq) sees the key at k_pos (..., Tk)
    iff k_pos <= q_pos and, in a window layer, q_pos - window < k_pos
    (the query's own position counts among the `window`)."""
    q_pos, k_pos = q_pos[..., :, None], k_pos[..., None, :]
    seen = k_pos <= q_pos
    if kind == KIND_WINDOW:
        seen = seen & (k_pos > q_pos - cfg.window)
    return seen


def block(p, x, cfg: TransformerConfig, attend: Attend, layer: int):
    """One pre-LN block on x (B, T, d): the only place its mathematics
    is written. Returns (x', the cache's new state for this layer)."""
    b, t, d = x.shape
    h = _layer_norm(p["ln1"], x)

    def heads(w):
        return _project(h, w).reshape(b, t, cfg.n_heads,
                                      cfg.head_dim).transpose(0, 2, 1, 3)

    att, state = attend(layer, KIND_FULL, heads(p["Wq"]), heads(p["Wk"]),
                        heads(p["Wv"]))
    att = att.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + att @ p["Wo"]
    h = _layer_norm(p["ln2"], x)
    x = x + jax.nn.gelu(h @ p["W1"] + p["b1"]) @ p["W2"] + p["b2"]
    return x, state


def forward(params, tokens, positions, cfg: TransformerConfig,
            attend: Attend, valid=None):
    """Every block over tokens (B, T). `positions` (B, T), or (T,) where
    every row stands at the same ones, index the learned table, clamped
    to it (a bucket of padding may overshoot `max_len`); None is 0..T-1
    as a slice of the table, the training step's form. `valid` is taken
    for the models that count their tokens and is not read. Returns
    (hidden (B, T, d) before the final norm, the cache states a layer,
    () for what an expert layer would count)."""
    x = params["embed"][tokens]
    if positions is None:
        x = x + params["pos"][:tokens.shape[1]]
    else:
        x = x + params["pos"][jnp.minimum(positions, cfg.max_len - 1)]
    states = []
    for i, p in enumerate(params["blocks"]):
        x, state = block(p, x, cfg, attend, i)
        states.append(state)
    return x, tuple(states), ()


def head(params, x, cfg: TransformerConfig):
    """x (..., d) -> logits over the vocabulary; the head is tied to
    the embedding."""
    return _layer_norm(params["ln_f"], x) @ params["embed"].T


def transformer_logits(params, tokens, cfg: TransformerConfig):
    """tokens: (B, T) int32 -> (B, T, vocab) logits, nothing cached."""
    t = tokens.shape[1]
    if t > cfg.max_len:
        raise ValueError(f"sequence {t} exceeds max_len {cfg.max_len}")
    x, _, _ = forward(
        params, tokens, None, cfg,
        lambda _l, kind, q, k, v: (causal_attention(cfg, kind, q, k, v),
                                   None))
    return head(params, x, cfg)


def lm_loss(params, tokens, cfg: TransformerConfig):
    """Next-token cross entropy, mean over (B, T-1) positions."""
    logits = transformer_logits(params, tokens[:, :-1], cfg)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    return jnp.mean(nll)


def _sgd_momentum_update(params, velocity, grads, lr, momentum=0.9):
    """The one update rule both training entry points share."""
    velocity = jax.tree_util.tree_map(
        lambda v, g: momentum * v + g, velocity, grads)
    params = jax.tree_util.tree_map(
        lambda p, v: p - lr * v.astype(p.dtype), params, velocity)
    return params, velocity


def make_train_step(cfg: TransformerConfig, lr: float = 1e-2):
    """One jitted SGD+momentum step on the LM loss; params and momentum
    are donated (outputs alias their HBM)."""

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, velocity, tokens):
        loss, grads = jax.value_and_grad(lm_loss)(params, tokens, cfg)
        params, velocity = _sgd_momentum_update(params, velocity, grads,
                                                lr)
        return params, velocity, loss

    return step


def init_velocity(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros_like(p, jnp.float32), params)


def fit_scan(params, tokens_batches, cfg: TransformerConfig,
             lr: float = 1e-2, epochs: int = 1):
    """Whole-epoch training as ONE compiled program (the fit_scan idiom:
    minibatches on a leading scan axis, zero per-step host dispatch).
    tokens_batches: (n_batches, B, T). Returns (params, last loss)."""

    @partial(jax.jit, donate_argnums=(0, 1), static_argnums=(3,))
    def run(params, velocity, batches, n_epochs):
        def one(carry, batch):
            params, velocity = carry
            loss, grads = jax.value_and_grad(lm_loss)(params, batch, cfg)
            params, velocity = _sgd_momentum_update(params, velocity,
                                                    grads, lr)
            return (params, velocity), loss

        def epoch(carry, _):
            carry, losses = jax.lax.scan(one, carry, batches)
            return carry, losses[-1]

        (params, velocity), last = jax.lax.scan(
            epoch, (params, velocity), None, length=n_epochs)
        return params, last[-1]

    return run(params, init_velocity(params), tokens_batches, int(epochs))


def generate(params, prompt, cfg: TransformerConfig, n_tokens: int,
             cache: bool = False):
    """Greedy decoding: prompt (B, T0) -> (B, T0 + n_tokens).

    `cache=True` routes through the preallocated KV cache
    (serving/kv_cache.py): prefill once, then O(1) decode steps inside
    one compiled scan — the serving path, parity-tested against the
    naive form below. `cache=False` keeps the full-recompute demo form
    (every step re-runs the whole prefix)."""
    b, t0 = prompt.shape
    if t0 + n_tokens > cfg.max_len:
        raise ValueError("generation would exceed max_len")
    if cache:
        if n_tokens < 1:
            raise ValueError(f"n_tokens must be >= 1, got {n_tokens}")
        # deferred import: serving builds on this module
        from deeplearning4j_tpu.serving.kv_cache import generate_cached
        return generate_cached(params, jnp.asarray(prompt, jnp.int32),
                               cfg, int(n_tokens))
    buf = jnp.zeros((b, t0 + n_tokens), jnp.int32).at[:, :t0].set(prompt)

    def step(buf, i):
        logits = transformer_logits(params, buf[:, :cfg.max_len], cfg)
        # next token = argmax at position t0 + i - 1
        nxt = jnp.argmax(
            jax.lax.dynamic_index_in_dim(logits, t0 + i - 1, axis=1,
                                         keepdims=False), axis=-1)
        return buf.at[:, t0 + i].set(nxt.astype(jnp.int32)), None

    # full-recompute over fixed-shape buffer keeps shapes static; pad
    # positions beyond the frontier influence nothing (causal mask)
    buf, _ = jax.lax.scan(step, buf, jnp.arange(n_tokens))
    return buf


__all__ = ["TransformerConfig", "KINDS", "KIND_FULL", "KIND_WINDOW",
           "KIND_LINEAR", "KIND_CONV", "SLOT_KINDS",
           "init_transformer_params", "causal_attention", "visible",
           "block", "forward", "head", "transformer_logits", "lm_loss",
           "make_train_step", "init_velocity", "fit_scan", "generate"]
