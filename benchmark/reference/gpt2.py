"""Plain float32 reference of the GPT-2 block as the program runs it.

Pre-LayerNorm residual blocks, multi-head causal attention, learned
positions, tanh-form GELU, output head tied to the embedding: forward,
loss and gradients in straightforward `jax.numpy`, float32, every
product at `Precision.HIGHEST`, no kernel, no cache, no batching. Two
departures from the published block, both the program's and noted in the
configuration files: no bias on the four attention projections, and the
tanh form of GELU for both models.

It imports nothing from `deeplearning4j_tpu` and is handed nothing the
program made: the weights come from `benchmark.weights` and the seed.
Its entries (`logits`, `loss_and_grad`, `init_state`, `update`) take
the configuration file, as `benchmark/families/__init__.py` says.

`mode` lowers the precision for the control that has to FAIL the
comparison (`benchmark/check.py`): "f32" is the reference; "bf16"
rounds every operand of every product to bfloat16, "fp8" to
float8_e4m3fn under a per-row scale (the step below bfloat16 that would
tempt a later PR).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-5
MODES = ("f32", "bf16", "fp8")


def to_bf16(a):
    """Round float32 to the values bfloat16 holds. `reduce_precision`,
    not a pair of casts: on a TPU the compiler may drop a cast down and
    back up (excess precision is allowed by default), and the rounding
    is the point."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _round(a, mode: str):
    """`a` as the lower precision would hold it, in float32; gradients
    pass straight through the rounding."""
    if mode == "f32":
        return a
    if mode == "bf16":
        low = to_bf16(a)
    elif mode == "fp8":
        # an 8-bit float (4 exponent bits, 3 of mantissa; as
        # `reduce_precision` rounds it the largest finite value is 240)
        # under a scale per row
        top = jnp.max(jnp.abs(a), axis=-1, keepdims=True)
        scale = jnp.where(top > 0, top / 240.0, 1.0)
        low = jax.lax.reduce_precision(
            a / scale, exponent_bits=4, mantissa_bits=3) * scale
    else:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    return a + jax.lax.stop_gradient(low - a)


def _mm(a, b, mode: str):
    """a @ b; a weight `b` is scaled per output column."""
    if mode != "f32":
        a = _round(a, mode)
        b = jnp.swapaxes(_round(jnp.swapaxes(b, -1, -2), mode), -1, -2)
    return jnp.matmul(a, b, precision=HIGHEST)


def layer_norm(p, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * p["g"] + p["b"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(p, x, n_heads: int, mode: str = "f32"):
    """One residual block on x (B, T, d), float32 throughout."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    b, t, d = x.shape
    hd = d // n_heads
    h = layer_norm(p["ln1"], x)

    def heads(w):
        return _mm(h, w, mode).reshape(b, t, n_heads, hd).transpose(
            0, 2, 1, 3)

    q, k, v = heads(p["Wq"]), heads(p["Wk"]), heads(p["Wv"])
    s = jnp.einsum("bhqd,bhkd->bhqk", _round(q, mode), _round(k, mode),
                   precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    att = jnp.einsum("bhqk,bhkd->bhqd", _round(w, mode),
                     jnp.swapaxes(_round(jnp.swapaxes(v, -1, -2), mode),
                                  -1, -2), precision=HIGHEST)
    att = att.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + _mm(att, p["Wo"], mode)
    h = layer_norm(p["ln2"], x)
    x = x + _mm(gelu_tanh(_mm(h, p["W1"], mode) + p["b1"]), p["W2"],
                mode) + p["b2"]
    return x


_block_jit = jax.jit(block, static_argnames=("n_heads", "mode"))


@jax.jit
def _embed(embed, pos, tokens):
    t = tokens.shape[1]
    return (embed[tokens].astype(jnp.float32)
            + pos[:t].astype(jnp.float32))


@partial(jax.jit, static_argnames=("mode",))
def _head(ln_f, embed, x, mode: str = "f32"):
    ln_f = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), ln_f)
    return _mm(layer_norm(ln_f, x), embed.astype(jnp.float32).T, mode)


def logits(config: dict, params, tokens, first: int, last: int,
           mode: str = "f32"):
    """Logits (B, last - first, V) of positions first..last-1 of
    `tokens` (B, T), layer by layer so that one block's float32 copy
    lives at a time. `config` is the configuration file."""
    n_heads = int(config["n_head"])
    x = _embed(params["embed"], params["pos"], tokens)
    for p in params["blocks"]:
        x = _block_jit(p, x, n_heads=n_heads, mode=mode)
    return _head(params["ln_f"], params["embed"], x[:, first:last],
                 mode=mode)


# ---------------------------------------------------------------- training
def loss_sum(params, tokens, n_heads: int, mode: str = "f32"):
    """SUM of next-token cross entropies over a block of rows (B, T+1);
    the mean is taken by the caller over all rows of the step. Each
    block is recomputed in the backward pass, so a block of rows of the
    real size fits beside the float32 weights."""
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    t = inp.shape[1]
    x = (params["embed"][inp].astype(jnp.float32)
         + params["pos"][:t].astype(jnp.float32))
    # one scanned, recomputed block: the program stays a layer long,
    # compiles in seconds and fits JAX's compile cache
    blk = jax.checkpoint(partial(block, n_heads=n_heads, mode=mode))
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                     *params["blocks"])
    x, _ = jax.lax.scan(lambda h, p: (blk(p, h), None), x, stacked)
    ln_f = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  params["ln_f"])
    lg = _mm(layer_norm(ln_f, x),
             params["embed"].astype(jnp.float32).T, mode)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tgt[..., None], -1))


_loss_grad = jax.jit(jax.value_and_grad(loss_sum),
                     static_argnames=("n_heads", "mode"))


def loss_and_grad(config: dict, params, tokens, rows_per_block: int,
                  mode: str = "f32", rows=None):
    """Mean loss over the step's rows and its gradient, in blocks of
    rows. `rows` picks a subset (the half-batch fault of the tests and
    the calibration); the mean is over the rows taken."""
    n_heads = int(config["n_head"])
    if rows is not None:
        tokens = tokens[rows]
    n_rows, width = tokens.shape
    count = n_rows * (width - 1)
    total, grads = 0.0, None
    for lo in range(0, n_rows, rows_per_block):
        val, g = _loss_grad(params, tokens[lo:lo + rows_per_block],
                            n_heads=n_heads, mode=mode)
        total = total + val
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    scale = 1.0 / count
    return total * scale, jax.tree_util.tree_map(
        lambda a: a * scale, grads)


def init_state(params):
    """The optimizer's state before the first step: a velocity of 0."""
    return jax.tree_util.tree_map(jnp.zeros_like, params)


def update(config: dict, params, state, grads):
    """One step of the optimizer the configuration's `training` section
    states; returns (params, state)."""
    tr = config["training"]
    return _sgd_momentum(params, state, grads, float(tr["lr"]),
                         float(tr["momentum"]), store=config["dtype"])


@partial(jax.jit, static_argnames=("store",), donate_argnums=(0, 1))
def _sgd_momentum(params, velocity, grads, lr: float, momentum: float,
                  store: str):
    """v <- m v + g in float32; p <- p - lr v, rounded to the type the
    configuration stores parameters in (`store`: "bfloat16" or
    "float32"), held here as float32 values of that type."""
    keep = to_bf16 if store == "bfloat16" else (lambda a: a)
    velocity = jax.tree_util.tree_map(
        lambda v, g: momentum * v + g, velocity, grads)
    params = jax.tree_util.tree_map(
        lambda p, v: keep(p - lr * v), params, velocity)
    return params, velocity
