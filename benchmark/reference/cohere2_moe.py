"""Plain float32 reference of the `cohere2_moe` block, told which experts
and vocabulary rows the configuration holds.

The equations (T tokens, d hidden, Hq query heads over Hkv K/V heads of
hd, window W, E experts of which k are chosen, f an expert's width):

    h = (x - mean x) / sqrt(var x + eps) * g              (no bias)
    q = h Wq (T, Hq, hd); k = h Wk, v = h Wv (T, Hkv, hd)  (no bias)
    window layer: q, k rotated pairwise (elements 2i, 2i+1 by the angle
        pos * theta^(-2i/hd)); key j visible to query i iff
        i - W < j <= i.  full layer: no rotation, j <= i
    query head n reads K/V head n // (Hq / Hkv)
    attn = softmax(q k^T / sqrt(hd)) v Wo
    s = sigmoid(h Wr) over E; I = the k largest; w_e = s_e / sum_I s
    F(h) = (silu(h G) * (h U)) D
    routed_here = sum over e in I that are HELD of w_e F_e(h)
    shared = 1/n_shared sum_j Fs_j(h)
    x' = x + attn + routed_here + shared       (one h for all three)
    logits = logit_scale * LN_f(x) Emb^T       over the rows held

in straightforward `jax.numpy`, float32, every product at
`Precision.HIGHEST`, no kernel, no cache, no grouped product: each held
expert is a plain product over the rows that chose it (the rows are
picked on the host; dense under a mask would be `held` times the
operations). The partial sum over the held experts is what goes on to
the next layer, as in the program: nothing stands in for the other
chips of the deployment.

Computed in blocks (one K/V head's query heads and a block of query
rows at a time, one expert's weights raised to float32 at a time) so
that a sequence of 8,192 fits beside the bfloat16 weights.

It imports nothing from `deeplearning4j_tpu` and is handed nothing the
program made. `mode` lowers the precision for the control that has to
FAIL the comparison: "bf16" rounds every operand of every product to
bfloat16, "fp8" to float8_e4m3 under a per-row scale.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "bf16", "fp8")
Q_ROWS = 1024        # query rows of one attention block
EXPERT_ROWS = 256    # an expert's rows are padded to a multiple of this


def _round(a, mode: str):
    """`a` as the lower precision would hold it, in float32."""
    if mode == "f32":
        return a
    if mode == "bf16":
        return jax.lax.reduce_precision(a, exponent_bits=8,
                                        mantissa_bits=7)
    if mode == "fp8":
        top = jnp.max(jnp.abs(a), axis=-1, keepdims=True)
        scale = jnp.where(top > 0, top / 240.0, 1.0)
        return jax.lax.reduce_precision(
            a / scale, exponent_bits=4, mantissa_bits=3) * scale
    raise ValueError(f"mode must be one of {MODES}, got {mode!r}")


def _mm(a, b, mode: str):
    """a @ b; a weight `b` is scaled per output column."""
    if mode != "f32":
        a = _round(a, mode)
        b = jnp.swapaxes(_round(jnp.swapaxes(b, -1, -2), mode), -1, -2)
    return jnp.matmul(a, b, precision=HIGHEST)


def gain_norm(g, x, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g.astype(jnp.float32)


def rotate_pairs(x, positions, theta: float):
    """x (T, H, hd): elements 2i and 2i+1 of every head turn by
    pos * theta^(-2i/hd) (rope_gptj)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None, None] * inv  # (T,1,hd/2)
    a, b = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def what_is_held(config: dict) -> dict:
    """The sizes and the share, from the configuration file."""
    kinds = tuple(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers disagree")
    return {"Hq": int(config["num_attention_heads"]),
            "Hkv": int(config["num_key_value_heads"]),
            "hd": int(config["head_dim"]),
            "W": int(config["sliding_window"]),
            "theta": float(config["rope_theta"]),
            "eps": float(config["layer_norm_eps"]),
            "k": int(config["num_experts_per_tok"]),
            "held": int(config["num_experts"]),
            "held_first": int(config["held_experts_first"]),
            "E": int(config["router_width"]),
            "kinds": kinds,
            "logit_scale": float(config["logit_scale"])}


@partial(jax.jit, static_argnames=("hq", "hkv", "hd", "windowed", "window",
                                   "theta", "eps", "mode"))
def _attention(p, x, *, hq, hkv, hd, windowed, window, theta, eps, mode):
    """(h, attn) of one layer on x (T, d)."""
    t = x.shape[0]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = gain_norm(p["ln"]["g"], x, eps)
    q = _mm(h, f32(p["Wq"]), mode).reshape(t, hq, hd)
    k = _mm(h, f32(p["Wk"]), mode).reshape(t, hkv, hd)
    v = _mm(h, f32(p["Wv"]), mode).reshape(t, hkv, hd)
    pos = jnp.arange(t)
    if windowed:
        q = rotate_pairs(q, pos, theta)
        k = rotate_pairs(k, pos, theta)
    group = hq // hkv
    rows = min(Q_ROWS, t)
    while t % rows:
        rows //= 2
    # (Hkv, group, T, hd) queries against (Hkv, T, hd) keys
    qh = q.reshape(t, hkv, group, hd).transpose(1, 2, 0, 3)
    kh, vh = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def one_head(args):
        qg, kk, vv = args                      # (group, T, hd), (T, hd)
        kk_r = _round(kk, mode)
        vv_r = jnp.swapaxes(_round(jnp.swapaxes(vv, -1, -2), mode), -1, -2)

        def one_block(i):
            qb = jax.lax.dynamic_slice_in_dim(qg, i * rows, rows, axis=1)
            s = jnp.einsum("gqd,kd->gqk", _round(qb, mode), kk_r,
                           precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
            qi = i * rows + jnp.arange(rows)[:, None]
            kj = jnp.arange(t)[None, :]
            seen = kj <= qi
            if windowed:
                seen = seen & (kj > qi - window)
            s = jnp.where(seen[None], s, -jnp.inf)
            w = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("gqk,kd->gqd", _round(w, mode), vv_r,
                              precision=HIGHEST)

        out = jax.lax.map(one_block, jnp.arange(t // rows))
        return out.transpose(1, 0, 2, 3).reshape(group, t, hd)

    att = jax.lax.map(one_head, (qh, kh, vh))    # (Hkv, group, T, hd)
    att = att.transpose(2, 0, 1, 3).reshape(t, hq * hd)
    return h, _mm(att, f32(p["Wo"]), mode)


@partial(jax.jit, static_argnames=("k", "mode"))
def _route(router, h, *, k, mode):
    """(chosen (T, k) of all E, weights (T, k) normalised over the k)."""
    s = jax.nn.sigmoid(_mm(h, router.astype(jnp.float32), mode))
    top, chosen = jax.lax.top_k(s, k)
    return chosen, top / jnp.sum(top, axis=-1, keepdims=True)


@partial(jax.jit, static_argnames=("mode",))
def _expert(gate, up, down, x, mode):
    """F(x) = (silu(x G) * (x U)) D of one expert on rows x."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    act = jax.nn.silu(_mm(x, f32(gate), mode)) * _mm(x, f32(up), mode)
    return _mm(act, f32(down), mode)


@jax.jit
def _add_rows(acc, rows, weights, y):
    return acc.at[rows].add(weights[:, None] * y)


def _experts(p, h, held: dict, mode: str):
    """routed_here + shared on h (T, d)."""
    chosen, weights = _route(p["router"], h, k=held["k"], mode=mode)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    out = jnp.zeros_like(h)
    ex = p["experts"]
    for e in range(held["held"]):
        rows, col = np.nonzero(chosen == held["held_first"] + e)
        if not rows.size:
            continue
        n = -(-rows.size // EXPERT_ROWS) * EXPERT_ROWS
        pad_rows = np.zeros((n,), np.int32)
        pad_rows[:rows.size] = rows
        pad_w = np.zeros((n,), np.float32)
        pad_w[:rows.size] = weights[rows, col]     # padding adds 0 * F
        y = _expert(ex["gate"][e], ex["up"][e], ex["down"][e],
                    h[jnp.asarray(pad_rows)], mode)
        out = _add_rows(out, jnp.asarray(pad_rows), jnp.asarray(pad_w), y)
    sh = p["shared"]
    n_shared = sh["gate"].shape[0]
    for j in range(n_shared):
        out = out + _expert(sh["gate"][j], sh["up"][j], sh["down"][j], h,
                            mode) / n_shared
    return out


@partial(jax.jit, static_argnames=("eps", "scale", "mode"))
def _head(g, embed, x, *, eps, scale, mode):
    return scale * _mm(gain_norm(g, x, eps), embed.astype(jnp.float32).T,
                       mode)


def logits(config: dict, params, tokens, first: int, last: int,
           mode: str = "f32"):
    """Logits (B, last - first, V) of positions first..last-1 of
    `tokens` (B, T), over the vocabulary rows held; row by row and layer
    by layer. `config` is the configuration file: it says which experts
    and rows are held."""
    held = what_is_held(config)
    out = []
    for row in range(tokens.shape[0]):
        x = params["embed"][tokens[row]].astype(jnp.float32)
        for p, kind in zip(params["blocks"], held["kinds"]):
            h, attn = _attention(
                p, x, hq=held["Hq"], hkv=held["Hkv"], hd=held["hd"],
                windowed=kind == "sliding_attention", window=held["W"],
                theta=held["theta"], eps=held["eps"], mode=mode)
            x = x + attn + _experts(p, h, held, mode)
        out.append(_head(params["ln_f"]["g"], params["embed"],
                         x[first:last], eps=held["eps"],
                         scale=held["logit_scale"], mode=mode))
    return jnp.stack(out)


def _no_trainer(*_a, **_k):
    raise NotImplementedError(
        "the cohere2_moe family trains nothing: no trainer for the "
        "expert layer is written (25 GB at the cell's cut), so the "
        "reference has no loss, gradient or update either")


loss_and_grad = init_state = update = _no_trainer
