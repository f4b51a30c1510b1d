"""Plain float32 reference of the `lfm2_moe` block.

The equations (T tokens, d hidden; RMSNorm `n(x) = x / sqrt(mean(x^2) +
eps) * g`, g the multiplier):

    x = x + mixer(n_op(x));  x = x + ff(n_ff(x))      every layer
    logits = n_f(x) E^T                               (head tied to E)
    layer l's mixer is layer_types[l]: "conv" or "full_attention"

    conv mixer (conv_L_cache = K = 3 taps, no bias):
        [b | c | x~] = h W_in                three blocks of d
        u_t = b_t * x~_t
        z_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t by channel,
            u = 0 before the sequence
        out = (c * z) W_out                  no activation anywhere

    attention mixer (Hq heads over Hkv K/V heads of hd = d / Hq):
        q = n_q(h Wq), k = n_k(h Wk) over each head; v = h Wv
        every pair (i, i + hd/2) of q and k turns by pos * theta^(-2i/hd)
        query head n reads K/V head n // (Hq / Hkv); causal
        out = softmax(q k^T / sqrt(hd)) v Wo

    ff of layer l < num_dense_layers: (silu(h G) * (h U)) D, width
        intermediate_size
    ff of the later layers: s = sigmoid(h Wr) over E experts;
        I = the k largest of s + expert_bias (the bias steers the
        choice only); w_e = s_e / (sum_I s + 1e-6) * routed_scaling_factor
        ff = sum over e in I of w_e F_e(h), F_e = (silu(h G_e) * (h U_e)) D_e

in straightforward `jax.numpy`, float32, every product at
`Precision.HIGHEST`, no kernel, no cache, no grouped product: the
convolution is the three-tap sum above, each expert a plain product over
the rows that chose it (picked on the host). Every expert is held.

Computed in blocks (one K/V head's query heads and a block of query
rows at a time; one layer's weights, and in the expert layer one
expert's, raised to float32 at a time; the head a block of rows at a
time) so that a sequence of 5,120 fits beside the bfloat16 weights. The
logits, 1.3 GB of them for such a sequence over 65,536 rows, are handed
back in host memory (an array of JAX's CPU device), where the check
reads them. A sequence is padded to `max_position_embeddings` (the
reference is causal: what follows a position does not reach it) and the
head's blocks to whole blocks, so that a check of sequences of any
length compiles each program for one width.

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
HEAD_ROWS = 1024     # rows of one block of the head
EXPERT_ROWS = 512    # an expert's rows are padded to a multiple of this
ROUTER_EPS = 1e-6    # beside the chosen scores' sum


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


def rms_norm(g, x, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def rotate_half_split(x, positions, theta: float):
    """x (T, H, hd): elements i and i + hd / 2 of every head turn by
    pos * theta^(-2i / hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32)
                    / x.shape[-1])
    ang = positions.astype(jnp.float32)[:, None, None] * inv  # (T,1,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def what_is_held(config: dict) -> dict:
    """The sizes, from the configuration file."""
    d, hq = int(config["hidden_size"]), int(config["num_attention_heads"])
    if len(config["layer_types"]) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers disagree")
    return {"Hq": hq, "Hkv": int(config["num_key_value_heads"]),
            "hd": d // hq, "d": d,
            "theta": float(config["rope_parameters"]["rope_theta"]),
            "eps": float(config["norm_eps"]),
            "K": int(config["conv_L_cache"]),
            "k": int(config["num_experts_per_tok"]),
            "E": int(config["num_experts"]),
            "dense": int(config["num_dense_layers"]),
            "bias": bool(config["use_expert_bias"]),
            "norm_topk": bool(config["norm_topk_prob"]),
            "scale": float(config["routed_scaling_factor"]),
            "kinds": tuple(config["layer_types"])}


@partial(jax.jit, static_argnames=("d", "kernel", "eps", "mode"))
def _conv_mixer(p, x, *, d, kernel, eps, mode):
    """mixer(n_op(x)) of a conv layer on x (T, d)."""
    t = x.shape[0]
    h = rms_norm(p["ln1"]["g"], x, eps)
    bcx = _mm(h, p["W_in"].astype(jnp.float32), mode)
    b, c, xt = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    u = b * xt
    w = p["conv"].astype(jnp.float32)                    # (K, d)
    ext = jnp.concatenate([jnp.zeros((kernel - 1, d), jnp.float32), u])
    # z_t = w_0 u_{t-K+1} + ... + w_{K-1} u_t
    z = sum(w[j] * ext[j:j + t] for j in range(kernel))
    return _mm(c * z, p["W_out"].astype(jnp.float32), mode)


@partial(jax.jit, static_argnames=("hq", "hkv", "hd", "theta", "eps",
                                   "mode"))
def _full_mixer(p, x, *, hq, hkv, hd, theta, eps, mode):
    """mixer(n_op(x)) of an attention layer on x (T, d)."""
    t = x.shape[0]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = rms_norm(p["ln1"]["g"], x, eps)
    q = _mm(h, f32(p["Wq"]), mode).reshape(t, hq, hd)
    k = _mm(h, f32(p["Wk"]), mode).reshape(t, hkv, hd)
    v = _mm(h, f32(p["Wv"]), mode).reshape(t, hkv, hd)
    pos = jnp.arange(t)
    q = rotate_half_split(rms_norm(p["q_norm"]["g"], q, eps), pos, theta)
    k = rotate_half_split(rms_norm(p["k_norm"]["g"], k, eps), pos, theta)
    group = hq // hkv
    rows = min(Q_ROWS, t)
    while t % rows:
        rows //= 2
    qh = q.reshape(t, hkv, group, hd).transpose(1, 2, 0, 3)
    kh, vh = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def one_head(args):
        qg_, kk, vv = args                     # (group, T, hd), (T, hd)
        kk_r = _round(kk, mode)
        vv_r = jnp.swapaxes(_round(jnp.swapaxes(vv, -1, -2), mode), -1, -2)

        def one_block(i):
            qb = jax.lax.dynamic_slice_in_dim(qg_, i * rows, rows, axis=1)
            s = jnp.einsum("gqd,kd->gqk", _round(qb, mode), kk_r,
                           precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
            qi = i * rows + jnp.arange(rows)[:, None]
            s = jnp.where((jnp.arange(t)[None, :] <= qi)[None], s,
                          -jnp.inf)
            w = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("gqk,kd->gqd", _round(w, mode), vv_r,
                              precision=HIGHEST)

        out = jax.lax.map(one_block, jnp.arange(t // rows))
        return out.transpose(1, 0, 2, 3).reshape(group, t, hd)

    att = jax.lax.map(one_head, (qh, kh, vh))    # (Hkv, group, T, hd)
    att = att.transpose(2, 0, 1, 3).reshape(t, hq * hd)
    return _mm(att, f32(p["Wo"]), mode)


@partial(jax.jit, static_argnames=("mode",))
def _gated(gate, up, down, x, mode):
    """(silu(x G) * (x U)) D on rows x: a dense layer or one expert."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    act = jax.nn.silu(_mm(x, f32(gate), mode)) * _mm(x, f32(up), mode)
    return _mm(act, f32(down), mode)


@partial(jax.jit, static_argnames=("k", "bias", "norm_topk", "scale",
                                   "mode"))
def _route(router, expert_bias, h, *, k, bias, norm_topk, scale, mode):
    """(chosen (T, k) of all E, weights (T, k)): sigmoid scores, the k
    largest of score + bias chosen, the unbiased scores normalised."""
    s = jax.nn.sigmoid(_mm(h, router.astype(jnp.float32), mode))
    steer = s + expert_bias.astype(jnp.float32) if bias else s
    _, chosen = jax.lax.top_k(steer, k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_EPS)
    return chosen, w * scale


@jax.jit
def _add_rows(acc, rows, weights, y):
    return acc.at[rows].add(weights[:, None] * y)


def _experts(p, h, held: dict, mode: str):
    """The expert layer on normed rows h (T, d): every expert applied to
    the rows that chose it, weighted and summed."""
    chosen, weights = _route(
        p["router"], p["expert_bias"], h, k=held["k"], bias=held["bias"],
        norm_topk=held["norm_topk"], scale=held["scale"], mode=mode)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    out = jnp.zeros_like(h)
    ex = p["experts"]
    for e in range(held["E"]):
        rows, col = np.nonzero(chosen == e)
        if not rows.size:
            continue
        n = -(-rows.size // EXPERT_ROWS) * EXPERT_ROWS
        pad_rows = np.zeros((n,), np.int32)
        pad_rows[:rows.size] = rows
        pad_w = np.zeros((n,), np.float32)
        pad_w[:rows.size] = weights[rows, col]     # padding adds 0 * F
        y = _gated(ex["gate"][e], ex["up"][e], ex["down"][e],
                   h[jnp.asarray(pad_rows)], mode)
        out = _add_rows(out, jnp.asarray(pad_rows), jnp.asarray(pad_w), y)
    return out


def _ff(p, x, layer: int, held: dict, mode: str):
    """ff(n_ff(x)) on x (T, d)."""
    h = jax.jit(rms_norm, static_argnums=2)(p["ln2"]["g"], x, held["eps"])
    if layer < held["dense"]:
        return _gated(p["W_gate"], p["W_up"], p["W_down"], h, mode)
    return _experts(p, h, held, mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def _head(g, embed, x, *, eps, mode):
    return _mm(rms_norm(g, x, eps), embed.astype(jnp.float32).T, mode)


def logits(config: dict, params, tokens, first: int, last: int,
           mode: str = "f32"):
    """Logits (B, last - first, V) of positions first..last-1 of
    `tokens` (B, T), in host memory; row by row and layer by layer."""
    held = what_is_held(config)
    vocab = params["embed"].shape[0]
    t = tokens.shape[1]
    width = max(t, int(config["max_position_embeddings"]))
    tokens = jnp.pad(tokens, ((0, 0), (0, width - t)))
    out = []
    for row in range(tokens.shape[0]):
        x = params["embed"][tokens[row]].astype(jnp.float32)
        for layer, (p, kind) in enumerate(zip(params["blocks"],
                                              held["kinds"])):
            if kind == "conv":
                x = x + _conv_mixer(p, x, d=held["d"], kernel=held["K"],
                                    eps=held["eps"], mode=mode)
            else:
                x = x + _full_mixer(
                    p, x, hq=held["Hq"], hkv=held["Hkv"], hd=held["hd"],
                    theta=held["theta"], eps=held["eps"], mode=mode)
            x = x + _ff(p, x, layer, held, mode)
        n = last - first
        block = min(HEAD_ROWS, 1 << (n - 1).bit_length())
        x = jnp.pad(x[first:last], ((0, -n % block), (0, 0)))
        rows = np.empty((x.shape[0], vocab), np.float32)
        for i in range(0, x.shape[0], block):
            rows[i:i + block] = np.asarray(_head(
                params["ln_f"]["g"], params["embed"], x[i:i + block],
                eps=held["eps"], mode=mode))
        out.append(rows[:n])
    return jax.device_put(np.stack(out), jax.devices("cpu")[0])


def expert_layer(config: dict, p, h, mode: str = "f32"):
    """The whole expert layer on normed rows h (T, d), every expert
    held: what the program's layer computes, for a test."""
    return _experts(p, h.astype(jnp.float32), what_is_held(config), mode)


def _no_trainer(*_a, **_k):
    raise NotImplementedError(
        "the lfm2_moe family trains nothing: the program has no trainer "
        "for it, so the reference has no loss, gradient or update either")


loss_and_grad = init_state = update = _no_trainer
