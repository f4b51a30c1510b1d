"""Plain float32 reference of the `qwen3_next` block, told which experts
and vocabulary rows the configuration holds.

The equations (T tokens, d hidden; RMSNorm `n(x) = x / sqrt(mean(x^2) +
eps) * g`, g the multiplier):

    x = x + mixer(n_1(x));  x = x + moe(n_2(x))       every layer
    logits = n_f(x) W_head                            (untied head)
    layer l is full where (l + 1) % full_attention_interval == 0

    full mixer (Hq heads over Hkv K/V heads of hd):
        [q_n | gate_n] = h Wq by head n; k = h Wk; v = h Wv
        q = n_q(q), k = n_k(k) over each head
        the first R = partial_rotary_factor hd dimensions of q and k
        turn in the pairing (i, i + R/2) by pos * theta^(-2i/R)
        query head n reads K/V head n // (Hq / Hkv); causal
        out = (softmax(q k^T / sqrt(hd)) v * sigmoid(gate)) Wo

    linear mixer (Hk key heads, Hv value heads, dk, dv, kernel K):
        [q | k | v | z] = h W_qkvz;  [b | a] = h W_ba
        c_t = silu(sum_{j<K} w_j u_{t-K+1+j}) over u = q | k | v,
            zeros before the sequence; split back
        value head n reads q and k of key head n // (Hv / Hk)
        q = q / sqrt(|q|^2 + 1e-6) / sqrt(dk); k = k / sqrt(|k|^2 + 1e-6)
        beta_t = sigmoid(b_t); g_t = -exp(A_log) softplus(a_t + dt_bias)
        S' = exp(g_t) S_{t-1};  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
        o_t = S_t^T q_t                      S (dk, dv), zero at t = 0
        out = (n_dv(o) * silu(z)) W_out

    moe: p = softmax(h Wr) over E; I = the k largest; w_e = p_e / sum_I p
        F(h) = (silu(h G) * (h U)) D
        routed_here = sum over e in I that are HELD of w_e F_e(h)
        moe = routed_here + sigmoid(h w_s) F_s(h)

in straightforward `jax.numpy`, float32, every product at
`Precision.HIGHEST`, no kernel, no cache, no grouped product and no
chunks: the recurrence runs TOKEN BY TOKEN (`lax.scan` over time), each
held expert is a plain product over the rows that chose it (picked on
the host). The partial sum over the held experts is what goes on to the
next layer, as in the program: nothing stands in for the other chips of
the deployment.

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
UNIT_EPS = 1e-6      # under the root of the L2 norm of q and k


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


def rotate_half_split(x, positions, theta: float, rotary: int):
    """x (T, H, hd): elements i and i + rotary / 2 (i < rotary / 2) of
    every head turn by pos * theta^(-2i / rotary); elements from
    `rotary` on stay."""
    half = rotary // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rotary)
    ang = positions.astype(jnp.float32)[:, None, None] * inv  # (T,1,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary:]], axis=-1)


def what_is_held(config: dict) -> dict:
    """The sizes and the share, from the configuration file."""
    every = int(config["full_attention_interval"])
    hd = int(config["head_dim"])
    return {"Hq": int(config["num_attention_heads"]),
            "Hkv": int(config["num_key_value_heads"]), "hd": hd,
            "rotary": int(round(hd * float(
                config["partial_rotary_factor"]))),
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "Hk": int(config["linear_num_key_heads"]),
            "Hv": int(config["linear_num_value_heads"]),
            "dk": int(config["linear_key_head_dim"]),
            "dv": int(config["linear_value_head_dim"]),
            "K": int(config["linear_conv_kernel_dim"]),
            "k": int(config["num_experts_per_tok"]),
            "held": int(config["num_experts"]),
            "held_first": int(config["held_experts_first"]),
            "E": int(config["router_width"]),
            "full": tuple((i + 1) % every == 0 for i in
                          range(int(config["num_hidden_layers"])))}


@partial(jax.jit, static_argnames=("hq", "hkv", "hd", "rotary", "theta",
                                   "eps", "mode"))
def _full_mixer(p, x, *, hq, hkv, hd, rotary, theta, eps, mode):
    """mixer(n_1(x)) of a full layer on x (T, d)."""
    t = x.shape[0]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = rms_norm(p["ln1"]["g"], x, eps)
    qg = _mm(h, f32(p["Wq"]), mode).reshape(t, hq, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = _mm(h, f32(p["Wk"]), mode).reshape(t, hkv, hd)
    v = _mm(h, f32(p["Wv"]), mode).reshape(t, hkv, hd)
    pos = jnp.arange(t)
    q = rotate_half_split(rms_norm(p["q_norm"]["g"], q, eps), pos, theta,
                          rotary)
    k = rotate_half_split(rms_norm(p["k_norm"]["g"], k, eps), pos, theta,
                          rotary)
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
    att = att.transpose(2, 0, 1, 3).reshape(t, hq, hd) \
        * jax.nn.sigmoid(gate)
    return _mm(att.reshape(t, hq * hd), f32(p["Wo"]), mode)


@partial(jax.jit, static_argnames=("hk", "hv", "dk", "dv", "kernel",
                                   "eps", "mode"))
def _linear_mixer(p, x, *, hk, hv, dk, dv, kernel, eps, mode):
    """mixer(n_1(x)) of a linear layer on x (T, d): the recurrence
    token by token."""
    t = x.shape[0]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = rms_norm(p["ln1"]["g"], x, eps)
    proj = _mm(h, f32(p["W_qkvz"]), mode)
    c = 2 * hk * dk + hv * dv
    u, z = proj[:, :c], proj[:, c:]
    ba = _mm(h, f32(p["W_ba"]), mode)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(f32(p["A_log"])) * jax.nn.softplus(
        ba[:, hv:] + f32(p["dt_bias"]))
    w = f32(p["conv"])                                   # (K, C)
    ext = jnp.concatenate([jnp.zeros((kernel - 1, c), jnp.float32), u])
    mixed = jax.nn.silu(sum(ext[j:j + t] * w[j] for j in range(kernel)))
    q = mixed[:, :hk * dk].reshape(t, hk, dk)
    k = mixed[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = mixed[:, 2 * hk * dk:].reshape(t, hv, dv)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                            + UNIT_EPS)

    q = jnp.repeat(unit(q) / jnp.sqrt(jnp.float32(dk)), hv // hk, axis=1)
    k = jnp.repeat(unit(k), hv // hk, axis=1)
    q, k, v = _round(q, mode), _round(k, mode), _round(v, mode)

    def step(s, now):
        q_t, k_t, v_t, g_t, b_t = now            # (Hv, dk) .. (Hv,)
        s = s * jnp.exp(g_t)[:, None, None]      # (Hv, dk, dv)
        mem = jnp.einsum("hkv,hk->hv", _round(s, mode), k_t,
                         precision=HIGHEST)
        delta = b_t[:, None] * (v_t - mem)
        s = s + k_t[:, :, None] * _round(delta, mode)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", _round(s, mode), q_t,
                             precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    y = rms_norm(p["norm"]["g"], o, eps) \
        * jax.nn.silu(z.reshape(t, hv, dv))
    return _mm(y.reshape(t, hv * dv), f32(p["W_out"]), mode)


@partial(jax.jit, static_argnames=("k", "mode"))
def _route(router, h, *, k, mode):
    """(chosen (T, k) of all E, weights (T, k) normalised over the k)."""
    s = jax.nn.softmax(_mm(h, router.astype(jnp.float32), mode), axis=-1)
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


@partial(jax.jit, static_argnames=("mode",))
def _shared(sh, gate_w, h, mode):
    opened = jax.nn.sigmoid(_mm(h, gate_w.astype(jnp.float32), mode))
    return opened * _expert(sh["gate"][0], sh["up"][0], sh["down"][0], h,
                            mode)


def _experts(p, x, held: dict, mode: str):
    """moe(n_2(x)) on x (T, d): routed_here + the gated shared expert."""
    h = jax.jit(rms_norm, static_argnums=2)(p["ln2"]["g"], x, held["eps"])
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
    return out + _shared(p["shared"], p["shared_gate"], h, mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def _head(g, w, x, *, eps, mode):
    return _mm(rms_norm(g, x, eps), w.astype(jnp.float32), mode)


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
        for p, full in zip(params["blocks"], held["full"]):
            if full:
                x = x + _full_mixer(
                    p, x, hq=held["Hq"], hkv=held["Hkv"], hd=held["hd"],
                    rotary=held["rotary"], theta=held["theta"],
                    eps=held["eps"], mode=mode)
            else:
                x = x + _linear_mixer(
                    p, x, hk=held["Hk"], hv=held["Hv"], dk=held["dk"],
                    dv=held["dv"], kernel=held["K"], eps=held["eps"],
                    mode=mode)
            x = x + _experts(p, x, held, mode)
        out.append(_head(params["ln_f"]["g"], params["head"],
                         x[first:last], eps=held["eps"], mode=mode))
    return jnp.stack(out)


def _no_trainer(*_a, **_k):
    raise NotImplementedError(
        "the qwen3_next family trains nothing: the program has no "
        "trainer for it, so the reference has no loss, gradient or "
        "update either")


loss_and_grad = init_state = update = _no_trainer
