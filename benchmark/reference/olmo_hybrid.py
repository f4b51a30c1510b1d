"""Plain float32 reference of the `olmo_hybrid` block.

The equations (T tokens, d hidden; RMSNorm `N(x) = x / sqrt(mean(x^2) +
eps) * g`, g the multiplier, on a sublayer's OUTPUT):

    x = x + N_a(mixer(x));  x = x + N_f(ff(x))          every layer
    ff(x) = (silu(x W_gate) * (x W_up)) W_down          dense
    logits = N_last(x) W_head                           (untied head)
    layer l is full where layer_types[l] == "full_attention"

    full mixer (H heads of hd, each with its own K/V head):
        q = N_q(x W_q), k = N_k(x W_k)   norms over ALL H hd columns
        v = x W_v;  no rotation, no bias, no output gate
        out = softmax(q_n k_n^T / sqrt(hd), causal) v_n  by head;  W_o

    linear mixer (H heads, dk, dv, kernel K):
        [q | k | v | z] = x W_qkvz;  [b | a] = x W_ba
        c_t = silu(sum_{j<K} w_j u_{t-K+1+j}) over u = q | k | v,
            zeros before the sequence; split back
        q = q / sqrt(|q|^2 + 1e-6) / sqrt(dk); k = k / sqrt(|k|^2 + 1e-6)
        beta_t = 2 sigmoid(b_t)          (linear_allow_neg_eigval)
        alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))
        S' = alpha_t S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
        o_t = S_t^T q_t                  S (dk, dv), zero at t = 0
        out = (N_dv(o) * silu(z)) W_out

in straightforward `jax.numpy`, float32, every product at
`Precision.HIGHEST`, no kernel, no cache and no chunks: the recurrence
runs TOKEN BY TOKEN (`lax.scan` over time), the prompt is never cut
into pieces. It imports nothing from `deeplearning4j_tpu` and is handed
nothing the program made.

Departures, each for room and none for arithmetic: attention a head and
a block of query rows at a time, the feed-forward and the head a block
of rows at a time (a sequence of 16,384 beside the bfloat16 weights);
the logits of a long sequence (16,384 x 100,352 float32 = 6.6 GB, which
does not fit beside 6.5 GB of weights) are handed back in HOST memory,
as an array of JAX's CPU device, block by block as the head computes
them (`_room_on_device` says when: a sequence of up to 8,192 stays on
the chip where the chip has the room for it and for one more forward).

`mode` lowers the precision for the control that has to FAIL the
comparison: "bf16" rounds every operand of every product to bfloat16,
"fp8" to float8_e4m3 under a per-row scale.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "bf16", "fp8")
Q_ROWS = 1024        # query rows of one attention block
ROW_BLOCKS = (2048, 1024, 512, 256, 128)   # rows of a feed-forward block
UNIT_EPS = 1e-6      # under the root of the L2 norm of q and k
#: the most logits handed back on the device, and what a forward pass
#: of this reference needs for its own arrays, a token
DEVICE_LOGITS_MOST = 3.4e9
FORWARD_BYTES_A_TOKEN = 0.4e6


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


def what_it_is(config: dict) -> dict:
    """The sizes, from the configuration file's published keys."""
    h = int(config["num_attention_heads"])
    return {"H": h, "Hkv": int(config["num_key_value_heads"]),
            "hd": int(config["hidden_size"]) // h,
            "eps": float(config["rms_norm_eps"]),
            "Hk": int(config["linear_num_key_heads"]),
            "Hv": int(config["linear_num_value_heads"]),
            "dk": int(config["linear_key_head_dim"]),
            "dv": int(config["linear_value_head_dim"]),
            "K": int(config["linear_conv_kernel_dim"]),
            "beta": 2.0 if config["linear_allow_neg_eigval"] else 1.0,
            "full": tuple(t == "full_attention"
                          for t in config["layer_types"])}


def _block_rows(t: int) -> int:
    return next((r for r in ROW_BLOCKS if t % r == 0), t)


@partial(jax.jit, static_argnames=("h", "hkv", "hd", "eps", "mode"))
def _full_mixer(p, x, *, h, hkv, hd, eps, mode):
    """N_a(mixer(x)) of a full layer on x (T, d)."""
    t = x.shape[0]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    q = rms_norm(p["q_norm"]["g"], _mm(x, f32(p["Wq"]), mode), eps)
    k = rms_norm(p["k_norm"]["g"], _mm(x, f32(p["Wk"]), mode), eps)
    v = _mm(x, f32(p["Wv"]), mode)
    group = h // hkv
    rows = min(Q_ROWS, t)
    while t % rows:
        rows //= 2
    qh = q.reshape(t, hkv, group, hd).transpose(1, 2, 0, 3)
    kh = k.reshape(t, hkv, hd).transpose(1, 0, 2)
    vh = v.reshape(t, hkv, hd).transpose(1, 0, 2)

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
    att = att.transpose(2, 0, 1, 3).reshape(t, h * hd)
    return rms_norm(p["ln1"]["g"], _mm(att, f32(p["Wo"]), mode), eps)


@partial(jax.jit, static_argnames=("hk", "hv", "dk", "dv", "kernel",
                                   "beta_scale", "eps", "mode"))
def _linear_mixer(p, x, *, hk, hv, dk, dv, kernel, beta_scale, eps, mode):
    """N_a(mixer(x)) of a linear layer on x (T, d): the recurrence
    token by token."""
    t = x.shape[0]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    proj = _mm(x, f32(p["W_qkvz"]), mode)
    c = 2 * hk * dk + hv * dv
    u, z = proj[:, :c], proj[:, c:]
    ba = _mm(x, f32(p["W_ba"]), mode)
    beta = beta_scale * jax.nn.sigmoid(ba[:, :hv])
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
    return rms_norm(p["ln1"]["g"],
                    _mm(y.reshape(t, hv * dv), f32(p["W_out"]), mode), eps)


@partial(jax.jit, static_argnames=("eps", "mode"))
def _feed_forward(p, x, *, eps, mode):
    """N_f(ff(x)) on a block of rows x (R, d)."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    act = jax.nn.silu(_mm(x, f32(p["W_gate"]), mode)) \
        * _mm(x, f32(p["W_up"]), mode)
    return rms_norm(p["ln2"]["g"], _mm(act, f32(p["W_down"]), mode), eps)


@partial(jax.jit, static_argnames=("eps", "mode"))
def _head(g, w, x, *, eps, mode):
    return _mm(rms_norm(g, x, eps), w.astype(jnp.float32), mode)


def _room_on_device(tokens: int, vocab: int) -> bool:
    """Whether (tokens, vocab) float32 logits may stay on the device:
    always where it reports no memory (the CPU of the tests); on a chip,
    where they are within `DEVICE_LOGITS_MOST` and what is free NOW
    holds them twice (the caller's `[0]` is a copy) beside the arrays of
    a forward pass over as many tokens (the caller keeps the last
    sequence's logits, and a control's, while it asks for the next)."""
    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return True
    need = 4.0 * tokens * vocab
    free = stats["bytes_limit"] - stats["bytes_in_use"]
    return need <= DEVICE_LOGITS_MOST \
        and 2.2 * need + FORWARD_BYTES_A_TOKEN * tokens < free


def _by_rows(fn, x):
    rows = _block_rows(x.shape[0])
    return jnp.concatenate([fn(x[i:i + rows])
                            for i in range(0, x.shape[0], rows)])


def logits(config: dict, params, tokens, first: int, last: int,
           mode: str = "f32"):
    """Logits (B, last - first, V) of positions first..last-1 of
    `tokens` (B, T); row by row and layer by layer. Where they do not
    fit on the device beside what is there they are handed back in host
    memory (an array of JAX's CPU device)."""
    it = what_it_is(config)
    vocab = params["head"].shape[1]
    host = None if _room_on_device(
        tokens.shape[0] * (last - first), vocab) else jax.devices("cpu")[0]
    out = []
    for row in range(tokens.shape[0]):
        x = params["embed"][tokens[row]].astype(jnp.float32)
        for p, full in zip(params["blocks"], it["full"]):
            if full:
                x = x + _full_mixer(p, x, h=it["H"], hkv=it["Hkv"],
                                    hd=it["hd"], eps=it["eps"], mode=mode)
            else:
                x = x + _linear_mixer(
                    p, x, hk=it["Hk"], hv=it["Hv"], dk=it["dk"],
                    dv=it["dv"], kernel=it["K"], beta_scale=it["beta"],
                    eps=it["eps"], mode=mode)
            x = x + _by_rows(partial(_feed_forward, p, eps=it["eps"],
                                     mode=mode), x)
        x = x[first:last]
        rows = _block_rows(x.shape[0])
        blocks = (_head(params["ln_f"]["g"], params["head"], x[i:i + rows],
                        eps=it["eps"], mode=mode)
                  for i in range(0, x.shape[0], rows))
        if host is None:
            out.append(jnp.concatenate(list(blocks)))
            continue
        # each block straight into its rows of one host array
        held = np.empty((x.shape[0], vocab), np.float32)
        for i, block in enumerate(blocks):
            held[i * rows:(i + 1) * rows] = np.asarray(block)
        out.append(held)
    if host is None:
        return jnp.stack(out)
    return jax.device_put(np.stack(out) if len(out) > 1 else out[0][None],
                          host)


def _no_trainer(*_a, **_k):
    raise NotImplementedError(
        "the olmo_hybrid family trains nothing: the program has no "
        "trainer for it, so the reference has no loss, gradient or "
        "update either")


loss_and_grad = init_state = update = _no_trainer
