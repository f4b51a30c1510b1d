"""A third language-model block: layers that keep a recurrent STATE
or a few convolution columns beside layers that keep K/V, a
feed-forward layer after either.

Sequential residuals with RMSNorm, two norms a layer, where the
configuration says (`cfg.norm_place`): "pre", `x = x + mixer(n_1(x))`
then `x = x + ff(n_2(x))`, or "post", `x = x + n_1(mixer(x))` then `x =
x + n_2(ff(x))`, the norm on the sublayer's OUTPUT. Layer `l` is one of
three kinds (`cfg.layer_kinds`, published as "three linear, one full"
or "conv, conv, full, conv, ..."):

- **full** (`KIND_FULL`): `n_heads` query heads over `n_kv_heads` K/V
  heads of `head_dim`; with `cfg.attn_gate`, `Wq` gives each head its
  query AND an output gate (`[q_n | gate_n]` side by side) and `out =
  (a * sigmoid(gate)) Wo`, without it `out = a Wo`; q and k take an
  RMSNorm over the head (`cfg.qk_norm = "head"`) or over all the
  heads' columns at once ("width"); the first `rotary_dim` dimensions
  of q and k turn by position in the half-split pairing `(i, i +
  rotary_dim / 2)` (`rotary_dim = 0`: no rotation, order comes from
  the linear layers); causal softmax attention.
- **linear** (`KIND_LINEAR`, the gated delta rule): `[q | k | v | z] =
  h W_qkvz`, `[b | a] = h W_ba`; a short causal depthwise convolution
  and SiLU over `q | k | v`; q and k L2-normalised a head, q scaled by
  `1 / sqrt(dk)`; `beta = sigmoid(b)`, doubled where
  `cfg.allow_neg_eigval` (a write strength in (0, 2): `I - beta k k^T`
  may then turn a direction round), `g = -exp(A_log) * softplus(a +
  dt_bias)` a value head, in float32; the recurrence of
  `attention/gdn_pallas.py` over a (dk, dv) state a value head (value
  head n reads the q and k of key head `n // (Hv / Hk)`); `y =
  RMSNorm_dv(o) * silu(z)`, `out = y W_out`.
- **conv** (`KIND_CONV`, the gated short convolution): `[b | c | x] = h
  W_in`, three blocks of `d_model`; `u = b * x`; a causal depthwise
  convolution of `conv_kernel` taps, no bias, zeros before the
  sequence, NO activation: `z_t = sum_j w_j u_{t - K + 1 + j}`; `out =
  (c * z) W_out`. A slot keeps the last `conv_kernel - 1` columns of u.

The feed-forward layer is `models/moe_transformer.expert_layer`, the
one there is (this configuration says `router_score = "softmax"` and
`shared_combine = "sigmoid_gate"` where that module's own says sigmoid
and average), or, where the configuration has no experts (`n_experts =
0`), one dense gated-SiLU product of width `d_ff`; or dense in the
first `n_dense_layers` layers (width `d_ff_dense`) and experts after
them. With `router_bias` the router chooses by its scores plus
`p["expert_bias"]` and weights by the scores alone. What this chip
holds of the experts and the vocabulary is stated as there (`n_held`,
`held_first`, rows `[0, vocab_size)`); the head is its own matrix, or
the embedding's transpose where `tied_head`.

**The block stands once.** A full layer leaves "write these K/V rows,
read what is visible" to its `attend` callback as every model does
(`models/transformer.Attend`). A linear layer has no K/V rows: it hands
the callback the pre-convolution columns `u` (B, T, C), the two gates
`(g, beta)` (B, T, Hv) and the convolution's weights, and gets the
mixer's rows `o` (B, T, Hv, dv) and the layer's new cache entry back.
A conv layer hands it the columns `u` (B, T, d) and the convolution's
weights and gets `z` back. What lies between is `slot_mix` (`linear_mix`
or `conv_mix`, both over the one `causal_conv`), written here once and
called by every callback with what its cache holds: nothing (the uncached
forward: zero state, the whole scan), a row's real length (the paged
prefill: padding must not move the state, and the convolution keeps
the last three REAL columns), or a slot's kept columns and state (the
decode step: one token, updated in place; a later piece of a prompt
that is prefilled in pieces: the scan from the kept state).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.attention.gdn_pallas import (CHUNK, gdn_scan,
                                                     gdn_update)
from deeplearning4j_tpu.models.moe_transformer import expert_layer
from deeplearning4j_tpu.models.transformer import (KIND_CONV, KIND_FULL,
                                                   KIND_LINEAR, Attend,
                                                   causal_attention)

__all__ = ["HybridConfig", "KIND_FULL", "KIND_LINEAR", "KIND_CONV",
           "init_hybrid_params", "rope_half", "causal_conv",
           "linear_mix", "conv_mix", "slot_mix", "block", "forward",
           "head", "logits", "causal_attention"]

KINDS = (KIND_FULL, KIND_LINEAR, KIND_CONV)


class HybridConfig(NamedTuple):
    """Sizes, kinds of layer and what this chip holds."""

    vocab_size: int            # embedding and head rows held
    d_model: int
    n_heads: int               # full layers: query heads
    n_kv_heads: int
    head_dim: int
    d_ff: int                  # an expert's width, routed and shared
    layer_kinds: Tuple[str, ...]   # one of KINDS a layer
    n_experts: int             # the router's width
    experts_per_token: int
    n_shared: int
    n_held: int                # routed experts on this chip ...
    held_first: int = 0        # ... from this one on
    lin_k_heads: int = 16      # linear layers: key heads,
    lin_v_heads: int = 32      # value heads,
    lin_k_dim: int = 128       # their widths
    lin_v_dim: int = 128
    conv_kernel: int = 4       # taps of either kind's convolution
    rotary_dim: int = 64       # of head_dim, the part that turns
    rope_theta: float = 1e7
    max_len: int = 256
    rms_eps: float = 1e-6
    dtype: Any = jnp.float32
    #: interpret-mode pallas for CPU tests (every kernel)
    interpret: bool = False
    #: how `expert_layer` scores and how it adds the shared experts
    router_score: str = "softmax"
    shared_combine: str = "sigmoid_gate"
    #: where a layer's two norms stand: "pre" on a sublayer's input,
    #: "post" on its output
    norm_place: str = "pre"
    #: beta = 2 sigmoid(b): `I - beta k k^T` may have the eigenvalue -1
    allow_neg_eigval: bool = False
    #: a full layer's output gate beside each query head in `Wq`
    attn_gate: bool = True
    #: the norm of q and k: "head" by head, "width" over all heads
    qk_norm: str = "head"
    #: the first `n_dense_layers` layers have a dense feed-forward of
    #: width `d_ff_dense` where the later ones have the expert layer
    n_dense_layers: int = 0
    d_ff_dense: int = 0
    #: the router chooses by score + `p["expert_bias"]` and weights the
    #: chosen by their scores alone, over their sum + 1e-6
    router_bias: bool = False
    #: the head is the embedding's transpose
    tied_head: bool = False

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def window(self) -> None:
        return None

    @property
    def conv_channels(self) -> int:
        """Columns the convolution runs over: q | k | v."""
        return (2 * self.lin_k_heads * self.lin_k_dim
                + self.lin_v_heads * self.lin_v_dim)

    @property
    def linear_state(self) -> dict:
        """A linear layer's cache entry a slot: name -> (shape, type).
        `conv` is the last `conv_kernel - 1` pre-convolution columns,
        oldest first, side by side (a dense row, no padded minor
        dimension of 3)."""
        return {"state": ((self.lin_v_heads, self.lin_k_dim,
                           self.lin_v_dim), jnp.float32),
                "conv": (((self.conv_kernel - 1) * self.conv_channels,),
                         self.dtype)}

    @property
    def slot_state(self) -> dict:
        """By kind held by slot that this model has, a layer's cache
        entry a slot (`linear_state`; a conv layer's `conv`, the last
        `conv_kernel - 1` columns of u side by side)."""
        out = {}
        if KIND_LINEAR in self.layer_kinds:
            out[KIND_LINEAR] = self.linear_state
        if KIND_CONV in self.layer_kinds:
            out[KIND_CONV] = {"conv": (((self.conv_kernel - 1)
                                        * self.d_model,), self.dtype)}
        return out

    def dense_at(self, layer: int) -> bool:
        """Whether layer `layer` has the dense feed-forward."""
        return not self.n_experts or layer < self.n_dense_layers

    @property
    def dense_width(self) -> int:
        return self.d_ff_dense if self.n_experts else self.d_ff

    def check(self) -> "HybridConfig":
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_kv_heads} K/V heads do not divide "
                             f"{self.n_heads} query heads")
        if self.lin_v_heads % self.lin_k_heads:
            raise ValueError(f"{self.lin_k_heads} key heads do not divide "
                             f"{self.lin_v_heads} value heads")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError("rotary_dim is even and at most head_dim")
        if self.norm_place not in ("pre", "post"):
            raise ValueError(f"norm_place is 'pre' or 'post', got "
                             f"{self.norm_place!r}")
        if self.qk_norm not in ("head", "width"):
            raise ValueError(f"qk_norm is 'head' or 'width', got "
                             f"{self.qk_norm!r}")
        if not self.n_experts and (self.n_held or self.n_shared
                                   or self.experts_per_token):
            raise ValueError("a dense feed-forward (n_experts = 0) has "
                             "no held, shared or chosen experts")
        bad = [k for k in self.layer_kinds if k not in KINDS]
        if bad or not self.layer_kinds:
            raise ValueError(f"layer_kinds must be of {KINDS}, got "
                             f"{self.layer_kinds}")
        if self.n_dense_layers and not (
                self.n_experts and self.d_ff_dense
                and self.n_dense_layers <= self.n_layers):
            raise ValueError("leading dense layers need experts after "
                             "them, a width and at most n_layers")
        if not 0 <= self.held_first <= self.n_experts - self.n_held:
            raise ValueError(
                f"held experts [{self.held_first}, "
                f"{self.held_first + self.n_held}) are not among "
                f"{self.n_experts}")
        if self.experts_per_token > self.n_experts:
            raise ValueError("more experts a token than experts")
        return self


def init_hybrid_params(key, cfg: HybridConfig):
    """N(0, 0.02) leaves, gains 1. `A_log` and `dt_bias` near 0 put the
    decay near 0.5 a token; a test that means a slow decay sets
    `dt_bias` itself."""
    d, f, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    fd = cfg.dense_width
    hk, hv = cfg.lin_k_heads, cfg.lin_v_heads
    dk, dv = cfg.lin_k_dim, cfg.lin_v_dim

    def normal(k, shape):
        return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(
            cfg.dtype)

    def gain(n):
        return {"g": jnp.ones((n,), cfg.dtype)}

    keys = jax.random.split(key, 2 + cfg.n_layers)
    blocks = []
    for i, kind in enumerate(cfg.layer_kinds):
        k = jax.random.split(keys[2 + i], 16)
        p = {"ln1": gain(d), "ln2": gain(d)}
        if not cfg.dense_at(i):
            p.update({
                "router": normal(k[0], (d, cfg.n_experts)),
                "experts": {"gate": normal(k[1], (cfg.n_held, d, f)),
                            "up": normal(k[2], (cfg.n_held, d, f)),
                            "down": normal(k[3], (cfg.n_held, f, d))},
                "shared": {"gate": normal(k[4], (cfg.n_shared, d, f)),
                           "up": normal(k[5], (cfg.n_shared, d, f)),
                           "down": normal(k[6], (cfg.n_shared, f, d))},
                "shared_gate": normal(k[7], (d, cfg.n_shared))})
            if cfg.router_bias:
                p["expert_bias"] = normal(k[14], (cfg.n_experts,))
        else:
            p.update({"W_gate": normal(k[1], (d, fd)),
                      "W_up": normal(k[2], (d, fd)),
                      "W_down": normal(k[3], (fd, d))})
        if kind == KIND_FULL:
            wide = cfg.qk_norm == "width"
            p.update({"Wq": normal(k[8], (d, (1 + cfg.attn_gate)
                                          * cfg.n_heads * hd)),
                      "Wk": normal(k[9], (d, cfg.n_kv_heads * hd)),
                      "Wv": normal(k[10], (d, cfg.n_kv_heads * hd)),
                      "Wo": normal(k[11], (cfg.n_heads * hd, d)),
                      "q_norm": gain(cfg.n_heads * hd if wide else hd),
                      "k_norm": gain(cfg.n_kv_heads * hd if wide
                                     else hd)})
        elif kind == KIND_CONV:
            p.update({"W_in": normal(k[8], (d, 3 * d)),
                      "conv": normal(k[10], (cfg.conv_kernel, d)),
                      "W_out": normal(k[13], (d, d))})
        else:
            p.update({"W_qkvz": normal(k[8], (d, 2 * hk * dk
                                              + 2 * hv * dv)),
                      "W_ba": normal(k[9], (d, 2 * hv)),
                      "conv": normal(k[10], (cfg.conv_kernel,
                                             cfg.conv_channels)),
                      "A_log": normal(k[11], (hv,)),
                      "dt_bias": normal(k[12], (hv,)),
                      "norm": gain(dv),
                      "W_out": normal(k[13], (hv * dv, d))})
        blocks.append(p)
    out = {"embed": normal(keys[0], (cfg.vocab_size, d)),
           "head": normal(keys[1], (d, cfg.vocab_size)),
           "ln_f": gain(d), "blocks": blocks}
    if cfg.tied_head:
        del out["head"]
    return out


def _rms_norm(p, x, eps: float):
    """x / sqrt(mean(x^2) + eps) * g over the last dimension, in f32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * p["g"].astype(jnp.float32)).astype(x.dtype)


def rope_half(x, positions, theta: float, rotary_dim: int):
    """Turn the first `rotary_dim` of x (..., T, H, hd) by position in
    the half-split pairing: elements i and i + rotary_dim / 2 turn by
    `pos * theta^(-2i / rotary_dim)`; the rest stays. The partner comes
    from a product with a constant (hd, hd) matrix of 0, 1 and -1, as
    in `moe_transformer.rope` and for its reason."""
    hd, half = x.shape[-1], rotary_dim // 2
    i = jnp.arange(hd)
    inv = theta ** (-2.0 * (i % half).astype(jnp.float32) / rotary_dim)
    ang = positions.astype(jnp.float32)[..., None, None] \
        * jnp.where(i < rotary_dim, inv, 0.0)          # (..., T, 1, hd)
    partner = jnp.where(i < half, i + half, i - half)
    sign = jnp.where(i < half, -1.0, 1.0)
    swap = (jnp.where(i < rotary_dim, sign, 0.0)[None, :]
            * (i[:, None] == partner[None, :])).astype(x.dtype)
    turned = jnp.einsum(
        "...d,de->...e", x, swap,
        precision=(jax.lax.Precision.HIGHEST
                   if jnp.dtype(x.dtype).itemsize >= 4 else None))
    return (x.astype(jnp.float32) * jnp.cos(ang)
            + turned.astype(jnp.float32) * jnp.sin(ang)).astype(x.dtype)


# ------------------------------------------ the convolution over kept columns
def causal_conv(u, conv_w, act=None, *, prev=None, true_len=None):
    """The causal depthwise convolution of a layer held by slot: u (B,
    T, C), `conv_w` (K, C), output column t = sum_j w_j u_{t - K + 1 +
    j} in float32, then `act` and u's type (`act` None: no activation,
    float32 out). `prev` (B, (K - 1) * C) the columns kept before this
    call (zeros where None: the start of a sequence). `true_len` (B,):
    rows are padded past it, and the columns kept are the last K - 1
    REAL ones. Returns (output (B, T, C), kept (B, K - 1, C))."""
    b, t, c = u.shape
    kk = conv_w.shape[0] - 1
    prev = (jnp.zeros((b, kk, c), u.dtype) if prev is None
            else prev.reshape(b, kk, c))
    ext = jnp.concatenate([prev, u], axis=1)              # (B, kk + T, C)
    w = conv_w.astype(jnp.float32)
    acc = sum(ext[:, j:j + t].astype(jnp.float32) * w[j]
              for j in range(kk + 1))
    out = acc if act is None else act(acc).astype(u.dtype)
    if true_len is None:
        kept = ext[:, t:]
    else:
        # u's rows true_len - kk .. true_len - 1 are ext's rows
        # true_len .. true_len + kk - 1
        rows = true_len[:, None] + jnp.arange(kk)[None, :]
        kept = jnp.take_along_axis(ext, rows[:, :, None], axis=1)
    return out, kept


# ------------------------------------------------------ the linear mixer
def linear_mix(cfg: HybridConfig, u, gates, conv_w, *, prev=None,
               state=None, true_len=None):
    """A linear layer between its projections: convolution, SiLU,
    normalised heads, the recurrence. u (B, T, C) the pre-convolution
    columns, `gates` = (g, beta) (B, T, Hv) float32, `conv_w`
    (conv_kernel, C). `prev` (B, (conv_kernel - 1) * C) the columns kept
    before this call and `state` (B, Hv, dk, dv) the state (both zero
    where None: the start of a sequence). `true_len` (B,): rows are
    padded past it, which must move neither the state (beta = g = 0
    there) nor the kept columns (the last REAL ones). One token with a
    `state` is the decode step's update in place; anything else is the
    chunked scan, from `state` or from zero. Returns (o (B, T, Hv, dv),
    {"state", "conv"} after the last real token)."""
    g, beta = gates
    b, t, c = u.shape
    kk = cfg.conv_kernel - 1
    hk, hv, dk, dv = (cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_k_dim,
                      cfg.lin_v_dim)
    with jax.named_scope("gdn_conv"):
        mixed, kept = causal_conv(u, conv_w, jax.nn.silu, prev=prev,
                                  true_len=true_len)
        if true_len is not None:
            real = jnp.arange(t)[None, :, None] < true_len[:, None, None]
            g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
        q = mixed[..., :hk * dk].reshape(b, t, hk, dk)
        k = mixed[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk)
        v = mixed[..., 2 * hk * dk:].reshape(b, t, hv, dv)

        def unit(x):
            x32 = x.astype(jnp.float32)
            return x32 * jax.lax.rsqrt(
                jnp.sum(x32 * x32, axis=-1, keepdims=True) + 1e-6)

        q = (unit(q) * dk ** -0.5).astype(u.dtype)
        k = unit(k).astype(u.dtype)
        if hv != hk:
            q = jnp.repeat(q, hv // hk, axis=2)
            k = jnp.repeat(k, hv // hk, axis=2)
    if t == 1 and state is not None:
        with jax.named_scope("gdn_update"):
            o, state = gdn_update(state, q[:, 0], k[:, 0], v[:, 0],
                                  g[:, 0], beta[:, 0],
                                  interpret=cfg.interpret)
            o = o[:, None]
    else:
        with jax.named_scope("gdn_scan"):
            pad = -t % CHUNK

            def time_major(x):
                """(B, T, H, ...) -> (B, H, T + pad, ...), zeros (no
                decay, no write) past T."""
                x = jnp.moveaxis(x, 1, 2)
                width = [(0, 0)] * x.ndim
                width[2] = (0, pad)
                return jnp.pad(x, width)

            o, state = gdn_scan(time_major(q), time_major(k),
                                time_major(v), time_major(g),
                                time_major(beta), state=state,
                                interpret=cfg.interpret)
            o = jnp.moveaxis(o[:, :, :t], 1, 2)
    return o, {"state": state, "conv": kept.reshape(b, kk * c)}


def conv_mix(u, conv_w, *, prev=None, true_len=None):
    """A conv layer between its projections: the convolution of u (B,
    T, d) over the columns its slot kept (`prev`, zeros where None),
    with no activation. Returns (z (B, T, d) float32, {"conv": the last
    `conv_kernel - 1` REAL columns of u})."""
    b = u.shape[0]
    z, kept = causal_conv(u, conv_w, prev=prev, true_len=true_len)
    return z, {"conv": kept.reshape(b, -1)}


def slot_mix(cfg: HybridConfig, kind: str, a, b, c, *, prev=None,
             state=None, true_len=None):
    """A layer of a kind held by slot between its projections, called
    with what the `attend` callback got (`linear_mix`'s columns, gates
    and weights; `conv_mix`'s columns, None and weights) and with what
    the cache holds: the kept columns `prev`, a linear layer's `state`,
    the rows' real lengths. Returns (the mixer's rows, the new entry)."""
    if kind == KIND_LINEAR:
        return linear_mix(cfg, a, b, c, prev=prev, state=state,
                          true_len=true_len)
    return conv_mix(a, c, prev=prev, true_len=true_len)


def _conv_layer(p, h, cfg: HybridConfig, attend: Attend, layer: int):
    """The gated short convolution: `[b | c | x] = h W_in`, `u = b *
    x`, the convolution z of u, `(c * z) W_out`."""
    d = cfg.d_model
    with jax.named_scope("short_conv"):
        proj = h @ p["W_in"]
        u = (proj[..., :d].astype(jnp.float32)
             * proj[..., 2 * d:].astype(jnp.float32)).astype(h.dtype)
        z, entry = attend(layer, KIND_CONV, u, None, p["conv"])
        y = proj[..., d:2 * d].astype(jnp.float32) * z
        return y.astype(h.dtype) @ p["W_out"], entry


def _linear_layer(p, h, cfg: HybridConfig, attend: Attend, layer: int):
    b, t, _ = h.shape
    hv, dv, c = cfg.lin_v_heads, cfg.lin_v_dim, cfg.conv_channels
    with jax.named_scope("gdn_proj"):
        proj = h @ p["W_qkvz"]
        u, z = proj[..., :c], proj[..., c:]
        ba = jnp.dot(h, p["W_ba"],
                     preferred_element_type=jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :hv])
        if cfg.allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[..., hv:] + p["dt_bias"].astype(jnp.float32))
    o, entry = attend(layer, KIND_LINEAR, u, (g, beta), p["conv"])
    with jax.named_scope("gdn_out"):
        y = _rms_norm(p["norm"], o, cfg.rms_eps).astype(jnp.float32) \
            * jax.nn.silu(z.reshape(b, t, hv, dv).astype(jnp.float32))
        out = y.astype(h.dtype).reshape(b, t, hv * dv) @ p["W_out"]
    return out, entry


def _full_layer(p, h, positions, cfg: HybridConfig, attend: Attend,
                layer: int):
    b, t, _ = h.shape
    hq, hd = cfg.n_heads, cfg.head_dim
    q, k, gate = h @ p["Wq"], h @ p["Wk"], None
    if cfg.attn_gate:
        qg = q.reshape(b, t, hq, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
    if cfg.qk_norm == "width":
        q = _rms_norm(p["q_norm"], q.reshape(b, t, hq * hd), cfg.rms_eps)
        k = _rms_norm(p["k_norm"], k, cfg.rms_eps)
    q = q.reshape(b, t, hq, hd)
    k = k.reshape(b, t, cfg.n_kv_heads, hd)
    v = (h @ p["Wv"]).reshape(b, t, cfg.n_kv_heads, hd)
    if cfg.qk_norm == "head":
        q = _rms_norm(p["q_norm"], q, cfg.rms_eps)
        k = _rms_norm(p["k_norm"], k, cfg.rms_eps)
    if cfg.rotary_dim:
        q = rope_half(q, positions, cfg.rope_theta, cfg.rotary_dim)
        k = rope_half(k, positions, cfg.rope_theta, cfg.rotary_dim)
    att, entry = attend(layer, KIND_FULL, q.transpose(0, 2, 1, 3),
                        k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    att = att.transpose(0, 2, 1, 3)
    if gate is not None:
        with jax.named_scope("attn_gate"):
            att = att.astype(jnp.float32) \
                * jax.nn.sigmoid(gate.astype(jnp.float32))
    return att.astype(h.dtype).reshape(b, t, hq * hd) @ p["Wo"], entry


def _dense_ff(p, h):
    """(silu(h W_gate) * (h W_up)) W_down, float32 out."""
    with jax.named_scope("dense_ff"):
        act = jax.nn.silu(jnp.dot(h, p["W_gate"],
                                  preferred_element_type=jnp.float32)) \
            * jnp.dot(h, p["W_up"], preferred_element_type=jnp.float32)
        return jnp.dot(act.astype(h.dtype), p["W_down"],
                       preferred_element_type=jnp.float32)


# ------------------------------------------------------------- the block
def block(p, x, positions, layer: int, cfg: HybridConfig, attend: Attend,
          valid=None):
    """One layer on x (B, T, d): the only place its mathematics is
    written. Returns (x', the cache's new entry for this layer, pairs
    by held expert)."""
    b, t, d = x.shape
    pre = cfg.norm_place == "pre"
    h = _rms_norm(p["ln1"], x, cfg.rms_eps) if pre else x
    kind = cfg.layer_kinds[layer]
    if kind == KIND_LINEAR:
        mix, entry = _linear_layer(p, h, cfg, attend, layer)
    elif kind == KIND_CONV:
        mix, entry = _conv_layer(p, h, cfg, attend, layer)
    else:
        mix, entry = _full_layer(p, h, positions, cfg, attend, layer)
    if not pre:
        mix = _rms_norm(p["ln1"], mix, cfg.rms_eps)
    x = x + mix.astype(x.dtype)
    h = _rms_norm(p["ln2"], x, cfg.rms_eps) if pre else x
    if not cfg.dense_at(layer):
        ff, pairs = expert_layer(
            p, h.reshape(b * t, d), cfg,
            None if valid is None else valid.reshape(b * t))
        ff = ff.reshape(b, t, d)
    else:
        # a dense layer of a model with experts counts no pairs
        ff = _dense_ff(p, h)
        pairs = jnp.zeros((cfg.n_held,), jnp.int32) if cfg.n_experts \
            else None
    if not pre:
        ff = _rms_norm(p["ln2"], ff, cfg.rms_eps)
    x = (x.astype(jnp.float32) + ff).astype(x.dtype)
    return x, entry, pairs


def forward(params, tokens, positions, cfg: HybridConfig, attend: Attend,
            valid=None):
    """Every block over tokens (B, T) at `positions` (B, T), or (T,)
    where every row stands at the same ones. Returns (hidden (B, T, d)
    before the final norm, the cache entries a layer, pairs (layers,
    n_held) int32, or () where the feed-forward is dense)."""
    x = params["embed"][tokens]
    entries, pairs = [], []
    for i, p in enumerate(params["blocks"]):
        x, entry, n = block(p, x, positions, i, cfg, attend, valid)
        entries.append(entry)
        pairs.append(n)
    return x, tuple(entries), jnp.stack(pairs) if cfg.n_experts else ()


def head(params, x, cfg: HybridConfig):
    """x (..., d) -> logits over the vocabulary rows held, f32; the
    head is its own matrix, or the embedding's transpose."""
    w = params["embed"].T if cfg.tied_head else params["head"]
    return jnp.dot(_rms_norm(params["ln_f"], x, cfg.rms_eps), w,
                   preferred_element_type=jnp.float32)


def uncached(cfg: HybridConfig) -> Attend:
    """The callback of a forward that keeps nothing: causal attention
    over the rows as they come, the whole scan from a zero state."""
    def attend(_layer, kind, a, b, c):
        if kind in (KIND_LINEAR, KIND_CONV):
            o, _ = slot_mix(cfg, kind, a, b, c)
            return o, None
        return causal_attention(cfg, kind, a, b, c), None
    return attend


def logits(params, tokens, cfg: HybridConfig):
    """tokens (B, T) -> (B, T, vocab_size) f32, nothing cached."""
    x, _, _ = forward(params, tokens, jnp.arange(tokens.shape[1]), cfg,
                      uncached(cfg))
    return head(params, x, cfg)
