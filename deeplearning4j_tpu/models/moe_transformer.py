"""A second language-model block: grouped K/V heads, window and full
attention layers, and an expert layer of which this chip holds a part.

One gain-only LayerNorm feeds attention and the expert layer side by
side (a parallel block): `x' = x + attn(h) + routed(h) + shared(h)`,
`h = LN(x)`. Attention has `n_heads` query heads over `n_kv_heads` K/V
heads (query head n reads K/V head n // (n_heads / n_kv_heads)). A
"window" layer rotates q and k pairwise by position (`rope`) and lets
query i see key j iff i - window < j <= i; a "full" layer carries no
positions at all and is plainly causal. There is no position table. The
router scores all `n_experts` in float32 through a sigmoid, keeps the
`experts_per_token` largest and normalises their scores over all of
them; experts are gated-SiLU MLPs; `n_shared` shared experts are
averaged and added. The head is tied to the embedding.

**What this chip holds.** The model is served as one chip's share of a
group: attention, router and shared experts whole, the routed experts
`[held_first, held_first + n_held)` and the vocabulary rows
`[0, vocab_size)`. The router still scores, chooses and normalises over
all `n_experts`; `expert_layer` sums only the chosen experts that are
held, and that partial sum is what goes on to the next layer (the other
shares' terms would be added by the group's exchange, which is not
written: ROADMAP Reach). `expert_layer` returns, beside its result, how
many (token, expert) pairs fell on each held expert.

**The block stands once.** `block` takes an `attend` callback for
"write these K/V rows, read what is visible" (`models/transformer.py`
has the contract, one for every model). The uncached forward (`logits`)
and every lane of the paged cache (`serving/paged_kinds.py`) are that
one definition under their callbacks; the router and the rotation are
written nowhere else.

The routed products are grouped: the pairs that fall on held experts
are sorted by expert and multiplied group by group (`grouped_matmul`:
the megablox Pallas kernel on a TPU, whose grid covers only the row
tiles that hold pairs and fetches only the weights of experts that have
any; `lax.ragged_dot` elsewhere), so the work follows the pairs. No
token is dropped and there is no capacity factor: the sorted pairs are
taken `chunk` rows at a time until none is left. Between token order
and the sorted order the rows travel by `models/moe_rows.py`: a row copy
a pair in, a row copy a pair out under the pair's weight, summed by
token in float32; no gather, no scatter, and rows that hold no pair are
not touched.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.moe_rows import rows_in, rows_out
from deeplearning4j_tpu.models.transformer import (KIND_FULL,  # noqa: F401
                                                   KIND_WINDOW, KINDS,
                                                   Attend, _project,
                                                   causal_attention)

__all__ = ["MoEConfig", "KINDS", "KIND_FULL", "KIND_WINDOW",
           "init_moe_params", "rope", "expert_layer", "grouped_matmul",
           "block", "forward", "head", "logits", "causal_attention"]


class MoEConfig(NamedTuple):
    """Sizes, kinds of layer and what this chip holds."""

    vocab_size: int            # embedding rows held: ids in [0, vocab_size)
    d_model: int
    n_heads: int               # query heads
    n_kv_heads: int
    head_dim: int
    d_ff: int                  # an expert's width
    layer_kinds: Tuple[str, ...]   # one of KINDS a layer
    window: int
    n_experts: int             # the router's width
    experts_per_token: int
    n_shared: int
    n_held: int                # routed experts on this chip ...
    held_first: int = 0        # ... from this one on
    rope_theta: float = 10000.0
    max_len: int = 256
    ln_eps: float = 1e-5
    logit_scale: float = 1.0
    dtype: Any = jnp.float32
    #: interpret-mode pallas for CPU tests (kernels AND grouped matmul)
    interpret: bool = False
    #: what `expert_layer` reads of any configuration beside the counts:
    #: how the router scores ("sigmoid", or "softmax" over all experts)
    #: and how the shared experts join ("average": their mean;
    #: "sigmoid_gate": each behind `sigmoid(h w_s)`, `p["shared_gate"]`)
    router_score: str = "sigmoid"
    shared_combine: str = "average"
    #: no selection bias: the k largest scores are chosen
    router_bias: bool = False

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    def check(self) -> "MoEConfig":
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_kv_heads} K/V heads do not divide "
                             f"{self.n_heads} query heads")
        if self.head_dim % 2:
            raise ValueError("rotary pairs need an even head_dim")
        bad = [k for k in self.layer_kinds if k not in KINDS]
        if bad or not self.layer_kinds:
            raise ValueError(f"layer_kinds must be of {KINDS}, got "
                             f"{self.layer_kinds}")
        if not 0 <= self.held_first <= self.n_experts - self.n_held:
            raise ValueError(
                f"held experts [{self.held_first}, "
                f"{self.held_first + self.n_held}) are not among "
                f"{self.n_experts}")
        if self.experts_per_token > self.n_experts:
            raise ValueError("more experts a token than experts")
        return self


def init_moe_params(key, cfg: MoEConfig):
    """N(0, 0.02) leaves, gains 1: embed, ln_f, and per block ln, Wq,
    Wk, Wv, Wo, router, experts{gate, up, down} stacked over the held
    experts, shared{gate, up, down} stacked over the shared ones."""
    d, f, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    s = 0.02

    def normal(k, shape):
        return (s * jax.random.normal(k, shape, jnp.float32)).astype(
            cfg.dtype)

    keys = jax.random.split(key, 1 + cfg.n_layers)
    blocks = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[1 + i], 11)
        blocks.append({
            "ln": {"g": jnp.ones((d,), cfg.dtype)},
            "Wq": normal(k[0], (d, cfg.n_heads * hd)),
            "Wk": normal(k[1], (d, cfg.n_kv_heads * hd)),
            "Wv": normal(k[2], (d, cfg.n_kv_heads * hd)),
            "Wo": normal(k[3], (cfg.n_heads * hd, d)),
            "router": normal(k[4], (d, cfg.n_experts)),
            "experts": {"gate": normal(k[5], (cfg.n_held, d, f)),
                        "up": normal(k[6], (cfg.n_held, d, f)),
                        "down": normal(k[7], (cfg.n_held, f, d))},
            "shared": {"gate": normal(k[8], (cfg.n_shared, d, f)),
                       "up": normal(k[9], (cfg.n_shared, d, f)),
                       "down": normal(k[10], (cfg.n_shared, f, d))},
        })
    return {"embed": normal(keys[0], (cfg.vocab_size, d)),
            "ln_f": {"g": jnp.ones((d,), cfg.dtype)}, "blocks": blocks}


def _gain_norm(p, x, eps: float):
    """LayerNorm with a gain and no bias; statistics in f32."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * p["g"]


def rope(x, positions, theta: float):
    """Rotate x (..., T, H, hd) pairwise by position: elements 2i and
    2i+1 of a head turn by the angle pos * theta^(-2i/hd), the whole
    head (the interleaved, "gptj" pairing). `positions` is (..., T).
    The pair's partner (-x[2i+1] at 2i, x[2i] at 2i+1) comes from a
    product with a constant (hd, hd) matrix of 0, 1 and -1, which is
    exact in any type and costs the MXU nothing to speak of; rolls or a
    reshape to a minor dimension of 2 make a TPU pad and copy the whole
    of q. The turn itself is in f32."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[..., None, None] \
        * jnp.repeat(inv, 2)                      # (..., T, 1, hd)
    i = jnp.arange(hd)
    partner = jnp.where(i % 2 == 0, i + 1, i - 1)
    swap = (jnp.where(i % 2 == 0, -1.0, 1.0)[None, :]
            * (i[:, None] == partner[None, :])).astype(x.dtype)
    turned = jnp.einsum(
        "...d,de->...e", x, swap,
        precision=(jax.lax.Precision.HIGHEST
                   if jnp.dtype(x.dtype).itemsize >= 4 else None))
    return (x.astype(jnp.float32) * jnp.cos(ang)
            + turned.astype(jnp.float32) * jnp.sin(ang)).astype(x.dtype)


# ------------------------------------------------------ the expert layer
def grouped_matmul(lhs, rhs, group_sizes, out_dtype, interpret=False):
    """Rows of `lhs` (m, k), sorted by group, times their group's
    matrix of `rhs` (g, k, n): rows [sum(sizes[:i]), sum(sizes[:i+1]))
    meet rhs[i]. Rows past the last group come back as anything (the
    caller masks them). On a TPU, or in interpret mode, the megablox
    grouped-matmul kernel: its grid holds only the row tiles that have
    rows and it fetches only the matrices of groups that have any, in
    bf16 with f32 accumulation. Elsewhere `lax.ragged_dot`."""
    if interpret or jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        m, k = lhs.shape
        n = rhs.shape[2]
        # few rows (a decode step): small row tiles, the time is the
        # weights'; many (a prefill): tiles that reuse a fetched block
        tm = 32 if m <= 1024 else 256
        while m % tm:
            tm //= 2
        return gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                   preferred_element_type=out_dtype,
                   tiling=(tm, min(k, 1024), min(n, 1024)),
                   interpret=interpret)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=out_dtype)


def _pair_chunk(t: int, cfg) -> Tuple[int, int]:
    """(most pairs that can fall on held experts, rows of the sorted
    pairs taken at a time) for `t` tokens: a chunk is a quarter more
    than the pairs expected on this share, at least 256, never more than
    the most. The chunk bounds the buffers under imbalance; what a pass
    pays follows the pairs (a second chunk is taken only when they
    outnumber the first), and the few passes over a whole chunk that
    are left (the activation, `y` laid out as slabs) follow its size."""
    most = t * min(cfg.experts_per_token, cfg.n_held)
    most = -(-most // 256) * 256
    expected = t * cfg.experts_per_token * cfg.n_held // cfg.n_experts
    return most, min(most, max(256, -(-5 * expected // 4 // 256) * 256))


ROUTER_SCORES = {"sigmoid": jax.nn.sigmoid,
                 "softmax": lambda x: jax.nn.softmax(x, axis=-1)}
SHARED_COMBINES = ("average", "sigmoid_gate")


def expert_layer(p, h, cfg, valid=None):
    """Router, choice, grouped expert products over the pairs on held
    experts, weighted sum; shared experts as plain products, joined as
    the configuration says. The one expert layer of every model that
    has one: `cfg` is any configuration with the counts (`n_experts`,
    `experts_per_token`, `n_held`, `held_first`, `n_shared`) and the
    three choices `router_score`, `shared_combine` and `router_bias`.
    With `router_bias` the k chosen are the largest of score +
    `p["expert_bias"]` (n_experts,), and their weights their scores
    alone over the scores' sum + 1e-6 (the published `lfm2_moe`
    router); without it the k largest scores over their sum. With no
    shared expert (`n_shared` 0) the routed sum is the whole output.

    h: (T, d) normed activations; `valid` (T,) bool marks the tokens
    that are real (padding rows and idle slots route nowhere and count
    nowhere). Returns (out (T, d) f32 = routed_here + shared, pairs
    (n_held,) int32 by held expert)."""
    t, d = h.shape
    k, held = cfg.experts_per_token, cfg.n_held
    if valid is None:
        valid = jnp.ones((t,), bool)
    with jax.named_scope("moe_router"):
        scores = ROUTER_SCORES[cfg.router_score](jnp.dot(
            h.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))          # (T, E) f32
        if cfg.router_bias:
            _, chosen = jax.lax.top_k(
                scores + p["expert_bias"].astype(jnp.float32), k)
            top = jnp.take_along_axis(scores, chosen, axis=-1)
            weight = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-6)
        else:
            top, chosen = jax.lax.top_k(scores, k)
            weight = top / jnp.sum(top, axis=-1, keepdims=True)
        local = chosen - cfg.held_first
        here = (local >= 0) & (local < held) & valid[:, None]
        # group `held` = "not here": sorts behind every held expert
        group = jnp.where(here, local, held).reshape(-1)       # (T*k,)
        order = jnp.argsort(group, stable=True)
        counts = jnp.sum(group[:, None] == jnp.arange(held), axis=0,
                         dtype=jnp.int32)
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(counts)])
        n_pairs = starts[held]
        # the inverse of the sort: the sorted row of each (token, choice)
        sorted_at = jnp.argsort(order).astype(jnp.int32).reshape(t, k)
    with jax.named_scope("moe_experts"):
        most, chunk = _pair_chunk(t, cfg)
        order = jnp.pad(order, (0, max(0, most + chunk - order.shape[0])))
        ex = p["experts"]

        def take(lo):
            """Sorted pairs [lo, lo + chunk): their tokens' rows in,
            three grouped products, each token's weighted rows out.
            Only rows that hold a pair move (`models/moe_rows.py`)."""
            here_n = jnp.clip(n_pairs - lo, 0, chunk)
            pairs = jax.lax.dynamic_slice(order, (lo,), (chunk,))
            sizes = jnp.clip(starts[1:], lo, lo + chunk) \
                - jnp.clip(starts[:-1], lo, lo + chunk)
            x = rows_in(h, pairs // k, here_n, interpret=cfg.interpret)
            g = grouped_matmul(x, ex["gate"], sizes, h.dtype,
                               cfg.interpret)
            u = grouped_matmul(x, ex["up"], sizes, h.dtype,
                               cfg.interpret)
            act = (jax.nn.silu(g.astype(jnp.float32))
                   * u.astype(jnp.float32)).astype(h.dtype)
            y = grouped_matmul(act, ex["down"], sizes, jnp.float32,
                               cfg.interpret)
            row = sorted_at - lo
            return rows_out(
                y, jnp.where((row >= 0) & (row < here_n), row, -1),
                weight, interpret=cfg.interpret)

        if chunk >= most:
            routed = take(jnp.int32(0))
        else:
            # one body for every chunk: a program holds each kernel once
            # a layer (twice as many made a program's load from the
            # compile cache seconds longer; PERF.md section 6, PR 36)
            routed = jax.lax.fori_loop(
                0, -(-n_pairs // chunk),
                lambda c, out: out + take(c * chunk),
                jnp.zeros((t, d), jnp.float32))
    if not cfg.n_shared:
        return routed, counts
    with jax.named_scope("moe_shared"):
        if cfg.shared_combine not in SHARED_COMBINES:
            raise ValueError(f"shared_combine must be one of "
                             f"{SHARED_COMBINES}, got "
                             f"{cfg.shared_combine!r}")
        sh = p["shared"]
        gated = cfg.shared_combine == "sigmoid_gate"
        if gated:
            opened = jax.nn.sigmoid(jnp.dot(
                h, p["shared_gate"], preferred_element_type=jnp.float32))
        shared = jnp.zeros((t, d), jnp.float32)
        for j in range(cfg.n_shared):
            act = jax.nn.silu(h @ sh["gate"][j]) * (h @ sh["up"][j])
            out = jnp.dot(act, sh["down"][j],
                          preferred_element_type=jnp.float32)
            shared = shared + (out * opened[:, j:j + 1] if gated else out)
        if not gated:
            shared = shared / cfg.n_shared
    return routed + shared, counts


# ------------------------------------------------------------- the block
def block(p, x, positions, layer: int, cfg: MoEConfig, attend: Attend,
          valid=None):
    """One parallel block on x (B, T, d). `attend` (the contract is
    `models/transformer.Attend`'s) gets q, k and v with heads before
    positions, rotated where the layer's kind has positions. Returns
    (x', the cache's new state for this layer, pairs by held expert)."""
    b, t, d = x.shape
    kind = cfg.layer_kinds[layer]
    h = _gain_norm(p["ln"], x, cfg.ln_eps)
    q = _project(h, p["Wq"]).reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = _project(h, p["Wk"]).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = _project(h, p["Wv"]).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    if kind == KIND_WINDOW:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    att, state = attend(layer, kind, q.transpose(0, 2, 1, 3),
                        k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    attn = att.astype(x.dtype).transpose(0, 2, 1, 3).reshape(
        b, t, -1) @ p["Wo"]
    moe, pairs = expert_layer(
        p, h.reshape(b * t, d), cfg,
        None if valid is None else valid.reshape(b * t))
    x = (x.astype(jnp.float32) + attn.astype(jnp.float32)
         + moe.reshape(b, t, d)).astype(x.dtype)
    return x, state, pairs


def forward(params, tokens, positions, cfg: MoEConfig, attend: Attend,
            valid=None):
    """Every block over tokens (B, T) at `positions` (B, T), or (T,)
    where every row stands at the same ones. Returns (hidden (B, T, d)
    before the final norm, the cache states a layer, pairs (layers,
    n_held) int32)."""
    x = params["embed"][tokens]
    states, pairs = [], []
    for i, p in enumerate(params["blocks"]):
        x, state, n = block(p, x, positions, i, cfg, attend, valid)
        states.append(state)
        pairs.append(n)
    return x, tuple(states), jnp.stack(pairs)


def head(params, x, cfg: MoEConfig):
    """x (..., d) -> logits over the vocabulary rows held, f32."""
    x = _gain_norm(params["ln_f"], x, cfg.ln_eps)
    return cfg.logit_scale * jnp.dot(
        x, params["embed"].T, preferred_element_type=jnp.float32)


def logits(params, tokens, cfg: MoEConfig):
    """tokens (B, T) -> (B, T, vocab_size) f32, nothing cached."""
    x, _, _ = forward(
        params, tokens, jnp.arange(tokens.shape[1]), cfg,
        lambda _l, kind, q, k, v: (causal_attention(cfg, kind, q, k, v),
                                   None))
    return head(params, x, cfg)
