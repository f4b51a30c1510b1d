"""Expert (MoE) parallelism — beyond parity.

The reference predates mixture-of-experts entirely (SURVEY §2.8). This
is the TPU-native expert-parallel primitive completing the mesh-axis
family (dp/sp/tp/pp/ep): experts live sharded on an `expert` mesh axis,
tokens are gated top-1, and each device computes its local experts'
contribution for the tokens routed to them, combined with one `psum`
over the expert axis.

Design notes:
- Gating is a learned linear router with top-1 (switch-style) hard
  assignment; the gate probability scales the expert output so the
  router receives gradient (the straight-through-free formulation
  switch transformers use).
- Dispatch is the dense/masked formulation: every device multiplies the
  full token batch masked down to its experts' tokens. No token
  dropping, no capacity factor, deterministic — the right baseline for
  correctness and small expert counts; capacity-based all-to-all
  dispatch is a bandwidth optimization on top, not a semantic change.
- A `data` axis composes: tokens shard over `data`, experts over
  `expert`, giving ep x dp on one 2-D mesh (`jax.grad` handles the
  psum transposes).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

EXPERT_AXIS = "expert"


def init_moe_params(key, n_experts: int, d_in: int, d_hidden: int,
                    scale: float = 0.5):
    """Router + per-expert 2-layer MLP. W1: (E, d_in, d_hidden),
    W2: (E, d_hidden, d_in) — a standard MoE FFN block."""
    kg, k1, k2 = jax.random.split(key, 3)
    u = lambda k, shape, d: jax.random.uniform(  # noqa: E731
        k, shape, jnp.float32, -scale / d, scale / d)
    return {
        "gate": u(kg, (d_in, n_experts), d_in),
        "W1": u(k1, (n_experts, d_in, d_hidden), d_in),
        "b1": jnp.zeros((n_experts, 1, d_hidden), jnp.float32),
        "W2": u(k2, (n_experts, d_hidden, d_in), d_hidden),
        "b2": jnp.zeros((n_experts, 1, d_in), jnp.float32),
    }


def _expert_ffn(w1, b1, w2, b2, x, act):
    return act(x @ w1 + b1) @ w2 + b2


def moe_reference(params, x, act: Callable = jnp.tanh):
    """Unsharded ground truth: top-1 gate, run every expert densely,
    combine. x: (N, d_in)."""
    logits = x @ params["gate"]                      # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    choice = jnp.argmax(logits, axis=-1)             # (N,)
    n_experts = params["W1"].shape[0]
    out = jnp.zeros_like(x)
    for e in range(n_experts):
        mask = (choice == e)[:, None]
        y = _expert_ffn(params["W1"][e], params["b1"][e],
                        params["W2"][e], params["b2"][e], x, act)
        out = out + jnp.where(mask, probs[:, e:e + 1] * y, 0.0)
    return out


def moe_apply(params, x, mesh: Mesh, axis: str = EXPERT_AXIS,
              act: Callable = jnp.tanh,
              data_axis: Optional[str] = None):
    """Expert-parallel forward: experts sharded over `axis`, tokens
    (optionally) sharded over `data_axis`; one psum combines the local
    expert contributions. Matches moe_reference exactly."""
    ep = int(mesh.shape[axis])
    n_experts = params["W1"].shape[0]
    if n_experts % ep:
        raise ValueError(f"{n_experts} experts not divisible by "
                         f"expert-axis size {ep}")
    local = n_experts // ep

    def per_device(p, xb):
        # p's expert leaves have leading dim n_experts/ep; gate is
        # replicated so routing is identical everywhere
        logits = xb @ p["gate"]                      # (n_local_tokens, E)
        probs = jax.nn.softmax(logits, axis=-1)
        choice = jnp.argmax(logits, axis=-1)
        first = jax.lax.axis_index(axis) * local
        out = jnp.zeros_like(xb)
        for j in range(local):
            e = first + j
            mask = choice == e
            y = _expert_ffn(p["W1"][j], p["b1"][j], p["W2"][j],
                            p["b2"][j], xb, act)
            # unrouted tokens are zeroed by the gate mask, so the
            # psum-combined result equals the dense reference
            gp = jnp.where(mask, jnp.take(probs, e, axis=1), 0.0)
            out = out + gp[:, None] * y
        return jax.lax.psum(out, axis)

    param_specs = {"gate": P(), "W1": P(axis), "b1": P(axis),
                   "W2": P(axis), "b2": P(axis)}
    x_spec = P(data_axis) if data_axis else P()
    return shard_map(
        per_device, mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
    )(params, x)


def moe_apply_a2a(params, x, mesh: Mesh, axis: str = EXPERT_AXIS,
                  act: Callable = jnp.tanh,
                  data_axis: Optional[str] = None,
                  capacity_factor: float = 1.0,
                  return_stats: bool = False):
    """Capacity-factor all-to-all dispatch — the bandwidth-optimal form.

    Where `moe_apply` has every device touch the FULL token batch
    (dense masked compute, traffic O(N·d) via psum), this variant moves
    each token ONCE to the device owning its expert and once back:
    tokens shard over the expert axis (composed with `data_axis` when
    given), each device packs its local tokens into per-expert buffers
    of static capacity `ceil(capacity_factor · n_local / n_experts)`,
    one `all_to_all` delivers them to the owning devices, the local
    experts run, and a second `all_to_all` returns the outputs to be
    unpermuted and gate-scaled. Tokens beyond an expert's capacity are
    DROPPED (output 0) — switch-transformer semantics; with
    `capacity_factor >= n_experts` capacity covers every local token,
    nothing can drop, and the result matches `moe_reference` exactly
    (tested). Overflow rows land in a garbage slot (`cap` index of a
    cap+1-deep buffer) so they never overwrite kept tokens.

    `return_stats` additionally returns the number of dropped tokens
    (scalar, summed over all devices).
    """
    ep = int(mesh.shape[axis])
    n_experts = params["W1"].shape[0]
    if n_experts % ep:
        raise ValueError(f"{n_experts} experts not divisible by "
                         f"expert-axis size {ep}")
    local = n_experts // ep
    shards = ep * (int(mesh.shape[data_axis]) if data_axis else 1)
    n_tokens = x.shape[0]
    if n_tokens % shards:
        raise ValueError(f"{n_tokens} tokens not divisible by "
                         f"{shards} token shards")
    n_loc = n_tokens // shards
    cap = max(1, int(-(-capacity_factor * n_loc // n_experts)))  # ceil

    def per_device(p, xb):
        logits = xb @ p["gate"]                      # (n_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        choice = jnp.argmax(logits, axis=-1)         # (n_loc,)
        prob = jnp.take_along_axis(probs, choice[:, None], 1)[:, 0]
        # slot of each token within its expert's buffer = its rank among
        # local tokens choosing the same expert (deterministic,
        # first-come-first-served like the switch router)
        onehot = choice[:, None] == jnp.arange(n_experts)[None, :]
        ranks = jnp.cumsum(onehot, axis=0) - 1       # (n_loc, E)
        rank = jnp.take_along_axis(ranks, choice[:, None], 1)[:, 0]
        keep = rank < cap
        # overflow tokens scatter into the cap-index garbage slot
        slot = jnp.where(keep, rank, cap)
        buf = jnp.zeros((n_experts, cap + 1, xb.shape[-1]), xb.dtype)
        buf = buf.at[choice, slot].set(xb)[:, :cap]  # (E, cap, d)

        # deliver: chunk e of dim 0 goes to expert e's owner; received
        # row (s·local + j) = what device s packed for my local expert j
        recv = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                  tiled=True)        # (ep·local, cap, d)
        recv = recv.reshape(ep, local, cap, -1)

        ys = []
        for j in range(local):
            t = recv[:, j].reshape(ep * cap, -1)     # all tokens for my j
            yj = _expert_ffn(p["W1"][j], p["b1"][j], p["W2"][j],
                             p["b2"][j], t, act)
            ys.append(yj.reshape(ep, cap, -1))
        out_buf = jnp.stack(ys, axis=1)              # (ep, local, cap, d)
        out_buf = out_buf.reshape(ep * local, cap, -1)

        # return trip: symmetric all_to_all; back[e, c] = my token that
        # sat in slot c of the buffer I sent toward expert e
        back = jax.lax.all_to_all(out_buf, axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        gathered = back[choice, jnp.clip(slot, 0, cap - 1)]
        out = jnp.where(keep[:, None], prob[:, None] * gathered, 0.0)
        if not return_stats:
            return (out,)
        # stats cost extra collectives — only when asked for
        dropped = jax.lax.psum(jnp.sum(~keep), axis)
        if data_axis:
            dropped = jax.lax.psum(dropped, data_axis)
        return out, dropped

    param_specs = {"gate": P(), "W1": P(axis), "b1": P(axis),
                   "W2": P(axis), "b2": P(axis)}
    # tokens shard over data x expert (just expert on a 1-D mesh): the
    # all_to_all runs within each data group's expert peers
    x_spec = P((data_axis, axis)) if data_axis else P(axis)
    out_specs = (x_spec, P()) if return_stats else (x_spec,)
    res = shard_map(
        per_device, mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=out_specs,
    )(params, x)
    return res if return_stats else res[0]


def moe_grad_step(params, x, y, mesh: Mesh, axis: str = EXPERT_AXIS,
                  lr: float = 0.1, act: Callable = jnp.tanh,
                  data_axis: Optional[str] = None,
                  dispatch: str = "dense",
                  capacity_factor: float = 1.0):
    """One SGD step on MSE through the expert-parallel block.
    dispatch: 'dense' (masked psum combine) or 'a2a' (capacity-factor
    all-to-all)."""

    if dispatch not in ("dense", "a2a"):
        raise ValueError(f"unknown dispatch {dispatch!r}; "
                         "expected 'dense' or 'a2a'")

    def loss_fn(p):
        if dispatch == "a2a":
            out = moe_apply_a2a(p, x, mesh, axis, act, data_axis,
                                capacity_factor=capacity_factor)
        else:
            out = moe_apply(p, x, mesh, axis, act, data_axis)
        return jnp.mean((out - y) ** 2)

    # one compiled program: eagerly, every op inside the shard_map body
    # is its own tiny multi-device dispatch
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
    return params, loss


__all__ = ["EXPERT_AXIS", "init_moe_params", "moe_reference", "moe_apply",
           "moe_apply_a2a", "moe_grad_step"]
