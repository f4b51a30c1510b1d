"""Pipeline (stage) parallelism — beyond parity.

The reference is data-parallel only (SURVEY §2.8: TP/PP/SP "ABSENT in
reference"). This is GPipe-style microbatch pipelining expressed the TPU
way: stages live on a `pipe` mesh axis, activations travel between
neighboring stages via `ppermute` over ICI, and the schedule is a
`lax.scan` over S + M - 1 ticks (S stages, M microbatches) — the
pipeline bubble is exactly the (S-1)-tick fill/drain the schedule
implies. Autodiff runs straight through the scan + ppermute (the
transpose of a ppermute is the reverse ppermute), so one `jax.grad`
trains the whole pipeline; composing a `data` axis into the mesh gives
pp x dp with the gradient psum inserted by shard_map's transpose.

Scope: uniform stages (each stage = one dense block of identical shape,
params stacked on a leading stage axis). That is the honest shape of
GPipe — heterogeneous stages need per-stage programs, which is a
compiler-level feature, not a framework primitive.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

PIPE_AXIS = "pipe"


def init_pipeline_params(key, n_stages: int, width: int, scale=0.5):
    """Uniform stack: W (S, d, d), b (S, 1, d)."""
    kw, _ = jax.random.split(key)
    w = jax.random.uniform(kw, (n_stages, width, width), jnp.float32,
                           -scale / width, scale / width)
    return {"W": w, "b": jnp.zeros((n_stages, 1, width), jnp.float32)}


def sequential_apply(params, x, act: Callable = jnp.tanh):
    """Ground truth: apply the S stacked stages one after another.
    x: (..., width)."""
    s = params["W"].shape[0]
    for i in range(s):
        x = act(x @ params["W"][i] + params["b"][i])
    return x


def pipeline_apply(params, xm, mesh: Mesh, axis: str = PIPE_AXIS,
                   act: Callable = jnp.tanh,
                   data_axis: Optional[str] = None):
    """Run microbatches through the stage pipeline.

    params: {"W": (S, d, d), "b": (S, 1, d)} sharded over `axis`;
    xm: (M, B, d) microbatches (B sharded over `data_axis` if given).
    Returns (M, B, d) pipeline outputs == sequential_apply per microbatch.
    """
    s = int(mesh.shape[axis])
    if params["W"].shape[0] != s:
        raise ValueError(f"{params['W'].shape[0]} stages vs pipe={s}")
    m = xm.shape[0]
    perm = [(i, (i + 1) % s) for i in range(s)]

    def per_stage(p, xs):
        # local views: p leaves have a leading stage axis of length 1
        w = p["W"][0]
        b = p["b"][0]
        idx = jax.lax.axis_index(axis)
        # mark the (replicated) microbatches as device-varying over the
        # pipe axis so the scan carry types stay consistent once values
        # mix with the per-stage params (new shard_map's vma tracking;
        # a no-op under the older experimental API)
        if hasattr(jax.lax, "pcast"):
            xs = jax.lax.pcast(xs, (axis,), to="varying")
        elif hasattr(jax.lax, "pvary"):  # pre-pcast jax
            xs = jax.lax.pvary(xs, (axis,))
        buf = jnp.zeros_like(xs[0])   # activation arriving from the left
        outs = jnp.zeros_like(xs)     # last stage's collected outputs

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t while they last; later stages
            # consume what the previous tick's ppermute delivered
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, m - 1), keepdims=False)
            inp = jnp.where((idx == 0) & (t < m), feed, buf)
            out = act(inp @ w + b)
            nxt = jax.lax.ppermute(out, axis, perm)
            # the LAST stage finishes microbatch t-(S-1) at this tick
            mb = t - (s - 1)
            done = (idx == s - 1) & (mb >= 0)
            slot = jnp.clip(mb, 0, m - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, slot, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(done, out, cur), slot, axis=0)
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs),
                                    jnp.arange(s + m - 1))
        # outputs exist only on the last stage; psum with masking
        # broadcasts them pipeline-wide (zero elsewhere)
        return jax.lax.psum(jnp.where(idx == s - 1, outs, 0.0), axis)

    batch_dim = P(*([None, data_axis] if data_axis else [None]))
    return shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), batch_dim),
        out_specs=batch_dim,
    )(params, xm)


def pipeline_grad_step(params, xm, ym, mesh: Mesh, axis: str = PIPE_AXIS,
                       lr: float = 0.1, act: Callable = jnp.tanh,
                       data_axis: Optional[str] = None):
    """One SGD step through the pipeline (mean-squared error over all
    microbatches); returns (params, loss). Grad flows backward through
    the scan/ppermute schedule — the pp analogue of backprop's reverse
    pipeline pass."""

    def loss_fn(p):
        out = pipeline_apply(p, xm, mesh, axis, act, data_axis)
        return jnp.mean((out - ym) ** 2)

    # one compiled program: eagerly, every op inside the shard_map body
    # is its own tiny multi-device dispatch
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
    return params, loss


__all__ = ["PIPE_AXIS", "init_pipeline_params", "sequential_apply",
           "pipeline_apply", "pipeline_grad_step"]
