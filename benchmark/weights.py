"""Weights and token ids from `--seed`, and nothing else from it.

The weights are made on the device in one jitted call, in the type they
are served or trained in. The layout is the cell's family's
(`param_shapes`, `is_gain`); the plain reference builds the same tree
from the same seed by calling this module again, so it never takes an
array the program has held.
"""

from __future__ import annotations

import numpy as np

#: every leaf is N(0, STD), biases too, so that a path that drops one
#: is seen; the leaves the family calls gains are 1
STD = 0.02


def key_for(seed: int):
    """A PRNG key for any whole number up to 2**64: the driver's seeds
    pass 2**31, which a 32-bit `PRNGKey(seed)` cannot take."""
    import jax

    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed out of range: {seed}")
    data = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


def make_params(seed: int, family, config: dict):
    """The family's whole tree for this configuration on the device,
    one jitted call, in the type the configuration states."""
    import jax
    import jax.numpy as jnp

    shapes = family.param_shapes(config)
    dtype = jnp.dtype(config["dtype"])
    is_shape = lambda x: isinstance(x, tuple)  # noqa: E731
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=is_shape)

    def build(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if family.is_gain(jax.tree_util.keystr(path)):
                out.append(jnp.ones(shape, dtype))
            else:
                out.append((STD * jax.random.normal(
                    k, shape, jnp.float32)).astype(dtype))
        return out

    flat = jax.jit(build)(key_for(seed))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(shapes, is_leaf=is_shape), flat)


def token_ids(seed: int, stream: int, index: int, n: int,
              vocab: int) -> np.ndarray:
    """`n` token ids for item `index` of `stream` (0 = prompts, 1 =
    training rows, 2 = warm-up): the contents of a request, never its
    length."""
    rng = np.random.default_rng([int(seed), int(stream), int(index)])
    return rng.integers(0, vocab, size=n, dtype=np.int32)
