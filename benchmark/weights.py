"""Weights and token ids from `--seed`, and nothing else from it.

The weights are made on the device in one jitted call, in the type they
are served or trained in. The layout is the one `models/transformer.py`
takes (embed, pos, ln_f, blocks of ln1/Wq/Wk/Wv/Wo/ln2/W1/b1/W2/b2);
the plain reference builds the same tree from the same seed by calling
this module again, so it never takes an array the program has held.
"""

from __future__ import annotations

import numpy as np

#: every matrix, the embedding and the positions are N(0, STD); biases
#: too, so that a path that drops one is seen; LayerNorm gains are 1
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


def leaf_shapes(shape_cfg: dict) -> dict:
    """The parameter tree as shapes: {"embed": (V, d), ..., "blocks":
    [{...}] * n_layers}. `shape_cfg` has vocab_size, d_model, n_layers,
    d_ff, max_len."""
    v, d = shape_cfg["vocab_size"], shape_cfg["d_model"]
    f, t = shape_cfg["d_ff"], shape_cfg["max_len"]
    block = {"ln1": {"g": (d,), "b": (d,)},
             "Wq": (d, d), "Wk": (d, d), "Wv": (d, d), "Wo": (d, d),
             "ln2": {"g": (d,), "b": (d,)},
             "W1": (d, f), "b1": (f,), "W2": (f, d), "b2": (d,)}
    return {"embed": (v, d), "pos": (t, d),
            "ln_f": {"g": (d,), "b": (d,)},
            "blocks": [block for _ in range(shape_cfg["n_layers"])]}


def make_params(seed: int, shape_cfg: dict, dtype):
    """The whole tree on the device, one jitted call."""
    import jax
    import jax.numpy as jnp

    shapes = leaf_shapes(shape_cfg)
    is_shape = lambda x: isinstance(x, tuple)  # noqa: E731
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=is_shape)

    def build(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if getattr(path[-1], "key", None) == "g":
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
