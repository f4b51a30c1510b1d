"""The third block (`models/hybrid_transformer.py`) at a small size on
the CPU: d 64; layers `l, l, l, f`; full layers of 4 query heads over 2
K/V heads of 16 (8 turning), linear layers of 2 key and 4 value heads of
8 with a convolution of 4; 16 experts of which 4 are held, 4 chosen, 1
shared behind its gate; an untied head.

The plain reference is the benchmark's (`benchmark/reference/
qwen3_next.py`, which imports nothing of the program and runs the
recurrence token by token), told the same share by the same
configuration file."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import qwen3_next as family
from deeplearning4j_tpu.models import hybrid_transformer as hybrid
from deeplearning4j_tpu.models import model_of, moe_transformer
from tests.benchmark_suite import tiny_hybrid

SEED = 2 ** 31 + 35


def _config(dtype="float32", **over):
    return dict(tiny_hybrid.CONFIG, dtype=dtype, **over)


def _tokens(shape, seed=1):
    return np.random.RandomState(seed).randint(0, 97, shape).astype(
        np.int32)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["plain", "kernels-interpreted"])
def test_uncached_forward_is_the_reference_s(interpret):
    """Whole rows, nothing cached, float32 on both sides: the chunked
    scan against the recurrence token by token, grouped products against
    plain ones, rotation by a constant matrix against slices. 2e-4 of
    the logits' scale (~0.7): what float32 sums in another order give
    over four layers (read 2e-6 to 4e-5)."""
    config = _config()
    cfg = family.model_config(config)._replace(interpret=interpret)
    assert model_of(cfg) is hybrid
    params = weights.make_params(SEED, family, config)
    toks = _tokens((2, 48))
    got = hybrid.logits(params, jnp.asarray(toks), cfg)
    want = family.reference().logits(config, params, jnp.asarray(toks), 0,
                                     48)
    assert got.shape == want.shape == (2, 48, 97)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4)
    # and the forward is causal: a later token moves no earlier logit
    again = hybrid.logits(params, jnp.asarray(toks).at[:, 30:].set(0), cfg)
    np.testing.assert_array_equal(np.asarray(got[:, :30]),
                                  np.asarray(again[:, :30]))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One expert layer held by eight chips, two of sixteen experts
    each: the shares' routed partial sums, and the shared expert counted
    ONCE, are the uncut reference's layer (each share's result holds the
    shared expert whole, so seven of the eight are taken off)."""
    config = _config(num_experts=16, router_width=16, held_experts_first=0)
    params = weights.make_params(SEED, family, config)
    p = params["blocks"][0]
    ref = family.reference()
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64), jnp.float32)
    want = ref._experts(p, x, ref.what_is_held(config), "f32")
    h = hybrid._rms_norm(p["ln2"], x, 1e-6)
    total, shared = 0.0, None
    for r in range(8):
        share = _config(num_experts=2, router_width=16,
                        held_experts_first=2 * r)
        cfg = family.model_config(share)
        held = dict(p, experts=jax.tree_util.tree_map(
            lambda a: a[2 * r:2 * r + 2], p["experts"]))
        out, pairs = moe_transformer.expert_layer(held, h, cfg)
        none_held, _ = moe_transformer.expert_layer(
            held, h, cfg, valid=jnp.zeros((40,), bool))
        shared = none_held           # no pair counted: the shared part
        total = total + (out - shared)
        assert int(pairs.sum()) <= 40 * 4
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(want), atol=2e-5)


def test_the_score_and_the_shared_gate_are_the_configuration_s():
    """`expert_layer` is one function for both expert families: the
    hybrid configuration says softmax and a sigmoid gate, the first
    family's says sigmoid and average, and an unknown word is an error
    that names the known ones."""
    cfg = family.model_config(_config())
    assert (cfg.router_score, cfg.shared_combine) == ("softmax",
                                                      "sigmoid_gate")
    moe_cfg = moe_transformer.MoEConfig(
        vocab_size=8, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
        d_ff=8, layer_kinds=("full",), window=4, n_experts=4,
        experts_per_token=2, n_shared=1, n_held=2)
    assert (moe_cfg.router_score, moe_cfg.shared_combine) == ("sigmoid",
                                                              "average")
    p = weights.make_params(SEED, family, _config())["blocks"][0]
    h = jnp.ones((4, 64), jnp.float32)
    with pytest.raises(ValueError, match="sigmoid_gate"):
        moe_transformer.expert_layer(
            p, h, cfg._replace(shared_combine="sum"))
    with pytest.raises(KeyError):
        moe_transformer.expert_layer(p, h, cfg._replace(router_score="x"))


def test_the_configuration_is_checked():
    cfg = family.model_config(_config())
    assert cfg.layer_kinds == ("linear", "linear", "linear", "full")
    assert cfg.window is None and cfg.n_layers == 4
    assert cfg.conv_channels == 2 * 2 * 8 + 4 * 8
    assert cfg.linear_state["state"][0] == (4, 8, 8)
    assert cfg.linear_state["conv"][0] == (3 * 64,)
    with pytest.raises(ValueError, match="key heads"):
        cfg._replace(lin_k_heads=3).check()
    with pytest.raises(ValueError, match="layer_kinds"):
        cfg._replace(layer_kinds=("window", "full")).check()
    with pytest.raises(ValueError, match="rotary_dim"):
        cfg._replace(rotary_dim=18).check()
    with pytest.raises(NotImplementedError, match="trains nothing"):
        family.make_train_step(_config(), None)
    with pytest.raises(NotImplementedError, match="trains nothing"):
        family.reference().loss_and_grad(_config(), None)


def test_rotation_turns_the_first_part_in_the_half_split_pairing():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 16))
    pos = jnp.arange(5)
    got = hybrid.rope_half(x, pos, 1e7, 8)
    want = family.reference().rotate_half_split(x[0], pos, 1e7, 8)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]),
                                  np.asarray(x[..., 8:]))
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(x[:, 0]),
                               atol=1e-7)      # position 0 turns nothing
