"""Which programs fix the layout of q, k and v (PERF.md section 6).

`transformer._project` fixes each projection's product row-major where a
row holds one position, a decode step's shape, so the compiler splits
the heads of a few rows instead of re-laying out the weights. The rule
follows the input's shape alone: the paged decode step of both blocks
holds the constraint once a projection a layer, and the prefill, the
verify step and the training step hold none (their programs are what
they were). And the constrained step still gives the uncached forward's
logits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import moe_transformer as moe
from deeplearning4j_tpu.models import transformer as tr
from deeplearning4j_tpu.serving import paged_kinds

PS, N_P, P = 4, 8, 11          # pages of 4; a prompt of 11, then a step

MODELS = {
    "gpt2": tr.TransformerConfig(vocab_size=17, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, max_len=64),
    "moe-window-full": moe.MoEConfig(
        vocab_size=17, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        d_ff=32, layer_kinds=("window", "full"), window=6, n_experts=8,
        experts_per_token=2, n_shared=1, n_held=4, held_first=2,
        max_len=64).check(),
}
CONSTRAINT = "@LayoutConstraint"


def _params(cfg):
    if isinstance(cfg, moe.MoEConfig):
        return moe.init_moe_params(jax.random.PRNGKey(0), cfg)
    return tr.init_transformer_params(jax.random.PRNGKey(0), cfg)


def _state(cfg):
    kinds = paged_kinds.kinds_of(cfg)
    pool = paged_kinds.init_pool(cfg, dict.fromkeys(kinds, N_P), PS)
    tables = {k: jnp.arange(N_P, dtype=jnp.int32)[None] for k in kinds}
    return pool, tables


def _lowered(fn, *args) -> str:
    return jax.jit(fn).lower(*args).as_text()


@pytest.mark.parametrize("model", sorted(MODELS))
def test_only_a_step_of_one_position_fixes_the_projections(model):
    cfg = MODELS[model]
    params = _params(cfg)
    pool, tables = _state(cfg)
    ids = {k: jnp.arange(4, dtype=jnp.int32)[None] for k in tables}
    row = jnp.zeros((1,), jnp.int32)
    step = _lowered(
        lambda p, tok, pool, ln: paged_kinds.decode_step(
            p, tok, pool, tables, ln, ln >= 0, cfg),
        params, row, pool, row)
    assert step.count(CONSTRAINT) == 3 * len(params["blocks"])
    prefill = _lowered(
        lambda p, tok, n, pool: paged_kinds.prefill(p, tok, n, pool, ids,
                                                    cfg),
        params, jnp.zeros((1, 16), jnp.int32), jnp.ones((1,), jnp.int32),
        pool)
    verify = _lowered(
        lambda p, tok, pool, ln: paged_kinds.verify_step(
            p, tok, pool, tables, ln, ln + 4, cfg),
        params, jnp.zeros((1, 4), jnp.int32), pool, row)
    assert CONSTRAINT not in prefill
    assert CONSTRAINT not in verify


def test_the_training_step_does_not_fix_the_projections():
    cfg = MODELS["gpt2"]._replace(interpret=True)
    params = tr.init_transformer_params(jax.random.PRNGKey(0), cfg)
    text = tr.make_train_step(cfg).lower(
        params, tr.init_velocity(params),
        jnp.zeros((2, 33), jnp.int32)).as_text()
    assert "dot_general" in text and CONSTRAINT not in text


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_step_gives_the_uncached_forward_s_last_logits(model, kernel):
    cfg = MODELS[model]._replace(interpret=kernel == "pallas")
    params = _params(cfg)
    tokens = np.random.RandomState(3).randint(0, 17, (P + 1,))
    pool, tables = _state(cfg)
    padded = np.zeros((1, 12), np.int32)
    padded[0, :P] = tokens[:P]
    _, pool, _ = paged_kinds.prefill(
        params, jnp.asarray(padded), jnp.asarray([P]), pool,
        {k: jnp.arange(3, dtype=jnp.int32)[None] for k in tables}, cfg)
    got, _, _ = paged_kinds.decode_step(
        params, jnp.asarray(tokens[P:]), pool, tables, jnp.asarray([P]),
        jnp.asarray([True]), cfg, kernel=kernel)
    uncached = (moe.logits if isinstance(cfg, moe.MoEConfig)
                else tr.transformer_logits)
    want = uncached(params, jnp.asarray(tokens[None]), cfg)[0, P]
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(want, np.float32), atol=1e-4)
