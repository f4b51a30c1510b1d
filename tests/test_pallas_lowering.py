"""Cross-lower every Pallas call for TPU on the CPU.

`jax.export.export(jax.jit(f), platforms=["tpu"])` runs the Pallas ->
Mosaic lowering without a chip, which is where the paged-decode kernel
was refused before PR 21 (its matrix-vector dots had no free dimension
on the left: `'lhs_non_contracting_dims'` failed to parse). The
interpreter never sees that layer, so tier-1 did not either. What this
cannot see is Mosaic's own compile (VMEM, layouts): that is the
`-m tpu` lane's and `chip_smoke.py`'s job."""

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.attention.flash_pallas import flash_attention
from deeplearning4j_tpu.attention.paged_pallas import paged_attention
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_transformer_params)
from deeplearning4j_tpu.serving import paged_kinds
from deeplearning4j_tpu.serving.paged_kv import (init_paged_pool,
                                                 pages_per_slot)

pytestmark = pytest.mark.pallas


def tpu_module(fn, *args) -> str:
    """The module lowered for a TPU. A decode step fixes its projections'
    layout (`transformer._project`), a custom call that XLA resolves
    when it compiles and that an export, which would keep the module,
    refuses unless told: nothing here keeps it."""
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
    return jax.export.export(
        jax.jit(fn), platforms=["tpu"],
        disabled_checks=[jax.export.DisabledSafetyCheck.custom_call(
            "LayoutConstraint")])(*shapes).mlir_module()


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("h,hd", [(8, 128), (16, 64)])
def test_paged_attention_lowers(h, hd, dtype):
    s, ps, n_p = 8, 16, 8
    pool = sds((s * n_p + 1, h, ps, hd), dtype)
    text = tpu_module(
        paged_attention, sds((s, h, hd), dtype), pool, pool,
        sds((s, n_p), "int32"), sds((s,), "int32"))
    assert 'kernel_name = "paged_decode_attention"' in text


def test_paged_attention_lowers_at_the_served_shape_with_blocks():
    """`cgpt13b-decode-sat`'s call (S16, H16, hd128, pages of 16, 128
    columns, bf16): 8 pages a block, the pools left in HBM and each
    page copied by the kernel itself, which the one-page kernel of the
    cases above does not do."""
    from deeplearning4j_tpu.attention.paged_pallas import block_pages

    s, h, hd, ps, n_p = 16, 16, 128, 16, 128
    assert block_pages(ps, h, hd, "bfloat16", n_p) == 8
    pool = sds((2561, h, ps, hd), "bfloat16")
    args = (sds((s, h, hd), "bfloat16"), pool, pool,
            sds((s, n_p), "int32"), sds((s,), "int32"))
    text = tpu_module(paged_attention, *args)
    assert text.count('kernel_name = "paged_decode_attention"') == 1
    jaxpr = str(jax.make_jaxpr(paged_attention)(*args))
    assert "dma_start" in jaxpr and "dma_wait" in jaxpr
    small = (sds((8, 8, 128), "bfloat16"), sds((65, 8, 8, 128), "bfloat16"),
             sds((65, 8, 8, 128), "bfloat16"), sds((8, 8), "int32"),
             sds((8,), "int32"))
    assert "dma_start" not in str(jax.make_jaxpr(paged_attention)(*small))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_and_verify_steps_lower_with_the_kernel(dtype):
    """The two programs `DecodeLoop` jits around the kernel, at the
    smoke's width (one layer: the lowering is per call site)."""
    cfg = TransformerConfig(vocab_size=256, d_model=1024, n_heads=8,
                            n_layers=1, d_ff=256, max_len=256,
                            dtype=jnp.dtype(dtype))
    s, ps, w = 4, 16, 3
    n_p = pages_per_slot(cfg, ps)
    params = jax.eval_shape(
        lambda: init_transformer_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: init_paged_pool(cfg, s * n_p, ps))
    table, lengths = sds((s, n_p), "int32"), sds((s,), "int32")
    text = tpu_module(
        lambda p, t, pool, tb, ln, act: paged_kinds.decode_step(
            p, t, pool, {"full": tb}, ln, act, cfg, kernel="pallas"),
        params, sds((s,), "int32"), pool, table, lengths, sds((s,), "bool"))
    assert text.count('kernel_name = "paged_decode_attention"') == 1
    text = tpu_module(
        lambda p, t, pool, tb, ln, wd: paged_kinds.verify_step(
            p, t, pool, {"full": tb}, ln, wd, cfg, kernel="pallas"),
        params, sds((s, w), "int32"), pool, table, lengths, lengths)
    # one single-query pass per draft column, the kernel lowered once
    # a shape (PR 31: `paged_attention`'s body is jitted)
    assert text.count('kernel_name = "paged_decode_attention"') == 1
    assert text.count("call @_paged_attention(") == w


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d", [64, 128])
def test_flash_forward_and_grad_lower(d, dtype):
    x = sds((2, 1024 // d, 256, d), dtype)
    text = tpu_module(lambda q, k, v: flash_attention(q, k, v, True),
                      x, x, x)
    assert 'kernel_name = "flash_fwd"' in text

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True).astype(jnp.float32))

    text = tpu_module(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f'kernel_name = "{name}"' in text
