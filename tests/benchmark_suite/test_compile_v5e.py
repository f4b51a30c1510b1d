"""Compile-only, for a v5e that is described and not attached: the
kernels of the four cells at their real widths, through the TPU's own
compiler (Mosaic's VMEM and layout checks included), about two seconds
each. They guard every later PR at no chip time.

The topology is described inside a module fixture, never at import, and
the tests skip where it cannot be described. All of them live in this
one file: the worker that is given it loads libtpu and keeps it."""

import os

import pytest

pytestmark = pytest.mark.pallas


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to JAX's persistent
    cache but cannot be read back without one; keep these out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def compile_for(fn, one_chip, *shapes):
    import jax
    import jax.numpy as jnp

    args = [jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def test_paged_decode_attention_h16_hd128_page16_bf16(one_chip,
                                                      no_compile_cache):
    """`cgpt13b-*`: 16 slots, 2560 pages and the trash page."""
    from deeplearning4j_tpu.attention.paged_pallas import paged_attention

    pool = ((2561, 16, 16, 128), "bfloat16")
    text = compile_for(paged_attention, one_chip,
                       ((16, 16, 128), "bfloat16"), pool, pool,
                       ((16, 128), "int32"), ((16,), "int32"))
    assert "paged_decode_attention" in text


@pytest.mark.parametrize("rows,t", [(1, 1024), (1, 2048), (16, 256)])
def test_flash_fwd_hd128_prefill_buckets(one_chip, no_compile_cache,
                                         rows, t):
    """`cgpt13b-*` prefill: 16 heads of 128 at the buckets the traffic
    touches."""
    from deeplearning4j_tpu.attention.flash_pallas import flash_attention

    x = ((rows, 16, t, 128), "bfloat16")
    text = compile_for(lambda q, k, v: flash_attention(q, k, v, True),
                       one_chip, x, x, x)
    assert "flash_fwd" in text


@pytest.mark.parametrize("rows,t", [(8, 1024), (64, 128)])
def test_flash_fwd_and_both_backward_kernels_hd64(one_chip,
                                                  no_compile_cache,
                                                  rows, t):
    """`gpt2m-train-*`: 16 heads of 64, forward and backward."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.attention.flash_pallas import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True).astype(jnp.float32))

    x = ((rows, 16, t, 64), "bfloat16")
    text = compile_for(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                       x, x, x)
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in text, kernel
