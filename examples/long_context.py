"""Long-context attention, three ways (all beyond the 2015 reference):
1. flash_attention — Pallas TPU kernel (blockwise/interpret off-TPU)
2. blockwise_attention — pure-JAX O(T) memory reference
3. ring_attention — sequence parallelism over a device mesh (dp x sp)
"""
import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.attention.blockwise import blockwise_attention
from deeplearning4j_tpu.attention.flash_pallas import flash_attention
from deeplearning4j_tpu.attention.ring import ring_attention
from deeplearning4j_tpu.parallel import make_mesh
from deeplearning4j_tpu.utils import jaxenv

jaxenv.configure()  # compile cache + platform pin, before JAX starts

B, H, S, D = 2, 4, 1024, 64
key = jax.random.PRNGKey(0)
kq, kk, kv = jax.random.split(key, 3)
q = jax.random.normal(kq, (B, H, S, D), jnp.bfloat16)
k = jax.random.normal(kk, (B, H, S, D), jnp.bfloat16)
v = jax.random.normal(kv, (B, H, S, D), jnp.bfloat16)

on_tpu = jax.devices()[0].platform == "tpu"
out_flash = flash_attention(q, k, v, causal=True, interpret=not on_tpu)
out_block = blockwise_attention(q, k, v, causal=True)
err = float(jnp.max(jnp.abs(out_flash.astype(jnp.float32)
                            - out_block.astype(jnp.float32))))
print(f"flash vs blockwise on {jax.devices()[0].platform}: max err {err:.4f}")

n = len(jax.devices())
if n >= 2 and S % n == 0:
    # sequence-sharded: each device holds S/n of the sequence; K/V rotate
    # via ppermute so every query attends to every key
    mesh = make_mesh({"sp": n})
    q3, k3, v3 = (a.reshape(B * H, S, D) for a in (q, k, v))
    out_ring = ring_attention(q3, k3, v3, mesh, axis="sp", causal=True)
    err = float(jnp.max(jnp.abs(out_ring.reshape(B, H, S, D).astype(jnp.float32)
                                - out_block.astype(jnp.float32))))
    print(f"ring over {n} devices: max err {err:.4f}")
else:
    print(f"ring attention needs >1 device (have {n}); try "
          "XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu")
