"""Self-attention layer for the layer registry.

Beyond-reference capability (the reference predates attention): a
single-head self-attention block usable in a MultiLayerNetwork stack on
(batch, T, d) inputs. On a TPU backend the forward computes through
`flash_attention` (the Pallas kernels for tile-aligned sequences, the
blockwise form for ragged ones); on any other backend it calls
`blockwise_attention` directly — same O(T) memory, and never the
Pallas interpreter. With a mesh configured, callers can swap the inner
call for `ring_attention` (sequence parallelism).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.attention.blockwise import blockwise_attention
from deeplearning4j_tpu.attention.flash_pallas import flash_attention
from deeplearning4j_tpu.nn.layers import (BaseLayer, apply_dropout,
                                          register_layer)


@register_layer("self_attention")
class SelfAttentionLayer(BaseLayer):
    """Wq/Wk/Wv projections + flash attention + Wo output proj.
    Config: n_in = model dim, n_out = total attention dim (defaults to
    n_in), n_heads = attention heads (n_out divisible by it), `causal` =
    causal masking. Params init through BaseLayer.init_params (none are
    bias-named, so all four get the weight-init scheme)."""

    def _dims(self):
        d_model = self.conf.n_in
        d_attn = self.conf.n_out or d_model
        n_heads = max(1, int(getattr(self.conf, "n_heads", 1)))
        if d_attn % n_heads:
            raise ValueError(
                f"attention dim {d_attn} not divisible by "
                f"n_heads {n_heads}")
        return d_model, d_attn, n_heads

    def is_causal(self) -> bool:
        return bool(self.conf.causal)

    def param_shapes(self) -> Dict[str, tuple]:
        d_model, d_attn, _ = self._dims()
        return {"Wq": (d_model, d_attn), "Wk": (d_model, d_attn),
                "Wv": (d_model, d_attn), "Wo": (d_attn, d_model)}

    def activate(self, params, x, *, rng: Optional[jax.Array] = None,
                 training: bool = False):
        """x: (B, T, d_model) -> (B, T, d_model)."""
        if x.ndim != 3:
            raise ValueError(
                f"self_attention expects (batch, time, dim), got {x.shape}")
        _, d_attn, n_heads = self._dims()
        d_head = d_attn // n_heads
        B, T, _ = x.shape
        cd = jnp.dtype(self.conf.compute_dtype)

        def heads(w):
            # (B, T, d_attn) -> (B, H, T, d_head)
            proj = x.astype(cd) @ w.astype(cd)
            return proj.reshape(B, T, n_heads, d_head).transpose(0, 2, 1, 3)

        q, k, v = heads(params["Wq"]), heads(params["Wk"]), heads(params["Wv"])
        attend = (flash_attention if jax.default_backend() == "tpu"
                  else blockwise_attention)
        out = attend(q, k, v, causal=self.is_causal())
        out = out.transpose(0, 2, 1, 3).reshape(B, T, d_attn)
        out = out.astype(jnp.dtype(self.conf.dtype)) @ params["Wo"]
        return apply_dropout(rng, out, self.conf.dropout, training)
