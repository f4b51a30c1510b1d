"""The gated delta rule's two kernels (`attention/gdn_pallas.py`)
against the recurrence token by token, on the CPU: the chunk arithmetic
as plain `jax.numpy` and the same kernels through the Pallas
interpreter. The benchmark's weights put the decay near 0.5 a token, at
which a state remembers a few tokens and a wrong carry between chunks
would hardly show; here the decay is ~0.997 a token over 600 tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import qwen3_next as family
from deeplearning4j_tpu.attention import gdn_pallas as gdn
from deeplearning4j_tpu.models import hybrid_transformer as hybrid
from tests.benchmark_suite import tiny_hybrid

pytestmark = pytest.mark.pallas


def _recurrence(q, k, v, g, beta):
    """(o (B, H, T, dv), S (B, H, dk, dv)) by the definition."""
    def one_head(q, k, v, g, beta):
        def step(s, now):
            q, k, v, g, b = now
            s = s * jnp.exp(g)
            s = s + jnp.outer(k, b * (v - s.T @ k))
            return s, s.T @ q
        s, o = jax.lax.scan(
            step, jnp.zeros((q.shape[1], v.shape[1]), jnp.float32),
            (q, k, v, g, beta))
        return o, s
    with jax.default_matmul_precision("highest"):
        return jax.vmap(jax.vmap(one_head))(q, k, v, g, beta)


def _case(t, decay, dk=16, dv=8, heads=3, rows=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (rows, heads, t, dk))
    k = jax.random.normal(ks[1], (rows, heads, t, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (rows, heads, t, dv))
    g = jnp.log(decay) * jax.random.uniform(
        ks[3], (rows, heads, t), minval=0.5, maxval=1.5)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, heads, t)))
    return q, k, v, g, beta


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["plain", "kernel-interpreted"])
@pytest.mark.parametrize("decay", [0.997, 0.5, 1e-3])
def test_the_chunked_scan_is_the_recurrence(decay, interpret):
    """640 tokens = ten chunks of 64, float32: 1e-5 of the output's
    scale. A decay of 1e-3 a token is where a form that divides by a
    running decay would overflow; this one never raises e above 0."""
    q, k, v, g, beta = _case(640, decay)
    want_o, want_s = _recurrence(q, k, v, g, beta)
    o, s = gdn.gdn_scan(q, k, v, g, beta, interpret=interpret)
    scale = float(jnp.max(jnp.abs(want_o)))
    assert float(jnp.max(jnp.abs(o - want_o))) < 1e-5 * max(scale, 1.0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                               atol=1e-5)


def test_keys_that_are_all_alike_do_not_break_the_inverse():
    """Every key the same and beta 1: the strictly lower matrix is all
    ones times the decay, the worst case for a Neumann product. Blocks
    of 16 keep the intermediate powers small enough for float32."""
    q, k, v, g, beta = _case(128, 0.999, rows=1, heads=1)
    k = jnp.broadcast_to(k[:, :, :1], k.shape)
    beta = jnp.ones_like(beta)
    want_o, want_s = _recurrence(q, k, v, g, beta)
    o, s = gdn.gdn_scan(q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                               atol=2e-3)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["plain", "kernel-interpreted"])
def test_padding_does_not_move_the_state(interpret):
    """600 real tokens in a row padded to 1,024 with g = beta = 0: the
    state after the last chunk is the recurrence's at token 600."""
    q, k, v, g, beta = _case(1024, 0.997, rows=1)
    real = jnp.arange(1024) < 600
    g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    _, want_s = _recurrence(q[:, :, :600], k[:, :, :600], v[:, :, :600],
                            g[:, :, :600], beta[:, :, :600])
    _, s = gdn.gdn_scan(q, k, v, g, beta, interpret=interpret)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                               atol=1e-5)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["plain", "kernel-interpreted"])
def test_the_update_is_one_step_and_leaves_an_idle_slot_alone(interpret):
    q, k, v, g, beta = _case(65, 0.9, rows=5, heads=4)
    _, state = _recurrence(q[:, :, :64], k[:, :, :64], v[:, :, :64],
                           g[:, :, :64], beta[:, :, :64])
    want_o, want_s = _recurrence(q, k, v, g, beta)
    at = (slice(None), slice(None), 64)
    live = jnp.asarray([True, False, True, True, False])[:, None]
    o, s = gdn.gdn_update(state, q[at], k[at], v[at],
                          jnp.where(live, g[at], 0.0),
                          jnp.where(live, beta[at], 0.0),
                          interpret=interpret)
    rows = np.asarray(live[:, 0])
    np.testing.assert_allclose(np.asarray(s)[rows],
                               np.asarray(want_s)[rows], atol=1e-6)
    np.testing.assert_allclose(np.asarray(o)[rows],
                               np.asarray(want_o[:, :, 64])[rows],
                               atol=1e-6)
    # g = beta = 0: the state comes back bit for bit
    np.testing.assert_array_equal(np.asarray(s)[~rows],
                                  np.asarray(state)[~rows])


def test_a_row_padded_to_its_bucket_keeps_the_last_real_columns():
    """`linear_mix` as the paged prefill calls it, `dt_bias` set so that
    the decay is ~0.997 a token: 530 real tokens in the 1,024 bucket give
    the rows, the state and the kept convolution columns of the same 530
    tokens alone; one more token through the update is the scan of 531."""
    config = dict(tiny_hybrid.CONFIG, dtype="float32")
    cfg = family.model_config(config)
    p = weights.make_params(7, family, config)["blocks"][0]
    p = dict(p, dt_bias=jnp.full_like(p["dt_bias"], -5.8))
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 1024, 64))
    seen = {}

    def attend_with(**how):
        def attend(_layer, _kind, u, gates, conv_w):
            seen["g"] = gates[0]
            seen["call"] = (u, gates, conv_w)
            o, entry = hybrid.linear_mix(cfg, u, gates, conv_w, **how)
            seen["entry"] = entry
            return o, entry
        return attend

    padded, _ = hybrid._linear_layer(
        p, h, cfg, attend_with(true_len=jnp.asarray([530])), 0)
    entry = seen["entry"]
    decay = float(jnp.exp(jnp.mean(seen["g"])))
    assert 0.996 < decay < 0.998, decay
    alone, _ = hybrid._linear_layer(p, h[:, :530], cfg, attend_with(), 0)
    np.testing.assert_allclose(np.asarray(padded[:, :530]),
                               np.asarray(alone), atol=1e-5)
    for name in ("state", "conv"):
        np.testing.assert_allclose(np.asarray(entry[name]),
                                   np.asarray(seen["entry"][name]),
                                   atol=1e-5)
    assert float(jnp.max(jnp.abs(entry["state"]))) > 1e-3   # it holds
    # the decode step's call: token 530 on top of what was kept
    hybrid._linear_layer(p, h[:, :531], cfg, attend_with(), 0)
    want = seen["entry"]
    u, gates, conv_w = seen["call"]
    _, got = hybrid.linear_mix(
        cfg, u[:, 530:531], tuple(x[:, 530:531] for x in gates), conv_w,
        prev=entry["conv"], state=entry["state"])
    for name in ("state", "conv"):
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), atol=1e-5)
    # several tokens on top of what was kept (a later piece of a
    # prompt prefilled in pieces; PR 37, which took the refusal that
    # stood here away): tokens 530..533 are the whole scan's
    hybrid._linear_layer(p, h[:, :534], cfg, attend_with(), 0)
    u, gates, conv_w = seen["call"]
    _, got = hybrid.linear_mix(
        cfg, u[:, 530:534], tuple(x[:, 530:534] for x in gates), conv_w,
        prev=entry["conv"], state=entry["state"])
    for name in ("state", "conv"):
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(seen["entry"][name]),
                                   atol=1e-5)
