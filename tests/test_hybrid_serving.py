"""A model with linear layers through the cache manager, at a small
size on the CPU (the sizes of `tests/test_hybrid_model.py`): the paged
prefill and the decode step against the reference's full forward, a
slot retired and taken again, what the `linear` kind refuses, what
`snapshot()` says of it, and the two attention kernels at heads of 256
through the Pallas interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import qwen3_next as family
from deeplearning4j_tpu.models import hybrid_transformer as hybrid
from deeplearning4j_tpu.serving import decode_loop as dl
from deeplearning4j_tpu.serving import paged_kinds as pk
from deeplearning4j_tpu.serving.engine import InferenceEngine
from deeplearning4j_tpu.telemetry import exposition
from tests.benchmark_suite import tiny_hybrid

PS, SEED, SLOTS, PAGES = 4, 2 ** 31 + 35, 3, 20


def _config(dtype="float32", **over):
    return dict(tiny_hybrid.CONFIG, dtype=dtype, **over)


def _tokens(n, seed=1):
    return np.random.RandomState(seed).randint(0, 97, (n,)).astype(np.int32)


def _loud(params):
    """The blocks' matrices scaled by 8: at N(0, 0.02) the tiny model
    repeats one token for ever and a stale state would serve the same
    tokens as a sound one."""
    return dict(params, blocks=jax.tree_util.tree_map(
        lambda a: a * 8 if a.ndim >= 2 else a, params["blocks"]))


def _through_the_cache(cfg, params, toks, plen, slot, kernel="gather"):
    """Teacher-forced logits of positions plen-1 .. len(toks)-1: the
    paged prefill of the first `plen` tokens into `slot`, then one
    decode step a token over all slots, the others idle."""
    pool = pk.init_pool(cfg, {"full": PAGES}, PS, slots=SLOTS)
    tb = 32
    padded = np.zeros((2, tb), np.int32)     # row 1 is a padding row
    padded[0, :plen] = toks[:plen]
    ids = np.full((2, tb // PS), PAGES, np.int32)
    ids[0, :-(-plen // PS)] = np.arange(-(-plen // PS))
    lg, pool, pairs = jax.jit(lambda *a: pk.prefill(*a, cfg))(
        params, jnp.asarray(padded), jnp.asarray([plen, 1]), pool,
        {"full": jnp.asarray(ids),
         "linear": jnp.asarray([slot, SLOTS], jnp.int32)})
    assert pairs.shape == (4, 4)
    out = [np.asarray(lg[0])]
    table = np.full((SLOTS, 16), PAGES, np.int32)
    table[slot] = np.arange(16)
    active = np.zeros((SLOTS,), bool)
    active[slot] = True
    step = jax.jit(lambda *a: pk.decode_step(*a, cfg, kernel=kernel))
    for pos in range(plen, len(toks)):
        tokens = np.zeros((SLOTS,), np.int32)
        tokens[slot] = toks[pos]
        lengths = np.zeros((SLOTS,), np.int32)
        lengths[slot] = pos
        lg, pool, _ = step(params, jnp.asarray(tokens), pool,
                           {"full": jnp.asarray(table)},
                           jnp.asarray(lengths), jnp.asarray(active))
        out.append(np.asarray(lg[slot]))
    return np.stack(out), pool


@pytest.mark.parametrize("kernel,interpret", [("gather", False),
                                              ("pallas", True)])
def test_prefill_then_decode_is_the_reference_s_forward(kernel, interpret):
    """21 tokens through the paged prefill (in the 32 bucket, beside a
    padding row whose slot is past the last), then 14 decode steps in
    slot 1 of 3: every position's logits are the float32 reference's
    full forward over the same 35 tokens. 3e-4 of a scale of ~0.7: the
    chunked scan, the one-token update and the paged read each sum in
    another order than the recurrence (read 1e-5 to 6e-5)."""
    config = _config()
    cfg = family.model_config(config)._replace(interpret=interpret)
    params = weights.make_params(SEED, family, config)
    toks = _tokens(35)
    got, pool = _through_the_cache(cfg, params, toks, 21, 1, kernel)
    want = family.reference().logits(config, params,
                                     jnp.asarray(toks[None]), 20, 35)[0]
    np.testing.assert_allclose(got, np.asarray(want), atol=3e-4)
    # the idle slots' state was never touched, the padding row's never
    # written: zeros as they were made
    for layer in pool.layers[:3]:
        for name in ("state", "conv"):
            rest = np.asarray(layer[name])[[0, 2]]
            assert not rest.any(), name
        assert np.asarray(layer["state"])[1].any()


def _engine(params, cfg, **kw):
    return InferenceEngine.for_hybrid_transformer(
        params, cfg, decode_slots=2, page_size=PS, kv_pages=32,
        prefill_tokens_per_pass=32, decode_kernel="gather", **kw)


def test_a_slot_taken_again_serves_the_reference_s_tokens():
    """Seven requests over two slots: every slot is retired and taken
    again, twice and more, and every request is served the tokens the
    float32 reference decodes greedily from its own prompt. A state
    left over from the slot's last owner would show from the first
    token on (the weights are loud)."""
    config = _config()
    cfg = family.model_config(config)
    params = _loud(weights.make_params(SEED, family, config))
    ref = family.reference()
    prompts = [_tokens(n, seed=n) for n in (21, 9, 30, 17, 5, 26, 13)]
    lens = [10, 14, 8, 12, 16, 6, 9]
    eng = _engine(params, cfg)
    try:
        loop = eng.decode_loop
        streams = loop.submit_many(prompts, lens, prefix_cache=False)
        served = [s.result(timeout=300) for s in streams]
        snap = loop.snapshot()
    finally:
        eng.close()
    for prompt, n, got in zip(prompts, lens, served):
        seq = list(prompt)
        for _ in range(n):
            lg = ref.logits(config, params, jnp.asarray([seq], jnp.int32),
                            len(seq) - 1, len(seq))
            seq.append(int(jnp.argmax(lg[0, 0])))
        assert list(got) == seq[len(prompt):]
        assert len(set(got)) > 2           # loud enough to tell
    assert snap["decode_step_programs"] == 1
    assert snap["requests"] == 7 and snap["tokens_streamed"] == sum(lens)
    per_slot = 3 * (4 * 8 * 8 * 4 + 3 * 64 * 4)
    assert snap["state"] == {"bytes": 2 * per_slot,
                             "bytes_per_slot": per_slot, "layers": 3,
                             "slots_live": 0}
    assert set(snap["pages_by_kind"]) == {"full"}
    assert snap["pages_by_kind"]["full"]["layers"] == 1
    assert snap["moe"]["n_held"] == 4 and snap["moe"]["tokens"] > 0
    assert snap["pool_bytes"] == 33 * 2 * 2 * PS * 16 * 4


def test_the_linear_kind_has_its_gauges():
    config = _config()
    cfg = family.model_config(config)
    params = weights.make_params(SEED, family, config)
    loop = dl.DecodeLoop(params, cfg, slots=2, page_size=PS, n_pages=32,
                         prefix_cache=False, kernel="gather", start=False,
                         name="hybrid-gauges")
    text = exposition.render_prometheus()
    per_slot = 3 * (4 * 8 * 8 * 4 + 3 * 64 * 4)
    assert (f'dl4j_state_bytes{{kind="linear",loop="hybrid-gauges"}} '
            f'{2 * per_slot}') in text.replace(".0\n", "\n")
    assert 'dl4j_state_slots_live{loop="hybrid-gauges"} 0' in \
        text.replace(".0\n", "\n")
    assert loop.state_bytes() == 2 * per_slot
    loop.submit(_tokens(9), 4, prefix_cache=False)
    loop.tick()
    assert loop.snapshot()["state"]["slots_live"] == 1
    loop.run_until_idle()
    assert loop.snapshot()["state"]["slots_live"] == 0


@pytest.mark.parametrize("kw,what", [
    ({"prefix_cache": True}, "prefix sharing"),
    ({"prefix_cache": True}, "copy-on-write"),
    ({"prefix_cache": True}, "/kv/export"),
    ({"speculation": 2}, "speculation"),
    ({"horizon": 2}, "horizon > 1"),
    ({"role": "prefill", "fleet_kv": "on"}, "prefill-role"),
], ids=["prefix", "cow", "export", "speculation", "horizon", "role"])
def test_what_counts_on_page_reuse_is_refused_by_name(kw, what):
    config = _config()
    cfg = family.model_config(config)
    params = weights.make_params(SEED, family, config)
    args = dict({"prefix_cache": False}, **kw)
    with pytest.raises(ValueError) as e:
        dl.DecodeLoop(params, cfg, slots=2, page_size=PS, n_pages=32,
                      kernel="gather", start=False, **args)
    assert what in str(e.value) and "linear layers" in str(e.value)
    # the device side says so too, should a caller come past the loop
    pool = pk.init_pool(cfg, {"full": 8}, PS, slots=2)
    with pytest.raises(NotImplementedError, match="linear kind"):
        pk.verify_step(params, jnp.zeros((2, 2), jnp.int32), pool,
                       {"full": jnp.zeros((2, 16), jnp.int32)},
                       jnp.zeros((2,), jnp.int32),
                       jnp.ones((2,), jnp.int32), cfg)


@pytest.mark.pallas
def test_the_paged_kernel_and_flash_take_heads_of_256():
    """16 query heads over 2 K/V heads of 256, pages of 128, through the
    interpreter: the paged kernel against the dense gather's masked
    softmax, the flash forward against blockwise attention."""
    from deeplearning4j_tpu.attention.blockwise import (blockwise_attention,
                                                        masked_attention)
    from deeplearning4j_tpu.attention.flash_pallas import flash_attention
    from deeplearning4j_tpu.attention.paged_pallas import (block_pages,
                                                           paged_attention)

    assert block_pages(128, 2, 256, jnp.bfloat16, 64) == 1
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    s, n_p, ps = 3, 3, 128
    q = jax.random.normal(ks[0], (s, 16, 256))
    kp = jax.random.normal(ks[1], (s * n_p + 1, 2, ps, 256))
    vp = jax.random.normal(ks[2], (s * n_p + 1, 2, ps, 256))
    table = jnp.arange(s * n_p, dtype=jnp.int32).reshape(s, n_p)
    lengths = jnp.asarray([0, 130, 383], jnp.int32)
    got = paged_attention(q, kp, vp, table, lengths, interpret=True)
    kg = jnp.repeat(pk._gathered(kp, table), 8, axis=1)
    vg = jnp.repeat(pk._gathered(vp, table), 8, axis=1)
    seen = jnp.arange(n_p * ps)[None, None, :] <= lengths[:, None, None]
    want = masked_attention(q[:, :, None], kg, vg, seen)[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)
    fq = jax.random.normal(ks[3], (1, 16, 256, 256))
    fk = jax.random.normal(ks[4], (1, 2, 256, 256))
    fv = jax.random.normal(ks[5], (1, 2, 256, 256))
    out = flash_attention(fq, fk, fv, True, interpret=True)
    ref = blockwise_attention(fq, jnp.repeat(fk, 8, axis=1),
                              jnp.repeat(fv, 8, axis=1), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


def test_auto_takes_the_kernel_for_heads_of_256_only_on_pages_of_128(
        monkeypatch):
    from deeplearning4j_tpu.attention.paged_pallas import \
        resolve_decode_kernel

    cfg = family.model_config(_config(dtype="bfloat16"))
    wide = cfg._replace(head_dim=256)
    assert resolve_decode_kernel("auto", wide, 128) == "gather"  # no TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_decode_kernel("auto", wide, 128) == "pallas"
    assert resolve_decode_kernel("auto", wide, 256) == "pallas"
    assert resolve_decode_kernel("auto", wide, 16) == "gather"
    assert resolve_decode_kernel("auto", wide._replace(head_dim=192),
                                 128) == "gather"
    assert resolve_decode_kernel("auto", cfg, 16) == "pallas"   # hd 16
