"""Opt-in real-chip test lane (SURVEY §4 tier (c) on actual hardware).

Run on a machine with a TPU (from the sandbox: through the chip tool):
    DL4J_TPU_TEST_PLATFORM=tpu python -m pytest tests/ -m tpu -q

Everything here executes on the chip: the Pallas kernels compile for
Mosaic (interpret=False), bf16 runs on the MXU, and buffer donation
exercises the real allocator. The default CPU suite skips these (see
conftest.pytest_collection_modifyitems); the lane conversely runs ONLY
these. Budget: the whole lane must stay under ~3 minutes including
compiles."""

import numpy as np
import pytest

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def qkv():
    import jax
    import jax.numpy as jnp

    assert jax.devices()[0].platform == "tpu", (
        "tpu lane launched without a real chip")
    B, H, S, D = 2, 4, 512, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(kq, (B, H, S, D), jnp.bfloat16),
            jax.random.normal(kk, (B, H, S, D), jnp.bfloat16),
            jax.random.normal(kv, (B, H, S, D), jnp.bfloat16))


class TestFlashKernelOnChip:
    def test_forward_kernel_engages_and_matches(self, qkv, monkeypatch):
        """The compiled Pallas kernel (not the blockwise fallback) must
        run, and agree with blockwise to bf16 tolerance."""
        import jax
        import jax.numpy as jnp

        import deeplearning4j_tpu.attention.flash_pallas as fp
        from deeplearning4j_tpu.attention.blockwise import blockwise_attention

        calls = {"n": 0}
        real = fp._flash_forward

        def counting(*a, **kw):
            calls["n"] += 1
            assert a[-1] is False or kw.get("interpret") is False
            return real(*a, **kw)

        monkeypatch.setattr(fp, "_flash_forward", counting)
        q, k, v = qkv
        out = jax.jit(lambda q, k, v: fp.flash_attention(
            q, k, v, causal=True))(q, k, v)
        np.asarray(jax.device_get(out.ravel()[:1]))  # force completion
        assert calls["n"] == 1, "fell back to blockwise on the chip"
        ref = blockwise_attention(q, k, v, causal=True)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < 0.05, f"kernel vs blockwise err {err}"

    def test_backward_kernels_engage_and_match(self, qkv, monkeypatch):
        import jax
        import jax.numpy as jnp

        import deeplearning4j_tpu.attention.flash_pallas as fp
        from deeplearning4j_tpu.attention.blockwise import blockwise_attention

        calls = {"n": 0}
        real = fp._flash_backward

        def counting(*a, **kw):
            calls["n"] += 1
            return real(*a, **kw)

        monkeypatch.setattr(fp, "_flash_backward", counting)
        q, k, v = qkv

        def loss_f(q, k, v):
            return jnp.sum(fp.flash_attention(
                q, k, v, causal=True).astype(jnp.float32) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(blockwise_attention(
                q, k, v, causal=True).astype(jnp.float32) ** 2)

        gf = jax.jit(jax.grad(loss_f, argnums=(0, 1, 2)))(q, k, v)
        np.asarray(jax.device_get(gf[0].ravel()[:1]))
        assert calls["n"] == 1, "backward fell back to vjp-of-blockwise"
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("dq dk dv".split(), gf, gr):
            scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) or 1.0
            err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
            assert err / scale < 0.02, f"{name} err {err} (scale {scale})"


class TestTrainingOnChip:
    def _net(self):
        from deeplearning4j_tpu.config import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        conf = (NeuralNetConfiguration.builder()
                .lr(0.05).n_in(784).activation_function("relu")
                .optimization_algo("iteration_gradient_descent")
                .num_iterations(1).batch_size(256)
                .compute_dtype("bfloat16")
                .list(3).hidden_layer_sizes([256, 128])
                .override(2, layer="output", loss_function="mcxent",
                          activation_function="softmax", n_out=10)
                .pretrain(False).build())
        return MultiLayerNetwork(conf)

    def test_donated_train_step_bf16(self):
        """fit_scan donates (params, updater state); two consecutive
        calls must work (donated buffers really were consumed) and the
        score must improve on a learnable batch."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.datasets.mnist import synthetic_mnist

        net = self._net()
        x_np, y_np = synthetic_mnist(1024)
        x, y = jnp.asarray(x_np), jnp.asarray(y_np)
        first = net.fit_scan(x, y, batch_size=256, epochs=2)
        second = net.fit_scan(x, y, batch_size=256, epochs=2)
        np.asarray(jax.device_get(net.params().ravel()[:1]))
        assert np.isfinite(first) and np.isfinite(second)
        assert second < first, (first, second)

    def test_bf16_eval_on_chip(self):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.datasets.mnist import synthetic_mnist
        from deeplearning4j_tpu.eval import Evaluation

        net = self._net()
        x_np, y_np = synthetic_mnist(512)
        x, y = jnp.asarray(x_np), jnp.asarray(y_np)
        net.fit_scan(x, y, batch_size=256, epochs=4)
        out = np.asarray(jax.device_get(net.output(x)))
        assert np.isfinite(out).all()
        ev = Evaluation()
        ev.eval(np.asarray(y_np), out)
        assert 0.0 <= ev.f1() <= 1.0
        assert ev.accuracy() > 0.2  # learned something on-chip


class TestDeviceLoopOnChip:
    def test_while_loop_solver_runs_on_tpu(self):
        """The device-side optimizer loop (one compiled lax.while_loop
        over the whole iteration schedule) must compile and run on the
        real chip, matching the eager path's result."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.config import NeuralNetConfiguration
        from deeplearning4j_tpu.optimize.solvers import (
            IterationGradientDescent)
        from deeplearning4j_tpu.optimize.terminations import EpsTermination

        conf = (NeuralNetConfiguration.builder()
                .lr(0.1).num_iterations(6).build())

        def quad(x):
            return 0.5 * jnp.sum(x * x)

        opt = IterationGradientDescent(conf, quad,
                                       terminations=[EpsTermination(1e-30)])
        x0 = jnp.linspace(1.0, 2.0, 8)
        params, score = opt.optimize(x0)
        assert getattr(opt, "_loop", None) is not None, "loop not taken"
        eager = IterationGradientDescent(conf, quad,
                                         terminations=[EpsTermination(1e-30)])
        eager._has_device_loop = lambda: False
        p_ref, s_ref = eager.optimize(jnp.array(x0, copy=True))
        np.testing.assert_allclose(np.asarray(params), np.asarray(p_ref),
                                   rtol=1e-5)
        assert float(score) == pytest.approx(float(s_ref), rel=1e-5)


class TestFlashLseOnChip:
    def test_with_lse_kernel_compiles_and_merges(self, qkv):
        """flash_attention_with_lse on the real chip: two disjoint KV
        halves merged via the documented lse formula must equal one full
        call — the exactness the ring/flash-decoding combines rely on."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.attention.flash_pallas import (
            flash_attention_with_lse)

        q, k, v = qkv
        full, _ = flash_attention_with_lse(q, k, v, False)
        half = k.shape[-2] // 2
        oa, la = flash_attention_with_lse(q, k[..., :half, :],
                                          v[..., :half, :], False)
        ob, lb = flash_attention_with_lse(q, k[..., half:, :],
                                          v[..., half:, :], False)
        m = jnp.maximum(la, lb)
        wa = jnp.exp(la - m)[..., None]
        wb = jnp.exp(lb - m)[..., None]
        merged = (wa * oa.astype(jnp.float32)
                  + wb * ob.astype(jnp.float32)) / (wa + wb)
        np.testing.assert_allclose(
            np.asarray(merged, np.float32),
            np.asarray(full, np.float32), atol=2e-2)


def _paged_case(rng, s_n, h, hd, ps, n_p, dtype):
    """Random pool + ragged page tables: an empty slot, a mid-page
    cursor, a page-boundary cursor and one slot at the window edge."""
    import jax.numpy as jnp

    n_pages = s_n * n_p
    window = n_p * ps
    q = rng.normal(size=(s_n, h, hd)).astype(np.float32)
    kp = rng.normal(size=(n_pages + 1, h, ps, hd)).astype(np.float32)
    vp = rng.normal(size=(n_pages + 1, h, ps, hd)).astype(np.float32)
    lengths = rng.integers(0, window, size=s_n).astype(np.int32)
    lengths[:4] = [0, ps + 3, 2 * ps - 1, window - 1]
    table = np.full((s_n, n_p), n_pages, np.int32)
    perm = rng.permutation(n_pages)
    for i in range(s_n):
        need = min(int(lengths[i]) // ps + 1, n_p)
        table[i, :need] = perm[i * n_p:i * n_p + need]
    as_dt = lambda a: jnp.asarray(a).astype(dtype)  # noqa: E731
    return (as_dt(q), as_dt(kp), as_dt(vp), jnp.asarray(table),
            jnp.asarray(lengths))


def _dense_paged_reference(q, kp, vp, table, lengths):
    """Host float64 masked softmax over the gathered window — no device
    matmul involved, so it does not inherit the TPU's default (single
    bf16 pass) f32 matmul precision."""
    q, kp, vp = (np.asarray(a.astype("float32"), np.float64)
                 for a in (q, kp, vp))
    table, lengths = np.asarray(table), np.asarray(lengths)
    s_n, h, hd = q.shape
    ps = kp.shape[2]
    window = table.shape[1] * ps
    kg = kp[table].transpose(0, 2, 1, 3, 4).reshape(s_n, h, window, hd)
    vg = vp[table].transpose(0, 2, 1, 3, 4).reshape(s_n, h, window, hd)
    sc = np.einsum("shd,shkd->shk", q, kg) / np.sqrt(hd)
    mask = np.arange(window)[None, :] <= lengths[:, None]
    sc = np.where(mask[:, None, :], sc, -np.inf)
    w = np.exp(sc - sc.max(axis=-1, keepdims=True))
    w /= w.sum(axis=-1, keepdims=True)
    return np.einsum("shk,shkd->shd", w, vg)


class TestPagedKernelOnChip:
    """The paged-decode kernel compiled by Mosaic against the gather
    math, at the widths `chip_smoke.py` serves (H8 x hd128) and the
    other full-width split (H16 x hd64). These shapes ARE the `auto`
    envelope's evidence (attention/paged_pallas.resolve_decode_kernel)."""

    @pytest.mark.parametrize("dtype,atol", [("float32", 1e-5),
                                            ("bfloat16", 2e-2)])
    @pytest.mark.parametrize("h,hd", [(8, 128), (16, 64)])
    @pytest.mark.parametrize("ps", [8, 16])
    def test_kernel_matches_dense_reference(self, h, hd, ps, dtype, atol):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.attention.paged_pallas import paged_attention

        assert jax.devices()[0].platform == "tpu"
        case = _paged_case(np.random.default_rng(0), 8, h, hd, ps,
                           128 // ps, jnp.dtype(dtype))
        out = jax.jit(paged_attention)(*case)
        ref = _dense_paged_reference(*case)
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32), np.float64), ref,
            atol=atol)

    @pytest.mark.parametrize("dtype,atol", [("float32", 1e-5),
                                            ("bfloat16", 2e-2)])
    def test_kernel_at_the_served_shape_sweeps_blocks(self, dtype, atol):
        """`cgpt13b-decode-sat`'s call: 16 slots, 16 heads of 128, pages
        of 16 over a 128-column table. Since PR 31 a block of 8 pages a
        step (4 in float32: a block holds 512 KiB of K), each page
        copied from where it lies in the pool."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.attention.paged_pallas import (
            block_pages, paged_attention)

        assert jax.devices()[0].platform == "tpu"
        assert block_pages(16, 16, 128, jnp.dtype(dtype), 128) == {
            "float32": 4, "bfloat16": 8}[dtype]
        case = _paged_case(np.random.default_rng(1), 16, 16, 128, 16,
                           128, jnp.dtype(dtype))
        out = jax.jit(paged_attention)(*case)
        ref = _dense_paged_reference(*case)
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32), np.float64), ref,
            atol=atol)

    @pytest.mark.parametrize("dtype,atol", [("float32", 1e-4),
                                            ("bfloat16", 2e-2)])
    @pytest.mark.parametrize("hq,hkv,hd,window", [(32, 8, 64, None),
                                                  (128, 8, 128, 4097)])
    def test_pages_of_128_in_blocks_match_dense_reference(
            self, hq, hkv, hd, window, dtype, atol):
        """Pages of 128 tokens swept several a step: lfm2's
        heads of 64, read transposed, 4 a block in bf16 (2 in f32), and
        ep8's window of 4,097 keys over 33 columns, 2 a block in bf16
        (1 in f32); cursors on a block's edges, at and past the table's
        end, a slot of trash and a shared prefix."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.attention.paged_pallas import (
            block_pages, paged_attention)
        from tests.test_grouped_window_kernels import _dense
        from tests.test_paged_kernel import _block_case

        assert jax.devices()[0].platform == "tpu"
        ps, n_p = 128, 40
        columns = -(-(window - 1) // ps) + 1 if window else n_p
        pages = block_pages(ps, hkv, hd, jnp.dtype(dtype), columns)
        assert pages == {(None, "bfloat16"): 4, (None, "float32"): 2,
                         (4097, "bfloat16"): 2,
                         (4097, "float32"): 1}[window, dtype]
        span, end = max(pages, 2) * ps, n_p * ps
        lengths = [0, span - 1, span, 3 * span + 5, end - 1, end, 4500,
                   0, span + 130]
        q, k, v, table, lengths = _block_case(
            ps, n_p, lengths, hq=hq, hkv=hkv, hd=hd, dtype=jnp.dtype(dtype))
        first = (np.maximum(lengths - window + 1, 0).astype(np.int32)
                 if window else np.zeros(len(lengths), np.int32))
        fn = jax.jit(lambda *a: paged_attention(
            *a[:5], first=a[5] if window else None,
            window_pages=columns if window else None))
        out = fn(q, k, v, jnp.asarray(table), jnp.asarray(lengths),
                 jnp.asarray(first))
        want = _dense(q.astype(jnp.float32), k.astype(jnp.float32),
                      v.astype(jnp.float32), table, lengths, first, ps)
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32), np.float64), want,
            atol=atol)

    @pytest.mark.parametrize("dtype,rtol", [("float32", 1e-4),
                                            ("bfloat16", 5e-2)])
    def test_decode_and_verify_steps_match_gather(self, dtype, rtol):
        """`paged_decode_step` / `paged_verify_step` with
        kernel="pallas" vs kernel="gather" at 1024d, two layers, over a
        prefilled pool. In f32, XLA's own matmuls run at "highest" on
        both sides so the comparison sees the kernel, not the MXU's
        default single-pass f32 precision. (Not in bf16: an ambient
        "highest" also reaches the flash kernel's bf16 dots, which
        Mosaic refuses — "Bad lhs type" for an fp32 contraction of
        bf16 operands.)"""
        import contextlib

        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.transformer import (
            TransformerConfig, init_transformer_params)
        from deeplearning4j_tpu.serving import paged_kinds
        from deeplearning4j_tpu.serving.paged_kv import (init_paged_pool,
                                                         pages_per_slot)

        cfg = TransformerConfig(vocab_size=8192, d_model=1024, n_heads=8,
                                n_layers=2, d_ff=4096, max_len=512,
                                dtype=jnp.dtype(dtype))
        ps, s_n = 16, 4
        n_p = pages_per_slot(cfg, ps)
        params = init_transformer_params(jax.random.PRNGKey(0), cfg)
        pool = init_paged_pool(cfg, s_n * n_p, ps)
        rng = np.random.default_rng(1)
        table = np.arange(s_n * n_p, dtype=np.int32).reshape(s_n, n_p)
        tokens = rng.integers(0, cfg.vocab_size, (s_n, 128)).astype(np.int32)
        true_len = np.asarray([128, 97, 16, 5], np.int32)
        precise = (jax.default_matmul_precision("highest")
                   if dtype == "float32" else contextlib.nullcontext())
        with precise:
            _, pool, _ = jax.jit(
                lambda p, t, tl, pool, ids: paged_kinds.prefill(
                    p, t, tl, pool, {"full": ids}, cfg))(
                params, jnp.asarray(tokens), jnp.asarray(true_len), pool,
                jnp.asarray(table[:, :128 // ps]))
            nxt = jnp.asarray(rng.integers(0, cfg.vocab_size, (s_n,)),
                              jnp.int32)
            lengths = jnp.asarray(true_len)
            active = jnp.ones((s_n,), bool)
            step = {k: jax.jit(
                lambda p, t, pool, tb, ln, act, k=k:
                paged_kinds.decode_step(p, t, pool, {"full": tb}, ln, act,
                                        cfg, kernel=k))
                for k in ("pallas", "gather")}
            lk, _, _ = step["pallas"](params, nxt, pool, jnp.asarray(table),
                                   lengths, active)
            lg, _, _ = step["gather"](params, nxt, pool, jnp.asarray(table),
                                   lengths, active)
            drafts = jnp.asarray(
                rng.integers(0, cfg.vocab_size, (s_n, 4)), jnp.int32)
            widths = jnp.asarray([4, 1, 3, 0], jnp.int32)
            ver = {k: jax.jit(
                lambda p, t, pool, tb, ln, w, k=k:
                paged_kinds.verify_step(p, t, pool, {"full": tb}, ln, w,
                                        cfg, kernel=k))
                for k in ("pallas", "gather")}
            vk, _, _ = ver["pallas"](params, drafts, pool, jnp.asarray(table),
                                  lengths, widths)
            vg, _, _ = ver["gather"](params, drafts, pool, jnp.asarray(table),
                                  lengths, widths)
        lk, lg = (np.asarray(a.astype(jnp.float32)) for a in (lk, lg))
        assert np.isfinite(lk).all()
        scale = float(np.abs(lg).max())
        assert float(np.abs(lk - lg).max()) <= rtol * scale
        # only real columns are defined (invalid ones are garbage the
        # host never reads)
        real = np.arange(4)[None, :] < np.asarray(widths)[:, None]
        vk, vg = (np.asarray(a.astype(jnp.float32))[real] for a in (vk, vg))
        assert float(np.abs(vk - vg).max()) <= rtol * float(
            np.abs(vg).max())


class TestFlashHd128OnChip:
    """Flash forward at every prefill bucket and backward at T=1024,
    head_dim 128 — the width of every full-size LM here — in the
    serving dtype (f32) and the training dtype (bf16)."""

    @pytest.mark.parametrize("dtype,tol", [("float32", 2e-2),
                                           ("bfloat16", 5e-2)])
    @pytest.mark.parametrize("t", [128, 256, 512, 1024, 2048])
    def test_forward_matches_blockwise(self, t, dtype, tol):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.attention.blockwise import blockwise_attention
        from deeplearning4j_tpu.attention.flash_pallas import flash_attention

        q, k, v = (jax.random.normal(key, (1, 8, t, 128), jnp.dtype(dtype))
                   for key in jax.random.split(jax.random.PRNGKey(t), 3))
        fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))
        assert "tpu_custom_call" in fn.lower(q, k, v).as_text()
        out = fn(q, k, v)
        with jax.default_matmul_precision("highest"):
            ref = blockwise_attention(q, k, v, causal=True)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < tol, f"T={t} {dtype}: kernel vs blockwise err {err}"

    @pytest.mark.parametrize("dtype,tol", [("float32", 2e-2),
                                           ("bfloat16", 4e-2)])
    def test_backward_matches_blockwise(self, dtype, tol):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.attention.blockwise import blockwise_attention
        from deeplearning4j_tpu.attention.flash_pallas import flash_attention

        q, k, v = (jax.random.normal(key, (2, 8, 1024, 128),
                                     jnp.dtype(dtype))
                   for key in jax.random.split(jax.random.PRNGKey(7), 3))

        def loss(attend):
            return lambda q, k, v: jnp.sum(
                attend(q, k, v).astype(jnp.float32) ** 2)

        gf_fn = jax.jit(jax.grad(loss(
            lambda q, k, v: flash_attention(q, k, v, True)),
            argnums=(0, 1, 2)))
        text = gf_fn.lower(q, k, v).as_text()
        for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            assert name in text, f"{name} not in the lowered program"
        gf = gf_fn(q, k, v)
        with jax.default_matmul_precision("highest"):
            gr = jax.grad(loss(
                lambda q, k, v: blockwise_attention(q, k, v, causal=True)),
                argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("dq dk dv".split(), gf, gr):
            scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) or 1.0
            err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
            assert err / scale < tol, f"{name} err {err} (scale {scale})"


class TestHeadsOf256OnChip:
    """PR 35: what lets `resolve_decode_kernel("auto")` take heads of
    256 (`qwen3-next-80b-a3b-ep8`'s full layers: 16 query heads over 2
    K/V heads, 8 query rows a K/V head, pages of 128 tokens, 4 pages a
    block in bf16 and 2 in f32), and the flash forward with the same
    heads."""

    @pytest.mark.parametrize("dtype,atol", [("float32", 1e-4),
                                            ("bfloat16", 2e-2)])
    def test_paged_kernel_matches_dense_reference(self, dtype, atol):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.attention.paged_pallas import (
            block_pages, paged_attention, resolve_decode_kernel)
        from deeplearning4j_tpu.models.hybrid_transformer import \
            HybridConfig

        assert jax.devices()[0].platform == "tpu"
        assert block_pages(128, 2, 256, jnp.dtype(dtype), 64) == {
            "float32": 2, "bfloat16": 4}[dtype]
        cfg = HybridConfig(
            vocab_size=8, d_model=2048, n_heads=16, n_kv_heads=2,
            head_dim=256, d_ff=512, layer_kinds=("linear", "full"),
            n_experts=512, experts_per_token=10, n_shared=1, n_held=64,
            dtype=jnp.dtype(dtype))
        assert resolve_decode_kernel("auto", cfg, 128) == "pallas"
        assert resolve_decode_kernel("auto", cfg, 16) == "gather"
        # cursors on the edges of a page and of the table, an empty slot
        q, kp, vp, table, lengths = _paged_case(
            np.random.default_rng(5), 16, 2, 256, 128, 64,
            jnp.dtype(dtype))
        q = jnp.asarray(np.random.default_rng(6).normal(
            size=(16, 16, 256)).astype(np.float32)).astype(dtype)
        out = jax.jit(paged_attention)(q, kp, vp, table, lengths)
        # query head n reads K/V head n // 8
        ref = _dense_paged_reference(
            q, jnp.repeat(kp, 8, axis=1), jnp.repeat(vp, 8, axis=1),
            table, lengths)
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32), np.float64), ref,
            atol=atol)

    @pytest.mark.parametrize("dtype,tol", [("float32", 2e-2),
                                           ("bfloat16", 3e-2)])
    def test_flash_forward_grouped_at_256_matches_blockwise(self, dtype,
                                                            tol):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.attention.blockwise import \
            blockwise_attention
        from deeplearning4j_tpu.attention.flash_pallas import \
            flash_attention

        keys = jax.random.split(jax.random.PRNGKey(11), 3)
        q = jax.random.normal(keys[0], (1, 16, 2048, 256), jnp.dtype(dtype))
        k = jax.random.normal(keys[1], (1, 2, 2048, 256), jnp.dtype(dtype))
        v = jax.random.normal(keys[2], (1, 2, 2048, 256), jnp.dtype(dtype))
        fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))
        assert "flash_fwd" in fn.lower(q, k, v).as_text()
        out = fn(q, k, v)
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        with jax.default_matmul_precision("highest"):
            ref = blockwise_attention(
                f32(q), jnp.repeat(f32(k), 8, axis=1),
                jnp.repeat(f32(v), 8, axis=1), causal=True)
        err = float(jnp.max(jnp.abs(f32(out) - ref)))
        assert err < tol, err


class TestGatedDeltaRuleOnChip:
    """PR 35: the chunked scan and the one-token update compiled by
    Mosaic against the recurrence token by token in float32, at the
    served head sizes (dk = dv = 128), a slow decay and a fast one."""

    @staticmethod
    def _case(decay, dtype, t=1024, heads=4):
        import jax
        import jax.numpy as jnp

        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        shape = (1, heads, t, 128)
        q = jax.random.normal(ks[0], shape)
        k = jax.random.normal(ks[1], shape)
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / 128 ** 0.5
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        v = jax.random.normal(ks[2], shape)
        g = jnp.log(decay) * jax.random.uniform(
            ks[3], shape[:3], minval=0.5, maxval=1.5)
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
        return tuple(a.astype(dtype) for a in (q, k, v)) + (g, beta)

    @staticmethod
    def _recurrence(q, k, v, g, beta):
        import jax
        import jax.numpy as jnp

        def one_head(q, k, v, g, beta):
            def step(s, now):
                q, k, v, g, b = now
                s = s * jnp.exp(g)
                mem = jnp.dot(s.T, k, precision="highest")
                s = s + jnp.outer(k, b * (v - mem))
                return s, jnp.dot(s.T, q, precision="highest")
            s, o = jax.lax.scan(step, jnp.zeros((128, 128), jnp.float32),
                                (q, k, v, g, beta))
            return o, s
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        return jax.jit(jax.vmap(jax.vmap(one_head)))(
            f32(q), f32(k), f32(v), g, beta)

    @pytest.mark.parametrize("dtype,tol", [("float32", 1e-3),
                                           ("bfloat16", 2e-2)])
    @pytest.mark.parametrize("decay", [0.997, 0.5])
    def test_scan_and_update_match_the_recurrence(self, decay, dtype, tol):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.attention.gdn_pallas import (gdn_scan,
                                                             gdn_update)

        assert jax.devices()[0].platform == "tpu"
        q, k, v, g, beta = self._case(decay, jnp.dtype(dtype))
        want_o, want_s = self._recurrence(q, k, v, g, beta)
        o, s = gdn_scan(q, k, v, g, beta)
        scale = float(jnp.max(jnp.abs(want_o)))
        assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - want_o))) \
            < tol * scale
        assert float(jnp.max(jnp.abs(s - want_s))) \
            < tol * float(jnp.max(jnp.abs(want_s)))
        # one more token on top of the kept state, by the update kernel
        # and by one step of the recurrence
        q1, k1, v1, g1, b1 = self._case(decay, jnp.dtype(dtype), t=64)
        at = (slice(None), slice(None), 7)
        o1, s1 = gdn_update(want_s + 0, q1[at], k1[at], v1[at], g1[at],
                            b1[at])
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        sp = want_s * jnp.exp(g1[at])[..., None, None]
        mem = jnp.einsum("nhkv,nhk->nhv", sp, f32(k1[at]),
                         precision="highest")
        sn = sp + f32(k1[at])[..., :, None] \
            * (b1[at][..., None] * (f32(v1[at]) - mem))[..., None, :]
        on = jnp.einsum("nhkv,nhk->nhv", sn, f32(q1[at]),
                        precision="highest")
        np.testing.assert_allclose(np.asarray(s1), np.asarray(sn),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(on),
                                   atol=1e-5)


class TestExpertRowsOnChip:
    """PR 36: `moe_rows_in` and `moe_rows_out` compiled by Mosaic at the
    two served cells' prefill shapes (8,192 tokens; 2,048 columns with
    ~8,960 pairs of 10 choices, 4,096 columns with ~7,168 of 8), with
    every token on held experts (every choice a pair), and at a decode
    step's, against the rows themselves and the float64 dense sum."""

    @pytest.mark.parametrize("t,d,k,n_pairs,chunk", [
        (8192, 2048, 10, 8960, 12800),      # qwen3-next-80b-a3b-ep8
        (8192, 4096, 8, 7168, 10240),       # command-a-plus-ep8
        (1024, 2048, 10, 10240, 10240),     # every choice a pair
        (1024, 4096, 8, 8192, 8192),
        (64, 2048, 10, 75, 256),            # a decode step
    ])
    def test_rows_in_and_out_match_the_dense_sum(self, t, d, k, n_pairs,
                                                 chunk):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.models import moe_rows

        assert jax.devices()[0].platform == "tpu"
        rng = np.random.RandomState(3)
        h = jnp.asarray(rng.randn(t, d), jnp.bfloat16)
        # n_pairs distinct (token, choice) pairs, in a sorted order of
        # their own: row i of the sorted buffers is pair flat[i]
        flat = rng.permutation(t * k)[:n_pairs]
        tok = np.zeros((chunk,), np.int32)
        tok[:n_pairs] = flat // k
        pos = np.full((t * k,), -1, np.int32)
        pos[flat] = np.arange(n_pairs)
        w = rng.rand(t, k).astype(np.float32)
        y = jnp.asarray(rng.randn(chunk, d), jnp.float32)

        fn_in = jax.jit(moe_rows.rows_in)
        assert "moe_rows_in" in fn_in.lower(
            h, jnp.asarray(tok), jnp.int32(n_pairs)).as_text()
        x = fn_in(h, jnp.asarray(tok), jnp.int32(n_pairs))
        assert x.shape == (chunk, d) and x.dtype == jnp.bfloat16
        assert np.array_equal(
            np.asarray(x[:n_pairs].astype(jnp.float32)),
            np.asarray(h.astype(jnp.float32))[tok[:n_pairs]])

        fn_out = jax.jit(moe_rows.rows_out)
        assert "moe_rows_out" in fn_out.lower(
            y, jnp.asarray(pos.reshape(t, k)), jnp.asarray(w)).as_text()
        out = fn_out(y, jnp.asarray(pos.reshape(t, k)), jnp.asarray(w))
        dense = np.zeros((t, d), np.float64)
        np.add.at(dense, flat // k,
                  w.reshape(-1)[flat].astype(np.float64)[:, None]
                  * np.asarray(y[:n_pairs], np.float64))
        np.testing.assert_allclose(np.asarray(out, np.float64), dense,
                                   atol=1e-5)
        # tokens with no pair come back as exact zeros
        none = np.setdiff1d(np.arange(t), flat // k)
        assert not np.asarray(out)[none].any()
