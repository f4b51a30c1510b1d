"""Pallas paged-attention decode kernel (ISSUE 13 acceptance).

The contracts under test (attention/paged_pallas.py,
serving/paged_kv.py `kernel=`, serving/decode_loop.py `kernel=`,
docs/SERVING.md "Decode kernel"):

1. **Parity**: the streamed-pages kernel is the dense-gather path to
   1e-5 — teacher-forced under ragged slot membership, through the
   decode loop under prefix-cache page sharing and post-CoW-fork, at
   the max_len window edge, and across horizon>1 chaining. Everything
   runs the REAL kernel code through the Pallas interpreter on CPU.
2. **One compiled program**: the kernel lane preserves
   `decode_step_programs() == 1` — page table and lengths stay traced
   values inside the kernel launch.
3. **Lane selection** (the tier-1 guard): `kernel="auto"` off-TPU is
   ALWAYS the gather path (interpret mode is a test lane, never a
   silent production fallback), and an explicit `kernel="pallas"`
   off-TPU raises a clear error unless `cfg.interpret` is set.
4. **Cost accounting**: `DecodeLoop._read_bytes` matches the pages the
   kernel grid actually computes, and the loop's
   dl4j_decode_kv_read_bytes{path} counters record streamed vs dense
   figures every dispatch.
5. **flash q_len=1** (satellite): `_fit_tile` admits the decode-shaped
   single-row query tile instead of demoting it to the dense fallback.
6. **Blocks of pages** (ISSUE 31): pages that are whole (sublane, lane)
   tiles are swept several a step, as many as hold 512 KiB of K
   (`block_pages`: 8 for sat's pages of 16, 4 for q3n's and lfm2's
   pages of 128, 2 for ep8's). Against a dense float64 reference and
   the gather lane with the block engaged: cursors in a block's first,
   middle and last page and on its edges, widths the block does not
   divide, a table narrower than a block, shared and trash columns
   inside a block, the cursor at max_len; pages of 128 tokens in blocks
   at q3n's grouping, in ep8's windowed sweep and read transposed at
   lfm2's heads of 64; a page that fills a block alone stays one a
   grid step, bit for bit. The count the loop reports
   (`snapshot()["paged_block_pages"]`, `dl4j_paged_kernel_block_pages`)
   is that rule's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.attention.blockwise import blockwise_attention
from deeplearning4j_tpu.attention.flash_pallas import (_fit_tile,
                                                       flash_attention)
from deeplearning4j_tpu.attention.paged_pallas import (
    block_pages, paged_attention, resolve_decode_kernel)
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_transformer_params)
from deeplearning4j_tpu.serving import paged_kinds
from deeplearning4j_tpu.serving.decode_loop import DecodeLoop
from deeplearning4j_tpu.serving.kv_cache import generate_cached
from deeplearning4j_tpu.serving.paged_kv import (init_paged_pool,
                                                 pages_for_tokens,
                                                 pages_per_slot)

from tests.test_grouped_window_kernels import _dense

pytestmark = pytest.mark.pallas

CFG = TransformerConfig(vocab_size=17, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_len=64, interpret=True)
CFG_NOINTERP = TransformerConfig(vocab_size=17, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, max_len=64,
                                 interpret=False)


def _params(seed=0):
    return init_transformer_params(jax.random.PRNGKey(seed), CFG)


def _prompt(rng, t):
    return rng.randint(0, CFG.vocab_size, (t,)).astype(np.int32)


def _ref_tokens(p, prompt, n):
    return np.asarray(generate_cached(
        p, jnp.asarray(prompt[None]), CFG, n))[0].tolist()


# ----------------------------------------------------- kernel vs dense
class TestPagedAttentionUnit:
    def test_kernel_matches_dense_reference_ragged(self):
        """The bare kernel against a dense gather + masked softmax over
        the same pool — ragged cursors including an empty slot and a
        slot AT the window edge (every page written)."""
        rng = np.random.default_rng(0)
        s_n, h, hd, ps, n_p, n_pages = 5, 2, 16, 4, 6, 20
        q = jnp.asarray(rng.normal(size=(s_n, h, hd)).astype(np.float32))
        kp = jnp.asarray(
            rng.normal(size=(n_pages + 1, h, ps, hd)).astype(np.float32))
        vp = jnp.asarray(
            rng.normal(size=(n_pages + 1, h, ps, hd)).astype(np.float32))
        trash = n_pages
        window = n_p * ps
        lengths = np.asarray([0, 3, 7, window - 1, window], np.int32)
        table = np.full((s_n, n_p), trash, np.int32)
        for i in range(s_n):
            need = min(int(lengths[i]) // ps + 1, n_p)
            table[i, :need] = rng.integers(0, n_pages, size=need)
        out = paged_attention(q, kp, vp, jnp.asarray(table),
                              jnp.asarray(lengths), interpret=True)
        kg = kp[jnp.asarray(table)].transpose(0, 2, 1, 3, 4).reshape(
            s_n, h, window, hd)
        vg = vp[jnp.asarray(table)].transpose(0, 2, 1, 3, 4).reshape(
            s_n, h, window, hd)
        sc = jnp.einsum("shd,shkd->shk", q, kg) / np.sqrt(hd)
        mask = jnp.arange(window)[None, :] <= jnp.asarray(lengths)[:, None]
        sc = jnp.where(mask[:, None, :], sc, -1e30)
        ref = jnp.einsum("shk,shkd->shd", jax.nn.softmax(sc, axis=-1), vg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_table_and_lengths_are_traced_one_program(self):
        """jitting over (table, lengths) compiles once — membership
        changes never become new programs inside the kernel launch."""
        from deeplearning4j_tpu.utils.jitcache import jit_cache_size

        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(2, 2, 8)).astype(np.float32))
        kp = jnp.asarray(rng.normal(size=(5, 2, 4, 8)).astype(np.float32))
        vp = jnp.asarray(rng.normal(size=(5, 2, 4, 8)).astype(np.float32))
        f = jax.jit(lambda t, ln: paged_attention(q, kp, vp, t, ln,
                                                  interpret=True))
        f(jnp.zeros((2, 3), jnp.int32), jnp.asarray([0, 5], jnp.int32))
        f(jnp.full((2, 3), 4, jnp.int32), jnp.asarray([11, 2], jnp.int32))
        assert jit_cache_size(f) in (1, -1)


# ------------------------------------------------- blocks of pages
def _block_case(ps, n_p, lengths, hq=2, hkv=2, hd=128, dtype=jnp.float32,
                seed=0):
    """Slots at `lengths` over distinct random pages, trash past each
    cursor's page. The last slot's first pages are slot 1's (a prefix
    two requests hold, one of them further on), and the one before it
    holds nothing but trash."""
    lengths = np.asarray(lengths, np.int32)
    s = len(lengths)
    n_pages = s * n_p
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (s, hq, hd), dtype)
    k = jax.random.normal(ks[1], (n_pages + 1, hkv, ps, hd), dtype)
    v = jax.random.normal(ks[2], (n_pages + 1, hkv, ps, hd), dtype)
    free = list(np.random.RandomState(seed).permutation(n_pages))
    table = np.full((s, n_p), n_pages, np.int32)
    for i in range(s):
        if i == s - 2:
            continue
        for c in range(min(int(lengths[i]) // ps, n_p - 1) + 1):
            table[i, c] = free.pop()
    shared = min(int(lengths[1]), int(lengths[s - 1])) // ps
    table[s - 1, :shared] = table[1, :shared]
    return q, k, v, table, lengths


class TestBlocksOfPages:
    # (page size, dtype, K/V heads, table width, pages a block): pages of
    # 64 KiB (32 KiB, 128 KiB) of K, widths the block does not divide and
    # one narrower than a block
    SHAPES = [(16, "float32", 8, 20, 8), (8, "float32", 8, 37, 16),
              (16, "bfloat16", 16, 19, 8), (32, "float32", 8, 9, 4),
              (16, "float32", 8, 5, 5)]

    def test_the_rule(self):
        """From the call's shapes alone: whole pages that are whole
        tiles, as many as hold 512 KiB of K, at least one and no more
        than the columns swept. The cells' shapes (bf16): sat and p80
        8, q3n 4, lfm2 4 (read transposed), ep8 2 for both kinds, olm
        1."""
        assert block_pages(16, 16, 128, jnp.bfloat16, 128) == 8
        assert block_pages(128, 2, 256, jnp.bfloat16, 64) == 4
        assert block_pages(128, 8, 64, jnp.bfloat16, 64) == 4
        assert block_pages(128, 8, 128, jnp.bfloat16, 64) == 2
        assert block_pages(128, 8, 128, jnp.bfloat16, 33) == 2
        assert block_pages(128, 30, 128, jnp.bfloat16, 128) == 1
        # the same pages in float32 hold twice the bytes
        assert block_pages(16, 16, 128, jnp.float32, 128) == 4
        assert block_pages(128, 2, 256, jnp.float32, 64) == 2
        assert block_pages(128, 8, 128, jnp.float32, 64) == 1
        # no wider than the columns swept; a page past the bytes alone
        assert block_pages(16, 16, 128, jnp.bfloat16, 3) == 3
        assert block_pages(128, 2, 256, jnp.bfloat16, 3) == 3
        assert block_pages(256, 8, 128, jnp.bfloat16, 33) == 1
        assert block_pages(16, 32, 128, jnp.float32, 128) == 2
        # no aligned place inside a block: half a bf16 tile, half a lane
        # tile, a page of 4, heads of 64 in pages of 64 (not transposed)
        assert block_pages(8, 8, 128, jnp.bfloat16, 32) == 1
        assert block_pages(16, 16, 64, jnp.bfloat16, 32) == 1
        assert block_pages(4, 2, 16, jnp.float32, 6) == 1
        assert block_pages(64, 8, 64, jnp.bfloat16, 32) == 1

    @pytest.mark.parametrize("ps,dtype,hkv,n_p,pages", SHAPES)
    def test_kernel_against_dense_float64(self, ps, dtype, hkv, n_p, pages):
        """Cursors at 0, in the first, a middle and the last page of a
        block, on a block's last lane and the next block's first, at
        the table's end and AT max_len; a slot of nothing but trash
        (cursor 0) and one that shares another's pages."""
        assert block_pages(ps, hkv, 128, dtype, n_p) == pages
        span, end = pages * ps, n_p * ps
        lengths = [3, min(span + ps + 3, end - 1), span - 1,
                   min(span, end - 1), end - 1, end,
                   min(span + span // 2, end - 2), 0,
                   min(span + 2 * ps + 1, end - 1)]
        q, k, v, table, lengths = _block_case(ps, n_p, lengths, hq=hkv,
                                              hkv=hkv,
                                              dtype=jnp.dtype(dtype))
        got = paged_attention(q, k, v, jnp.asarray(table),
                              jnp.asarray(lengths), interpret=True)
        want = _dense(q, k, v, table, lengths,
                        np.zeros(len(lengths), np.int32), ps)
        tol = 1e-5 if dtype == "float32" else 2e-2
        assert np.abs(np.asarray(got, np.float64) - want).max() < tol

    # (query heads, K/V heads, head size, dtype, window keys, pages a
    # block): q3n's grouping (16 over 2 K/V heads of 256), ep8's
    # windowed sweep over 5 columns (and its float32 pages, a block
    # each), lfm2's heads of 64 read transposed
    PAGES_OF_128 = [(16, 2, 256, "bfloat16", None, 4),
                    (16, 2, 256, "float32", None, 2),
                    (32, 8, 128, "bfloat16", 513, 2),
                    (16, 4, 128, "float32", 513, 2),
                    (32, 8, 128, "float32", 513, 1),
                    (32, 8, 64, "bfloat16", None, 4),
                    (16, 4, 64, "float32", None, 4)]

    @pytest.mark.parametrize("hq,hkv,hd,dtype,window,pages", PAGES_OF_128)
    def test_pages_of_128_in_blocks_against_dense_float64(
            self, hq, hkv, hd, dtype, window, pages):
        """Pages of 128 tokens swept several a step: cursors at 0, on a
        block's last lane and the next block's first, in a later block,
        at the table's end and past it, a slot of nothing but trash and
        one that shares another's pages; where a window is given, its
        first position in the middle of a page and of a block, the
        pages before it given back, and a sweep of 5 columns that
        blocks of 2 do not divide."""
        ps = 128
        n_p = 12 if window else 9
        span, end = pages * ps, n_p * ps
        lengths = [0, span - 1, span, 2 * span + 5, end - 1, end, 300, 0,
                   span + 130]
        q, k, v, table, lengths = _block_case(
            ps, n_p, lengths, hq=hq, hkv=hkv, hd=hd, dtype=jnp.dtype(dtype))
        if window is None:
            assert block_pages(ps, hkv, hd, dtype, n_p) == pages
            first = np.zeros(len(lengths), np.int32)
            got = paged_attention(q, k, v, jnp.asarray(table),
                                  jnp.asarray(lengths), interpret=True)
        else:
            columns = -(-(window - 1) // ps) + 1
            assert block_pages(ps, hkv, hd, dtype, columns) == pages
            first = np.maximum(lengths - window + 1, 0).astype(np.int32)
            held = table.copy()
            for i in range(len(lengths)):
                held[i, :first[i] // ps] = len(k) - 1
            got = paged_attention(q, k, v, jnp.asarray(held),
                                  jnp.asarray(lengths),
                                  first=jnp.asarray(first),
                                  window_pages=columns, interpret=True)
        want = _dense(q, k, v, table, lengths, first, ps)
        tol = 1e-5 if dtype == "float32" else 2e-2
        assert np.abs(np.asarray(got, np.float64) - want).max() < tol

    def test_step_kernel_lane_equals_the_gather_lane_with_blocks(self):
        """`paged_decode_step` at a head size that engages the block
        (8 heads of 128, pages of 8 of 32 KiB: 16 a block over a
        20-column table), teacher-forced from cursors on both sides of
        a block's edge."""
        cfg = TransformerConfig(vocab_size=17, d_model=1024, n_heads=8,
                                n_layers=1, d_ff=32, max_len=160,
                                interpret=True)
        ps = 8
        n_p = pages_per_slot(cfg, ps)
        assert block_pages(ps, 8, 128, cfg.dtype, n_p) == 16
        p = init_transformer_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        pool = init_paged_pool(cfg, 3 * n_p, ps)
        # the pool's rows as some prefill left them
        pool = pool._replace(layers=tuple(
            {kk: jnp.asarray(rng.normal(size=a.shape).astype(np.float32))
             for kk, a in layer.items()} for layer in pool.layers))
        table = rng.permutation(3 * n_p).reshape(3, n_p).astype(np.int32)
        lengths = np.asarray([126, 5, 150], np.int32)
        pool_k = pool
        for _ in range(4):
            args = (jnp.asarray(rng.randint(0, 17, (3,)).astype(np.int32)),
                    jnp.asarray(table), jnp.asarray(lengths),
                    jnp.asarray([True, True, True]))
            lg_g, pool, _ = paged_kinds.decode_step(
                p, args[0], pool, {"full": args[1]}, *args[2:], cfg,
                kernel="gather")
            lg_p, pool_k, _ = paged_kinds.decode_step(
                p, args[0], pool_k, {"full": args[1]}, *args[2:], cfg,
                kernel="pallas")
            np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_g),
                                       atol=1e-5)
            lengths += 1

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_pages_of_128_are_one_a_grid_step_bit_for_bit(self, dtype):
        """A page of 128 tokens that holds a block's 512 KiB of K alone
        (olm's, 30 heads of 128 in bf16, is 960 KiB): a block of one,
        fetched by Pallas's own pipeline over grid (slot, column) as
        ever: no copy by hand in the call, and the sums of
        `test_paged_kernel_at_today_s_shapes_is_bit_for_bit`
        (tests/test_grouped_window_kernels.py) bit for bit."""
        hkv = 32 // jnp.dtype(dtype).itemsize
        assert block_pages(128, hkv, 128, dtype, 3) == 1
        q, k, v, table, lengths = _block_case(
            128, 3, [0, 200, 127, 128, 383, 384, 5], hq=2 * hkv, hkv=hkv,
            dtype=dtype)
        args = (q, k, v, jnp.asarray(table), jnp.asarray(lengths))
        text = str(jax.make_jaxpr(
            lambda *a: paged_attention(*a, interpret=True))(*args))
        assert "dma_start" not in text
        sat = jax.ShapeDtypeStruct((9, 2, 16, 128), dtype)
        assert "dma_start" in str(jax.make_jaxpr(paged_attention)(
            q, sat, sat, *args[3:]))
        today = paged_attention(*args, interpret=True)
        swept = paged_attention(*args, first=jnp.zeros((7,), jnp.int32),
                                window_pages=3, interpret=True)
        assert np.array_equal(np.asarray(today), np.asarray(swept))
        want = _dense(q, k, v, table, lengths, np.zeros(7, np.int32), 128)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        assert np.abs(np.asarray(today, np.float64) - want).max() < tol

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_narrow_heads_in_pages_of_128_read_transposed(self, dtype):
        """Heads of 64 in pages of 128 (lfm2's, groups of 4): the
        kernel reads each page as (H, hd, page_size), the pool's layout
        on the TPU, and the swapped contractions of scores and values
        still give the dense sums; a cursor at 0, inside, at and past a
        page's edge, at the table's end, a slot of trash and a shared
        prefix."""
        q, k, v, table, lengths = _block_case(
            128, 3, [0, 200, 127, 128, 383, 384, 5, 300], hq=8, hkv=2,
            hd=64, dtype=dtype)
        args = (q, k, v, jnp.asarray(table), jnp.asarray(lengths))
        text = str(jax.make_jaxpr(
            lambda *a: paged_attention(*a, interpret=True))(*args))
        assert "permutation=(0, 1, 3, 2)" in text
        got = paged_attention(*args, interpret=True)
        want = _dense(q, k, v, table, lengths, np.zeros(8, np.int32), 128)
        # f32: the sums in another order; bf16: P rounded to bf16 for
        # the value product, as on every lane
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        assert np.abs(np.asarray(got, np.float64) - want).max() < tol

    def test_loop_reports_the_block_it_was_built_with(self):
        """`snapshot()["paged_block_pages"]` and the gauge: the rule's
        answer for the pool the loop built, 0 on the gather lane."""
        from deeplearning4j_tpu.telemetry import exposition

        cfg = TransformerConfig(vocab_size=17, d_model=256, n_heads=2,
                                n_layers=1, d_ff=32, max_len=512,
                                interpret=True)
        p = init_transformer_params(jax.random.PRNGKey(0), cfg)
        # 2 heads of 128 in float32: pages of 16 hold 16 KiB of K, 32
        # a block over 32 columns; pages of 128 hold 128 KiB, 4 a block
        # over 4 columns
        for ps, kernel, want in ((16, "pallas", 32), (128, "pallas", 4),
                                 (16, "gather", 0)):
            name = f"blk-{kernel}-{ps}"
            with DecodeLoop(p, cfg, slots=2, page_size=ps, kernel=kernel,
                            start=False, name=name) as loop:
                assert loop.snapshot()["paged_block_pages"] == want
            assert (f'dl4j_paged_kernel_block_pages{{kind="full",'
                    f'loop="{name}"}} {want}'
                    ) in exposition.render_prometheus()


class TestStepParity:
    def test_teacher_forced_parity_ragged_slots(self):
        """kernel="pallas" vs kernel="gather" on the SAME evolving pool
        state, teacher-forced: logits at 1e-5 every step, pool bytes
        identical (the scatter write path is shared)."""
        p = _params()
        rng = np.random.RandomState(0)
        ps, n_pages = 8, 16
        P = pages_per_slot(CFG, ps)
        pool = init_paged_pool(CFG, n_pages, ps)
        trash = pool.trash_page
        prompts = [_prompt(rng, 10), _prompt(rng, 5)]
        table = np.full((2, P), trash, np.int32)
        free = list(range(n_pages))
        lengths = np.zeros((2,), np.int32)
        tb = 16
        padded = np.zeros((2, tb), np.int32)
        pids = np.full((2, tb // ps), trash, np.int32)
        for i, pr in enumerate(prompts):
            padded[i, :len(pr)] = pr
            need = pages_for_tokens(len(pr), ps)
            pages = [free.pop(0) for _ in range(need)]
            pids[i, :need] = pages
            table[i, :need] = pages
            lengths[i] = len(pr)
        _, pool, _ = paged_kinds.prefill(p, jnp.asarray(padded),
                                jnp.asarray(lengths), pool,
                                {"full": jnp.asarray(pids)}, CFG)
        pool_k = pool  # kernel-lane copy evolves in lockstep
        active = np.ones((2,), bool)
        for _ in range(12):
            toks = rng.randint(0, CFG.vocab_size, (2,)).astype(np.int32)
            for i in range(2):
                pidx = lengths[i] // ps
                if table[i, pidx] == trash:
                    table[i, pidx] = free.pop(0)
            args = (jnp.asarray(toks), jnp.asarray(table),
                    jnp.asarray(lengths), jnp.asarray(active))
            lg_g, pool, _ = paged_kinds.decode_step(
                p, args[0], pool, {"full": args[1]}, args[2], args[3], CFG,
                kernel="gather")
            lg_p, pool_k, _ = paged_kinds.decode_step(
                p, args[0], pool_k, {"full": args[1]}, args[2], args[3], CFG,
                kernel="pallas")
            np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_g),
                                       atol=1e-5)
            for a, b in zip(pool.layers, pool_k.layers):
                np.testing.assert_allclose(np.asarray(a["k"]),
                                           np.asarray(b["k"]), atol=1e-5)
            lengths += 1

    def test_cursor_at_max_len_clamps_and_matches_gather(self):
        """Window edge: a cursor AT max_len (all pages real) — the
        kernel output is finite, matches the gather path, and the K/V
        write still lands on the trash page only."""
        p = _params()
        pool = init_paged_pool(CFG, n_pages=8, page_size=8)
        table = jnp.arange(8, dtype=jnp.int32)[None, :]
        args = (jnp.asarray([3], jnp.int32), table,
                jnp.asarray([CFG.max_len], jnp.int32),
                jnp.asarray([False]))
        lg_g, _, _ = paged_kinds.decode_step(p, args[0], pool,
                                             {"full": args[1]}, args[2],
                                    args[3], CFG, kernel="gather")
        lg_p, new_pool, _ = paged_kinds.decode_step(p, args[0], pool,
                                                    {"full": args[1]},
                                           args[2], args[3], CFG,
                                           kernel="pallas")
        assert bool(jnp.isfinite(lg_p).all())
        np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_g),
                                   atol=1e-5)
        for old, new in zip(pool.layers, new_pool.layers):
            assert bool((old["k"][:8] == new["k"][:8]).all())
            assert bool((old["v"][:8] == new["v"][:8]).all())

    def test_auto_must_be_resolved_before_the_step(self):
        p = _params()
        pool = init_paged_pool(CFG, n_pages=4, page_size=8)
        with pytest.raises(ValueError, match="resolve"):
            paged_kinds.decode_step(
                p, jnp.asarray([1], jnp.int32), pool,
                {"full": jnp.zeros((1, 8), jnp.int32)},
                jnp.zeros((1,), jnp.int32), jnp.asarray([True]), CFG,
                kernel="auto")


# ------------------------------------------------- decode loop parity
class TestLoopParity:
    def _pair(self, p, **kw):
        return (DecodeLoop(p, CFG, kernel="pallas", **kw),
                DecodeLoop(p, CFG, kernel="gather", **kw))

    def test_shared_page_and_post_fork_parity(self):
        """Prefix-cache drill on both lanes: a seeding request, a
        fully-covered replay (CoW fork on the first decode write), and
        a warm-tail request — token streams identical between lanes
        and equal to the solo reference."""
        p = _params()
        rng = np.random.RandomState(2)
        base = _prompt(rng, 16)               # 2 full cacheable pages
        tail = _prompt(rng, 4)
        warm = np.concatenate([base, tail])
        jobs = [(base, 6), (base, 6), (warm, 5)]
        outs = []
        for loop in self._pair(p, slots=2, page_size=8):
            with loop:
                got = []
                for pr, n in jobs:  # sequential: deterministic seeding
                    got.append(loop.submit(pr, n).full_sequence(240))
                snap = loop.snapshot()
                assert snap["prefix_cache"]["hits"] >= 2
                assert snap["prefix_cache"]["forks"] >= 1
                outs.append(got)
        assert outs[0] == outs[1]
        for (pr, n), seq in zip(jobs, outs[0]):
            assert seq == _ref_tokens(p, pr, n)

    def test_horizon_chaining_parity(self):
        """horizon=4 chains steps inside one dispatch on the kernel
        lane: same tokens as the gather lane and the solo reference."""
        p = _params()
        rng = np.random.RandomState(3)
        prompts = [_prompt(rng, t) for t in (5, 13)]
        ns = [11, 6]
        outs = []
        for loop in self._pair(p, slots=2, page_size=8, horizon=4):
            with loop:
                streams = [loop.submit(pr, n)
                           for pr, n in zip(prompts, ns)]
                outs.append([st.full_sequence(240) for st in streams])
        assert outs[0] == outs[1]
        for pr, n, seq in zip(prompts, ns, outs[0]):
            assert seq == _ref_tokens(p, pr, n)

    def test_one_program_with_kernel_lane(self):
        """The kernel lane preserves the recompile guard: one compiled
        step across ragged joins/leaves."""
        p = _params()
        rng = np.random.RandomState(4)
        with DecodeLoop(p, CFG, slots=3, page_size=8,
                        kernel="pallas") as loop:
            assert loop.decode_kernel == "pallas"
            loop.submit(_prompt(rng, 4), 3).result(240)
            for t, n in ((3, 5), (11, 2), (17, 7)):
                loop.submit(_prompt(rng, t), n).result(240)
            assert loop.decode_step_programs() == 1
            assert loop.snapshot()["decode_kernel"]["selected"] == "pallas"


# -------------------------------------------------- lane selection
class TestKernelSelection:
    """Tier-1 guard: off-TPU, "auto" NEVER runs the kernel (no silent
    interpret-mode slowdown in production paths) and explicit "pallas"
    demands interpret mode."""

    def test_auto_off_tpu_selects_gather(self):
        if jax.default_backend() == "tpu":  # pragma: no cover
            pytest.skip("guard is for the off-TPU lane")
        assert resolve_decode_kernel("auto", CFG, 8) == "gather"
        # even with interpret set: interpret is a test lane, not a
        # production fallback
        assert resolve_decode_kernel("auto", CFG_NOINTERP, 16) == "gather"
        with DecodeLoop(_params(), CFG, slots=1, page_size=8,
                        start=False) as loop:
            assert loop.kernel_requested == "auto"
            assert loop.decode_kernel == "gather"

    def test_explicit_pallas_off_tpu_needs_interpret(self):
        if jax.default_backend() == "tpu":  # pragma: no cover
            pytest.skip("guard is for the off-TPU lane")
        with pytest.raises(ValueError, match="interpret"):
            resolve_decode_kernel("pallas", CFG_NOINTERP, 8)
        with pytest.raises(ValueError, match="interpret"):
            DecodeLoop(_params(), CFG_NOINTERP, slots=1, page_size=8,
                       kernel="pallas", start=False)
        assert resolve_decode_kernel("pallas", CFG, 8) == "pallas"

    def test_gather_always_allowed(self):
        assert resolve_decode_kernel("gather", CFG_NOINTERP, 8) == "gather"

    def test_unknown_kernel_rejected(self):
        with pytest.raises(ValueError, match="kernel"):
            resolve_decode_kernel("triton", CFG, 8)

    def test_engine_threads_the_knob(self):
        from deeplearning4j_tpu.serving.engine import InferenceEngine

        eng = InferenceEngine.for_transformer(
            _params(), CFG, decode_slots=1, page_size=8,
            decode_kernel="gather")
        try:
            assert eng.decode_loop.kernel_requested == "gather"
            assert eng.decode_loop.decode_kernel == "gather"
        finally:
            eng.close()


# ------------------------------------------------- cost accounting
class TestDecodeReadBytes:
    def test_formula(self):
        hd = CFG.d_model // CFG.n_heads
        page_b = CFG.n_heads * 8 * hd * 4
        with DecodeLoop(_params(), CFG, slots=2, page_size=8,
                        start=False) as loop:   # an 8-page table
            # cursors 0, 7 -> 1 page; 8 -> 2 pages; 64 (window edge)
            # -> capped at 8
            assert loop._read_bytes([0])[0] == 2 * 2 * page_b * 1
            assert loop._read_bytes([7])[0] == 2 * 2 * page_b * 1
            assert loop._read_bytes([8])[0] == 2 * 2 * page_b * 2
            assert loop._read_bytes([64])[0] == 2 * 2 * page_b * 8
            assert loop._read_bytes([0, 8])[0] == 2 * 2 * page_b * 3
            # the dense-gather figure: every slot reads its FULL
            # reservation
            assert loop._read_bytes([0, 8])[1] == 2 * 2 * page_b * 16

    def test_loop_records_both_paths_per_dispatch(self):
        """Every dispatch accounts streamed-kernel and dense-gather
        bytes; short requests in a wide window show the kernel's
        traffic win (the acceptance-criteria ratio rides bench)."""
        p = _params()
        rng = np.random.RandomState(5)
        with DecodeLoop(p, CFG, slots=2, page_size=8) as loop:
            loop.submit(_prompt(rng, 5), 8).result(240)
            snap = loop.snapshot()
        got = snap["decode_kernel"]["kv_read_bytes"]
        assert got["kernel"] > 0
        token_steps = snap["dispatches"]  # horizon=1
        dense_per_step = loop._read_bytes([0] * loop.slots)[1]
        assert got["gather"] == token_steps * dense_per_step
        # one busy short slot + one idle slot vs a 2 x 8-page dense
        # window: the streamed figure must be well under the dense one
        assert got["gather"] >= 4 * got["kernel"]


# --------------------------------------------- flash q_len=1 satellite
class TestFlashDecodeShapedQuery:
    def test_fit_tile_admits_single_row(self):
        assert _fit_tile(1, 1024) == 1
        assert _fit_tile(128, 1024) == 128
        # non-degenerate ragged lengths still fall back
        assert _fit_tile(60, 1024) is None

    def test_single_row_query_runs_kernel_in_interpret(self):
        """q_len=1 (decode-shaped) rides the flash kernel — bottom-right
        causal alignment: the single query row sees every key."""
        rng = np.random.default_rng(6)
        q = jnp.asarray(rng.normal(size=(4, 1, 32)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(4, 128, 32)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(4, 128, 32)).astype(np.float32))
        out = flash_attention(q, k, v, True, 1024, 128, True)
        ref = blockwise_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_single_row_query_grad_matches_blockwise(self):
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.normal(size=(2, 1, 16)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(2, 128, 16)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(2, 128, 16)).astype(np.float32))

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, 1024, 128,
                                           True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(blockwise_attention(q, k, v,
                                               causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)
