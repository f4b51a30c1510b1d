"""Continuous-batching decode + paged KV cache (ISSUE 6 acceptance).

The contracts under test (serving/paged_kv.py, serving/decode_loop.py,
docs/SERVING.md):

1. **Bit-parity**: the paged-pool decode is the contiguous `KVCache`
   path to 1e-5, teacher-forced per step — paging changes the memory
   layout, never the math (masked lanes underflow to exactly 0, so
   page-tail garbage contributes exactly 0).
2. **Slot join/leave**: a request joining mid-flight produces exactly
   the tokens it would produce alone, and never perturbs the streams
   already running — slots are independent through their page tables.
3. **Page-exhaustion backpressure**: admission waits for free pages
   instead of over-reserving; pool occupancy tracks written tokens.
4. **One compiled program**: the decode step's program cache stays at 1
   across ragged joins/leaves of every shape (utils/jitcache.py).
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_transformer_params)
from deeplearning4j_tpu.serving import paged_kinds
from deeplearning4j_tpu.serving.decode_loop import DecodeLoop
from deeplearning4j_tpu.serving.kv_cache import (decode_step,
                                                 generate_cached,
                                                 init_cache, kv_cache_bytes,
                                                 prefill)
from deeplearning4j_tpu.serving.paged_kinds import _write_rows
from deeplearning4j_tpu.serving.paged_kv import (init_paged_pool,
                                                 paged_kv_bytes,
                                                 pages_for_tokens,
                                                 pages_per_slot,
                                                 prompt_buckets)

CFG = TransformerConfig(vocab_size=17, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_len=64, interpret=True)


def _params(seed=0):
    return init_transformer_params(jax.random.PRNGKey(seed), CFG)


def _prompt(rng, t):
    return rng.randint(0, CFG.vocab_size, (t,)).astype(np.int32)


def _ref_tokens(p, prompt, n):
    """Greedy reference via the contiguous compiled-scan path."""
    return np.asarray(generate_cached(
        p, jnp.asarray(prompt[None]), CFG, n))[0].tolist()


# ---------------------------------------------------------- pool basics
class TestPagedPool:
    def test_pool_shapes_and_trash_page(self):
        pool = init_paged_pool(CFG, n_pages=10, page_size=8)
        hd = CFG.d_model // CFG.n_heads
        for layer in pool.layers:
            assert layer["k"].shape == (11, CFG.n_heads, 8, hd)
        assert pool.n_pages == 10 and pool.trash_page == 10
        assert pool.page_size == 8

    def test_pool_memory_envelope(self):
        # 2 (K,V) * n_layers * (pages+trash) * page_size * d_model * 4
        assert paged_kv_bytes(CFG, 10, 8) == 2 * 2 * 11 * 8 * 32 * 4
        with pytest.raises(ValueError, match="n_pages"):
            paged_kv_bytes(CFG, 0, 8)

    def test_page_math(self):
        assert pages_per_slot(CFG, 8) == 8
        assert pages_for_tokens(1, 8) == 1
        assert pages_for_tokens(8, 8) == 1
        assert pages_for_tokens(9, 8) == 2
        assert prompt_buckets(CFG, 8) == (8, 16, 32, 64)

    def test_validates_args(self):
        with pytest.raises(ValueError, match="n_pages"):
            init_paged_pool(CFG, 0, 8)
        with pytest.raises(ValueError, match="page_size"):
            init_paged_pool(CFG, 4, 0)


# ------------------------------------------------- contiguous satellite
class TestInitCacheValidation:
    """ISSUE satellite: an explicit length=0 must be rejected, not
    silently allocate the full window; batch_size is validated."""

    def test_explicit_zero_length_rejected(self):
        with pytest.raises(ValueError, match="length"):
            init_cache(CFG, 1, length=0)
        with pytest.raises(ValueError, match="length"):
            kv_cache_bytes(CFG, 1, length=0)
        with pytest.raises(ValueError, match="length"):
            init_cache(CFG, 1, length=-3)

    def test_default_still_allocates_full_window(self):
        cache = init_cache(CFG, 2)
        assert cache.layers[0]["k"].shape[2] == CFG.max_len
        assert init_cache(CFG, 2, length=None).layers[0]["k"].shape[2] \
            == CFG.max_len

    def test_batch_size_validated(self):
        with pytest.raises(ValueError, match="batch_size"):
            init_cache(CFG, 0)
        with pytest.raises(ValueError, match="batch_size"):
            kv_cache_bytes(CFG, -1)


# ------------------------------------------------------------ parity
class TestPagedParity:
    """Acceptance bar: paged-pool decode is bit-parity (1e-5) with the
    contiguous KVCache path, teacher-forced per step — including RAGGED
    slots at different lengths sharing one pool."""

    def test_teacher_forced_parity_ragged_slots(self):
        p = _params()
        rng = np.random.RandomState(0)
        ps, n_pages = 8, 16
        P = pages_per_slot(CFG, ps)
        pool = init_paged_pool(CFG, n_pages, ps)
        trash = pool.trash_page
        t0s = [10, 5]
        prompts = [_prompt(rng, t) for t in t0s]

        # contiguous reference, one cache per stream
        caches, ref_first = [], []
        for pr in prompts:
            lg, c = prefill(p, jnp.asarray(pr[None]),
                            init_cache(CFG, 1), CFG)
            caches.append(c)
            ref_first.append(np.asarray(lg))

        # paged: both prompts in ONE batched prefill (ragged -> each
        # row padded to its shared bucket)
        table = np.full((2, P), trash, np.int32)
        free = list(range(n_pages))
        lengths = np.zeros((2,), np.int32)
        tb = 16  # bucket covering both prompts
        padded = np.zeros((2, tb), np.int32)
        pids = np.full((2, tb // ps), trash, np.int32)
        for i, pr in enumerate(prompts):
            padded[i, :len(pr)] = pr
            need = pages_for_tokens(len(pr), ps)
            pages = [free.pop(0) for _ in range(need)]
            pids[i, :need] = pages
            table[i, :need] = pages
            lengths[i] = len(pr)
        logits, pool, _ = paged_kinds.prefill(p, jnp.asarray(padded),
                                     jnp.asarray(lengths), pool,
                                     {"full": jnp.asarray(pids)}, CFG)
        logits = np.asarray(logits)
        for i in range(2):
            np.testing.assert_allclose(logits[i], ref_first[i][0],
                                       atol=1e-5)

        # teacher-forced decode: same tokens through both paths
        active = np.ones((2,), bool)
        for step in range(12):
            toks = rng.randint(0, CFG.vocab_size, (2,)).astype(np.int32)
            for i in range(2):  # grant boundary pages
                pidx = lengths[i] // ps
                if table[i, pidx] == trash:
                    table[i, pidx] = free.pop(0)
            lg, pool, _ = paged_kinds.decode_step(
                p, jnp.asarray(toks), pool, {"full": jnp.asarray(table)},
                jnp.asarray(lengths), jnp.asarray(active), CFG)
            lg = np.asarray(lg)
            for i in range(2):
                ref, caches[i] = decode_step(
                    p, jnp.asarray(toks[i][None]), caches[i], CFG)
                np.testing.assert_allclose(lg[i], np.asarray(ref)[0],
                                           atol=1e-5)
            lengths += 1

    def test_inactive_slot_state_is_never_touched(self):
        """A masked slot's pages keep their exact bytes across steps
        (writes divert to the trash page)."""
        p = _params()
        rng = np.random.RandomState(1)
        ps = 8
        P = pages_per_slot(CFG, ps)
        pool = init_paged_pool(CFG, 8, ps)
        trash = pool.trash_page
        pr = _prompt(rng, 9)
        table = np.full((2, P), trash, np.int32)
        pids = np.full((2, 16 // ps), trash, np.int32)
        padded = np.zeros((2, 16), np.int32)
        padded[0, :9] = pr
        pids[0] = [0, 1]
        table[0, :2] = [0, 1]
        lengths = np.asarray([9, 0], np.int32)
        _, pool, _ = paged_kinds.prefill(p, jnp.asarray(padded),
                                jnp.asarray([9, 1], np.int32), pool,
                                {"full": jnp.asarray(pids)}, CFG)
        before = [np.asarray(layer["k"])[:2] for layer in pool.layers]
        # run steps with slot 0 INACTIVE, slot 1 active on page 2
        table[1, 0] = 2
        active = np.asarray([False, True])
        for _ in range(3):
            toks = rng.randint(0, CFG.vocab_size, (2,)).astype(np.int32)
            _, pool, _ = paged_kinds.decode_step(
                p, jnp.asarray(toks), pool, {"full": jnp.asarray(table)},
                jnp.asarray(lengths), jnp.asarray(active), CFG)
            lengths = lengths + np.asarray([0, 1], np.int32)
        after = [np.asarray(layer["k"])[:2] for layer in pool.layers]
        for b, a in zip(before, after):
            np.testing.assert_array_equal(b, a)


# ------------------------------------------------------- the K/V write
def _destinations(table, pos, live, ps, trash):
    """Physical (page, offset) of each cursor in `pos`, as both step
    functions compute them: not live, or at or past the window's edge,
    goes to the trash page."""
    n_p = table.shape[1]
    rows = np.arange(table.shape[0]).reshape((-1,) + (1,) * (pos.ndim - 1))
    page = table[rows, np.minimum(pos // ps, n_p - 1)]
    return (np.where(live & (pos // ps < n_p), page, trash).astype(np.int32),
            (pos % ps).astype(np.int32))


#: name -> (cursors (S,), widths (S,)); page 8, a window of 4 pages.
#: A width of 0 is an idle slot; in the step a slot is active iff its
#: width is nonzero
WRITE_CASES = {
    "all_live": ([0, 7, 8, 21], [1, 2, 4, 3]),
    # slots 1 and 3 idle at the SAME offset: they collide on the trash
    "idle_collide_on_trash": ([5, 13, 9, 21], [4, 0, 2, 0]),
    # cursor 32 is the window's edge (page 4 of 4); 30 crosses it at
    # its third column
    "cursor_at_window_edge": ([32, 30, 31, 3], [1, 4, 1, 2]),
    "ragged_widths": ([2, 15, 16, 23], [4, 1, 3, 2]),
}


class TestWriteRows:
    """ISSUE 27: `_write_rows` indexes page, head and offset where the
    old write indexed page and offset with the head dimension as a
    window between them. Same values in the same places, bit for bit,
    for the step ((S,) cursors) and the verify step ((S, W))."""

    PS, N_PAGES, H, HD, W = 8, 20, 2, 16, 4

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("case", sorted(WRITE_CASES))
    @pytest.mark.parametrize("step", ["decode", "verify"])
    def test_same_bits_as_the_two_index_scatter(self, step, case, dtype):
        rng = np.random.RandomState(27)
        s, trash = 4, self.N_PAGES
        shape = (self.N_PAGES + 1, self.H, self.PS, self.HD)
        arr = jnp.asarray(rng.randn(*shape), dtype)
        table = rng.permutation(self.N_PAGES)[:s * 4].reshape(s, 4)
        cursors, widths = (np.asarray(a) for a in WRITE_CASES[case])
        if step == "decode":
            pos, live = cursors, widths > 0
        else:
            pos = cursors[:, None] + np.arange(self.W)[None, :]
            live = np.arange(self.W)[None, :] < widths[:, None]
        dest, offset = _destinations(table, pos, live, self.PS, trash)
        # float32 rows into a bfloat16 pool: the write casts
        rows = jnp.asarray(rng.randn(*pos.shape, self.H, self.HD),
                           jnp.float32)
        new = np.asarray(_write_rows(arr, jnp.asarray(dest),
                                     jnp.asarray(offset), rows)
                         .astype(jnp.float32))
        old = np.asarray(arr.at[dest, :, offset, :].set(
            rows.astype(arr.dtype)).astype(jnp.float32))
        assert new.dtype == old.dtype and new.shape == shape
        np.testing.assert_array_equal(new[:trash], old[:trash])
        before = np.asarray(arr.astype(jnp.float32))
        assert (new[:trash] != before[:trash]).any()  # it did write
        # the trash page: a row that several writes hit holds one of
        # them (their order is not promised), any other is untouched
        want = np.asarray(rows.astype(arr.dtype).astype(jnp.float32))
        hits = list(zip(*np.nonzero(dest == trash)))
        for off in range(self.PS):
            got = new[trash, :, off, :]
            cands = [want[i] for i in hits if offset[i] == off]
            if not cands:
                np.testing.assert_array_equal(got, before[trash, :, off])
            else:
                for h in range(self.H):
                    assert any((got[h] == c[h]).all() for c in cands)


# --------------------------------------------------------- decode loop
class TestDecodeLoop:
    def test_concurrent_ragged_streams_match_reference(self):
        """Several ragged streams decoded CONCURRENTLY produce exactly
        the per-request reference tokens — continuous batching changes
        scheduling, never output."""
        p = _params()
        rng = np.random.RandomState(0)
        with DecodeLoop(p, CFG, slots=4, page_size=8) as loop:
            prompts = [_prompt(rng, t) for t in (10, 5, 17, 3)]
            ns = [12, 6, 20, 1]
            streams = [loop.submit(pr, n) for pr, n in zip(prompts, ns)]
            for pr, n, st in zip(prompts, ns, streams):
                assert st.full_sequence(120) == _ref_tokens(p, pr, n)
                assert st.finish_reason == "max_tokens"

    def test_join_mid_flight_no_interleave(self):
        """ISSUE acceptance: a late-joining request's tokens never
        interleave into another stream, and joining does not perturb
        the in-flight stream's remaining tokens."""
        p = _params()
        rng = np.random.RandomState(3)
        long_pr, short_pr = _prompt(rng, 12), _prompt(rng, 6)
        ref_long = _ref_tokens(p, long_pr, 30)
        ref_short = _ref_tokens(p, short_pr, 8)
        with DecodeLoop(p, CFG, slots=2, page_size=8) as loop:
            st_a = loop.submit(long_pr, 30)
            it = st_a.tokens(timeout=120)
            got_early = [next(it) for _ in range(3)]  # A is mid-flight
            st_b = loop.submit(short_pr, 8)           # B joins late
            assert st_b.full_sequence(120) == ref_short
            got_rest = list(it)
            assert long_pr.tolist() + got_early + got_rest == ref_long

    def test_leave_frees_slot_for_queued_request(self):
        """More streams than slots: completions hand slots to queued
        requests and every stream still matches its solo reference."""
        p = _params()
        rng = np.random.RandomState(4)
        prompts = [_prompt(rng, int(t)) for t in
                   rng.randint(3, 20, size=6)]
        ns = [int(n) for n in rng.randint(1, 12, size=6)]
        with DecodeLoop(p, CFG, slots=2, page_size=8) as loop:
            streams = [loop.submit(pr, n) for pr, n in zip(prompts, ns)]
            for pr, n, st in zip(prompts, ns, streams):
                assert st.full_sequence(240) == _ref_tokens(p, pr, n)

    def test_eos_early_termination(self):
        p = _params()
        rng = np.random.RandomState(5)
        pr = _prompt(rng, 9)
        gen = _ref_tokens(p, pr, 20)[9:]
        eos = gen[min(4, len(gen) - 1)]
        first = gen.index(eos)
        with DecodeLoop(p, CFG, slots=2, page_size=8) as loop:
            st = loop.submit(pr, 20, eos_id=eos)
            assert st.result(120) == gen[:first + 1]
            assert st.finish_reason == "eos"
            # EOS freed the pages immediately
            assert loop.snapshot()["pages_in_use"] == 0

    def test_page_exhaustion_admission_backpressure(self):
        """ISSUE acceptance: a pool too small for all requests at once
        admits what fits, holds the rest until pages free, and peak
        occupancy never exceeds the pool."""
        p = _params()
        rng = np.random.RandomState(6)
        # each request needs 2 pages (8-token prompt + decode growth)
        with DecodeLoop(p, CFG, slots=2, page_size=8,
                        n_pages=4) as loop:
            streams = [loop.submit(_prompt(rng, 8), 9)
                       for _ in range(4)]
            outs = [s.result(240) for s in streams]
            snap = loop.snapshot()
        assert all(len(o) == 9 for o in outs)
        assert snap["peak_pages_in_use"] <= 4
        assert snap["admission_waits"] >= 1

    def test_pool_occupancy_tracks_written_tokens(self):
        """Acceptance bar: KV accounting is proportional to written
        tokens, not max_len x active requests."""
        p = _params()
        rng = np.random.RandomState(7)
        loop = DecodeLoop(p, CFG, slots=2, page_size=8, start=False)
        pr = _prompt(rng, 9)  # 2 pages of prompt
        loop.submit(pr, 4)
        loop.tick()  # admit + first chunk
        snap = loop.snapshot()
        # 9 prompt tokens + a handful decoded: 2 pages, not the
        # 8-page max_len reservation the contiguous path would pin
        assert snap["pages_in_use"] == pages_for_tokens(9 + 4, 8)
        assert snap["pages_in_use"] < pages_per_slot(CFG, 8)
        loop.run_until_idle()
        assert loop.snapshot()["pages_in_use"] == 0
        loop.close()

    def test_pool_exhaustion_with_no_path_forward_fails_loudly(self):
        """A single stream needing more pages than the whole pool must
        error out, not deadlock the scheduler."""
        p = _params()
        with DecodeLoop(p, CFG, slots=1, page_size=8,
                        n_pages=2) as loop:
            st = loop.submit(np.arange(8, dtype=np.int32) % 17, 30)
            with pytest.raises(RuntimeError, match="exhausted"):
                st.result(120)
            assert st.finish_reason == "error"

    def test_submit_validation(self):
        p = _params()
        with DecodeLoop(p, CFG, slots=1, page_size=8) as loop:
            with pytest.raises(ValueError, match="empty"):
                loop.submit([], 4)
            with pytest.raises(ValueError, match="max_tokens"):
                loop.submit([1, 2], 0)
            with pytest.raises(ValueError, match="max_len"):
                loop.submit(np.zeros(60, np.int32), 8)

    def test_close_drains_then_rejects(self):
        p = _params()
        rng = np.random.RandomState(8)
        loop = DecodeLoop(p, CFG, slots=2, page_size=8)
        pr = _prompt(rng, 5)
        st = loop.submit(pr, 6)
        loop.close()
        assert st.full_sequence(1) == _ref_tokens(p, pr, 6)
        with pytest.raises(RuntimeError, match="closed"):
            loop.submit(pr, 2)


# -------------------------------------------------- one program, ever
class TestRecompileGuard:
    def test_decode_step_compiles_exactly_once_across_ragged_joins(self):
        """ISSUE acceptance: the decode step stays at ONE compiled
        program across ragged joins/leaves (every prompt length,
        max_tokens, EOS mix) — membership is traced, never a shape."""
        p = _params()
        rng = np.random.RandomState(9)
        with DecodeLoop(p, CFG, slots=3, page_size=8) as loop:
            loop.submit(_prompt(rng, 4), 3).result(120)  # warmup
            programs = loop.decode_step_programs()
            assert programs >= 0, "jax _cache_size API drifted"
            assert programs == 1
            # ragged joins: varying prompt lengths, budgets, eos
            streams = []
            for t, n in ((3, 5), (11, 2), (21, 9), (7, 1), (16, 14)):
                streams.append(loop.submit(_prompt(rng, t), n))
            for st in streams:
                st.result(240)
            assert loop.decode_step_programs() == 1  # zero recompiles
            # prefill stays on its bucket ladder
            assert loop.prefill_programs() <= len(prompt_buckets(CFG, 8))

    def test_horizon_chunking_preserves_tokens_and_one_program(self):
        """A horizon>1 loop (several decode steps per dispatch) changes
        scheduling granularity only — same tokens, still one compiled
        step program."""
        p = _params()
        rng = np.random.RandomState(10)
        with DecodeLoop(p, CFG, slots=2, page_size=8,
                        horizon=4) as loop:
            prompts = [_prompt(rng, t) for t in (5, 13)]
            ns = [11, 6]
            streams = [loop.submit(pr, n) for pr, n in zip(prompts, ns)]
            for pr, n, st in zip(prompts, ns, streams):
                assert st.full_sequence(120) == _ref_tokens(p, pr, n)
            assert loop.decode_step_programs() == 1


# ---------------------------------------------- window-edge regression
class TestWindowEdge:
    """ISSUE 12 satellite: `paged_decode_step` indexed
    `params["pos"][pos]` unclamped while `paged_prefill` clamps — a
    cursor AT the window edge must reuse the last position embedding,
    not read past the (max_len, d) table."""

    def test_generation_to_the_exact_window_edge(self):
        """prompt + max_tokens == max_len: the slot decodes to the last
        writable position and still matches the contiguous reference
        token-for-token."""
        p = _params()
        rng = np.random.RandomState(20)
        pr = _prompt(rng, 34)
        n = CFG.max_len - len(pr)  # 30: the largest budget validate allows
        ref = _ref_tokens(p, pr, n)
        with DecodeLoop(p, CFG, slots=1, page_size=8) as loop:
            st = loop.submit(pr, n)
            assert st.full_sequence(240) == ref
            assert st.finish_reason == "max_tokens"

    def test_cursor_at_max_len_writes_trash_and_stays_finite(self):
        """Direct step call with a cursor AT max_len (an inactive lane
        a horizon chunk can carry): the K/V write lands on the trash
        page — every real page is untouched — and the embedding lookup
        clamps instead of reading out of bounds."""
        p = _params()
        pool = init_paged_pool(CFG, n_pages=8, page_size=8)
        table = jnp.arange(8, dtype=jnp.int32)[None, :]  # all real pages
        logits, new_pool, _ = paged_kinds.decode_step(
            p, jnp.asarray([3], jnp.int32), pool, {"full": table},
            jnp.asarray([CFG.max_len], jnp.int32),
            jnp.asarray([False]), CFG)
        assert bool(jnp.isfinite(logits).all())
        for old, new in zip(pool.layers, new_pool.layers):
            # real pages bit-unchanged; only the trash page absorbed it
            assert bool((old["k"][:8] == new["k"][:8]).all())
            assert bool((old["v"][:8] == new["v"][:8]).all())


# ------------------------------------------------- concurrent clients
class TestConcurrentSubmitters:
    def test_many_threads_submitting_concurrently(self):
        """Thread-safety: concurrent submitters all get their own
        reference streams back."""
        p = _params()
        rng = np.random.RandomState(11)
        jobs = [(_prompt(rng, int(t)), int(n))
                for t, n in zip(rng.randint(3, 16, 8),
                                rng.randint(1, 10, 8))]
        refs = [_ref_tokens(p, pr, n) for pr, n in jobs]
        results = [None] * len(jobs)
        with DecodeLoop(p, CFG, slots=3, page_size=8) as loop:
            def worker(i):
                pr, n = jobs[i]
                results[i] = loop.submit(pr, n).full_sequence(240)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(jobs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert results == refs


# ------------------------------------------------ admission shedding
class TestAdmissionShedding:
    def test_max_waiting_sheds_only_when_not_immediately_admittable(self):
        """ISSUE 7 satellite: with `max_waiting` set, a submit that
        cannot start right now (no free slot / pages) while the
        admission queue is at its bound raises OverloadedError — but a
        request that COULD start immediately is never shed."""
        from deeplearning4j_tpu.serving.errors import OverloadedError

        p = _params()
        # start=False: no scheduler thread, so nothing is admitted and
        # the queue state is fully deterministic
        loop = DecodeLoop(p, CFG, slots=1, page_size=8, max_waiting=0,
                          start=False)
        first = loop.submit([1, 2, 3], 4)  # admittable now -> queued
        assert first is not None
        with pytest.raises(OverloadedError) as e:
            loop.submit([4, 5], 3)  # queue occupied, bound is 0
        assert e.value.retry_after_ms > 0
        assert loop.snapshot()["shed"] == 1
        # drain the queued request; the loop accepts again after
        loop.run_until_idle()
        assert first.done
        second = loop.submit([4, 5], 3)
        loop.run_until_idle()
        assert second.done
        loop.close()

    def test_validation_errors_stay_400_shaped(self):
        """Permanent failures (prompt can never fit) are ValueError,
        not OverloadedError — a client must not retry them."""
        p = _params()
        loop = DecodeLoop(p, CFG, slots=1, page_size=8, n_pages=2,
                          max_waiting=4, start=False)
        with pytest.raises(ValueError, match="pages"):
            loop.submit(list(range(40)), 4)
        loop.close()
