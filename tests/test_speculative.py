"""Speculative decoding: draft-and-verify on the deterministic decode
lane (ISSUE 16 acceptance).

The contracts under test (serving/speculation.py, the DecodeLoop
speculative dispatch, docs/SERVING.md "Speculative decoding"):

1. **Bit-identity**: speculative output equals non-speculative output
   token for token, for BOTH drafter flavors, with prefix-cache reuse,
   and through the HTTP surface — acceptance is exact (longest draft
   run matching the target's own argmax, first mismatch replaced by
   the verify logits' token), so speculation moves throughput, never
   bits.
2. **Verify-step parity**: ONE widened `paged_verify_step` over k+1
   columns matches k+1 chained `paged_decode_step` calls on both
   kernel lanes — verify is a widened step, not new math.
3. **Program pinning**: `decode_step_programs <= 2` (decode + verify)
   no matter how rounds mix drafted and undrafted slots.
4. **Accounting**: dl4j_spec_{proposed,accepted,rounds} + the
   acceptance-rate gauge, scraped end to end off a live `/metrics`;
   page refcounts stay partitioned (free + in-use + cached == pool).
5. **Canary path**: `/reload {"target": "draft"}` swaps ONLY the draft
   weights; a bad draft can only cost acceptance rate.
"""

from __future__ import annotations

import json
import subprocess
import sys
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_transformer_params)
from deeplearning4j_tpu.serving import paged_kinds
from deeplearning4j_tpu.serving.decode_loop import DecodeLoop
from deeplearning4j_tpu.serving.kv_cache import generate_cached
from deeplearning4j_tpu.serving.paged_kv import (init_paged_pool,
                                                 pages_for_tokens,
                                                 pages_per_slot)
from deeplearning4j_tpu.serving.prefix_cache import PrefixIndex
from deeplearning4j_tpu.serving.speculation import (ModelDrafter,
                                                    NgramDrafter,
                                                    build_drafter)

pytestmark = pytest.mark.spec

CFG = TransformerConfig(vocab_size=17, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_len=64, interpret=True)
DRAFT_CFG = TransformerConfig(vocab_size=17, d_model=16, n_heads=2,
                              n_layers=1, d_ff=32, max_len=64,
                              interpret=True)


def _params(seed=0, cfg=CFG):
    return init_transformer_params(jax.random.PRNGKey(seed), cfg)


def _prompt(rng, t):
    return rng.randint(0, CFG.vocab_size, (t,)).astype(np.int32)


def _ref_tokens(p, prompt, n):
    return np.asarray(generate_cached(
        p, jnp.asarray(np.asarray(prompt)[None]), CFG, n))[0].tolist()


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def draft_params():
    return _params(7, DRAFT_CFG)


# ------------------------------------------------------- drafter units
class TestNgramDrafter:
    def test_proposes_from_own_history(self):
        d = NgramDrafter(ngram=3)
        # ...5,6,7 occurred earlier followed by 8,9 — propose that
        hist = [1, 5, 6, 7, 8, 9, 2, 5, 6, 7]
        assert d.propose(hist, 2) == [8, 9]

    def test_most_recent_occurrence_wins(self):
        d = NgramDrafter(ngram=1)
        assert d.propose([4, 1, 4, 2, 4], 1) == [2]

    def test_prefers_occurrence_with_full_k_continuation(self):
        d = NgramDrafter(ngram=2)
        # suffix [1,2]: i=5 has the most recent followed occurrence but
        # only 3 tokens after it; k=3 takes it, k=4 reaches back to i=0
        hist = [1, 2, 3, 4, 9, 1, 2, 5, 1, 2]
        assert d.propose(hist, 3) == [5, 1, 2]
        assert d.propose(hist, 4) == [3, 4, 9, 1]

    def test_period_one_tail_proposes_full_k(self):
        # a greedy model stuck on one token — the drill regime: the
        # LAST occurrence has 1 follower, an earlier one has k
        d = NgramDrafter(ngram=3)
        assert d.propose([7, 8] + [5] * 10, 4) == [5, 5, 5, 5]

    def test_falls_back_to_shorter_ngrams(self):
        d = NgramDrafter(ngram=3)
        assert d.propose([9, 9, 3, 1, 2, 3], 1) == [1]

    def test_corpus_fallback(self):
        corpus = [[1, 2, 3, 4, 5, 6]]
        d = NgramDrafter(ngram=2, corpus=lambda: corpus)
        assert d.propose([7, 2, 3], 3) == [4, 5, 6]

    def test_own_history_preferred_over_corpus(self):
        corpus = [[2, 3, 9]]
        d = NgramDrafter(ngram=2, corpus=lambda: corpus)
        assert d.propose([2, 3, 8, 2, 3], 1) == [8]

    def test_no_match_returns_empty(self):
        d = NgramDrafter(ngram=3)
        assert d.propose([1, 2, 3], 4) == []
        assert d.propose([5], 4) == []
        assert d.propose([1, 2, 3], 0) == []

    def test_validates_ngram(self):
        with pytest.raises(ValueError, match="ngram"):
            NgramDrafter(ngram=0)


class TestModelDrafter:
    def test_window_clamped_to_max_len(self, draft_params):
        d = ModelDrafter(draft_params, DRAFT_CFG, window=1000)
        assert d.window == DRAFT_CFG.max_len

    def test_one_program_across_ragged_rounds(self, draft_params):
        d = ModelDrafter(draft_params, DRAFT_CFG, window=8)
        rng = np.random.RandomState(0)
        assert d.draft_programs() == 0  # lazy until first use
        for _ in range(3):
            win = rng.randint(0, 17, (4, 8)).astype(np.int32)
            out = d.propose_all(win, 3)
            assert out.shape == (4, 3)
        assert d.draft_programs() == 1

    def test_greedy_rollout_matches_manual(self, draft_params):
        from deeplearning4j_tpu.models.transformer import \
            transformer_logits

        d = ModelDrafter(draft_params, DRAFT_CFG, window=8)
        win = np.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], np.int32)
        got = d.propose_all(win, 2)[0].tolist()
        w = win.copy()
        want = []
        for _ in range(2):
            lg = np.asarray(transformer_logits(
                draft_params, jnp.asarray(w), DRAFT_CFG))
            nxt = int(np.argmax(lg[0, -1]))
            want.append(nxt)
            w = np.concatenate([w[:, 1:], [[nxt]]], axis=1).astype(
                np.int32)
        assert got == want


class TestBuildDrafter:
    def test_model_needs_params_and_cfg(self):
        with pytest.raises(ValueError, match="draft_params"):
            build_drafter("model", k=4, cfg=CFG)

    def test_vocab_mismatch_named(self, draft_params):
        bad = DRAFT_CFG._replace(vocab_size=99)
        with pytest.raises(ValueError, match="vocab_size"):
            build_drafter("model", k=4, cfg=CFG,
                          draft_params=draft_params, draft_cfg=bad)

    def test_unknown_flavor(self):
        with pytest.raises(ValueError, match="drafter"):
            build_drafter("oracle", k=4, cfg=CFG)


class TestPrefixCorpus:
    def test_iter_sequences_yields_maximal_paths(self):
        idx = PrefixIndex(page_size=2)
        idx.insert([1, 2, 3, 4], [0, 1])
        idx.insert([1, 2, 9, 9], [0, 2])
        seqs = list(idx.iter_sequences())
        assert sorted(seqs) == [[1, 2, 3, 4], [1, 2, 9, 9]]

    def test_recently_touched_first(self):
        idx = PrefixIndex(page_size=2)
        idx.insert([1, 2, 3, 4], [0, 1])
        idx.insert([5, 6, 7, 8], [2, 3])
        idx.match([1, 2, 3, 4])  # touch the first path
        assert next(iter(idx.iter_sequences())) == [1, 2, 3, 4]


# --------------------------------------------------- verify-step parity
@pytest.mark.pallas
class TestVerifyStepParity:
    """One widened verify step == W chained single-token decode steps,
    teacher-forced, on both kernel lanes (ragged widths included)."""

    @pytest.mark.parametrize("kernel", ["gather", "pallas"])
    def test_matches_chained_decode_steps(self, params, kernel):
        rng = np.random.RandomState(3)
        ps, n_pages, W = 8, 16, 4
        P = pages_per_slot(CFG, ps)
        t0s = [10, 5, 8]
        prompts = [_prompt(rng, t) for t in t0s]
        trash = n_pages

        def seeded_pool():
            pool = init_paged_pool(CFG, n_pages, ps)
            table = np.full((3, P), trash, np.int32)
            free = list(range(n_pages))
            lengths = np.zeros((3,), np.int32)
            tb = 16
            padded = np.zeros((3, tb), np.int32)
            pids = np.full((3, tb // ps), trash, np.int32)
            for i, pr in enumerate(prompts):
                padded[i, :len(pr)] = pr
                # grant pages covering prompt + W continuations so the
                # widened writes land in real pages
                need = pages_for_tokens(len(pr) + W, ps)
                pages = [free.pop(0) for _ in range(need)]
                pids[i, :pages_for_tokens(len(pr), ps)] = \
                    pages[:pages_for_tokens(len(pr), ps)]
                table[i, :need] = pages
                lengths[i] = len(pr)
            _, pool, _ = paged_kinds.prefill(params, jnp.asarray(padded),
                                    jnp.asarray(lengths), pool,
                                    {"full": jnp.asarray(pids)}, CFG)
            return pool, table, lengths

        tokens = rng.randint(0, CFG.vocab_size, (3, W)).astype(np.int32)
        widths = np.asarray([4, 4, 2], np.int32)

        # chained reference: W teacher-forced single-token steps
        pool_a, table, lengths = seeded_pool()
        ref = np.full((3, W, CFG.vocab_size), np.nan, np.float32)
        cur = lengths.copy()
        for j in range(W):
            act = widths > j
            lg, pool_a, _ = paged_kinds.decode_step(
                params, jnp.asarray(tokens[:, j]), pool_a,
                {"full": jnp.asarray(table)}, jnp.asarray(cur),
                jnp.asarray(act), CFG, kernel=kernel)
            lg = np.asarray(lg)
            for i in range(3):
                if act[i]:
                    ref[i, j] = lg[i]
            cur = cur + act.astype(np.int32)

        # one widened verify step
        pool_b, table, lengths = seeded_pool()
        lg, pool_b, _ = paged_kinds.verify_step(
            params, jnp.asarray(tokens), pool_b, {"full": jnp.asarray(table)},
            jnp.asarray(lengths), jnp.asarray(widths), CFG,
            kernel=kernel)
        lg = np.asarray(lg)
        for i in range(3):
            for j in range(int(widths[i])):
                np.testing.assert_allclose(lg[i, j], ref[i, j],
                                           atol=1e-5)
                assert (int(np.argmax(lg[i, j]))
                        == int(np.argmax(ref[i, j])))

    def test_rejects_unresolved_kernel(self, params):
        pool = init_paged_pool(CFG, 4, 8)
        with pytest.raises(ValueError, match="kernel"):
            paged_kinds.verify_step(
                params, jnp.zeros((1, 2), jnp.int32), pool,
                {"full": jnp.zeros((1, 2), jnp.int32)},
                jnp.zeros((1,), jnp.int32),
                jnp.ones((1,), jnp.int32), CFG, kernel="auto")


# ------------------------------------------------------ loop bit-identity
class TestSpeculativeLoop:
    PROMPTS = ([1, 2, 3, 4, 5, 6, 7, 8],
               [3, 1, 4, 1, 5, 9, 2, 6, 5, 3],
               [7, 7, 7, 7])
    MT = (24, 20, 16)

    def _run(self, params, **kw):
        with DecodeLoop(params, CFG, slots=4, page_size=8,
                        kernel="gather", **kw) as loop:
            streams = loop.submit_many(list(self.PROMPTS), list(self.MT))
            out = [s.result(timeout=120) for s in streams]
            reasons = [s.finish_reason for s in streams]
            snap = loop.snapshot()
            programs = loop.decode_step_programs()
            pages_ok = (len(loop._free) + loop.pages_in_use
                        + loop._cached_unref() == loop.n_pages)
        return out, reasons, snap, programs, pages_ok

    def test_ngram_bit_identical_and_pinned(self, params):
        ref, ref_r, _, ref_prog, _ = self._run(params)
        assert ref_prog == 1
        out, reasons, snap, programs, pages_ok = self._run(
            params, speculation=4, drafter="ngram")
        assert out == ref
        assert reasons == ref_r
        assert programs <= 2
        assert pages_ok
        spec = snap["speculation"]
        assert spec["enabled"] and spec["k"] == 4
        assert spec["drafter"] == "ngram"
        assert spec["rounds"] >= 1
        assert 0 <= spec["accepted"] <= spec["proposed"]
        assert 0.0 <= spec["acceptance_rate"] <= 1.0

    def test_model_drafter_bit_identical(self, params, draft_params):
        ref, _, _, _, _ = self._run(params)
        out, _, snap, programs, pages_ok = self._run(
            params, speculation=3, drafter="model",
            draft_params=draft_params, draft_cfg=DRAFT_CFG,
            draft_window=16)
        assert out == ref
        assert programs <= 2
        assert pages_ok
        assert snap["speculation"]["drafter"] == "model"
        assert snap["speculation"]["draft_programs"] <= 1

    def test_self_draft_accepts_nearly_everything(self, params):
        """The target model drafting for itself agrees with the verify
        almost always — NOT exactly (the drafter runs a right-aligned
        window with window-relative positions, so its logits drift from
        the full-context target's once the padding/truncation differs).
        The residual disagreement is precisely why the verify step, not
        the drafter, must own every emitted token."""
        ref, _, _, _, _ = self._run(params)
        out, _, snap, _, _ = self._run(
            params, speculation=3, drafter="model",
            draft_params=params, draft_cfg=CFG, draft_window=32)
        assert out == ref
        spec = snap["speculation"]
        assert spec["proposed"] > 0
        assert spec["acceptance_rate"] >= 0.9

    def test_eos_mid_round_matches_plain(self, params):
        """EOS inside an accepted run must stop the stream exactly
        where the plain lane stops it (overshoot discarded)."""
        prompt = self.PROMPTS[0]
        full = _ref_tokens(params, prompt, 24)
        gen = full[len(prompt):]
        eos = gen[len(gen) // 2]  # an id that fires mid-generation
        with DecodeLoop(params, CFG, slots=2, page_size=8,
                        kernel="gather") as loop:
            a = loop.submit(prompt, 24, eos_id=eos).full_sequence(120)
        with DecodeLoop(params, CFG, slots=2, page_size=8,
                        kernel="gather", speculation=4) as loop:
            b = loop.submit(prompt, 24, eos_id=eos).full_sequence(120)
        assert a == b

    def test_per_request_opt_out(self, params):
        ref, _, _, _, _ = self._run(params)
        with DecodeLoop(params, CFG, slots=4, page_size=8,
                        kernel="gather", speculation=4) as loop:
            streams = loop.submit_many(list(self.PROMPTS), list(self.MT),
                                       speculation=False)
            out = [s.result(timeout=120) for s in streams]
            snap = loop.snapshot()["speculation"]
        assert out == ref
        assert snap["proposed"] == 0  # nothing was ever drafted

    def test_mixed_opt_in_and_out_share_rounds(self, params):
        ref, _, _, _, _ = self._run(params)
        with DecodeLoop(params, CFG, slots=4, page_size=8,
                        kernel="gather", speculation=4) as loop:
            s0 = loop.submit(self.PROMPTS[0], self.MT[0])
            s1 = loop.submit(self.PROMPTS[1], self.MT[1],
                             speculation=False)
            s2 = loop.submit(self.PROMPTS[2], self.MT[2])
            out = [s.result(timeout=120) for s in (s0, s1, s2)]
            programs = loop.decode_step_programs()
        assert out == ref
        assert programs <= 2

    def test_prefix_cache_reuse_stays_bit_identical(self, params):
        """Round 2 of the same prompt hits the cache (CoW fork of the
        tail page) — the speculative verify writes into the fork and
        output doesn't move."""
        prompt = self.PROMPTS[1]
        with DecodeLoop(params, CFG, slots=4, page_size=8,
                        kernel="gather", speculation=4) as loop:
            a = loop.submit(prompt, 20).full_sequence(120)
            b = loop.submit(prompt, 20).full_sequence(120)
            snap = loop.snapshot()
            pages_ok = (len(loop._free) + loop.pages_in_use
                        + loop._cached_unref() == loop.n_pages)
        assert a == b == _ref_tokens(params, prompt, 20)
        assert snap["prefix_cache"]["hits"] >= 1
        assert pages_ok

    def test_spec_corpus_feeds_from_prefix_trie(self, params):
        """After a retired request seeds the trie, a DIFFERENT request
        whose suffix appears in that prompt gets corpus proposals."""
        seed_prompt = list(range(1, 13))  # 12 tokens -> 1 full page
        with DecodeLoop(params, CFG, slots=2, page_size=8,
                        kernel="gather", speculation=4) as loop:
            loop.submit(seed_prompt, 4).result(timeout=120)
            assert loop.snapshot()["prefix_cache"]["nodes"] >= 1
            corpus = list(loop._prefix.iter_sequences())
            assert seed_prompt[:8] in [c[:8] for c in corpus]
            # the drafter sees the trie through its corpus hook
            hit = loop._drafter.propose([9, 1, 2, 3], 3)
            assert hit == [4, 5, 6]

    def test_validation(self, params, draft_params):
        with pytest.raises(ValueError, match="speculation"):
            DecodeLoop(params, CFG, speculation=-1, start=False)
        with pytest.raises(ValueError, match="mutually exclusive"):
            DecodeLoop(params, CFG, speculation=4, horizon=2,
                       start=False)
        with pytest.raises(ValueError, match="vocab_size"):
            DecodeLoop(params, CFG, speculation=4, drafter="model",
                       draft_params=draft_params,
                       draft_cfg=DRAFT_CFG._replace(vocab_size=5),
                       start=False)


# --------------------------------------------------------- satellites
class TestSubmitManyUpFrontValidation:
    """Satellite: per-row list mistakes fail with a NAMED error before
    any row-mate is enqueued or admitted."""

    def test_short_max_tokens_list_named(self, params):
        with DecodeLoop(params, CFG, slots=2, page_size=8,
                        kernel="gather") as loop:
            with pytest.raises(ValueError, match="max_tokens needs 3"):
                loop.submit_many([[1, 2]] * 3, [4, 4])
            with loop._cond:
                assert not loop._waiting
            assert loop.occupied_slots == 0

    def test_short_token_index_base_list_named(self, params):
        with DecodeLoop(params, CFG, slots=2, page_size=8,
                        kernel="gather") as loop:
            with pytest.raises(ValueError,
                               match="token_index_base needs 2"):
                loop.submit_many([[1, 2]] * 2, 4, token_index_base=[0])
            with loop._cond:
                assert not loop._waiting

    def test_negative_base_rejected_before_any_enqueue(self, params):
        with DecodeLoop(params, CFG, slots=2, page_size=8,
                        kernel="gather") as loop:
            with pytest.raises(ValueError, match="token_index_base"):
                loop.submit_many([[1, 2]] * 2, 4,
                                 token_index_base=[3, -1])
            with loop._cond:
                assert not loop._waiting
            assert loop.occupied_slots == 0


class TestTier1Guards:
    """Satellite: speculation is opt-in and the lane imports cleanly
    without jax."""

    def test_speculation_off_by_default(self, params):
        loop = DecodeLoop(params, CFG, start=False)
        assert loop.spec_k == 0
        assert loop._drafter is None
        snap = loop.snapshot()["speculation"]
        assert snap["enabled"] is False and snap["drafter"] is None

    def test_stream_defaults_opt_in_when_loop_speculates(self, params):
        loop = DecodeLoop(params, CFG, start=False)
        s = loop.submit_many([[1, 2]], 2)[0]
        assert s.speculation is True  # per-REQUEST default: ride along
        s.cancel()

    def test_speculation_module_imports_without_jax(self):
        """The drafter module itself must import clean off-platform —
        jax loads lazily, only when a model drafter actually runs. The
        serving package __init__ chain pulls jax for other reasons, so
        load the module by file path to test ITS import discipline."""
        from deeplearning4j_tpu.serving import speculation
        code = (
            "import sys, importlib.util\n"
            "assert 'jax' not in sys.modules\n"
            f"spec = importlib.util.spec_from_file_location(\n"
            f"    'speculation_standalone', {speculation.__file__!r})\n"
            "mod = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(mod)\n"
            "assert 'jax' not in sys.modules, 'speculation "
            "imported jax at module scope'\n"
            "d = mod.NgramDrafter(ngram=2)\n"
            "assert d.propose([1, 2, 3, 1, 2], 1) == [3]\n"
            "assert 'jax' not in sys.modules\n"
            "print('clean')\n")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "clean" in out.stdout


# --------------------------------------------------------- HTTP surface
class TestSpeculativeHTTP:
    """e2e: serve with speculation on, scrape dl4j_spec_* off the live
    /metrics, exercise the per-request opt-out and the draft canary
    reload."""

    @pytest.fixture()
    def served(self, params, draft_params):
        from deeplearning4j_tpu.config import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.serving import InferenceEngine
        from deeplearning4j_tpu.serving.server import serve_network

        conf = (NeuralNetConfiguration.builder()
                .lr(0.1).n_in(4).activation_function("tanh")
                .optimization_algo("iteration_gradient_descent")
                .num_iterations(1).use_adagrad(False)
                .list(2).hidden_layer_sizes([8])
                .override(1, layer="output", loss_function="mcxent",
                          activation_function="softmax", n_out=3)
                .pretrain(False).build())
        gen = InferenceEngine.for_transformer(params, CFG)
        handle = serve_network(
            MultiLayerNetwork(conf), generate_engine=gen, n_replicas=1,
            max_delay_ms=1.0, slots=4, page_size=8, speculation=4,
            drafter="model", draft_params=draft_params,
            draft_cfg=DRAFT_CFG, draft_window=16)
        try:
            yield handle, gen
        finally:
            handle.close()

    @staticmethod
    def _post(url, body):
        req = urllib.request.Request(
            url, json.dumps(body).encode(),
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    @staticmethod
    def _get(url):
        with urllib.request.urlopen(url, timeout=120) as r:
            return r.read().decode()

    def test_opt_out_and_metrics_scrape(self, served):
        handle, gen = served
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        r1 = self._post(f"{handle.url}/generate",
                        {"prompt": prompt, "max_tokens": 20})
        r2 = self._post(f"{handle.url}/generate",
                        {"prompt": prompt, "max_tokens": 20,
                         "speculation": False})
        assert r1["tokens"] == r2["tokens"]
        # live exposition carries the whole dl4j_spec_* catalogue
        metrics = self._get(f"{handle.url}/metrics")
        for name in ("dl4j_spec_proposed", "dl4j_spec_accepted",
                     "dl4j_spec_rounds", "dl4j_spec_acceptance_rate"):
            assert name in metrics
        rate = [ln for ln in metrics.splitlines()
                if ln.startswith("dl4j_spec_acceptance_rate{")]
        assert rate and 0.0 <= float(rate[0].split()[-1]) <= 1.0
        stats = json.loads(self._get(f"{handle.url}/stats"))
        spec = stats["generate"]["decode"]["speculation"]
        assert spec["enabled"] and spec["proposed"] > 0
        assert gen.decode_loop.decode_step_programs() <= 2

    def test_streaming_token_index_unchanged(self, served):
        """NDJSON chunks under speculation carry the same contiguous
        absolute token_index contract durable streams dedupe on."""
        handle, _ = served
        body = json.dumps({"prompt": [1, 2, 3, 4], "max_tokens": 8,
                           "stream": True}).encode()
        req = urllib.request.Request(
            f"{handle.url}/generate", body,
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            events = [json.loads(ln) for ln in r if ln.strip()]
        toks = [e for e in events if "token" in e]
        assert [e["token_index"] for e in toks] == list(range(8))
        assert events[-1].get("done") is True

    def test_draft_canary_reload(self, served, tmp_path):
        from deeplearning4j_tpu.checkpoint.format import write_checkpoint

        handle, gen = served
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        before = self._post(f"{handle.url}/generate",
                            {"prompt": prompt, "max_tokens": 16})
        ck = str(tmp_path / "draft")
        write_checkpoint(ck, 5, {"params": _params(11, DRAFT_CFG)})
        out = self._post(f"{handle.url}/reload",
                         {"path": ck, "target": "draft"})
        assert out["reloaded"] and out["target"] == "draft"
        assert out["step"] == 5
        # serving identity untouched; output bits untouched
        assert out["checkpoint"] is None
        after = self._post(f"{handle.url}/generate",
                           {"prompt": prompt, "max_tokens": 16})
        assert after["tokens"] == before["tokens"]
        assert gen.draft_checkpoint["step"] == 5
        stats = json.loads(self._get(f"{handle.url}/stats"))
        assert stats["last_reload"]["target"] == "draft"

    def test_draft_reload_shape_mismatch_is_400(self, served, tmp_path):
        from deeplearning4j_tpu.checkpoint.format import write_checkpoint

        handle, gen = served
        wrong = DRAFT_CFG._replace(d_model=24)
        ck = str(tmp_path / "wrong")
        write_checkpoint(ck, 1, {"params": _params(2, wrong)})
        with pytest.raises(urllib.error.HTTPError) as e:
            self._post(f"{handle.url}/reload",
                       {"path": ck, "target": "draft"})
        assert e.value.code == 400
        assert gen.draft_checkpoint is None  # nothing was installed

    def test_reload_without_model_drafter_is_400(self, params,
                                                 tmp_path):
        from deeplearning4j_tpu.checkpoint.format import write_checkpoint
        from deeplearning4j_tpu.config import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.serving import InferenceEngine
        from deeplearning4j_tpu.serving.server import serve_network

        conf = (NeuralNetConfiguration.builder()
                .lr(0.1).n_in(4).activation_function("tanh")
                .optimization_algo("iteration_gradient_descent")
                .num_iterations(1).use_adagrad(False)
                .list(2).hidden_layer_sizes([8])
                .override(1, layer="output", loss_function="mcxent",
                          activation_function="softmax", n_out=3)
                .pretrain(False).build())
        gen = InferenceEngine.for_transformer(params, CFG)
        handle = serve_network(
            MultiLayerNetwork(conf), generate_engine=gen, n_replicas=1,
            max_delay_ms=1.0, slots=2, page_size=8, speculation=4)
        try:
            ck = str(tmp_path / "draft")
            write_checkpoint(ck, 1, {"params": _params(11, DRAFT_CFG)})
            with pytest.raises(urllib.error.HTTPError) as e:
                self._post(f"{handle.url}/reload",
                           {"path": ck, "target": "draft"})
            assert e.value.code == 400
            body = json.loads(e.value.read())
            assert "drafter" in body["error"]
        finally:
            handle.close()
