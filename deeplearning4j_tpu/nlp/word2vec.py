"""Word2Vec: skip-gram with hierarchical softmax and negative sampling.

Parity: reference nlp/models/word2vec/Word2Vec.java (fit :101, buildVocab
:257, trainSentence :298, skipGram :314, iterate :337, lr decay by words
seen :191-296) + InMemoryLookupTable.java (syn0/syn1/syn1Neg, unigram
table resetWeights :88, iterateSample :188-260) + WordVectorsImpl
(similarity / wordsNearest).

TPU-native design: the reference's hot loop does ONE (dot, sigmoid, axpy)
at a time per (center, context, code-bit), racing hogwild threads over
shared syn0/syn1. Here the host mines (center, context) pairs + their
Huffman codes/points into padded index tensors, and a single jitted step
computes the batch loss:

    HS:  BCE over dot(syn0[context], syn1[points]) against (1 - codes)
    NEG: BCE over dot(syn0[context], syn1neg[target|negatives])

jax.grad turns the gathers into scatter-adds — a deterministic segment-sum
formulation of the same update (colliding pairs ACCUMULATE instead of
racing), running on the MXU over thousands of pairs at once. Negative
samples are drawn on-device from the unigram^0.75 table via
jax.random.categorical over precomputed logits.
"""

from __future__ import annotations

import logging
import queue
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.huffman import build_huffman, max_code_length
from deeplearning4j_tpu.nlp.sentence_iterator import (
    CollectionSentenceIterator,
    SentenceIterator,
)
from deeplearning4j_tpu.nlp.tokenization import (
    DefaultTokenizerFactory,
    TokenizerFactory,
)
from deeplearning4j_tpu.nlp.vocab import VocabCache, build_vocab

log = logging.getLogger(__name__)


def _prefetch(iterator, depth: int = 2):
    """Run a chunk producer in a background thread so host-side pair
    mining overlaps device training (the reference overlaps via its
    parallel sentence-training threads, Word2Vec.java:191). Exceptions
    propagate to the consumer."""
    q: "queue.Queue" = queue.Queue(maxsize=depth)
    _DONE, _ERR = object(), object()

    def produce():
        try:
            for item in iterator:
                q.put(item)
            q.put(_DONE)
        except BaseException as e:  # noqa: BLE001 — relay to consumer
            q.put((_ERR, e))

    t = threading.Thread(target=produce, name="w2v-miner", daemon=True)
    t.start()
    while True:
        item = q.get()
        if item is _DONE:
            return
        if isinstance(item, tuple) and len(item) == 2 and item[0] is _ERR:
            raise item[1]
        yield item


class WordVectors:
    """Similarity / nearest-words API over the learned table
    (reference WordVectorsImpl.java)."""

    def __init__(self, cache: VocabCache, syn0: np.ndarray):
        self.vocab = cache
        self.syn0 = np.asarray(syn0)
        norms = np.linalg.norm(self.syn0, axis=1, keepdims=True)
        self._unit = self.syn0 / np.maximum(norms, 1e-12)

    def _require_fitted(self) -> None:
        if getattr(self, "syn0", None) is None \
                or getattr(self, "_unit", None) is None:
            raise RuntimeError(
                f"{type(self).__name__} has no trained vectors yet — "
                "call fit() first")

    def get_word_vector(self, word: str) -> Optional[np.ndarray]:
        self._require_fitted()
        i = self.vocab.index_of(word)
        return self.syn0[i] if i >= 0 else None

    def has_word(self, word: str) -> bool:
        return self.vocab.index_of(word) >= 0

    def similarity(self, w1: str, w2: str) -> float:
        self._require_fitted()
        i, j = self.vocab.index_of(w1), self.vocab.index_of(w2)
        if i < 0 or j < 0:
            return float("nan")
        return float(self._unit[i] @ self._unit[j])

    def words_nearest(self, word: str, n: int = 10) -> List[Tuple[str, float]]:
        self._require_fitted()
        i = self.vocab.index_of(word)
        if i < 0:
            return []
        sims = self._unit @ self._unit[i]
        order = np.argsort(-sims)
        out = []
        for j in order:
            if j == i:
                continue
            out.append((self.vocab.word_at(int(j)), float(sims[j])))
            if len(out) >= n:
                break
        return out


class Word2Vec(WordVectors):
    """Skip-gram trainer (builder-style kwargs mirror the reference's
    Word2Vec.Builder: layerSize/windowSize/minWordFrequency/iterations/
    learningRate/minLearningRate/negativeSample/sample/seed)."""

    def __init__(self, sentences=None, *, layer_size: int = 100,
                 window: int = 5, min_word_frequency: float = 1.0,
                 iterations: int = 1, learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4, negative: int = 0,
                 sample: float = 0.0, batch_pairs: int = 4096,
                 chunk_batches: int = 32, seed: int = 123,
                 tokenizer_factory: Optional[TokenizerFactory] = None):
        self.layer_size = layer_size
        self.window = window
        self.min_word_frequency = min_word_frequency
        self.iterations = iterations
        self.alpha = learning_rate
        self.min_alpha = min_learning_rate
        self.negative = negative
        self.sample = sample
        self.batch_pairs = batch_pairs
        self.chunk_batches = chunk_batches  # scan length of the chunk step
        self.seed = seed
        self.tokenizer_factory = tokenizer_factory or DefaultTokenizerFactory()
        if isinstance(sentences, SentenceIterator):
            self.sentence_iter = sentences
        elif sentences is not None:
            self.sentence_iter = CollectionSentenceIterator(list(sentences))
        else:
            self.sentence_iter = None
        self.vocab = VocabCache()
        self.syn0 = None
        self.syn1 = None
        self.syn1neg = None
        self._code_len = 0
        self.pairs_trained = 0
        self._step_cache = None  # jitted step, keyed to the built vocab
        self._key = jax.random.PRNGKey(seed)

    # ----------------------------------------------------------- vocab/init
    def build_vocab(self) -> None:
        """reference buildVocab :257 + Huffman(vocab).build() :348."""
        build_vocab(self.sentence_iter, self.tokenizer_factory,
                    self.min_word_frequency, self.vocab)
        self._extend_vocab()  # hook: subclasses add pseudo-words (labels)
        build_huffman(self.vocab)
        self._code_len = max(1, max_code_length(self.vocab))
        self._step_cache = None  # vocab-dependent shapes changed

    def _extend_vocab(self) -> None:
        pass

    def reset_weights(self) -> None:
        """reference InMemoryLookupTable.resetWeights :88: syn0 uniform in
        +-0.5/dim, syn1 zeros."""
        n, d = self.vocab.num_words(), self.layer_size
        self._key, k = jax.random.split(self._key)
        self.syn0 = jax.random.uniform(k, (n, d), jnp.float32,
                                       -0.5 / d, 0.5 / d)
        if self.negative > 0:
            self.syn1neg = jnp.zeros((n, d), jnp.float32)
        else:  # hierarchical softmax path
            self.syn1 = jnp.zeros((n, d), jnp.float32)

    UNIGRAM_TABLE_SIZE = 1 << 20

    def _unigram_table(self) -> jnp.ndarray:
        """unigram^0.75 sampling table (the reference's unigram table,
        InMemoryLookupTable's `table` — 1e8 entries there, 2^20 here):
        table[i] = word index owning cdf bucket i, so drawing a negative
        is ONE random int + ONE gather. On TPU this beats both
        jax.random.categorical (which materializes (B, K, V) Gumbel
        noise — 20+ ms/step at V=10k, B=16k) and jnp.searchsorted
        (~12 ms/step); the table gather is ~0.1 ms. Quantization at
        2^-20 granularity matches the reference's quantized table."""
        counts = np.array([vw.count for vw in self.vocab.vocab_words()],
                          np.float64)
        probs = counts ** 0.75
        probs /= probs.sum()
        cdf = np.cumsum(probs)
        t = self.UNIGRAM_TABLE_SIZE
        # bucket midpoints -> owning word index
        table = np.searchsorted(cdf, (np.arange(t) + 0.5) / t)
        return jnp.asarray(np.minimum(table, len(cdf) - 1), jnp.int32)

    # ------------------------------------------------------------- training
    def _codes_points(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Pad per-word Huffman codes/points to (V, L) with a mask."""
        v, L = self.vocab.num_words(), self._code_len
        codes = np.zeros((v, L), np.float32)
        points = np.zeros((v, L), np.int32)
        mask = np.zeros((v, L), np.float32)
        for vw in self.vocab.vocab_words():
            ln = vw.code_length()
            codes[vw.index, :ln] = vw.codes
            points[vw.index, :ln] = vw.points
            mask[vw.index, :ln] = 1.0
        return codes, points, mask

    def _keep_probs(self) -> np.ndarray:
        """Per-vocab-index subsampling keep probability (reference
        trainSentence's frequent-word subsampling, vectorized as a table)."""
        total = max(1.0, self.vocab.total_word_count)
        counts = np.array([vw.count for vw in self.vocab.vocab_words()],
                          np.float64)
        f = np.maximum(counts, 1.0) / total
        keep = (np.sqrt(f / self.sample) + 1.0) * self.sample / f
        return np.minimum(keep, 1.0)

    def _tokens_to_indices(self, sentence: str) -> np.ndarray:
        toks = self.tokenizer_factory.tokenize(sentence)
        idx = np.fromiter((self.vocab.index_of(t) for t in toks),
                          np.int32, count=len(toks))
        return idx[idx >= 0]

    @staticmethod
    def _window_pairs(idx: np.ndarray, sid: np.ndarray, window: int,
                      rng: np.random.RandomState
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized skip-gram windowing over concatenated sentences.

        `idx` holds vocab indices, `sid` the sentence id of each position.
        For every offset 1..window, pairs (center@i, context@i±off) are
        kept when both positions share a sentence and off <= b[i], where b
        is the per-center random window shrink (reference skipGram :314's
        `b = random % window` semantics) — no Python per-token loop.
        """
        n = idx.size
        if n == 0:
            return (np.empty(0, np.int32),) * 2
        b = rng.randint(1, window + 1, size=n)
        cs, xs = [], []
        for off in range(1, window + 1):
            if off >= n:
                break
            same = sid[off:] == sid[:-off]
            m = same & (b[off:] >= off)      # context BEFORE center
            cs.append(idx[off:][m])
            xs.append(idx[:-off][m])
            m = same & (b[:-off] >= off)     # context AFTER center
            cs.append(idx[:-off][m])
            xs.append(idx[off:][m])
        return np.concatenate(cs), np.concatenate(xs)

    def _iter_pair_chunks(self, rng: np.random.RandomState,
                          chunk_tokens: int = 1 << 18
                          ):
        """Stream (centers, contexts, words_seen) chunks: sentences are
        tokenized and buffered up to ~chunk_tokens indices, then windowed
        in one vectorized shot. A text8-scale corpus (~17M tokens, ~1e8
        pairs at window 5) never materializes more than one chunk of pairs
        (~2.6M) in RAM. Overridable (ParagraphVectors appends label pairs).
        """
        keep = self._keep_probs() if self.sample > 0 else None
        buf_idx: List[np.ndarray] = []
        buf_sid: List[np.ndarray] = []
        count = 0
        sid = 0

        words_in_buf = 0  # in-vocab tokens BEFORE subsampling: the alpha
        # decay numerator must count the same mass as its denominator
        # (sum of kept-vocab counts), which subsampling doesn't reduce

        def flush():
            idx = np.concatenate(buf_idx)
            s = np.concatenate(buf_sid)
            c, x = self._window_pairs(idx, s, self.window, rng)
            return c, x, words_in_buf

        for sentence in self.sentence_iter:
            arr = self._tokens_to_indices(sentence)
            words_in_buf += arr.size
            if keep is not None and arr.size:
                arr = arr[rng.rand(arr.size) < keep[arr]]
            if arr.size:
                buf_idx.append(arr)
                buf_sid.append(np.full(arr.size, sid, np.int32))
                count += arr.size
                sid += 1
            if count >= chunk_tokens:
                yield flush()
                buf_idx, buf_sid, count, words_in_buf = [], [], 0, 0
        if count:
            yield flush()

    def _build_step(self):
        codes, points, mask = self._codes_points()
        codes_t, points_t, mask_t = (jnp.asarray(codes), jnp.asarray(points),
                                     jnp.asarray(mask))
        negative = self.negative
        uni_table = self._unigram_table() if negative > 0 else None

        def _bce(logits, labels):
            return (jnp.maximum(logits, 0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))

        def loss_fn(tables, centers, contexts, negs):
            """Batched equivalent of the reference's sequential per-pair
            axpy updates. Each ROW (of syn0 OR syn1/syn1neg) moves by the
            MEAN gradient over the pairs touching it in this batch, at the
            full per-pair alpha: a plain sum diverges whenever a hot row
            (small vocab; the HS root node; frequent negative targets)
            accumulates thousands of same-direction gradients that the
            reference's re-read-each-step loop would have saturated, while
            a plain mean scales the effective lr by 1/batch_pairs. The
            two sides need different normalizations, so the loss is split
            with stop_gradient: the first term only trains syn0, the
            second only trains syn1/syn1neg."""
            syn0 = tables["syn0"]
            l1 = syn0[contexts]  # (B, D) — reference trains syn0[context]
            l1_sg = jax.lax.stop_gradient(l1)
            counts = jnp.zeros(syn0.shape[0],
                               jnp.float32).at[contexts].add(1.0)
            w = 1.0 / counts[contexts]  # (B,) syn0-side weights
            loss = 0.0
            if "syn1" in tables:
                # hierarchical softmax over the center word's code path
                p = points_t[centers]          # (B, L)
                c = codes_t[centers]           # (B, L)
                m = mask_t[centers]            # (B, L)
                labels = 1.0 - c               # word2vec label convention
                rows = tables["syn1"][p]       # (B, L, D)
                pc = jnp.zeros(tables["syn1"].shape[0],
                               jnp.float32).at[p].add(m)
                u = m / jnp.maximum(pc[p], 1.0)  # (B, L) syn1-side weights
                syn0_side = _bce(
                    jnp.einsum("bd,bld->bl", l1,
                               jax.lax.stop_gradient(rows)), labels)
                syn1_side = _bce(
                    jnp.einsum("bd,bld->bl", l1_sg, rows), labels)
                loss = loss + jnp.sum(w[:, None] * syn0_side * m) \
                    + jnp.sum(u * syn1_side * m)
            if "syn1neg" in tables:
                tgt = jnp.concatenate([centers[:, None], negs], axis=1)
                labels = jnp.concatenate(
                    [jnp.ones_like(centers[:, None], jnp.float32),
                     jnp.zeros_like(negs, jnp.float32)], axis=1)
                # mask negatives that drew the positive target itself
                # (reference: `if (target == word) continue`)
                valid = jnp.concatenate(
                    [jnp.ones_like(centers[:, None], jnp.float32),
                     (negs != centers[:, None]).astype(jnp.float32)], axis=1)
                rows = tables["syn1neg"][tgt]  # (B, K, D)
                tc = jnp.zeros(tables["syn1neg"].shape[0],
                               jnp.float32).at[tgt].add(valid)
                u = valid / jnp.maximum(tc[tgt], 1.0)
                syn0_side = _bce(
                    jnp.einsum("bd,bkd->bk", l1,
                               jax.lax.stop_gradient(rows)), labels)
                syn1_side = _bce(
                    jnp.einsum("bd,bkd->bk", l1_sg, rows), labels)
                loss = loss + jnp.sum(w[:, None] * syn0_side * valid) \
                    + jnp.sum(u * syn1_side * valid)
            return loss

        def step_core(tables, centers, contexts, alpha, key):
            if negative > 0:
                draws = jax.random.randint(
                    key, (centers.shape[0], negative), 0,
                    uni_table.shape[0])
                negs = uni_table[draws]
            else:
                negs = jnp.zeros((centers.shape[0], 0), jnp.int32)
            loss, grads = jax.value_and_grad(loss_fn)(
                tables, centers, contexts, negs)
            tables = jax.tree_util.tree_map(
                lambda t, g: t - alpha * g, tables, grads)
            return tables, loss

        step = jax.jit(step_core)

        # Whole-chunk training as one program: batches are a scan axis, so
        # the per-batch host work (two H2D transfers + RNG split +
        # dispatch) is paid once per CHUNK. This
        # kernel is gather-bound, not MXU-bound, so scanning costs nothing
        # (unlike the dense-MLP case — see MultiLayerNetwork.fit_scan).
        @jax.jit
        def step_chunk(tables, cb, xb, alpha, key):
            keys = jax.random.split(key, cb.shape[0])

            def body(tables, inp):
                c, x, k = inp
                return step_core(tables, c, x, alpha, k)

            tables, losses = jax.lax.scan(body, tables, (cb, xb, keys))
            return tables, losses[-1]

        return step, step_chunk

    # ---------------------------------------------------- pre-mined pairs
    def mine_pairs(self, rng=None):
        """Mine every (center, context) skip-gram pair for ONE corpus
        pass, as two int32 arrays. Public surface over the chunk miner
        for callers that reuse pairs across repeated training (resumed
        runs, benchmarks) instead of re-mining per fit()."""
        if self.vocab.num_words() == 0:
            self.build_vocab()
        rng = rng or np.random.RandomState(self.seed + 1)
        chunks = list(self._iter_pair_chunks(rng))
        if not chunks:
            return np.empty(0, np.int32), np.empty(0, np.int32)
        centers = np.concatenate([c for c, _, _ in chunks])
        contexts = np.concatenate([x for _, x, _ in chunks])
        return centers, contexts

    def train_pairs(self, centers, contexts, alpha: float = None) -> int:
        """Train on pre-mined pairs through the production chunked-scan
        step at a FIXED learning rate (callers own any decay schedule).
        Whole chunks (chunk_batches x batch_pairs) ride the scan; the
        tail trains in single batches (each an eager dispatch), dropping
        only the sub-batch remainder — unless the whole input is smaller
        than one batch, which is tiled up. Returns the number of pairs
        trained."""
        if self.syn0 is None:
            self.reset_weights()
        if self._step_cache is None:
            self._step_cache = self._build_step()
        step, step_chunk = self._step_cache
        alpha = self.alpha if alpha is None else float(alpha)
        tables = {"syn0": self.syn0}
        if self.syn1 is not None:
            tables["syn1"] = self.syn1
        if self.syn1neg is not None:
            tables["syn1neg"] = self.syn1neg
        # jnp.asarray is a no-op for device-resident int32 inputs, so
        # callers looping train_pairs can upload once and pay zero
        # host->device transfer per call (a per-call transfer would
        # otherwise dominate this short step)
        centers = jnp.asarray(centers, jnp.int32)
        contexts = jnp.asarray(contexts, jnp.int32)
        B, CB = self.batch_pairs, self.chunk_batches
        n = centers.size // (B * CB) * (B * CB)
        trained = 0
        if n:
            cb = centers[:n].reshape(-1, CB, B)
            xb = contexts[:n].reshape(-1, CB, B)
            for i in range(cb.shape[0]):
                self._key, k = jax.random.split(self._key)
                tables, _ = step_chunk(tables, cb[i], xb[i],
                                       jnp.float32(alpha), k)
            trained = n
        tail_c, tail_x = centers[n:], contexts[n:]
        for lo in range(0, tail_c.size // B * B, B):
            self._key, k = jax.random.split(self._key)
            tables, _ = step(tables, tail_c[lo:lo + B],
                             tail_x[lo:lo + B], jnp.float32(alpha), k)
            trained += B
        rem = tail_c.size % B
        if rem and trained == 0:
            # smaller than one batch: tile up so tiny inputs still train
            pad = jnp.arange(B - rem) % rem
            self._key, k = jax.random.split(self._key)
            tables, _ = step(
                tables, jnp.concatenate([tail_c[-rem:], tail_c[-rem:][pad]]),
                jnp.concatenate([tail_x[-rem:], tail_x[-rem:][pad]]),
                jnp.float32(alpha), k)
            trained = rem
        self.syn0 = tables["syn0"]
        self.syn1 = tables.get("syn1")
        self.syn1neg = tables.get("syn1neg")
        self.pairs_trained += trained
        # NOTE: the similarity/nearest-words view is NOT refreshed here
        # (that would D2H the whole table every call — train_pairs is
        # built for tight loops); call refresh_vectors() when done.
        return trained

    def refresh_vectors(self) -> None:
        """Pull syn0 to host and refresh the WordVectors view (after a
        train_pairs loop; fit() does this automatically)."""
        WordVectors.__init__(self, self.vocab, np.asarray(self.syn0))

    def fit(self) -> "Word2Vec":
        """reference fit :101: build vocab, Huffman, reset weights, train
        with lr decaying by words seen (Word2Vec.java :191-296's
        `alpha * (1 - wordsSeen/totalWords)`), streaming pair chunks so a
        text8-scale corpus trains in bounded memory."""
        if self.sentence_iter is None:
            raise ValueError("Word2Vec needs sentences")
        if self.vocab.num_words() == 0:
            self.build_vocab()
        if self.syn0 is None:
            self.reset_weights()
        rng = np.random.RandomState(self.seed)
        # the miner runs in a prefetch thread concurrently with the
        # training loop's permutation draws — it needs its OWN RandomState
        # (numpy RandomState is not thread-safe)
        mine_rng = np.random.RandomState(self.seed + 1)
        if self._step_cache is None:
            self._step_cache = self._build_step()
        step, step_chunk = self._step_cache

        tables = {"syn0": self.syn0}
        if self.syn1 is not None:
            tables["syn1"] = self.syn1
        if self.syn1neg is not None:
            tables["syn1neg"] = self.syn1neg

        # denominator = kept-vocab token mass (total_word_count still
        # includes mass truncate() dropped, which words_seen never counts —
        # using it would stall the decay well above min_alpha)
        kept_mass = sum(vw.count for vw in self.vocab.vocab_words())
        total_words = max(1.0, float(kept_mass) * self.iterations)
        words_seen = 0
        self.pairs_trained = 0
        loss = None
        B = self.batch_pairs
        carry_c = np.empty(0, np.int32)
        carry_x = np.empty(0, np.int32)

        def train_batch(bc, bx, ts):
            nonlocal tables
            self._key, k = jax.random.split(self._key)
            alpha = max(self.min_alpha,
                        self.alpha * (1.0 - words_seen / total_words))
            ts, ls = step(ts, jnp.asarray(bc), jnp.asarray(bx),
                          jnp.float32(alpha), k)
            return ts, ls

        # fixed scan length => exactly two compiled programs all run long:
        # the CB-batch chunk scan and the single-batch tail step
        CB = self.chunk_batches

        def train_chunk(bc, bx, ts):
            nonlocal loss
            self._key, k = jax.random.split(self._key)
            alpha = max(self.min_alpha,
                        self.alpha * (1.0 - words_seen / total_words))
            cb = jnp.asarray(bc.reshape(CB, B))
            xb = jnp.asarray(bx.reshape(CB, B))
            ts, loss = step_chunk(ts, cb, xb, jnp.float32(alpha), k)
            return ts

        for _ in range(self.iterations):
            for centers, contexts, n_words in _prefetch(
                    self._iter_pair_chunks(mine_rng)):
                self.pairs_trained += centers.size
                perm = rng.permutation(centers.size)
                centers = np.concatenate([carry_c, centers[perm]])
                contexts = np.concatenate([carry_x, contexts[perm]])
                lo = 0
                while centers.size - lo >= CB * B:
                    # one program per CB batches: batches are a scan axis,
                    # so per-batch host overhead (transfers + dispatch) is
                    # paid once per CB steps. Alpha is constant across the
                    # scan (decay advances per mined chunk, as before).
                    tables = train_chunk(centers[lo:lo + CB * B],
                                         contexts[lo:lo + CB * B], tables)
                    lo += CB * B
                # remainder rides into the next chunk, keeping every
                # compiled shape static
                carry_c, carry_x = centers[lo:], contexts[lo:]
                # decay lags the chunk (the reference decays by words
                # ALREADY seen) so the first batch trains at full alpha and
                # the last iteration is not spent at min_alpha
                words_seen += n_words
            # iteration tail: full batches through the single-batch step,
            # then tile the final partial batch up to the batch shape
            n_full = carry_c.size // B * B
            for lo in range(0, n_full, B):
                tables, loss = train_batch(carry_c[lo:lo + B],
                                           carry_x[lo:lo + B], tables)
            carry_c, carry_x = carry_c[n_full:], carry_x[n_full:]
            if carry_c.size:
                pad = np.arange(B - carry_c.size) % carry_c.size
                tables, loss = train_batch(
                    np.concatenate([carry_c, carry_c[pad]]),
                    np.concatenate([carry_x, carry_x[pad]]), tables)
                carry_c = np.empty(0, np.int32)
                carry_x = np.empty(0, np.int32)
        if self.pairs_trained == 0:
            raise ValueError("No training pairs (vocab/corpus too small)")
        self.syn0 = tables["syn0"]
        self.syn1 = tables.get("syn1")
        self.syn1neg = tables.get("syn1neg")
        log.info("word2vec trained: %d pairs, final loss %.4f",
                 self.pairs_trained, float(loss))
        # refresh the WordVectors view
        WordVectors.__init__(self, self.vocab, np.asarray(self.syn0))
        return self
