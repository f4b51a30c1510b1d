"""Benchmark harness (to be replaced: ROADMAP S0).

Timing protocol (v2, "amortized-chained-d2h"): every timed window (a)
runs its steps CHAINED ON DEVICE (lax.scan / whole-epoch programs /
chunked scans — never identical-args eager loops), (b) is sized to
hundreds of ms of device work so one host<->device round trip is a
small share of it, and (c) ends with a forced D2H read (np.asarray of a
result slice) before the clock stops. Whether the plainer
`block_until_ready` timing is enough on the machine the chip tool gives
is ROADMAP S1's question.

What a run may and may not say:

- Only a run on a TPU measures the device. Off-TPU every config shrinks
  to a smoke size (`_fast()`), so each summary line of such a run
  STARTS with a `not_a_device_measurement` key naming the platform —
  its values are counts and CPU timings, never device metrics.
- `main` exits non-zero when a selected config raised.
- One process owns a chip. This process touches JAX, so on a TPU the
  configs that spawn `cli serve` / worker children (`SPAWNS_CHILDREN`)
  cannot get the chip: they are left out of the default selection
  there and report an error when selected by name.
- Each config runs REPEATS timed windows after a compile warm-up and
  reports the median.
- vs_baseline compares against a *pinned* baseline in BENCH_HISTORY.json
  (median of >= 5 separate idle-host processes at pin time, never
  overwritten by later runs). Re-pin by deleting the metric from the
  "baselines" dict. Baselines from the pre-v2 protocol are archived to
  "baselines_v1" and never compared against.

Output: after EVERY config completes, the full cumulative summary JSON
line is printed (flushed) — the last stdout line is always a valid,
maximal summary, so a driver timeout still leaves the completed configs
on record. History is likewise written incrementally.

Select a subset with BENCH_CONFIGS=mlp,lenet (default: all). A soft
budget (BENCH_BUDGET_S, default 720 s) skips configs not yet started
once exhausted, marking them "skipped" in the summary.
"""

import json
import os
import statistics
import subprocess
import sys
import time

import numpy as np

REPEATS = 3
PROTOCOL = "v2-amortized-chained-d2h"
HERE = os.path.dirname(os.path.abspath(__file__))
HIST_PATH = os.path.join(HERE, "BENCH_HISTORY.json")


def _d2h(tree) -> None:
    """Force a host read of (a sliver of) a device value — the sync
    that ends every timed window."""
    import jax

    leaf = jax.tree_util.tree_leaves(tree)[0]
    # slice ON DEVICE before fetching — device_get of the whole leaf
    # would add a full-array transfer to every window
    np.asarray(jax.device_get(leaf.ravel()[:1]))


def _median_rate(run_window, units_per_window, repeats=REPEATS):
    """Median units/sec over `repeats` timed windows. run_window() must
    end with a D2H read."""
    rates, secs = [], []
    for _ in range(repeats):
        start = time.perf_counter()
        run_window()
        dt = time.perf_counter() - start
        rates.append(units_per_window / dt)
        secs.append(dt)
    return statistics.median(rates), statistics.median(secs)


def _fast() -> bool:
    """True off-TPU (CI smoke): shrink workloads, keep code paths."""
    import jax

    return jax.devices()[0].platform != "tpu"


# ----------------------------------------------------------------- configs
def _mlp_net():
    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    batch_size = 512 if _fast() else 4096
    conf = (NeuralNetConfiguration.builder()
            .lr(0.05).n_in(784).activation_function("relu")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(1)
            .batch_size(batch_size)
            .compute_dtype("bfloat16")
            .list(3)
            .hidden_layer_sizes([2048, 1024])
            .override(2, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=10)
            .pretrain(False)
            .build())
    return MultiLayerNetwork(conf), batch_size


def bench_mlp():
    """BASELINE config 1: MNIST 3-layer MLP, samples/sec/chip, trained
    via the whole-epoch scan path (fit_scan) so every timed step is
    chained on-device."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.mnist import synthetic_mnist

    net, batch_size = _mlp_net()
    n_batches, epochs = (4, 2) if _fast() else (16, 16)
    x_np, y_np = synthetic_mnist(batch_size * n_batches)
    x, y = jnp.asarray(x_np), jnp.asarray(y_np)

    net.fit_scan(x, y, batch_size=batch_size, epochs=epochs)  # compile
    _d2h(net.params())
    steps = n_batches * epochs

    def window():
        net.fit_scan(x, y, batch_size=batch_size, epochs=epochs)
        _d2h(net.params())

    rate, win_s = _median_rate(window, steps * batch_size)
    return {"value": round(rate / max(1, len(jax.devices())), 2),
            "unit": "samples/sec/chip",
            "steps_per_window": steps, "window_s": round(win_s, 3)}


def bench_feed():
    """Device-feed pipeline: iterator-driven fit() over a RAGGED stream
    (N deliberately not a multiple of batch) through shape bucketing +
    async H2D prefetch — steps/sec plus a recompile counter from the
    jitted step's program cache. Unlike the scan configs this measures
    the real iterator-driven dispatch loop (per-step host dispatch is
    part of the metric — it is what the feed pipeline exists to keep off
    the chip's critical path); compiled_programs is the regression guard:
    it must stay at the bucket-hit count, not grow with epochs."""
    import math

    from deeplearning4j_tpu.datasets import DeviceFeed, ListDataSetIterator
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.datasets.mnist import synthetic_mnist

    net, batch_size = _mlp_net()
    n_batches = 4 if _fast() else 16
    n = batch_size * n_batches + batch_size // 3  # ragged last batch
    x_np, y_np = synthetic_mnist(n)
    feed = DeviceFeed(ListDataSetIterator(DataSet(x_np, y_np), batch_size),
                      prefetch=2)
    epochs = 1 if _fast() else 4
    steps_per_epoch = math.ceil(n / batch_size)

    net.fit(feed, epochs=1)  # compile every bucket program
    _d2h(net.params())
    programs_after_warmup = net.train_step_cache_size()

    def window():
        net.fit(feed, epochs=epochs)
        _d2h(net.params())

    rate, win_s = _median_rate(window, epochs * steps_per_epoch)
    programs = net.train_step_cache_size()
    # a negative counter means the private _cache_size API drifted —
    # report null rather than a fake "0 recompiles"
    counters_ok = programs >= 0 and programs_after_warmup >= 0
    return {"value": round(rate, 2), "unit": "steps/sec",
            "batch_size": batch_size, "ragged_n": n,
            "compiled_programs": programs if counters_ok else None,
            "recompiled_after_warmup":
                (programs - programs_after_warmup) if counters_ok else None,
            "feed": feed.stats(),
            "steps_per_window": epochs * steps_per_epoch,
            "window_s": round(win_s, 3)}


def bench_lenet():
    """BASELINE config 2: LeNet-5-style CNN on MNIST, per-step time.
    Reference path: core/nn/layers/convolution/
    ConvolutionDownSampleLayer.java:52."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.preprocessors import (
        ConvolutionInputPreProcessor, ConvolutionPostProcessor)
    from deeplearning4j_tpu.datasets.mnist import synthetic_mnist
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    batch_size = 256 if _fast() else 1024
    conf = (NeuralNetConfiguration.builder()
            .lr(0.05).activation_function("relu")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(1).use_adagrad(False)
            .batch_size(batch_size)
            .compute_dtype("bfloat16")
            .list(4)
            .override(0, layer="conv", filter_size=[5, 5], stride=[2, 2],
                      num_in_feature_maps=1, num_feature_maps=6)
            .override(1, layer="conv", filter_size=[5, 5], stride=[2, 2],
                      num_in_feature_maps=6, num_feature_maps=16)
            .override(2, layer="dense", n_in=4 * 4 * 16, n_out=120)
            .override(3, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_in=120, n_out=10)
            .input_preprocessor(0, ConvolutionInputPreProcessor(28, 28, 1))
            .input_preprocessor(2, ConvolutionPostProcessor())
            .pretrain(False)
            .build())
    net = MultiLayerNetwork(conf)
    n_batches, epochs = (4, 2) if _fast() else (8, 32)
    x_np, y_np = synthetic_mnist(batch_size * n_batches)
    x, y = jnp.asarray(x_np), jnp.asarray(y_np)

    net.fit_scan(x, y, batch_size=batch_size, epochs=epochs)  # compile
    _d2h(net.params())
    steps = n_batches * epochs

    def window():
        net.fit_scan(x, y, batch_size=batch_size, epochs=epochs)
        _d2h(net.params())

    rate, win_s = _median_rate(window, steps)
    return {"value": round(1000.0 / rate, 3), "unit": "ms/step",
            "lower_is_better": True, "batch_size": batch_size,
            "steps_per_window": steps, "window_s": round(win_s, 3)}


def bench_dbn():
    """BASELINE config 4: DBN (RBM stack) pretrain + finetune,
    samples/sec/chip over the whole pretrain+finetune pass. The solver
    iterations dispatch eagerly (the pretrain path is host-driven), so
    the window batches several full fit() passes and the per-dispatch
    host cost is reported as part of the metric — it is the honest
    end-to-end cost of this host-in-the-loop training mode. Reference
    path: core/models/featuredetectors/rbm/RBM.java:105 +
    nn/multilayer/MultiLayerNetwork.java:142."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.datasets.mnist import synthetic_mnist
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    batch_size = 256 if _fast() else 2048
    iters = 5  # pretrain + finetune iterations per fit() call

    conf = (NeuralNetConfiguration.builder()
            .lr(0.05).n_in(784).activation_function("sigmoid")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(iters)
            .batch_size(batch_size)
            .compute_dtype("bfloat16")
            .list(3)
            .hidden_layer_sizes([1024, 512])
            .override(0, layer="rbm", k=1)
            .override(1, layer="rbm", k=1)
            .override(2, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=10)
            .pretrain(True)
            .build())
    net = MultiLayerNetwork(conf)
    x_np, y_np = synthetic_mnist(batch_size)
    x, y = jnp.asarray(x_np), jnp.asarray(y_np)

    net.fit(x, y)  # compile every phase
    _d2h(net.params())
    # 12 fits keep the window >1 s now that the device-loop pretrain path
    # removed the per-optimize host syncs (short windows measure
    # dispatch jitter, not throughput — see the GloVe spread history)
    fits = 1 if _fast() else 12

    def window():
        for _ in range(fits):
            net.fit(x, y)
        _d2h(net.params())

    processed = fits * batch_size * iters * 3
    rate, win_s = _median_rate(window, processed)
    return {"value": round(rate / max(1, len(jax.devices())), 2),
            "unit": "samples/sec/chip",
            "fits_per_window": fits, "window_s": round(win_s, 3)}


def _zipf_sentences(n_tokens, vocab_size, seed=0, sent_len=40):
    rng = np.random.RandomState(seed)
    vocab = [f"w{i}" for i in range(vocab_size)]
    zipf = 1.0 / np.arange(1, vocab_size + 1)
    probs = zipf / zipf.sum()
    tokens = rng.choice(vocab_size, size=n_tokens, p=probs)
    return [" ".join(vocab[t] for t in tokens[i:i + sent_len])
            for i in range(0, n_tokens, sent_len)]


def bench_word2vec():
    """BASELINE config 3 shape: Word2Vec skip-gram device-training
    throughput (pairs/sec) on a synthetic zipfian corpus. Pairs are
    mined ONCE up front and reused across all timed windows (mining
    throughput is a host property, reported separately as mine_s);
    training runs the production chunked-scan step. Reference path:
    nlp/models/word2vec/Word2Vec.java:101,
    InMemoryLookupTable.java:188."""
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    n_tokens = 20_000 if _fast() else 200_000
    w2v = Word2Vec(_zipf_sentences(n_tokens, 2000), layer_size=128,
                   window=5, min_word_frequency=1, negative=5,
                   iterations=1, seed=0)
    w2v.build_vocab()  # before the clock: mine_s times MINING only
    t0 = time.perf_counter()
    centers, contexts = w2v.mine_pairs(np.random.RandomState(1))
    mine_s = time.perf_counter() - t0
    B, CB = w2v.batch_pairs, w2v.chunk_batches
    if centers.size < B * CB:  # tiny corpus: tile up to one chunk
        reps = (B * CB) // centers.size + 1
        centers = np.tile(centers, reps)[:B * CB]
        contexts = np.tile(contexts, reps)[:B * CB]
    n = centers.size // (B * CB) * (B * CB)
    # upload ONCE; train_pairs passes device-resident arrays through
    import jax.numpy as jnp
    centers = jnp.asarray(centers[:n], jnp.int32)
    contexts = jnp.asarray(contexts[:n], jnp.int32)

    w2v.train_pairs(centers[:B * CB], contexts[:B * CB])  # compile
    _d2h(w2v.syn0)

    def window():
        w2v.train_pairs(centers, contexts)
        _d2h(w2v.syn0)

    rate, win_s = _median_rate(window, n)
    return {"value": round(rate, 2), "unit": "pairs/sec",
            "pairs_per_window": int(n), "mine_s": round(mine_s, 3),
            "window_s": round(win_s, 3)}


def bench_glove():
    """GloVe co-occurrence training throughput (triples/sec): corpus
    mined once via prepare(), timed windows run whole-epoch compiled
    scans. Reference path: nlp/models/glove/Glove.java:57-160."""
    from deeplearning4j_tpu.nlp.glove import Glove

    n_tokens = 20_000 if _fast() else 200_000
    glove = Glove(_zipf_sentences(n_tokens, 2000), layer_size=128,
                  window=5, min_word_frequency=1, batch_size=8192,
                  seed=0)
    t0 = time.perf_counter()
    glove.prepare()
    prep_s = time.perf_counter() - t0
    glove.train_epochs(1)  # compile (same per-epoch program all epochs)
    n = glove._triples[0].size
    B = glove.batch_size
    n_pad = (n + B - 1) // B * B
    # 16 epochs/window: with the round-5 device-side shuffle the
    # per-epoch H2D upload is gone and the per-call cost is the syn0
    # view refresh (~2 MB D2H) — longer windows amortize it so the pin
    # stops measuring transfer jitter (old spread was ±35%)
    epochs = 1 if _fast() else 16

    def window():
        glove.train_epochs(epochs)  # train_epochs D2H-syncs (syn0 view)

    rate, win_s = _median_rate(window, epochs * n_pad)
    return {"value": round(rate, 2), "unit": "triples/sec",
            "triples": int(n), "prepare_s": round(prep_s, 3),
            "epochs_per_window": epochs, "window_s": round(win_s, 3)}


def bench_guardian():
    """Guardian robustness config (docs/FAULT_TOLERANCE.md): (a) guarded
    vs unguarded fit_scan step time — both driven as identical one-epoch
    compiled calls so the delta isolates the fused finite-check +
    where-commit (<2% target); (b) a NaN-injection recovery drill on the
    guarded iterator path — the poisoned batch must never commit
    (params finite) and the final score must land within 1e-3 of the
    fault-free run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.datasets import ListDataSetIterator
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.datasets.mnist import synthetic_mnist
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.guardian import GuardianPolicy

    # ---- (a) guarded vs unguarded step time, chained on device
    net_u, batch_size = _mlp_net()
    net_g, _ = _mlp_net()
    n_batches, epochs = (4, 2) if _fast() else (16, 16)
    x_np, y_np = synthetic_mnist(batch_size * n_batches)
    x, y = jnp.asarray(x_np), jnp.asarray(y_np)
    # huge check/snapshot cadence: the window times the pure device-side
    # guard (the ladder's host syncs are per-check, amortized separately)
    policy = GuardianPolicy(check_every=10 ** 9, snapshot_every=10 ** 9)

    def one_pass(net, guarded):
        for _ in range(epochs):
            if guarded:
                net.fit_scan(x, y, batch_size=batch_size, epochs=1,
                             guardian=policy)
            else:
                net.fit_scan(x, y, batch_size=batch_size, epochs=1)
        _d2h(net.params())

    one_pass(net_u, False)  # compile
    one_pass(net_g, True)
    steps = n_batches * epochs
    rate_u, _ = _median_rate(lambda: one_pass(net_u, False), steps)
    rate_g, win_s = _median_rate(lambda: one_pass(net_g, True), steps)
    ms_u, ms_g = 1000.0 / rate_u, 1000.0 / rate_g
    overhead_pct = (ms_g - ms_u) / ms_u * 100.0

    # ---- (b) NaN-injection recovery drill (tiny net, guarded fit): ONE
    # transient fault in a long converging stream — the guarded run skips
    # the poisoned step and must land within 1e-3 of the clean run (the
    # skipped batch's influence decays once both runs sit in convergence)
    from deeplearning4j_tpu.datasets.iris import load_iris

    data = load_iris()
    ix, iy = np.asarray(data.features), np.asarray(data.labels)
    rng = np.random.RandomState(0)
    bs, n_steps = 24, 150
    sel = np.concatenate([rng.choice(len(ix), bs, replace=False)
                          for _ in range(n_steps)])
    dx, dy = ix[sel].copy(), iy[sel].copy()
    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(4).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(1).use_adagrad(False).momentum(0.5)
            .list(2).hidden_layer_sizes([8])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=3)
            .pretrain(False).build())

    clean = MultiLayerNetwork(conf)
    clean.fit(ListDataSetIterator(DataSet(dx, dy), bs))
    score_clean = clean.score(ix, iy)

    dx_bad = dx.copy()
    dx_bad[7 * bs:8 * bs] = np.nan  # one poisoned batch mid-stream
    faulty = MultiLayerNetwork(conf)
    faulty.fit(ListDataSetIterator(DataSet(dx_bad, dy), bs),
               guardian=GuardianPolicy(check_every=4, snapshot_every=16))
    params_finite = bool(np.isfinite(np.asarray(faulty.params())).all())
    score_faulty = faulty.score(ix, iy)
    delta = abs(score_faulty - score_clean)

    return {"value": round(ms_g, 4), "unit": "ms/guarded_step",
            "lower_is_better": True,
            "unguarded_ms": round(ms_u, 4),
            "overhead_pct": round(overhead_pct, 2),
            "recovery": {"params_finite": params_finite,
                         "score_clean": round(score_clean, 6),
                         "score_after_nan": round(score_faulty, 6),
                         "score_delta": round(delta, 6),
                         "recovered": bool(params_finite and delta < 1e-3)},
            "steps_per_window": steps, "window_s": round(win_s, 3)}


def bench_serve():
    """Serving config (docs/SERVING.md): (a) InferenceEngine throughput
    + p50/p99 latency over a synthetic RAGGED request stream — per-
    request eager dispatch is part of the metric (it is what serving
    pays per call), with the program-cache counter as the recompile
    guard; (b) transformer decode tokens/sec, KV-cache vs naive
    full-recompute — the cached path must win per token; (c)
    decode_concurrent: sustained DELIVERED tokens/sec under concurrent
    ragged EOS-terminated generate streams, continuous batching
    (DecodeLoop) vs the per-request generate_cached path — the >= 5x
    ROADMAP gate, with the decode-step program-cache counter proving
    one compiled program across all joins/leaves."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.mnist import synthetic_mnist
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       generate,
                                                       init_transformer_params)
    from deeplearning4j_tpu.serving.engine import InferenceEngine

    fast = _fast()

    # ---- (a) ragged request stream through one engine
    net, _ = _mlp_net()
    max_batch = 64 if fast else 256
    engine = InferenceEngine.for_network(net, max_batch_size=max_batch)
    engine.warmup((784,))
    programs_after_warmup = engine.program_cache_size()
    rng = np.random.RandomState(0)
    n_requests = 24 if fast else 200
    sizes = rng.randint(1, max_batch + 1, size=n_requests)
    x_all, _ = synthetic_mnist(int(sizes.max()))
    requests = [x_all[:s] for s in sizes]
    total_rows = int(sizes.sum())

    def window():
        for req in requests:
            engine.infer(req)  # np.asarray inside = per-request D2H

    rows_rate, win_s = _median_rate(window, total_rows)
    programs = engine.program_cache_size()
    counters_ok = programs >= 0 and programs_after_warmup >= 0
    snap = engine.snapshot()

    # ---- (b) decode tokens/sec: KV cache vs naive full-recompute
    cfg = TransformerConfig(vocab_size=512, d_model=128, n_heads=4,
                            n_layers=2, d_ff=256,
                            max_len=64 if fast else 512,
                            interpret=fast)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    b, t0 = 4, 16
    n_tok = (16 if fast else 128)
    prompt = jnp.asarray(
        np.random.RandomState(1).randint(0, cfg.vocab_size, (b, t0)),
        jnp.int32)

    def decode_window(cache):
        def run():
            _d2h(generate(params, prompt, cfg, n_tok, cache=cache))
        run()  # compile
        rate, _ = _median_rate(run, b * n_tok)
        return rate

    tok_naive = decode_window(False)
    tok_cached = decode_window(True)

    # ---- (c) decode_concurrent: continuous batching vs per-request.
    # Chat-shaped workload: generous max_tokens caps, EOS-terminated
    # completions far shorter than the cap (each stream's EOS is a
    # token the model actually emits early, derived from its own greedy
    # reference). The per-request path CANNOT stop at EOS — n_tokens is
    # baked into its compiled signature — so it pays the full cap per
    # request, serially; the slot scheduler stops each stream at its
    # EOS and hands the freed slot to the next. Tokens/sec counts
    # DELIVERED (EOS-trimmed) tokens for both paths. Per-token compute
    # is identical by construction (parity-pinned), so the CPU-smoke
    # speedup isolates early-exit + admission batching; the TPU lane
    # adds batch-utilisation on top (a B=1 decode step starves the
    # chip).
    from deeplearning4j_tpu.serving.decode_loop import DecodeLoop
    from deeplearning4j_tpu.serving.kv_cache import (generate_cached,
                                                     kv_cache_bytes)
    from deeplearning4j_tpu.serving.paged_kv import pages_for_tokens

    ccfg = TransformerConfig(
        vocab_size=512, d_model=64 if fast else 256,
        n_heads=4, n_layers=2, d_ff=128 if fast else 512,
        max_len=128 if fast else 512, interpret=fast)
    cparams = init_transformer_params(jax.random.PRNGKey(0), ccfg)
    n_streams = 16 if fast else 32
    crng = np.random.RandomState(1)
    t0s = [int(crng.choice([8, 16]))
           for _ in range(n_streams)]
    cap_hi = ccfg.max_len * 3 // 4
    caps = [min(int(crng.choice([cap_hi * 2 // 3, cap_hi])),
                ccfg.max_len - t)
            for t in t0s]
    prompts = [crng.randint(0, ccfg.vocab_size, (t,)).astype(np.int32)
               for t in t0s]
    # greedy references double as the per-request compile warmup; the
    # EOS for each stream is a token its reference emits within the
    # first ~8 positions (clipped to the first occurrence)
    refs = [np.asarray(generate_cached(
                cparams, jnp.asarray(p[None]), ccfg, n))[0, t:].tolist()
            for p, n, t in zip(prompts, caps, t0s)]
    eos_ids, actuals = [], []
    for gen_toks in refs:
        tok = gen_toks[min(7, len(gen_toks) - 1)]
        eos_ids.append(tok)
        actuals.append(gen_toks.index(tok) + 1)
    useful = sum(actuals)

    def window_per_request():
        for p, n in zip(prompts, caps):
            np.asarray(generate_cached(cparams, jnp.asarray(p[None]),
                                       ccfg, n))

    seq_rate, seq_win = _median_rate(window_per_request, useful)

    loop = DecodeLoop(cparams, ccfg, slots=n_streams,
                      page_size=16, horizon=8)

    def window_continuous():
        streams = [loop.submit(p, n, eos_id=e)
                   for p, n, e in zip(prompts, caps, eos_ids)]
        for s in streams:
            s.result(240)

    window_continuous()  # warmup: compiles prefill buckets + the step
    step_programs_after_warmup = loop.decode_step_programs()
    cont_rate, cont_win = _median_rate(window_continuous, useful)
    csnap = loop.snapshot()
    step_programs = loop.decode_step_programs()
    counters_ok2 = (step_programs >= 0
                    and step_programs_after_warmup >= 0)
    # HBM accounting: the contiguous path reserves max_len per request;
    # the pool's peak holds only pages for tokens actually written
    contiguous_bytes = kv_cache_bytes(ccfg, 1) * n_streams
    page_bytes = csnap["pool_bytes"] // (csnap["pages_total"] + 1)
    peak_paged_bytes = csnap["peak_pages_in_use"] * page_bytes
    ideal_pages = sum(pages_for_tokens(t + a, 16)
                      for t, a in zip(t0s, actuals))
    # per-step KV traffic: the loop accounts BOTH lane figures every
    # dispatch (streamed-kernel pages vs the dense gather window), so
    # the reduction is visible whichever lane actually ran
    ckv = csnap["decode_kernel"]["kv_read_bytes"]
    loop.close()
    decode_concurrent = {
        "tokens_per_sec_continuous": round(cont_rate, 2),
        "tokens_per_sec_per_request": round(seq_rate, 2),
        "speedup": round(cont_rate / seq_rate, 2),
        "gate_5x": bool(cont_rate / seq_rate >= 5.0),
        "n_streams": n_streams,
        "useful_tokens": useful,
        "cap_tokens": sum(caps),
        "decode_step_programs":
            step_programs if counters_ok2 else None,
        "recompiled_after_warmup":
            (step_programs - step_programs_after_warmup)
            if counters_ok2 else None,
        "prefill_programs": csnap["prefill_programs"],
        "kv_hbm": {
            "contiguous_reservation_bytes": contiguous_bytes,
            "paged_pool_bytes": csnap["pool_bytes"],
            "peak_pages_in_use": csnap["peak_pages_in_use"],
            "peak_paged_bytes": peak_paged_bytes,
            "ideal_pages_for_written_tokens": ideal_pages,
            "paged_vs_contiguous":
                round(peak_paged_bytes / contiguous_bytes, 4),
        },
        "kv_read_per_step": {
            "path_selected": csnap["decode_kernel"]["selected"],
            "kernel_bytes": ckv["kernel"],
            "gather_bytes": ckv["gather"],
            "reduction": (round(ckv["gather"] / ckv["kernel"], 2)
                          if ckv["kernel"] else None),
        },
        "window_s": round(cont_win, 3),
        "per_request_window_s": round(seq_win, 3),
    }

    return {"value": round(tok_cached, 2), "unit": "tokens/sec_cached",
            "decode": {"tokens_per_sec_cached": round(tok_cached, 2),
                       "tokens_per_sec_naive": round(tok_naive, 2),
                       "cache_speedup": round(tok_cached / tok_naive, 2),
                       "batch": b, "prompt_len": t0, "n_tokens": n_tok},
            "decode_concurrent": decode_concurrent,
            "engine": {"rows_per_sec": round(rows_rate, 2),
                       "requests": n_requests,
                       "latency_p50_ms": snap["latency_p50_ms"],
                       "latency_p99_ms": snap["latency_p99_ms"],
                       "occupancy": round(snap["occupancy"], 4),
                       "compiled_programs":
                           programs if counters_ok else None,
                       "recompiled_after_warmup":
                           (programs - programs_after_warmup)
                           if counters_ok else None},
            "window_s": round(win_s, 3)}


def bench_prefix_cache():
    """Prefix-cache config (docs/SERVING.md "Prefix caching"). All
    numbers here are deterministic counters, not timings: the workload
    is token-for-token identical between a cache-OFF pass and a
    cache-ON pass, so the ratio of the loops' `prefill_tokens`
    counters IS the prefill work the cache removed — platform-
    independent and exactly reproducible. Three phases: (a) the
    shared-system-prompt drill — N requests share a page-aligned
    48-token head with short ragged tails, submitted sequentially so
    each retiree seeds the cache for its successors; gate >= 5x fewer
    real prefill tokens at bit-identical outputs, with ONE decode-step
    program and zero recompiles after warmup pinned across the whole
    run (admitting via cached pages must not mint new programs);
    (b) multi-turn replay — a conversation resubmits its own growing
    transcript each turn and the cache re-prefills only the new tail;
    (c) an end-to-end /metrics scrape off a live server, with a
    copy-on-write fork forced by replaying a fully cached prompt."""
    import urllib.request

    import jax

    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       init_transformer_params)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving.decode_loop import DecodeLoop
    from deeplearning4j_tpu.serving.engine import InferenceEngine
    from deeplearning4j_tpu.serving.server import serve_network

    fast = _fast()
    ps = 8
    cfg = TransformerConfig(vocab_size=512, d_model=64 if fast else 256,
                            n_heads=4, n_layers=2,
                            d_ff=128 if fast else 512,
                            max_len=128, interpret=fast)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(7)
    head = rng.randint(0, cfg.vocab_size, (48,)).astype(np.int32)
    tails = [2, 3, 4, 5, 6, 4, 4, 4]  # ragged user turns, avg 4
    drill_prompts = [
        np.concatenate([head,
                        rng.randint(0, cfg.vocab_size, (t,)
                                    ).astype(np.int32)])
        for t in tails]
    turns = 4
    base = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
    turn_suffixes = [rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
                     for _ in range(turns - 1)]
    gen_tokens = 4

    def run_pass(enabled):
        loop = DecodeLoop(params, cfg, slots=4, page_size=ps,
                          horizon=4, prefix_cache=enabled)

        def gen(prompt):
            stream = loop.submit(np.asarray(prompt, np.int32),
                                 gen_tokens)
            return stream.full_sequence(240)

        outs, programs_after_first = [], None
        for p in drill_prompts:
            outs.append(gen(p))
            if programs_after_first is None:
                programs_after_first = loop.decode_step_programs()
        drill_prefill = loop.snapshot()["prefill_tokens"]
        convo, transcript = base.tolist(), []
        for t in range(turns):
            full = list(gen(convo))
            transcript.append(full)
            if t < turns - 1:
                convo = full + turn_suffixes[t].tolist()
        snap = loop.snapshot()
        loop.close()
        return {"outs": outs, "transcript": transcript,
                "drill_prefill": drill_prefill,
                "replay_prefill": snap["prefill_tokens"] - drill_prefill,
                "programs_after_first": programs_after_first,
                "snap": snap}

    cold = run_pass(False)
    warm = run_pass(True)

    identical = (cold["outs"] == warm["outs"]
                 and cold["transcript"] == warm["transcript"])
    reduction = cold["drill_prefill"] / max(1, warm["drill_prefill"])
    replay_reduction = (cold["replay_prefill"]
                        / max(1, warm["replay_prefill"]))
    step_programs = warm["snap"]["decode_step_programs"]
    counters_ok = (step_programs >= 0
                   and warm["programs_after_first"] >= 0)
    recompiled = step_programs - warm["programs_after_first"]
    pc = warm["snap"]["prefix_cache"]

    # ---- (c) e2e: the counters must be scrapeable off a live server.
    # Replaying a fully cached page-aligned prompt makes the first
    # decode write land in a shared page -> one copy-on-write fork.
    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(4).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(1).use_adagrad(False)
            .list(2).hidden_layer_sizes([8])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=3)
            .pretrain(False).build())
    gen_engine = InferenceEngine.for_transformer(params, cfg)
    prompt16 = [head[:16].tolist()]  # 2 full pages

    def post(url, payload):
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    def series(text, name):
        vals = [float(line.rsplit(" ", 1)[1])
                for line in text.splitlines() if line.startswith(name)]
        return sum(vals) if vals else -1.0

    with serve_network(MultiLayerNetwork(conf), n_replicas=1,
                       max_delay_ms=1.0, generate_engine=gen_engine,
                       slots=2, page_size=ps) as handle:
        first = post(f"{handle.url}/generate",
                     {"prompt": prompt16, "max_tokens": 4})
        replay = post(f"{handle.url}/generate",
                      {"prompt": prompt16, "max_tokens": 4})
        with urllib.request.urlopen(f"{handle.url}/metrics",
                                    timeout=30) as r:
            metrics_text = r.read().decode()
    hits_scraped = series(metrics_text, "dl4j_kv_prefix_hits_total")
    forks_scraped = series(metrics_text, "dl4j_kv_prefix_forks_total")
    scrape_ok = (replay["tokens"] == first["tokens"]
                 and hits_scraped >= 1.0 and forks_scraped >= 1.0)

    return {
        "value": round(reduction, 2),
        "unit": "x_prefill_token_reduction",
        "gate_5x": bool(identical and reduction >= 5.0),
        "outputs_identical": identical,
        "shared_prompt": {
            "requests": len(drill_prompts),
            "head_tokens": int(head.size),
            "page_size": ps,
            "prefill_tokens_cold": cold["drill_prefill"],
            "prefill_tokens_warm": warm["drill_prefill"],
            "reduction": round(reduction, 2),
        },
        "multi_turn": {
            "turns": turns,
            "prefill_tokens_cold": cold["replay_prefill"],
            "prefill_tokens_warm": warm["replay_prefill"],
            "reduction": round(replay_reduction, 2),
        },
        "prefix_cache": {"hits": pc["hits"], "misses": pc["misses"],
                         "forks": pc["forks"],
                         "evictions": pc["evictions"],
                         "pages_cached": pc["pages_cached"]},
        "decode_step_programs": step_programs if counters_ok else None,
        "recompiled_after_warmup": recompiled if counters_ok else None,
        "prefill_ctx_programs": warm["snap"]["prefill_ctx_programs"],
        "metrics_scrape": {"hits_total": hits_scraped,
                           "forks_total": forks_scraped,
                           "replay_bit_identical":
                               replay["tokens"] == first["tokens"],
                           "ok": scrape_ok},
    }


def bench_speculative():
    """Speculative-decoding config (docs/SERVING.md "Speculative
    decoding"): the chat-replay drill — templated prompts (shared
    system head + short user tails) whose greedy continuations recur —
    decoded plain vs draft-and-verify with BOTH drafter flavors at
    BIT-IDENTICAL output. The gated metric is deterministic and
    platform-independent: delivered tokens per TARGET-model dispatch
    (the weight sweep speculation amortizes), which must be >= 2x the
    plain lane's for both flavors. Wall tokens/sec is reported for
    both lanes but only meaningful where the step is bandwidth/
    dispatch-bound (the TPU lane); the CPU smoke is compute-bound, so
    a widened verify costs ~W forwards and wall speedup < 1 there by
    construction. The model flavor runs a draft DISTILLED on the
    target's own greedy traffic (drafter-shaped right-aligned windows
    — the positions the drafter actually sees), the pairing a real
    deployment ships; acceptance rates for both flavors are also
    scraped END TO END off a live /metrics."""
    import urllib.request

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig, init_transformer_params, transformer_logits)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving.decode_loop import DecodeLoop
    from deeplearning4j_tpu.serving.engine import InferenceEngine
    from deeplearning4j_tpu.serving.kv_cache import generate_cached
    from deeplearning4j_tpu.serving.server import serve_network

    fast = _fast()
    cfg = TransformerConfig(vocab_size=512, d_model=64 if fast else 256,
                            n_heads=4, n_layers=2 if fast else 4,
                            d_ff=128 if fast else 512,
                            max_len=128 if fast else 512,
                            interpret=fast)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    dcfg = TransformerConfig(vocab_size=512, d_model=32 if fast else 64,
                             n_heads=2, n_layers=1,
                             d_ff=64 if fast else 128,
                             max_len=cfg.max_len, interpret=fast)
    spec_k, draft_win = 4, 32
    n_streams, cap = 8, 48
    rng = np.random.RandomState(1)
    system = rng.randint(0, cfg.vocab_size, (12,)).astype(np.int32)
    prompts = [np.concatenate(
        [system, rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)])
        for _ in range(n_streams)]

    # ---- distill the draft on the target's own greedy rollouts of
    # this traffic, sample-shaped exactly like drafter inference:
    # right-aligned zero-padded windows predicting the next token
    seqs = np.asarray(generate_cached(
        params, jnp.asarray(np.stack(prompts)), cfg, cap))
    wins, labels = [], []
    for s in seqs:
        for cut in range(4, len(s)):
            w = np.zeros((draft_win,), np.int32)
            h = s[max(0, cut - draft_win):cut]
            w[draft_win - len(h):] = h
            wins.append(w)
            labels.append(s[cut])
    wins = np.stack(wins)
    labels = np.asarray(labels, np.int32)

    def distill_loss(p, w, y):
        logits = transformer_logits(p, w, dcfg)[:, -1, :]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))

    @jax.jit
    def distill_step(p, m, v, i, w, y):
        g = jax.grad(distill_loss)(p, w, y)
        b1, b2, lr, eps = 0.9, 0.999, 3e-3, 1e-8
        m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b,
                                   m, g)
        v = jax.tree_util.tree_map(
            lambda a, b: b2 * a + (1 - b2) * b * b, v, g)

        def upd(p_, m_, v_):
            return p_ - lr * (m_ / (1 - b1 ** i)) / (
                jnp.sqrt(v_ / (1 - b2 ** i)) + eps)

        return jax.tree_util.tree_map(upd, p, m, v), m, v

    dparams = init_transformer_params(jax.random.PRNGKey(7), dcfg)
    m = jax.tree_util.tree_map(jnp.zeros_like, dparams)
    v = jax.tree_util.tree_map(jnp.zeros_like, dparams)
    t_distill = time.perf_counter()
    wj, yj = jnp.asarray(wins), jnp.asarray(labels)
    for i in range(1, 401):
        idx = np.random.RandomState(i).randint(0, len(wins), (64,))
        dparams, m, v = distill_step(dparams, m, v, jnp.float32(i),
                                     wj[idx], yj[idx])
    dparams = jax.tree_util.tree_map(np.asarray, dparams)
    distill_s = time.perf_counter() - t_distill

    # ---- the three lanes over the identical replayed workload
    def run_lane(**kw):
        loop = DecodeLoop(params, cfg, slots=n_streams, page_size=16,
                          **kw)

        def window():
            streams = [loop.submit(list(p), cap) for p in prompts]
            for s in streams:
                s.result(240)
            return [s.full_sequence(1) for s in streams]

        outs = window()  # warmup: compiles + seeds the replay corpus
        if kw.get("speculation"):
            # the width-1 fallback chain is part of the speculative
            # lane (rounds where nothing drafts run it) — warm it too
            # so the recompile guard pins BOTH programs
            loop.submit(list(prompts[0]), 2,
                        speculation=False).result(240)
        programs_warm = loop.decode_step_programs()
        d0 = loop.snapshot()["dispatches"]
        rate, win_s = _median_rate(window, n_streams * cap)
        snap = loop.snapshot()
        dispatches = (snap["dispatches"] - d0) / REPEATS
        programs = loop.decode_step_programs()
        spec = snap["speculation"]
        loop.close()
        return outs, {
            "tokens_per_sec": round(rate, 2),
            "tokens_per_dispatch":
                round(n_streams * cap / dispatches, 2),
            "dispatches_per_window": round(dispatches, 1),
            "acceptance_rate": round(spec["acceptance_rate"], 4),
            "proposed": spec["proposed"],
            "accepted": spec["accepted"],
            "decode_step_programs":
                programs if programs >= 0 else None,
            "recompiled_after_warmup":
                (programs - programs_warm) if programs >= 0
                and programs_warm >= 0 else None,
            "window_s": round(win_s, 3),
        }

    ref, plain = run_lane()
    out_ng, ngram = run_lane(speculation=spec_k, drafter="ngram")
    out_md, model = run_lane(speculation=spec_k, drafter="model",
                             draft_params=dparams, draft_cfg=dcfg,
                             draft_window=draft_win)
    identical = ref == out_ng == out_md
    for lane, res in (("ngram", ngram), ("model", model)):
        res["speedup_tokens_per_dispatch"] = round(
            res["tokens_per_dispatch"] / plain["tokens_per_dispatch"],
            2)
        res["speedup_wall"] = round(
            res["tokens_per_sec"] / plain["tokens_per_sec"], 2)
    gate = bool(identical
                and ngram["speedup_tokens_per_dispatch"] >= 2.0
                and model["speedup_tokens_per_dispatch"] >= 2.0
                and ngram["recompiled_after_warmup"] == 0
                and model["recompiled_after_warmup"] == 0
                and (ngram["decode_step_programs"] or 0) <= 2
                and (model["decode_step_programs"] or 0) <= 2)

    # ---- e2e: acceptance rate scraped off a LIVE /metrics
    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(4).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(1).use_adagrad(False)
            .list(2).hidden_layer_sizes([8])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=3)
            .pretrain(False).build())

    def post(url, payload):
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    def series(text, name, agg):
        # the registry is process-global: earlier lanes in THIS run
        # left their (zeroed, closed-loop) series behind, so aggregate
        # across labels instead of trusting line order
        vals = [float(line.rsplit(" ", 1)[1])
                for line in text.splitlines() if line.startswith(name)]
        return agg(vals) if vals else -1.0

    gen_engine = InferenceEngine.for_transformer(params, cfg)
    with serve_network(MultiLayerNetwork(conf), n_replicas=1,
                       max_delay_ms=1.0, generate_engine=gen_engine,
                       slots=4, page_size=16, speculation=spec_k,
                       drafter="model", draft_params=dparams,
                       draft_cfg=dcfg,
                       draft_window=draft_win) as handle:
        first = post(f"{handle.url}/generate",
                     {"prompt": [prompts[0].tolist()],
                      "max_tokens": cap})
        replay = post(f"{handle.url}/generate",
                      {"prompt": [prompts[0].tolist()],
                       "max_tokens": cap})
        with urllib.request.urlopen(f"{handle.url}/metrics",
                                    timeout=30) as r:
            metrics_text = r.read().decode()
        with urllib.request.urlopen(f"{handle.url}/stats",
                                    timeout=30) as r:
            spec_live = json.loads(r.read())[
                "generate"]["decode"]["speculation"]
    # dead bench-lane loops above still expose zeroed gauge lines;
    # max picks the live serving loop's
    rate_scraped = series(metrics_text, "dl4j_spec_acceptance_rate",
                          max)
    scrape_ok = (replay["tokens"] == first["tokens"]
                 and "dl4j_spec_proposed" in metrics_text
                 and "dl4j_spec_rounds" in metrics_text
                 and spec_live["proposed"] >= 1
                 and 0.0 < rate_scraped <= 1.0
                 and abs(rate_scraped - spec_live["acceptance_rate"])
                 < 1e-6)

    return {
        "value": ngram["speedup_tokens_per_dispatch"],
        "unit": "x_tokens_per_target_dispatch",
        "gate_2x": gate,
        "outputs_identical": identical,
        "spec_k": spec_k,
        "workload": {"n_streams": n_streams, "max_tokens": cap,
                     "system_head_tokens": int(system.size),
                     "replayed_windows": REPEATS + 1},
        "plain": plain,
        "ngram": ngram,
        "model": dict(model, distill_s=round(distill_s, 1),
                      distill_pairs=len(wins)),
        "metrics_scrape": {
            "acceptance_rate": rate_scraped,
            "proposed_total": spec_live["proposed"],
            "replay_bit_identical": replay["tokens"] == first["tokens"],
            "ok": scrape_ok},
    }


def bench_fleet():
    """Fleet config (docs/FLEET.md): (a) scaling curve — aggregate
    /predict rows/sec and client-side p99 through the router over 1 ->
    2 -> 4 local replica PROCESSES (each a spawned `cli serve`; on the
    1-core CPU smoke the curve is flat by construction — the record is
    the router overhead and the harness, the TPU lane is where the
    fan-out pays); (b) availability drill: kill one of two replicas
    mid-hammer — the gate is ZERO client errors (idempotent retries on
    the surviving replica) and bounded p99 degradation."""
    import json as _json
    import shutil
    import tempfile
    import threading
    import urllib.request

    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.scaleout.checkpoint import DefaultModelSaver
    from deeplearning4j_tpu.serving.fleet import Fleet, ReplicaSpawner
    from deeplearning4j_tpu.serving.router import serve_fleet

    fast = _fast()
    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(16).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(1).use_adagrad(False)
            .list(2).hidden_layer_sizes([32])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=4)
            .pretrain(False).build())
    work = tempfile.mkdtemp(prefix="dl4j_bench_fleet_")
    ckpt = os.path.join(work, "fleet.ckpt")
    DefaultModelSaver(ckpt, keep_old=False).save(MultiLayerNetwork(conf))
    spawner = ReplicaSpawner(ckpt, serve_args=["--max-delay-ms", "1"])

    rows = 4
    body = _json.dumps(
        {"inputs": np.random.RandomState(0).rand(rows, 16).tolist()}
    ).encode()

    def hammer(url, n_threads, per_thread):
        """Concurrent client load; returns (latencies_s, errors)."""
        lats, errors = [], []
        lock = threading.Lock()

        def worker():
            for _ in range(per_thread):
                t0 = time.perf_counter()
                try:
                    req = urllib.request.Request(
                        url + "/predict", data=body,
                        headers={"Content-Type": "application/json"})
                    with urllib.request.urlopen(req, timeout=60) as r:
                        r.read()
                    dt = time.perf_counter() - t0
                    with lock:
                        lats.append(dt)
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errors.append(repr(e))

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(n_threads)]
        start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return lats, errors, time.perf_counter() - start

    def p99(lats):
        return (sorted(lats)[max(0, int(len(lats) * 0.99) - 1)]
                if lats else None)

    n_threads = 4
    per_thread = 16 if fast else 64
    scaling = {}
    drill = None
    try:
        for n in (1, 2, 4):
            fleet = Fleet(spawner=spawner, heartbeat_interval=0.2,
                          heartbeat_timeout=2.0)
            router = None
            try:
                fleet.spawn(n)
                fleet.wait_ready(n, timeout=240)
                router = serve_fleet(fleet)
                hammer(router.url, n_threads, 4)  # warm every replica
                lats, errors, wall = hammer(router.url, n_threads,
                                            per_thread)
                sp99 = p99(lats)
                scaling[str(n)] = {
                    "rows_per_sec": round(len(lats) * rows / wall, 2),
                    "p99_ms": round(sp99 * 1e3, 2) if sp99 else None,
                    "requests": len(lats),
                    "errors": len(errors),
                }
                if n == 2:
                    # ---- availability drill on this rung: kill one
                    # replica under load, count client-visible errors
                    calm_p99 = p99(lats)
                    victim = next(iter(fleet._replicas.values()))
                    stop = threading.Event()
                    drill_lats, drill_errors = [], []
                    dlock = threading.Lock()

                    def drill_worker():
                        while not stop.is_set():
                            t0 = time.perf_counter()
                            try:
                                req = urllib.request.Request(
                                    router.url + "/predict", data=body,
                                    headers={"Content-Type":
                                             "application/json"})
                                with urllib.request.urlopen(
                                        req, timeout=60) as r:
                                    r.read()
                                with dlock:
                                    drill_lats.append(
                                        time.perf_counter() - t0)
                            except Exception as e:  # noqa: BLE001
                                with dlock:
                                    drill_errors.append(repr(e))

                    workers = [threading.Thread(target=drill_worker,
                                                daemon=True)
                               for _ in range(n_threads)]
                    for t in workers:
                        t.start()
                    time.sleep(0.4)
                    victim.proc.kill()
                    killed_at = time.monotonic()
                    evicted_in = None
                    while time.monotonic() - killed_at < 10.0:
                        if victim.state == "evicted":
                            evicted_in = time.monotonic() - killed_at
                            break
                        time.sleep(0.02)
                    time.sleep(0.8)  # keep hammering the survivor
                    stop.set()
                    for t in workers:
                        t.join(timeout=60)
                    dp99 = p99(drill_lats)
                    bound = max(20 * calm_p99, 5.0)
                    snap = fleet.snapshot()
                    drill = {
                        "errors": len(drill_errors),
                        "requests": len(drill_lats),
                        "p99_ms": round(dp99 * 1e3, 2) if dp99 else None,
                        "calm_p99_ms": round(calm_p99 * 1e3, 2),
                        "p99_bound_ms": round(bound * 1e3, 2),
                        "evicted_in_s": (round(evicted_in, 3)
                                         if evicted_in else None),
                        "retries": snap["retries"],
                        "gate_zero_errors": len(drill_errors) == 0,
                        "gate_p99_bounded": bool(dp99 and dp99 <= bound),
                    }
            finally:
                if router is not None:
                    router.close(stop_replicas=True)
                else:
                    fleet.close(stop_replicas=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    top = scaling[str(max(int(k) for k in scaling))]
    return {"value": top["rows_per_sec"], "unit": "rows/sec",
            "replicas_at_value": max(int(k) for k in scaling),
            "scaling": scaling,
            "availability_drill": drill,
            "threads": n_threads, "rows_per_request": rows}


def bench_chaos():
    """Chaos availability drill (ISSUE 8, docs/FLEET.md "Chaos
    runbook"): SIGSTOP one of two replica processes mid-hammer — hung,
    NOT dead: the kernel keeps accepting connections into the listen
    backlog, so connection-failure eviction never fires and only the
    request path stalls. Every client request carries an
    `X-Deadline-Ms` budget. Gates: ZERO client-visible failures within
    those budgets (per-hop deadline-derived timeouts + retries on the
    healthy peer absorb every stall), the circuit breaker evicts the
    hung member within 2x its detection window (breaker_threshold x
    request_timeout + breaker_reset_s — the heartbeat path cannot see
    this failure mode), bounded p99 degradation, and SIGCONT leads to
    half-open `/readyz` readmission."""
    import json as _json
    import shutil
    import tempfile
    import threading
    import urllib.request

    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.scaleout.checkpoint import DefaultModelSaver
    from deeplearning4j_tpu.serving.fleet import (Fleet, ReplicaSpawner,
                                                  EVICTED, READY)
    from deeplearning4j_tpu.serving.router import serve_fleet
    from deeplearning4j_tpu.testing import chaos as chaos_mod

    fast = _fast()
    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(16).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(1).use_adagrad(False)
            .list(2).hidden_layer_sizes([32])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=4)
            .pretrain(False).build())
    work = tempfile.mkdtemp(prefix="dl4j_bench_chaos_")
    ckpt = os.path.join(work, "chaos.ckpt")
    DefaultModelSaver(ckpt, keep_old=False).save(MultiLayerNetwork(conf))
    spawner = ReplicaSpawner(ckpt, serve_args=["--max-delay-ms", "1"])

    rows = 4
    deadline_ms = 20_000
    body = _json.dumps(
        {"inputs": np.random.RandomState(0).rand(rows, 16).tolist()}
    ).encode()
    request_timeout, breaker_threshold, breaker_reset_s = 0.5, 2, 0.4
    # the breaker's detection window: enough consecutive timeouts to
    # reach the threshold, plus the open -> half-open wait
    detection_s = breaker_threshold * request_timeout + breaker_reset_s

    def p99(lats):
        return (sorted(lats)[max(0, int(len(lats) * 0.99) - 1)]
                if lats else None)

    fleet = Fleet(spawner=spawner, heartbeat_interval=0.2,
                  heartbeat_timeout=3.0,
                  request_timeout=request_timeout,
                  retry_budget=2,
                  breaker_threshold=breaker_threshold,
                  breaker_reset_s=breaker_reset_s)
    router = None
    try:
        fleet.spawn(2)
        fleet.wait_ready(2, timeout=240)
        router = serve_fleet(fleet)

        lats, errors = [], []
        lock = threading.Lock()
        stop = threading.Event()

        def worker():
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    req = urllib.request.Request(
                        router.url + "/predict", data=body,
                        headers={"Content-Type": "application/json",
                                 "X-Deadline-Ms": str(deadline_ms)})
                    with urllib.request.urlopen(
                            req, timeout=deadline_ms / 1e3) as r:
                        r.read()
                    with lock:
                        lats.append(time.perf_counter() - t0)
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errors.append(repr(e))

        n_threads = 4
        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        warm_s = 0.5 if fast else 1.5
        time.sleep(warm_s)              # calm traffic through both
        with lock:
            calm_lats, calm_n = list(lats), len(lats)
        calm_p99 = p99(calm_lats)

        victim = next(iter(fleet._replicas.values()))
        chaos_mod.sigstop(victim.proc)  # hung-but-TCP-alive
        stopped_at = time.monotonic()
        evicted_in = None
        while time.monotonic() - stopped_at < 30.0:
            if victim.state == EVICTED:
                evicted_in = time.monotonic() - stopped_at
                break
            time.sleep(0.02)
        time.sleep(0.5 if fast else 1.0)  # hammer the survivor
        chaos_mod.sigcont(victim.proc)    # recovery half of the drill
        cont_at = time.monotonic()
        readmitted_in = None
        while time.monotonic() - cont_at < 30.0:
            if victim.state == READY:
                readmitted_in = time.monotonic() - cont_at
                break
            time.sleep(0.05)
        time.sleep(0.3)                   # traffic over the full fleet
        stop.set()
        for t in threads:
            t.join(timeout=60)

        with lock:
            drill_lats = lats[calm_n:]
            n_errors = len(errors)
            err_sample = errors[:3]
        dp99 = p99(drill_lats)
        bound = max(20 * calm_p99, 5.0) if calm_p99 else 5.0
        snap = fleet.snapshot()
        return {
            "value": round(evicted_in, 3) if evicted_in else None,
            "unit": "s_to_breaker_eviction",
            "lower_is_better": True,
            "requests": len(drill_lats) + calm_n,
            "errors": n_errors,
            "error_sample": err_sample,
            "deadline_ms": deadline_ms,
            "calm_p99_ms": (round(calm_p99 * 1e3, 2)
                            if calm_p99 else None),
            "drill_p99_ms": round(dp99 * 1e3, 2) if dp99 else None,
            "p99_bound_ms": round(bound * 1e3, 2),
            "eviction_reason": victim.eviction_reason,
            "breaker_detection_window_s": detection_s,
            "evicted_in_s": (round(evicted_in, 3)
                             if evicted_in else None),
            "readmitted_in_s": (round(readmitted_in, 3)
                                if readmitted_in else None),
            "request_timeouts": snap["request_timeouts"],
            "breaker_opens": snap["breaker_opens"],
            "retries": snap["retries"],
            "gate_zero_errors_within_deadline": n_errors == 0,
            "gate_breaker_eviction_bounded": bool(
                evicted_in is not None
                and evicted_in <= 2.0 * detection_s),
            "gate_p99_bounded": bool(dp99 and dp99 <= bound),
            "gate_half_open_readmission": readmitted_in is not None,
        }
    finally:
        if router is not None:
            router.close(stop_replicas=True)
        else:
            fleet.close(stop_replicas=True)
        shutil.rmtree(work, ignore_errors=True)


def bench_stream_failover():
    """Durable-stream failover drill (ISSUE 15, docs/FLEET.md "Stream
    failover"): SIGKILL one of two replica processes while concurrent
    /generate streams are mid-flight. The replicas serve a
    deterministically-initialized transformer (`--transformer SPEC`),
    so the router's resume — replaying `prompt + delivered` on the
    survivor — must produce a continuation BIT-IDENTICAL to an
    uninterrupted reference. Gates: ZERO client-visible stream
    failures (every stream gapless, duplicate-free, token-for-token
    equal to the reference), replayed-prefill tokens bounded by
    prompt+generated per resumed stream (and the survivor's warm
    prefix cache absorbs the replayed prompt page), and bounded p99
    time-to-next-token across the hop."""
    import json as _json
    import shutil
    import tempfile
    import threading
    import urllib.request

    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.scaleout.checkpoint import DefaultModelSaver
    from deeplearning4j_tpu.serving.fleet import Fleet, ReplicaSpawner
    from deeplearning4j_tpu.serving.router import serve_fleet
    from deeplearning4j_tpu.testing import chaos as chaos_mod

    fast = _fast()
    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(4).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(1).use_adagrad(False)
            .list(2).hidden_layer_sizes([8])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=3)
            .pretrain(False).build())
    work = tempfile.mkdtemp(prefix="dl4j_bench_failover_")
    ckpt = os.path.join(work, "failover.ckpt")
    DefaultModelSaver(ckpt, keep_old=False).save(MultiLayerNetwork(conf))
    spec = os.path.join(work, "tf.json")
    with open(spec, "w") as f:
        _json.dump({"vocab_size": 17, "d_model": 32, "n_heads": 2,
                    "n_layers": 2, "d_ff": 64, "max_len": 64,
                    "interpret": fast,  # pallas interpreter off-TPU
                    "seed": 0}, f)
    # pace token emission so the SIGKILL lands MID-stream
    delay_s = 0.02 if fast else 0.03
    env = dict(os.environ,
               **chaos_mod.env_spec([chaos_mod.Rule(
                   "generate.midstream", "delay", delay_s=delay_s)]))
    spawner = ReplicaSpawner(
        ckpt, serve_args=["--max-delay-ms", "1", "--transformer", spec,
                          "--slots", "8", "--page-size", "8"],
        env=env)

    # prompt fills exactly one KV page: the warm passes seed it into
    # each replica's prefix cache, so a resumed replay's prefill is a
    # cache hit instead of recompute
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]
    n_tokens = 16 if fast else 32
    n_streams = 4
    body = _json.dumps({"prompt": [prompt], "max_tokens": n_tokens,
                        "stream": True}).encode()

    def run_stream(out_events, out_times):
        req = urllib.request.Request(
            f"{router.url}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as r:
            for ln in r:
                if not ln.strip():
                    continue
                out_events.append(_json.loads(ln))
                out_times.append(time.perf_counter())

    def p99(gaps):
        return (sorted(gaps)[max(0, int(len(gaps) * 0.99) - 1)]
                if gaps else None)

    fleet = Fleet(spawner=spawner, heartbeat_interval=0.2,
                  heartbeat_timeout=3.0, breaker_threshold=2,
                  breaker_reset_s=0.4)
    router = None
    try:
        fleet.spawn(2)
        fleet.wait_ready(2, timeout=300)
        router = serve_fleet(fleet)

        # warm passes: compile the decode path AND seed the prompt's
        # page into both replicas' prefix caches (sequential requests
        # round-robin across the pair)
        ref_toks = None
        calm_gaps = []
        for _ in range(2):
            ev, ts = [], []
            run_stream(ev, ts)
            toks = [e["token"] for e in ev if "token" in e]
            assert len(toks) == n_tokens
            if ref_toks is None:
                ref_toks = toks
            assert toks == ref_toks
            calm_gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        calm_p99 = p99(calm_gaps)

        # drill: concurrent streams, SIGKILL the busy replica mid-flight
        all_events = [[] for _ in range(n_streams)]
        all_times = [[] for _ in range(n_streams)]
        errors = []

        def worker(i):
            try:
                run_stream(all_events[i], all_times[i])
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

        threads = [threading.Thread(target=worker, args=(i,),
                                    daemon=True)
                   for i in range(n_streams)]
        for t in threads:
            t.start()
        victim = None
        kill_by = time.monotonic() + 30.0
        while victim is None and time.monotonic() < kill_by:
            busy = [r for r in fleet._replicas.values()
                    if r.outstanding]
            victim = busy[0] if busy else None
            time.sleep(0.01)
        time.sleep(6 * delay_s)          # a few tokens in flight
        chaos_mod.sigkill(victim.proc)
        for t in threads:
            t.join(timeout=300)

        # exactly-once + bit-identical across every stream
        failures = list(errors)
        resumes = 0
        drill_gaps = []
        for ev, ts in zip(all_events, all_times):
            toks = [e for e in ev if "token" in e]
            if [e["token_index"] for e in toks] != list(range(n_tokens)):
                failures.append("token_index gap/dup")
            if [e["token"] for e in toks] != ref_toks:
                failures.append("tokens diverged from reference")
            if not (ev and ev[-1].get("done")):
                failures.append("stream ended without done")
            else:
                resumes += ev[-1]["resumes"]
            drill_gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        dp99 = p99(drill_gaps)
        bound = max(20 * calm_p99, 5.0) if calm_p99 else 5.0

        snap = fleet.snapshot()
        survivor = next(r for r in fleet._replicas.values()
                        if r.id != victim.id)
        sdec = survivor.client.stats()["generate"]["decode"]
        # replay budget: each resumed stream replays at most its
        # prompt + everything generated so far
        replay_budget = n_streams * (len(prompt) + n_tokens)
        return {
            "value": round(dp99 * 1e3, 2) if dp99 else None,
            "unit": "p99_time_to_next_token_ms",
            "lower_is_better": True,
            "streams": n_streams,
            "tokens_per_stream": n_tokens,
            "stream_failures": len(failures),
            "failure_sample": failures[:3],
            "resumes": resumes,
            "fleet_stream_resumes": snap["stream_resumes"],
            "tokens_replayed": snap["stream_tokens_replayed"],
            "tokens_deduped": snap["stream_tokens_deduped"],
            "replay_budget_tokens": replay_budget,
            "survivor_prefix_hits": sdec["prefix_cache"]["hits"],
            "survivor_decode_programs": sdec["decode_step_programs"],
            "calm_p99_ttnt_ms": (round(calm_p99 * 1e3, 2)
                                 if calm_p99 else None),
            "drill_p99_ttnt_ms": (round(dp99 * 1e3, 2)
                                  if dp99 else None),
            "p99_bound_ms": round(bound * 1e3, 2),
            "gate_zero_stream_failures": not failures,
            "gate_resumed": snap["stream_resumes"] >= 1,
            "gate_replay_bounded": (
                0 < snap["stream_tokens_replayed"] <= replay_budget),
            "gate_warm_replay_prefix_hits":
                sdec["prefix_cache"]["hits"] >= 1,
            "gate_p99_ttnt_bounded": bool(dp99 and dp99 <= bound),
            "gate_one_decode_program":
                sdec["decode_step_programs"] == 1,
        }
    finally:
        if router is not None:
            router.close(stop_replicas=True)
        else:
            fleet.close(stop_replicas=True)
        shutil.rmtree(work, ignore_errors=True)


def bench_fleet_prefix():
    """Fleet KV plane drill (docs/FLEET.md "Fleet KV plane"): a
    fleet of 4 replica processes serving one shared system prompt
    with per-request tails — the chat-shaped traffic the plane
    exists for. Two phases over the SAME warm fleet (distinct
    system prompts per phase, so neither inherits the other's
    caches):

    - fleet_kv=off router: round-robin sprays the shared head
      across the fleet, every replica pays its own cold prefill —
      the single-replica cache's fleet-wide reduction collapses.
    - fleet_kv=on router: prefix affinity converges the head onto
      one replica (tail-only prefill from request 2 on), and under
      a concurrent hammer the slack-bounded spill ships the hot
      pages peer-to-peer instead of recomputing them.

    Gates: fleet-wide prefill-token reduction >= 4x with affinity
    (and strictly above the off-mode figure), zero client-visible
    stream failures with the AFFINITY HOLDER SIGKILLed mid-hammer,
    p99 no worse than the same hammer+kill without affinity (a dead
    preferred replica must not convoy), >= 1 real page ship, and
    `dl4j_fleet_prefix_{affinity_hits,page_ships}` scraped live off
    the router's /metrics."""
    import json as _json
    import shutil
    import tempfile
    import threading
    import urllib.request

    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.scaleout.checkpoint import DefaultModelSaver
    from deeplearning4j_tpu.serving import fleetkv
    from deeplearning4j_tpu.serving.fleet import READY, Fleet, ReplicaSpawner
    from deeplearning4j_tpu.serving.router import serve_fleet
    from deeplearning4j_tpu.testing import chaos as chaos_mod

    fast = _fast()
    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(4).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(1).use_adagrad(False)
            .list(2).hidden_layer_sizes([8])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=3)
            .pretrain(False).build())
    work = tempfile.mkdtemp(prefix="dl4j_bench_fleetkv_")
    ckpt = os.path.join(work, "fleetkv.ckpt")
    DefaultModelSaver(ckpt, keep_old=False).save(MultiLayerNetwork(conf))
    spec = os.path.join(work, "tf.json")
    with open(spec, "w") as f:
        _json.dump({"vocab_size": 17, "d_model": 32, "n_heads": 2,
                    "n_layers": 2, "d_ff": 64, "max_len": 96,
                    "interpret": fast, "seed": 0}, f)
    # pace token emission so both phases' SIGKILLs land MID-stream —
    # without it the hammer streams finish before the kill and the
    # p99 comparison is a control in name only
    delay_s = 0.03
    env = dict(os.environ,
               **chaos_mod.env_spec([chaos_mod.Rule(
                   "generate.midstream", "delay", delay_s=delay_s)]))
    # one shared CPU core: donors answer /kv/export while decoding, so
    # give ships headroom over the 2 s production default — expiry
    # would silently fall back to plain prefill and starve the drill
    spawner = ReplicaSpawner(
        ckpt, serve_args=["--max-delay-ms", "1", "--transformer", spec,
                          "--slots", "8", "--page-size", "8",
                          "--kv-pages", "64", "--fleet-kv", "on",
                          "--kv-ship-timeout", "10"],
        env=env)

    n_fleet = 4
    # shared system prompt = 5 full KV pages, per-request tail = 1:
    # with affinity every request after the first prefills only its
    # tail, so the fleet-wide reduction approaches 6x (48/8) while
    # round-robin re-pays the head once per replica
    head_len, tail_len = 40, 8
    n_tokens = 4          # calm phase: measure prefill, not decode
    n_hammer_tokens = 24  # hammer: long enough to be killed mid-flight
    n_calm = 16 if fast else 24
    n_hammer = 8 if fast else 16

    def prompts_for(seed):
        rng = np.random.RandomState(seed)
        head = rng.randint(1, 17, (head_len,)).tolist()
        return [head + rng.randint(1, 17, (tail_len,)).tolist()
                for _ in range(max(n_calm, n_hammer))]

    def post(url, payload, timeout=300):
        req = urllib.request.Request(
            url, data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return _json.loads(r.read())

    def fleet_prefill():
        total = 0
        for r in fleet._replicas.values():
            if r.state != READY:
                continue
            try:
                total += (r.client.stats()["generate"]["decode"]
                          ["prefill_tokens"])
            except Exception:
                pass
        return total

    def calm_phase(router, prompts):
        """Sequential requests; returns (reduction, latencies)."""
        before = fleet_prefill()
        lats = []
        for pr in prompts[:n_calm]:
            t0 = time.perf_counter()
            post(f"{router.url}/generate",
                 {"prompt": [pr], "max_tokens": n_tokens})
            lats.append(time.perf_counter() - t0)
        submitted = sum(len(p) for p in prompts[:n_calm])
        measured = max(1, fleet_prefill() - before)
        return submitted / measured, lats

    def hammer_phase(router, prompts, wait_ships=False):
        """Concurrent durable streams + SIGKILL mid-drill. The victim
        is the busiest replica — with affinity on that IS the
        prefix holder/donor, so the drill proves a dead preferred
        replica cannot convoy routing. Streams launch in two waves:
        the first fills the preferred replica past PLACEMENT_SLACK so
        the second wave demonstrably spills (off-donor landings ->
        donor hints -> page ships); with `wait_ships` the kill holds
        until the fleet counters show a ship landed — the donor dies
        AFTER proving the plane works, while its streams are still
        mid-flight."""
        lats, errors, resumes = [], [], [0]

        def worker(i):
            body = {"prompt": [prompts[i % len(prompts)]],
                    "max_tokens": n_hammer_tokens, "stream": True}
            try:
                t0 = time.perf_counter()
                req = urllib.request.Request(
                    f"{router.url}/generate",
                    data=_json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})
                events = []
                with urllib.request.urlopen(req, timeout=300) as r:
                    for ln in r:
                        if ln.strip():
                            events.append(_json.loads(ln))
                lats.append(time.perf_counter() - t0)
                toks = [e for e in events if "token" in e]
                if not (events and events[-1].get("done")
                        and len(toks) == n_hammer_tokens):
                    errors.append(
                        f"stream {i}: bad terminal "
                        f"({len(toks)}/{n_hammer_tokens} tokens)")
                else:
                    resumes[0] += events[-1].get("resumes", 0)
            except Exception as e:  # noqa: BLE001
                errors.append(f"stream {i}: {e!r}")

        threads = [threading.Thread(target=worker, args=(i,),
                                    daemon=True)
                   for i in range(n_hammer)]
        wave1 = fleetkv.PLACEMENT_SLACK + 1  # fills the preference
        for t in threads[:wave1]:
            t.start()
        time.sleep(0.4)
        for t in threads[wave1:]:            # these spill (and ship)
            t.start()
        if wait_ships:
            ship_by = time.monotonic() + 8.0
            while time.monotonic() < ship_by:
                if fleet.snapshot()["prefix_cache"]["page_ships"] >= 1:
                    break
                time.sleep(0.05)
        victim = None
        kill_by = time.monotonic() + 30.0
        while victim is None and time.monotonic() < kill_by:
            busy = sorted((r for r in fleet._replicas.values()
                           if r.outstanding and r.proc is not None),
                          key=lambda r: -r.outstanding)
            victim = busy[0] if busy else None
            time.sleep(0.01)
        if victim is not None:
            time.sleep(6 * delay_s)  # a few tokens in flight
            chaos_mod.sigkill(victim.proc)
        for t in threads:
            t.join(timeout=300)
        return lats, errors, resumes[0]

    def p99(xs):
        return (sorted(xs)[max(0, int(len(xs) * 0.99) - 1)]
                if xs else None)

    fleet = Fleet(spawner=spawner, heartbeat_interval=0.2,
                  heartbeat_timeout=3.0, breaker_threshold=2,
                  breaker_reset_s=0.4)
    router = None
    try:
        fleet.spawn(n_fleet)
        fleet.wait_ready(n_fleet, timeout=600)

        # ---- phase OFF: same fleet, affinity-blind router
        router = serve_fleet(fleet, fleet_kv="off")
        off_reduction, _ = calm_phase(router, prompts_for(1))
        off_lats, off_errs, _ = hammer_phase(router, prompts_for(2))
        router.http.close()  # keep the fleet; retire only the router
        router = None
        fleet.spawn(1)  # refill the killed slot (no auto-respawn)
        fleet.wait_ready(n_fleet, timeout=600)

        # ---- phase ON: affinity + shipping (fresh system prompt, so
        # nothing phase OFF cached can leak into the measurement)
        router = serve_fleet(fleet, fleet_kv="on")
        on_reduction, _ = calm_phase(router, prompts_for(3))
        on_lats, on_errs, resumes = hammer_phase(
            router, prompts_for(3), wait_ships=True)

        time.sleep(1.0)  # let heartbeat probes fold final ship stats
        stats = fleet.snapshot()["prefix_cache"]
        with urllib.request.urlopen(f"{router.url}/metrics",
                                    timeout=30) as r:
            metrics_text = r.read().decode()
        scraped = all(
            s in metrics_text
            for s in ("dl4j_fleet_prefix_affinity_hits",
                      "dl4j_fleet_prefix_page_ships"))

        op99, fp99 = p99(on_lats), p99(off_lats)
        # "zero affinity-induced regression": the same hammer+kill
        # without affinity is the control; allow measurement noise
        p99_ok = bool(op99 and fp99 and op99 <= max(1.5 * fp99,
                                                    fp99 + 1.0))
        return {
            "value": round(on_reduction, 2),
            "unit": "fleet_prefill_token_reduction",
            "replicas": n_fleet,
            "calm_requests": n_calm,
            "hammer_streams": n_hammer,
            "reduction_affinity_off": round(off_reduction, 2),
            "reduction_affinity_on": round(on_reduction, 2),
            "affinity_hits": stats["affinity"]["hits"],
            "affinity_hit_rate": stats["affinity"]["rate"],
            "page_ships": stats["page_ships"],
            "ship_bytes": stats["ship_bytes"],
            "ship_failures": stats["ship_failures"],
            "stream_failures": len(on_errs) + len(off_errs),
            "failure_sample": (on_errs + off_errs)[:3],
            "failover_resumes": resumes,
            "p99_off_ms": round(fp99 * 1e3, 1) if fp99 else None,
            "p99_on_ms": round(op99 * 1e3, 1) if op99 else None,
            "gate_reduction_4x": on_reduction >= 4.0,
            "gate_beats_affinity_off": on_reduction > off_reduction,
            "gate_zero_stream_failures": not (on_errs or off_errs),
            "gate_no_affinity_p99_regression": p99_ok,
            "gate_affinity_hits": stats["affinity"]["hits"] >= 1,
            "gate_page_shipped": stats["page_ships"] >= 1,
            "gate_metrics_scraped": scraped,
        }
    finally:
        if router is not None:
            router.close(stop_replicas=True)
        else:
            fleet.close(stop_replicas=True)
        shutil.rmtree(work, ignore_errors=True)


def bench_disagg():
    """Disaggregated-roles drill (docs/FLEET.md "Disaggregated
    roles"): a long-prompt storm against a prefill=1/decode=2 fleet,
    with a second model pooled on the same registry. Four legs over
    real replica processes:

    - calm: sequential long-prompt streams on the disagg fleet set
      the decode inter-token p99 baseline.
    - storm: staggered concurrent long-prompt streams — every prompt
      hands off (router /prefill -> kv_donor -> page ship), so the
      decode replicas prefill only tails and inter-token pacing holds
      near calm; concurrent second-model traffic proves per-model
      routing isolation (the m2 replica's prefill-token ledger must
      match EXACTLY the m2 prompts submitted).
    - kill: the same storm with the prefill replica SIGKILLed mid-
      flight — every handoff that dies falls back to plain unified
      prefill with zero client-visible failures.
    - control: the same storm on a unified fleet of equal decode
      capacity, where storm prefills run inline on the decode
      scheduler and inflate inter-token gaps.

    Gates: storm decode p99 <= 1.5x calm, >= 1 handoff per storm
    prompt, zero cross-model routing errors, zero handoff-induced
    stream failures (including the SIGKILL leg), and the
    `dl4j_disagg_*` counters scraped live off the router's /metrics."""
    import json as _json
    import shutil
    import tempfile
    import threading
    import urllib.request

    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.scaleout.checkpoint import DefaultModelSaver
    from deeplearning4j_tpu.serving.fleet import Fleet, ReplicaSpawner
    from deeplearning4j_tpu.serving.router import serve_fleet
    from deeplearning4j_tpu.testing import chaos as chaos_mod

    fast = _fast()
    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(4).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(1).use_adagrad(False)
            .list(2).hidden_layer_sizes([8])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=3)
            .pretrain(False).build())
    work = tempfile.mkdtemp(prefix="dl4j_bench_disagg_")
    ckpt = os.path.join(work, "disagg.ckpt")
    DefaultModelSaver(ckpt, keep_old=False).save(MultiLayerNetwork(conf))
    spec = os.path.join(work, "tf.json")
    with open(spec, "w") as f:
        _json.dump({"vocab_size": 17, "d_model": 32, "n_heads": 2,
                    "n_layers": 2, "d_ff": 64, "max_len": 96,
                    "interpret": fast, "seed": 0}, f)
    # pace token emission so inter-token gaps are measurable and the
    # SIGKILL lands while handoffs/streams are genuinely in flight;
    # the gap a storm ADDS on top of this pace is the signal
    delay_s = 0.1
    env = dict(os.environ,
               **chaos_mod.env_spec([chaos_mod.Rule(
                   "generate.midstream", "delay", delay_s=delay_s)]))

    def spawner(role=None, model_id=None):
        args = ["--max-delay-ms", "1", "--transformer", spec,
                "--slots", "8", "--page-size", "8",
                "--kv-pages", "64", "--fleet-kv", "on",
                "--kv-ship-timeout", "10"]
        if role is not None:
            args += ["--role", role]
        if model_id is not None:
            args += ["--model-id", model_id]
        return ReplicaSpawner(ckpt, serve_args=args, env=env)

    # the storm's weapon is prompt-length VARIETY: page_size=8 /
    # max_len=96 gives the prefill bucket ladder (8,16,32,64,96);
    # calm traffic lives in bucket 64 (length 42), the storm cycles
    # lengths that hit the three buckets calm never touched — on a
    # unified fleet each novel bucket compiles INLINE on the decode
    # scheduler and craters inter-token pacing, on the disagg fleet
    # those compiles land on the prefill replica while the decode
    # replicas prefill only warm-bucket tails
    calm_len = 42
    storm_lens = (12, 20, 70)       # buckets 16, 32, 96
    n_tokens = 10
    n_calm = 4 if fast else 8
    n_storm = 6 if fast else 9
    n_m2 = 3

    def prompts_for(seed, n, length):
        rng = np.random.RandomState(seed)
        if isinstance(length, tuple):
            lens = [length[i % len(length)] for i in range(n)]
        else:
            lens = [length] * n
        return [rng.randint(1, 17, (ln,)).tolist() for ln in lens]

    def post(url, payload, headers=(), timeout=300):
        hdrs = {"Content-Type": "application/json"}
        hdrs.update(dict(headers))
        req = urllib.request.Request(
            url, data=_json.dumps(payload).encode(), headers=hdrs)
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return _json.loads(r.read())

    def stream_gaps(router, prompt, model_id=None):
        """One streamed request; returns (inter-token gaps s, ok)."""
        body = {"prompt": [prompt], "max_tokens": n_tokens,
                "stream": True}
        hdrs = {"Content-Type": "application/json"}
        if model_id is not None:
            hdrs["X-Model"] = model_id
        req = urllib.request.Request(
            f"{router.url}/generate", data=_json.dumps(body).encode(),
            headers=hdrs)
        stamps, events = [], []
        with urllib.request.urlopen(req, timeout=300) as r:
            for ln in r:
                if ln.strip():
                    events.append(_json.loads(ln))
                    if "token" in events[-1]:
                        stamps.append(time.perf_counter())
        ok = (events and events[-1].get("done")
              and len(stamps) == n_tokens)
        return ([b - a for a, b in zip(stamps, stamps[1:])], ok)

    def storm(router, prompts, stagger_s=0.06, kill=None,
              model_id=None):
        """Staggered concurrent streams; later prompts' prefills land
        while earlier streams decode — on a unified fleet that
        co-schedules them with decode, on the disagg fleet they run on
        the prefill replica. Returns (gaps, errors)."""
        gaps, errors = [], []
        lock = threading.Lock()

        def worker(i):
            try:
                g, ok = stream_gaps(router, prompts[i],
                                    model_id=model_id)
                with lock:
                    gaps.extend(g)
                    if not ok:
                        errors.append(f"stream {i}: bad terminal")
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(f"stream {i}: {e!r}")

        threads = [threading.Thread(target=worker, args=(i,),
                                    daemon=True)
                   for i in range(len(prompts))]
        for i, t in enumerate(threads):
            t.start()
            time.sleep(stagger_s)
            if kill is not None and i == len(threads) // 2:
                kill()
        for t in threads:
            t.join(timeout=300)
        return gaps, errors

    def p99(xs):
        return (sorted(xs)[max(0, int(len(xs) * 0.99) - 1)]
                if xs else None)

    def disagg_counters(router):
        with urllib.request.urlopen(f"{router.url}/stats",
                                    timeout=30) as r:
            return _json.loads(r.read())["fleet"]["disagg"]

    # ---- disagg fleet: prefill=1/decode=2 for m1, unified=1 for m2
    fleet = Fleet(heartbeat_interval=0.2, heartbeat_timeout=3.0,
                  breaker_threshold=2, breaker_reset_s=0.4)
    router = None
    try:
        fleet.add_pool(model_id="m1", role="prefill",
                       spawner=spawner("prefill", "m1"))
        fleet.add_pool(model_id="m1", role="decode",
                       spawner=spawner("decode", "m1"))
        fleet.add_pool(model_id="m2", role="unified",
                       spawner=spawner(None, "m2"))
        pre_rep = fleet.spawn_pool("m1", "prefill", 1)[0]
        fleet.spawn_pool("m1", "decode", 2)
        m2_rep = fleet.spawn_pool("m2", "unified", 1)[0]
        fleet.wait_ready(4, timeout=600)
        router = serve_fleet(fleet, fleet_kv="on")

        # warmup streams compile the calm buckets + decode step on
        # every decode replica so the calm baseline measures pacing,
        # not one-time compiles (sequential spread covers the pool)
        for pr in prompts_for(11, 4, calm_len):
            stream_gaps(router, pr, model_id="m1")
        calm_gaps = []
        for pr in prompts_for(1, n_calm, calm_len):
            g, ok = stream_gaps(router, pr, model_id="m1")
            assert ok, "calm stream lost tokens"
            calm_gaps.extend(g)

        # ---- storm + concurrent second-model traffic
        before = disagg_counters(router)
        m2_prompts = prompts_for(7, n_m2, 24)
        m2_errors = []

        def m2_traffic():
            for pr in m2_prompts:
                try:
                    out = post(f"{router.url}/generate",
                               {"prompt": [pr], "max_tokens": 2,
                                "model_id": "m2"})
                    if out.get("finish_reasons") != ["max_tokens"]:
                        m2_errors.append("bad finish")
                except Exception as e:  # noqa: BLE001
                    m2_errors.append(repr(e))

        m2_thread = threading.Thread(target=m2_traffic, daemon=True)
        m2_thread.start()
        storm_prompts = prompts_for(2, n_storm, storm_lens)
        storm_gaps, storm_errors = storm(router, storm_prompts,
                                         model_id="m1")
        m2_thread.join(timeout=300)
        after = disagg_counters(router)
        handoffs_storm = after["handoffs"] - before["handoffs"]

        # per-model isolation ledger: the m2 replica prefilled EXACTLY
        # the m2 prompts — one leaked request either way breaks it
        m2_expected = sum(len(p) for p in m2_prompts)
        m2_stats = m2_rep.client.stats()
        m2_prefill = m2_stats["generate"]["decode"]["prefill_tokens"]

        # ---- kill leg: SIGKILL the prefill replica mid-storm
        kill_prompts = prompts_for(3, n_storm, storm_lens)
        _, kill_errors = storm(
            router, kill_prompts, model_id="m1",
            kill=lambda: chaos_mod.sigkill(pre_rep.proc))
        final = disagg_counters(router)

        with urllib.request.urlopen(f"{router.url}/metrics",
                                    timeout=30) as r:
            metrics_text = r.read().decode()
        scraped = all(s in metrics_text for s in
                      ("dl4j_disagg_handoffs",
                       "dl4j_disagg_handoff_bytes",
                       "dl4j_disagg_handoff_failures",
                       "dl4j_disagg_fallbacks",
                       "dl4j_fleet_role_replicas"))
        router.close(stop_replicas=True)
        router = None

        # ---- control: unified fleet of equal decode capacity
        ctl = Fleet(spawner=spawner(), heartbeat_interval=0.2,
                    heartbeat_timeout=3.0, breaker_threshold=2,
                    breaker_reset_s=0.4)
        ctl_router = None
        try:
            ctl.spawn(3)
            ctl.wait_ready(3, timeout=600)
            ctl_router = serve_fleet(ctl, fleet_kv="on")
            for pr in prompts_for(12, 6, calm_len):   # warm the pool
                stream_gaps(ctl_router, pr)
            ctl_calm_gaps = []
            for pr in prompts_for(5, n_calm, calm_len):
                g, _ = stream_gaps(ctl_router, pr)
                ctl_calm_gaps.extend(g)
            ctl_gaps, ctl_errors = storm(
                ctl_router, prompts_for(4, n_storm, storm_lens))
        finally:
            if ctl_router is not None:
                ctl_router.close(stop_replicas=True)
            else:
                ctl.close(stop_replicas=True)

        cp99, sp99 = p99(calm_gaps), p99(storm_gaps)
        ucp99, up99 = p99(ctl_calm_gaps), p99(ctl_gaps)
        sp99_ms = round(sp99 * 1e3, 1) if sp99 else None
        return {
            "value": sp99_ms,
            "unit": "decode_inter_token_p99_ms_under_prefill_storm",
            "replicas": {"m1": {"prefill": 1, "decode": 2},
                         "m2": {"unified": 1}, "control_unified": 3},
            "calm_streams": n_calm,
            "storm_streams": n_storm,
            "calm_p99_ms": round(cp99 * 1e3, 1) if cp99 else None,
            "storm_p99_ms": sp99_ms,
            "unified_calm_p99_ms":
                round(ucp99 * 1e3, 1) if ucp99 else None,
            "unified_storm_p99_ms":
                round(up99 * 1e3, 1) if up99 else None,
            "handoffs_storm": handoffs_storm,
            "handoff_bytes": final["handoff_bytes"],
            "handoff_failures": final["handoff_failures"],
            "fallbacks": final["fallbacks"],
            "m2_requests": n_m2,
            "m2_prefill_tokens": m2_prefill,
            "m2_prefill_expected": m2_expected,
            "stream_failures":
                len(storm_errors) + len(kill_errors) + len(m2_errors),
            "failure_sample":
                (storm_errors + kill_errors + m2_errors)[:3],
            "gate_decode_p99_bounded":
                bool(cp99 and sp99 and sp99 <= 1.5 * cp99),
            "gate_handoff_per_storm_prompt":
                handoffs_storm >= n_storm,
            "gate_zero_cross_model_errors":
                not m2_errors and m2_prefill == m2_expected,
            "gate_zero_handoff_failures":
                not (storm_errors or kill_errors),
            "gate_unified_control_degrades":
                bool(ucp99 and up99 and up99 > 1.5 * ucp99),
            "gate_metrics_scraped": scraped,
        }
    finally:
        if router is not None:
            router.close(stop_replicas=True)
        else:
            fleet.close(stop_replicas=True)
        shutil.rmtree(work, ignore_errors=True)


def bench_slo_tiers():
    """SLO tiers drill (docs/SERVING.md "Priority tiers"): saturate a
    fleet's decode slots with batch-tier /generate streams, then run
    interactive requests through the flood. Interactive latency must
    hold (preemption evicts batch slots past the fair share), and the
    preempted batch work must be LOSSLESS: the router's durable-stream
    resume re-admits each preempted row, so every batch stream still
    delivers its full token budget gapless, duplicate-free, and
    fleet's decode slots with batch-tier /generate streams, then run
    interactive requests through the flood. Interactive latency must
    hold (preemption evicts batch slots past the fair share), and the
    preempted batch work must be LOSSLESS: the router's durable-stream
    resume re-admits each preempted row, so every batch stream still
    delivers its full token budget gapless, duplicate-free, and
    bit-identical to a calm reference. Gates: bounded interactive p99
    vs the calm baseline, zero lost/duplicated batch rows, at least
    one observed preemption, and the three-way page-pool invariant
    intact at the end."""
    import json as _json
    import shutil
    import tempfile
    import threading
    import urllib.request

    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.scaleout.checkpoint import DefaultModelSaver
    from deeplearning4j_tpu.serving.fleet import Fleet, ReplicaSpawner
    from deeplearning4j_tpu.serving.router import serve_fleet
    from deeplearning4j_tpu.testing import chaos as chaos_mod

    fast = _fast()
    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(4).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(1).use_adagrad(False)
            .list(2).hidden_layer_sizes([8])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=3)
            .pretrain(False).build())
    work = tempfile.mkdtemp(prefix="dl4j_bench_slo_")
    ckpt = os.path.join(work, "slo.ckpt")
    DefaultModelSaver(ckpt, keep_old=False).save(MultiLayerNetwork(conf))
    spec = os.path.join(work, "tf.json")
    with open(spec, "w") as f:
        _json.dump({"vocab_size": 17, "d_model": 32, "n_heads": 2,
                    "n_layers": 2, "d_ff": 64, "max_len": 96,
                    "interpret": fast,
                    "seed": 0}, f)
    # pace the decode scheduler itself so interactive arrivals land
    # while batch streams HOLD slots: with the compile cache hot a
    # replica decodes ~2 ms/token, and an unpaced flood frees every
    # slot before a probe can arrive — decode.step is the chaos point
    # at the top of every scheduler pass
    delay_s = 0.01 if fast else 0.02
    step_s = 0.03 if fast else 0.05
    env = dict(os.environ,
               **chaos_mod.env_spec([
                   chaos_mod.Rule("generate.midstream", "delay",
                                  delay_s=delay_s),
                   chaos_mod.Rule("decode.step", "delay",
                                  delay_s=step_s)]))
    # 4 slots, batch_share 0.5: an idle fleet lets batch take all 4,
    # and the first interactive arrival preempts down toward 2
    spawner = ReplicaSpawner(
        ckpt, serve_args=["--max-delay-ms", "1", "--transformer", spec,
                          "--slots", "4", "--page-size", "8",
                          "--batch-share", "0.5"],
        env=env)

    prompt = [1, 2, 3, 4, 5, 6, 7, 8]
    batch_tokens = 48 if fast else 64
    inter_tokens = 4
    n_batch_streams = 4
    n_probes = 12 if fast else 24

    def p99(xs):
        return (sorted(xs)[max(0, int(len(xs) * 0.99) - 1)]
                if xs else None)

    def interactive_once():
        body = _json.dumps({"prompt": [prompt],
                            "max_tokens": inter_tokens}).encode()
        req = urllib.request.Request(
            f"{router.url}/generate", data=body,
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=300) as r:
            reply = _json.loads(r.read())
        assert "tokens" in reply, reply
        return time.perf_counter() - t0, reply["tokens"][0]

    def batch_stream(events):
        body = _json.dumps({"prompt": [prompt],
                            "max_tokens": batch_tokens,
                            "priority": "batch",
                            "stream": True}).encode()
        req = urllib.request.Request(
            f"{router.url}/generate", data=body,
            headers={"Content-Type": "application/json",
                     "X-Priority": "batch"})
        with urllib.request.urlopen(req, timeout=300) as r:
            for ln in r:
                if ln.strip():
                    events.append(_json.loads(ln))

    fleet = Fleet(spawner=spawner, heartbeat_interval=0.2,
                  heartbeat_timeout=3.0, shed_high_water=64)
    router = None
    try:
        fleet.spawn(1)
        fleet.wait_ready(1, timeout=300)
        router = serve_fleet(fleet)

        # calm baseline: compile the decode path, take the reference
        # continuation (deterministic weights: tier never changes the
        # tokens), then measure undisturbed interactive latency
        _, ref_inter = interactive_once()
        ref_events = []
        batch_stream(ref_events)
        ref_batch = [e["token"] for e in ref_events if "token" in e]
        assert len(ref_batch) == batch_tokens
        calm = [interactive_once()[0] for _ in range(n_probes)]
        calm_p99 = p99(calm)

        # flood: saturate every slot with batch streams, then push the
        # interactive probes through the flood
        all_events = [[] for _ in range(n_batch_streams)]
        errors = []

        def worker(i):
            try:
                batch_stream(all_events[i])
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

        threads = [threading.Thread(target=worker, args=(i,),
                                    daemon=True)
                   for i in range(n_batch_streams)]
        for t in threads:
            t.start()
        # wait until the flood actually OCCUPIES every decode slot
        # (router-side outstanding also counts relay-lagged streams)
        rep0 = next(iter(fleet._replicas.values()))
        occupy_by = time.monotonic() + 30.0
        while time.monotonic() < occupy_by:
            occ = rep0.client.stats()["generate"]["decode"][
                "tiers"]["occupied"]
            if occ["batch"] >= n_batch_streams:
                break
            time.sleep(0.02)
        flood = []
        util_peak = 0.0
        for i in range(n_probes):
            dt, toks = interactive_once()
            flood.append(dt)
            assert toks == ref_inter, "interactive tokens diverged"
            util_peak = max(util_peak,
                            fleet.snapshot()["tiers"]["utilization"])
        flood_p99 = p99(flood)
        for t in threads:
            t.join(timeout=300)

        # lossless batch lane: every stream full-length, gapless,
        # duplicate-free, bit-identical to the calm reference
        failures = list(errors)
        resumes = 0
        for ev in all_events:
            toks = [e for e in ev if "token" in e]
            if [e["token_index"] for e in toks] != list(
                    range(batch_tokens)):
                failures.append("batch token_index gap/dup")
            if [e["token"] for e in toks] != ref_batch:
                failures.append("batch tokens diverged from reference")
            if not (ev and ev[-1].get("done")):
                failures.append("batch stream ended without done")
            else:
                resumes += ev[-1].get("preempt_resumes", 0)

        snap = fleet.snapshot()
        rep = next(iter(fleet._replicas.values()))
        sdec = rep.client.stats()["generate"]["decode"]
        preemptions = sdec["tiers"]["preemptions"]
        pages_leaked = sdec["pages_in_use"]  # all streams done by now
        bound = max(1.5 * calm_p99, 2.0) if calm_p99 else 2.0
        return {
            "value": round(flood_p99 * 1e3, 2) if flood_p99 else None,
            "unit": "interactive_p99_under_flood_ms",
            "lower_is_better": True,
            "batch_streams": n_batch_streams,
            "batch_tokens_per_stream": batch_tokens,
            "interactive_probes": n_probes,
            "calm_p99_ms": (round(calm_p99 * 1e3, 2)
                            if calm_p99 else None),
            "flood_p99_ms": (round(flood_p99 * 1e3, 2)
                             if flood_p99 else None),
            "p99_bound_ms": round(bound * 1e3, 2),
            "preemptions": preemptions,
            "preempt_resumes": snap["tiers"]["preempt_resumes"],
            "client_preempt_resumes": resumes,
            "batch_row_failures": len(failures),
            "failure_sample": failures[:3],
            "utilization_peak": round(util_peak, 4),
            "tier_requests": snap["tiers"]["requests"],
            "gate_interactive_p99_bounded": bool(
                flood_p99 and flood_p99 <= bound),
            "gate_zero_batch_loss": not failures,
            "gate_preempted": preemptions >= 1,
            "gate_lossless_resume":
                snap["tiers"]["preempt_resumes"] >= 1,
            "gate_no_leaked_pages": pages_leaked == 0,
            "gate_one_decode_program":
                sdec["decode_step_programs"] == 1,
        }
    finally:
        if router is not None:
            router.close(stop_replicas=True)
        else:
            fleet.close(stop_replicas=True)
        shutil.rmtree(work, ignore_errors=True)


def bench_train_elastic():
    """Self-healing elastic training drills (ISSUE 9,
    docs/FAULT_TOLERANCE.md "Supervisor runbook"). Three drills over a
    TrainingSupervisor with 2 out-of-process workers:

    (a) **kill drill** — SIGKILL one worker mid-run; the supervisor
        evicts (process exit is observed directly), respawns, the wave
        re-forms, and the completed run's params must be BIT-IDENTICAL
        to an uninterrupted run at the same wave schedule (canonical
        job-seq fold order + exact wave membership). Recovery time
        (kill -> replacement RUNNING) is the primary metric.
    (b) **capacity-loss drill** — SIGKILL with respawn budget 0; the
        supervisor flushes and restarts the wave from the last
        COMMITTED sharded checkpoint resharded 2 -> 1 workers, with
        ZERO lost or double-trained examples (the folded batch-index
        trace must tile the stream exactly once).
    (c) **SIGSTOP drill** — a stopped worker still holds TCP, so
        liveness never lapses (heartbeat_timeout is set far beyond the
        run); only the steps-per-heartbeat progress watermark may evict
        it, within its configured window.
    """
    import tempfile
    import threading

    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.datasets.iris import load_iris
    from deeplearning4j_tpu.scaleout.api import CollectionJobIterator
    from deeplearning4j_tpu.scaleout.registry import ConfigRegistry
    from deeplearning4j_tpu.scaleout.supervisor import (TrainingSupervisor,
                                                        WorkerSpawner)
    from deeplearning4j_tpu.testing import chaos as chaos_mod

    conf_json = (NeuralNetConfiguration.builder()
                 .lr(0.1).n_in(4).activation_function("tanh")
                 .optimization_algo("iteration_gradient_descent")
                 .num_iterations(2).use_adagrad(False).momentum(0.0)
                 .list(2).hidden_layer_sizes([8])
                 .override(1, layer="output", loss_function="mcxent",
                           activation_function="softmax", n_out=3)
                 .pretrain(False).build().to_json())
    x, y = load_iris()
    x, y = np.asarray(x), np.asarray(y)
    rng = np.random.RandomState(0)
    batches = [(x[i], y[i])
               for i in (rng.choice(len(x), 24, replace=False)
                         for _ in range(6))]
    work = tempfile.mkdtemp(prefix="dl4j_bench_elastic_")

    def supervisor(tag, **kw):
        registry_root = os.path.join(work, f"reg_{tag}")
        jobs = [DataSet(bx, by) for bx, by in batches]
        kw.setdefault("heartbeat_timeout", 2.0)
        kw.setdefault("progress_timeout", 90.0)
        return TrainingSupervisor(
            CollectionJobIterator(jobs), run_name=tag,
            registry=ConfigRegistry(registry_root),
            performer_class=("deeplearning4j_tpu.scaleout.perform."
                            "NeuralNetWorkPerformer"),
            performer_conf={"conf_json": conf_json, "epochs": 1},
            n_workers=2, conf_json=conf_json,
            spawner=WorkerSpawner(registry_root, tag), **kw)

    n_jobs = len(batches)
    exact = list(range(n_jobs))

    # -------- uninterrupted reference (same wave schedule)
    ref = supervisor("ref").run(timeout=240.0)

    # -------- (a) kill drill: SIGKILL -> respawn -> bit-identical
    sup_a = supervisor("kill", checkpoint_dir=os.path.join(work, "ck_a"),
                       max_respawns=2, respawn_backoff_s=0.05)
    drill_a = {}

    def killer():
        deadline = time.time() + 120
        while time.time() < deadline:
            for rec in list(sup_a.members.values()):
                if (rec.performed >= 1 and rec.proc is not None
                        and rec.generation == 0):
                    chaos_mod.sigkill(rec.proc)
                    t_kill = time.monotonic()
                    drill_a["killed"] = rec.id
                    while time.monotonic() - t_kill < 120:
                        if any(r.generation > 0 and r.state == "running"
                               for r in list(sup_a.members.values())):
                            drill_a["recovery_s"] = round(
                                time.monotonic() - t_kill, 3)
                            return
                        time.sleep(0.005)
                    return
            time.sleep(0.005)

    kt = threading.Thread(target=killer, daemon=True)
    kt.start()
    final_a = sup_a.run(timeout=240.0)
    kt.join(timeout=10)
    bit_identical = bool(final_a is not None
                         and np.array_equal(ref, final_a))
    trace_a_exact = sorted(sup_a.folded_seqs) == exact

    # -------- (b) capacity loss: no respawn budget -> resharded resume
    sup_b = supervisor("caploss",
                       checkpoint_dir=os.path.join(work, "ck_b"),
                       max_respawns=0)
    drill_b = {}

    def killer_b():
        deadline = time.time() + 120
        while time.time() < deadline:
            if sup_b.waves >= 1:
                for rec in list(sup_b.members.values()):
                    if rec.performed >= 1 and rec.proc is not None:
                        chaos_mod.sigkill(rec.proc)
                        drill_b["killed"] = rec.id
                        return
            time.sleep(0.005)

    kbt = threading.Thread(target=killer_b, daemon=True)
    kbt.start()
    final_b = sup_b.run(timeout=240.0)
    kbt.join(timeout=10)
    resume = (sup_b.resume_events[-1] if sup_b.resume_events else {})
    trace_b_exact = sorted(sup_b.folded_seqs) == exact
    resharded = bool(resume.get("resharded")
                     and resume.get("survivors") == 1)

    # -------- (c) SIGSTOP: watermark detection within its window
    progress_timeout = 2.0
    sup_c = supervisor("sigstop", max_respawns=1,
                       respawn_backoff_s=0.05,
                       heartbeat_timeout=600.0,  # liveness CANNOT evict
                       progress_timeout=progress_timeout)
    drill_c = {}

    def stopper():
        deadline = time.time() + 120
        while time.time() < deadline:
            for rec in list(sup_c.members.values()):
                if (rec.performed >= 1 and rec.proc is not None
                        and rec.generation == 0):
                    chaos_mod.sigstop(rec.proc)
                    drill_c["stopped"] = rec.id
                    drill_c["t"] = time.monotonic()
                    return
            time.sleep(0.005)

    st = threading.Thread(target=stopper, daemon=True)
    st.start()
    final_c = sup_c.run(timeout=240.0)
    st.join(timeout=10)
    detect_s = None
    if drill_c.get("stopped"):
        rec = sup_c.members[drill_c["stopped"]]
        if rec.evicted_at is not None:
            detect_s = round(rec.evicted_at - drill_c["t"], 3)
        drill_c["reason"] = rec.eviction_reason
    # detection bound: the job must first be dispatched to the stopped
    # member (one wave) and then sit a full watermark window; allow one
    # extra window of monitor slack
    detect_bound = 3 * progress_timeout + 5.0
    sigstop_ok = bool(
        detect_s is not None and detect_s <= detect_bound
        and (drill_c.get("reason") or "").startswith("hung")
        and final_c is not None
        and sorted(sup_c.folded_seqs) == exact)

    return {
        "value": drill_a.get("recovery_s"),
        "unit": "s_kill_to_respawned_running",
        "lower_is_better": True,
        "workers": 2, "jobs": n_jobs,
        "kill_drill": {**drill_a, "bit_identical": bit_identical,
                       "trace_exact": trace_a_exact,
                       "respawns": sup_a.respawns_used},
        "capacity_loss_drill": {**drill_b, "resume": resume,
                                "trace_exact": trace_b_exact},
        "sigstop_drill": {**drill_c, "detect_s": detect_s,
                          "bound_s": detect_bound},
        "gate_bit_identical_after_respawn": bit_identical,
        "gate_no_lost_or_double_trained": bool(trace_a_exact
                                               and trace_b_exact),
        "gate_resharded_resume": resharded,
        "gate_recovery_bounded": bool(
            drill_a.get("recovery_s") is not None
            and drill_a["recovery_s"] <= 60.0
            and resume.get("recovery_s") is not None
            and resume["recovery_s"] <= 60.0),
        "gate_sigstop_watermark": sigstop_ok,
    }


def bench_controlplane():
    """Control-plane crash-safety drills (ISSUE 10,
    docs/FAULT_TOLERANCE.md "Who watches the watcher" + docs/FLEET.md
    "Router restart runbook"). Two REAL-PROCESS drills over the
    journaled (`--state-dir`) control plane:

    (a) **supervisor-kill drill** — `cli watchdog -- train --elastic 2
        --state-dir ...`; SIGKILL the supervisor process as soon as a
        COMMITTED checkpoint proves the run is mid-flight. The
        watchdog's next incarnation must RE-ADOPT the surviving worker
        processes (adopted >= 1, zero respawns of live pids) and
        complete the run with params BIT-IDENTICAL to an uninterrupted
        reference and `folded == jobs` (zero lost / double-trained
        examples).
    (b) **router-kill drill** — `cli fleet --replicas 2 --state-dir`
        under a /predict hammer; SIGKILL the router process
        mid-hammer, restart it immediately (the bench plays watchdog).
        The restarted incarnation must readmit every journaled replica
        WARM through /readyz: same pids (zero respawns), per-replica
        compiled-program counts unchanged (zero recompiles), client
        errors confined to the kill->readmission window, and recovery
        (restart launch -> first routed success) under 5 s on the CPU
        smoke.
    """
    import signal
    import tempfile
    import threading
    import urllib.request

    from deeplearning4j_tpu.checkpoint.format import list_steps
    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.datasets.iris import load_iris
    from deeplearning4j_tpu.scaleout.checkpoint import load_checkpoint
    from deeplearning4j_tpu.testing import chaos as chaos_mod

    work = tempfile.mkdtemp(prefix="dl4j_bench_cp_")
    x, y = load_iris()
    data = np.hstack([np.asarray(x),
                      np.argmax(np.asarray(y), axis=1)[:, None]])
    csv = os.path.join(work, "iris.csv")
    np.savetxt(csv, data, delimiter=",", fmt="%.6f")
    conf_json = (NeuralNetConfiguration.builder()
                 .lr(0.1).n_in(4).activation_function("tanh")
                 .optimization_algo("iteration_gradient_descent")
                 .num_iterations(2).use_adagrad(False).momentum(0.0)
                 .list(2).hidden_layer_sizes([8])
                 .override(1, layer="output", loss_function="mcxent",
                           activation_function="softmax", n_out=3)
                 .pretrain(False).build().to_json())
    conf_path = os.path.join(work, "conf.json")
    with open(conf_path, "w") as f:
        f.write(conf_json)
    import sys as _sys

    py = _sys.executable

    def train_args(out):
        # --straggler-factor 50: compile jitter must not evict anyone
        # mid-drill (this drill is about the control plane, not the
        # straggler defense)
        return ["train", "--elastic", "2", "-i", csv, "-m", conf_path,
                "-o", out, "--batch-size", "8", "--epochs", "6",
                "--straggler-factor", "50", "--run-timeout", "240"]

    # ---- (a) supervisor-kill drill --------------------------------
    ref_out = os.path.join(work, "ref.ckpt")
    ref = subprocess.run(
        [py, "-m", "deeplearning4j_tpu.cli"] + train_args(ref_out)
        + ["--checkpoint-dir", os.path.join(work, "ck_ref")],
        capture_output=True, text=True, timeout=300, cwd=HERE)
    if ref.returncode != 0:
        raise RuntimeError(f"reference elastic run failed: "
                           f"{ref.stdout[-500:]} {ref.stderr[-500:]}")

    state = os.path.join(work, "state")
    ck = os.path.join(work, "ck")
    drill_out = os.path.join(work, "drill.ckpt")
    cmd = ([py, "-m", "deeplearning4j_tpu.cli", "watchdog",
            "--max-restarts", "3", "--backoff", "0.2", "--"]
           + train_args(drill_out)
           + ["--state-dir", state, "--checkpoint-dir", ck])
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                            cwd=HERE)
    children, killed, restart_ts = [], [], []
    drill_sup = {}

    def killer():
        deadline = time.time() + 240
        while time.time() < deadline and not killed:
            if children:
                try:
                    if list_steps(ck):
                        chaos_mod.sigkill(children[0])
                        killed.append(time.monotonic())
                        return
                except (OSError, ProcessLookupError):
                    return
            time.sleep(0.05)

    threading.Thread(target=killer, daemon=True).start()
    lines = []
    for line in proc.stdout:
        lines.append(line)
        if line.startswith("{"):
            try:
                e = json.loads(line)
            except ValueError:
                continue
            if "watchdog_child" in e:
                children.append(e["watchdog_child"])
                restart_ts.append(time.monotonic())
            elif "saved" in e:
                drill_sup = e
    rc = proc.wait(timeout=60)
    sup_restart_s = (round(restart_ts[1] - killed[0], 3)
                     if killed and len(restart_ts) > 1 else None)
    ref_net, _ = load_checkpoint(ref_out)
    sup_bit_identical = False
    if rc == 0 and os.path.exists(drill_out):
        drill_net, _ = load_checkpoint(drill_out)
        sup_bit_identical = bool(np.array_equal(
            np.asarray(ref_net.params()),
            np.asarray(drill_net.params())))
    sup_exact = bool(drill_sup
                     and drill_sup.get("folded") == drill_sup.get("jobs"))
    sup_adopted = bool(drill_sup and drill_sup.get("adopted", 0) >= 1
                       and drill_sup.get("respawns", 1) == 0)

    # ---- (b) router-kill drill ------------------------------------
    fstate = os.path.join(work, "fstate")
    fleet_cmd = [py, "-m", "deeplearning4j_tpu.cli", "fleet",
                 "-m", conf_path, "--replicas", "2",
                 "--state-dir", fstate,
                 "--heartbeat-interval", "0.2",
                 "--request-timeout", "10"]

    def launch_router():
        p = subprocess.Popen(fleet_cmd, stdout=subprocess.PIPE,
                             stderr=subprocess.DEVNULL, text=True,
                             start_new_session=True, cwd=HERE)
        announce = None
        for line in p.stdout:
            if line.startswith("{") and '"router"' in line:
                announce = json.loads(line)
                break
        if announce is None:
            p.kill()
            raise RuntimeError("router never announced")
        # keep draining so the child never blocks on a full pipe
        threading.Thread(target=lambda: [None for _ in p.stdout],
                         daemon=True).start()
        return p, announce

    def get_json(url, timeout=10.0):
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return json.loads(r.read())

    def replica_programs(endpoints):
        """Per-replica compiled-program counts, scraped from each
        replica's OWN /stats — unchanged across the router restart
        means the warm engines never recompiled."""
        out = {}
        for url in endpoints:
            stats = get_json(url + "/stats")
            out[url] = stats.get("replicas", {}).get(
                "compiled_programs")
        return out

    results = []          # (t, ok) per hammer request
    hammer_stop = threading.Event()
    router_url = {}

    def hammer():
        body = json.dumps({"inputs": data[:4, :4].tolist()}).encode()
        while not hammer_stop.is_set():
            url = router_url.get("url")
            if url is None:
                time.sleep(0.02)
                continue
            t = time.monotonic()
            try:
                req = urllib.request.Request(
                    url + "/predict", data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=5) as r:
                    ok = r.status == 200
            except Exception:
                ok = False
            results.append((t, ok))
            time.sleep(0.01)

    p1 = p2 = None
    replica_pids = []
    try:
        p1, ann1 = launch_router()
        endpoints = ann1["endpoints"]
        # both replicas ready before the drill starts
        deadline = time.time() + 180
        while time.time() < deadline:
            if get_json(ann1["router"] + "/readyz",
                        timeout=5).get("ready_replicas", 0) >= 2:
                break
            time.sleep(0.1)
        snap = get_json(ann1["router"] + "/stats")["fleet"]
        replica_pids = sorted(r["pid"]
                              for r in snap["replicas"].values()
                              if "pid" in r)
        programs_before = replica_programs(endpoints)
        router_url["url"] = ann1["router"]
        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(1.5)  # steady traffic through the warm fleet
        t_kill = time.monotonic()
        chaos_mod.sigkill(p1.pid)  # the router process, not the group:
        # replicas live in their own sessions and must survive
        t_launch = time.monotonic()
        p2, ann2 = launch_router()
        router_url["url"] = ann2["router"]
        t_announce = time.monotonic()
        # first routed success after the restart
        t_ok = None
        deadline = time.time() + 60
        while time.time() < deadline and t_ok is None:
            t_ok = next((t for t, ok in list(results)
                         if ok and t > t_announce), None)
            time.sleep(0.02)
        time.sleep(1.0)  # post-recovery traffic for the window audit
        hammer_stop.set()
        for t in threads:
            t.join(timeout=5)
        snap2 = get_json(ann2["router"] + "/stats")["fleet"]
        replica_pids2 = sorted(r["pid"]
                               for r in snap2["replicas"].values()
                               if "pid" in r)
        programs_after = replica_programs(endpoints)
        failures_after_ok = [t for t, ok in results
                             if not ok and t_ok and t > t_ok]
        recovery_s = (round(t_ok - t_launch, 3)
                      if t_ok is not None else None)
        error_window_s = (round(t_ok - t_kill, 3)
                          if t_ok is not None else None)
        router_drill = {
            "incarnation": ann2.get("incarnation"),
            "adopted": ann2.get("adopted"),
            "replica_pids_before": replica_pids,
            "replica_pids_after": replica_pids2,
            "programs_before": programs_before,
            "programs_after": programs_after,
            "announce_s": round(t_announce - t_launch, 3),
            "recovery_s": recovery_s,
            "error_window_s": error_window_s,
            "requests": len(results),
            "failures": sum(1 for _, ok in results if not ok),
            "failures_after_readmission": len(failures_after_ok),
        }
    finally:
        hammer_stop.set()
        for p in (p1, p2):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()
        for pid in replica_pids:
            try:
                os.killpg(pid, signal.SIGKILL)
            except (OSError, ProcessLookupError):
                try:
                    os.kill(pid, signal.SIGKILL)
                except (OSError, ProcessLookupError):
                    pass

    gate_router_zero_respawns = bool(
        router_drill["adopted"] == 2
        and replica_pids and router_drill["replica_pids_after"]
        == replica_pids)
    gate_router_zero_recompiles = bool(
        router_drill["programs_before"]
        == router_drill["programs_after"])
    gate_router_recovery = bool(
        router_drill["recovery_s"] is not None
        and router_drill["recovery_s"] <= 5.0)
    gate_error_window = bool(
        router_drill["error_window_s"] is not None
        and router_drill["failures_after_readmission"] == 0
        and router_drill["error_window_s"]
        <= router_drill["announce_s"] + 5.0)

    return {
        "value": router_drill["recovery_s"],
        "unit": "s_router_restart_to_first_routed_success",
        "lower_is_better": True,
        "supervisor_drill": {
            "rc": rc, "summary": drill_sup,
            "restart_s": sup_restart_s,
            "incarnations": len(children),
            "bit_identical": sup_bit_identical,
        },
        "router_drill": router_drill,
        "gate_supervisor_bit_identical": sup_bit_identical,
        "gate_supervisor_zero_lost_or_double": sup_exact,
        "gate_supervisor_adopted_not_respawned": sup_adopted,
        "gate_router_zero_respawns": gate_router_zero_respawns,
        "gate_router_zero_recompiles": gate_router_zero_recompiles,
        "gate_router_recovery_bounded": gate_router_recovery,
        "gate_router_error_window_bounded": gate_error_window,
    }


def bench_pipeline():
    """Train→serve conveyor drill (ISSUE 14, docs/PIPELINE.md): one
    model continuously training AND continuously serving its newest
    good weights, with every process in the chain kill -9'd mid-flight
    under a client request hammer.

    Topology (all real processes): `cli watchdog -- train --elastic 2
    --checkpoint-dir ck` commits sharded steps; `cli fleet --replicas 2`
    serves them behind the router; `cli watchdog -- pipeline` watches
    ck, eval-gates each COMMITTED step on a held-out set, and canary-
    promotes through POST /reload. The drill kills, in order: the
    elastic SUPERVISOR (watchdog restarts it, elastic resume), the
    deployment CONTROLLER (watchdog restarts it, journal resume), one
    REPLICA (fleet evicts it, retries mask the hammer), and the ROUTER
    (the bench relaunches it on the same port; the journal re-adopts
    the surviving replica warm). Then a poisoned checkpoint (random
    weights → eval-fail → quarantine) and an arch-mismatched one
    (canary reload failure → rollback + quarantine) ride the conveyor.

    Gates: zero hammer errors outside the kill→readmission windows; no
    torn promotion — the router's checkpoint-identity /stats shows every
    serving replica on EXACTLY one champion; the fleet converges to the
    newest eval-passed COMMITTED step; both poison steps carry
    QUARANTINED markers; dl4j_pipeline_{promotions,rollbacks,
    quarantines} scraped live from the controller's /metrics. Value:
    seconds from the training run's last commit to the fleet serving
    that step (the conveyor's end-to-end latency).
    """
    import signal
    import socket
    import tempfile
    import threading
    import urllib.request

    from deeplearning4j_tpu.checkpoint import ShardedModelSaver
    from deeplearning4j_tpu.checkpoint.restore import list_committed_steps
    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.deploy import QUARANTINE_MARKER
    from deeplearning4j_tpu.checkpoint import format as ckfmt
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.testing import chaos as chaos_mod
    import sys as _sys

    py = _sys.executable
    work = tempfile.mkdtemp(prefix="dl4j_bench_pipe_")

    # separable 3-class clusters: the gate spread between a fit net
    # (~1.0 f1) and a random-init poison (~0.33) is wide and reliable
    rng = np.random.RandomState(0)
    labels = rng.randint(0, 3, 240)
    feats = (np.eye(3, 4, dtype=np.float32)[labels] * 4.0
             + 0.3 * rng.randn(240, 4)).astype(np.float32)
    train_csv = os.path.join(work, "train.csv")
    np.savetxt(train_csv, np.hstack([feats[:192], labels[:192, None]]),
               delimiter=",", fmt="%.6f")
    holdout_csv = os.path.join(work, "holdout.csv")
    np.savetxt(holdout_csv, np.hstack([feats[192:],
                                       labels[192:, None]]),
               delimiter=",", fmt="%.6f")

    def build_conf(hidden=8):
        return (NeuralNetConfiguration.builder()
                .lr(0.1).n_in(4).activation_function("tanh")
                .optimization_algo("iteration_gradient_descent")
                .num_iterations(1).use_adagrad(False)
                .list(2).hidden_layer_sizes([hidden])
                .override(1, layer="output", loss_function="mcxent",
                          activation_function="softmax", n_out=3)
                .pretrain(False).build())

    conf_path = os.path.join(work, "conf.json")
    with open(conf_path, "w") as f:
        f.write(build_conf().to_json())
    boot_dir = os.path.join(work, "boot")
    with ShardedModelSaver(boot_dir, sync=True) as s:
        s.save(MultiLayerNetwork(build_conf()), step=0)
    ck = os.path.join(work, "ck")
    fstate = os.path.join(work, "fstate")
    pstate = os.path.join(work, "pstate")

    def free_port():
        with socket.socket() as sk:
            sk.bind(("127.0.0.1", 0))
            return sk.getsockname()[1]

    router_port, status_port = free_port(), free_port()
    router_url = f"http://127.0.0.1:{router_port}"
    status_url = f"http://127.0.0.1:{status_port}"

    def get_json(url, timeout=10.0):
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return json.loads(r.read())

    def scrape_pipeline_counters():
        with urllib.request.urlopen(status_url + "/metrics",
                                    timeout=10) as r:
            text = r.read().decode()
        out = {}
        for line in text.splitlines():
            if line.startswith("dl4j_pipeline_") and " " in line:
                name = line.split("{", 1)[0]
                try:
                    out[name] = out.get(name, 0.0) + float(
                        line.rsplit(" ", 1)[1])
                except ValueError:
                    pass
        return out

    fleet_cmd = [py, "-m", "deeplearning4j_tpu.cli", "fleet",
                 "-m", boot_dir, "--replicas", "2",
                 "--port", str(router_port), "--state-dir", fstate,
                 "--heartbeat-interval", "0.2",
                 "--request-timeout", "10"]

    def launch_router():
        p = subprocess.Popen(fleet_cmd, stdout=subprocess.PIPE,
                             stderr=subprocess.DEVNULL, text=True,
                             start_new_session=True, cwd=HERE)
        for line in p.stdout:
            if line.startswith("{") and '"router"' in line:
                ann = json.loads(line)
                threading.Thread(
                    target=lambda: [None for _ in p.stdout],
                    daemon=True).start()
                return p, ann
        p.kill()
        raise RuntimeError("router never announced")

    def launch_watchdog(args):
        p = subprocess.Popen(
            [py, "-m", "deeplearning4j_tpu.cli", "watchdog",
             "--max-restarts", "4", "--backoff", "0.2", "--"] + args,
            stdout=subprocess.PIPE, text=True, cwd=HERE)
        return p

    # hammer bookkeeping: (t, ok) per request; kill windows excuse
    # failures between a kill and the first success after it
    results, kills = [], []
    hammer_stop = threading.Event()

    def hammer():
        body = json.dumps({"inputs": feats[:4].tolist()}).encode()
        while not hammer_stop.is_set():
            t = time.monotonic()
            try:
                req = urllib.request.Request(
                    router_url + "/predict", data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=5) as r:
                    ok = r.status == 200
            except Exception:
                ok = False
            results.append((t, ok))
            time.sleep(0.01)

    def watch_children(proc, sink, tag):
        """Drain a watchdog's stdout, recording child pids."""
        def run():
            for line in proc.stdout:
                if not line.startswith("{"):
                    continue
                try:
                    e = json.loads(line)
                except ValueError:
                    continue
                if "watchdog_child" in e:
                    sink.setdefault(tag, []).append(e["watchdog_child"])
                elif "watchdog_done" in e:
                    sink[tag + "_done"] = True
        threading.Thread(target=run, daemon=True).start()

    p_router = p_train = p_pipe = None
    replica_pids = []
    children = {}
    drill = {"kills": []}
    try:
        # ---- boot the serving side --------------------------------
        p_router, ann = launch_router()
        deadline = time.time() + 180
        while time.time() < deadline:
            if get_json(router_url + "/readyz",
                        timeout=5).get("ready_replicas", 0) >= 2:
                break
            time.sleep(0.1)
        snap = get_json(router_url + "/stats")["fleet"]
        replica_pids = sorted(r["pid"]
                              for r in snap["replicas"].values()
                              if "pid" in r)
        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()

        # ---- the controller (under its watchdog) ------------------
        p_pipe = launch_watchdog(
            ["pipeline", "--checkpoint-dir", ck,
             "--fleet-url", router_url, "--eval-data", holdout_csv,
             "--eval-threshold", "0.5", "--regression-margin", "0.25",
             "--poll-interval", "0.25", "--state-dir", pstate,
             "--status-port", str(status_port), "--name", "bench"])
        watch_children(p_pipe, children, "pipe")

        # ---- the training side (under its watchdog) ---------------
        p_train = launch_watchdog(
            ["train", "--elastic", "2", "-i", train_csv,
             "-m", conf_path, "-o", os.path.join(work, "out.ckpt"),
             "--batch-size", "8", "--epochs", "4",
             "--checkpoint-dir", ck, "--state-dir",
             os.path.join(work, "tstate"),
             "--straggler-factor", "50", "--run-timeout", "240",
             "--checkpoint-keep", "100"])
        watch_children(p_train, children, "train")

        # ---- kill 1: the elastic SUPERVISOR, first commit seen ----
        deadline = time.time() + 120
        while time.time() < deadline:
            if list_committed_steps(ck) and children.get("train"):
                chaos_mod.sigkill(children["train"][0])
                kills.append(("supervisor", time.monotonic()))
                break
            time.sleep(0.05)

        # ---- kill 2: the CONTROLLER, first promotion landed -------
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                if scrape_pipeline_counters().get(
                        "dl4j_pipeline_promotions_total", 0) >= 1 \
                        and children.get("pipe"):
                    chaos_mod.sigkill(children["pipe"][0])
                    kills.append(("controller", time.monotonic()))
                    break
            except Exception:
                pass
            time.sleep(0.1)

        # ---- kill 3: one REPLICA (fleet evicts, retries mask) -----
        time.sleep(1.0)
        if replica_pids:
            chaos_mod.sigkill(replica_pids[-1])
            kills.append(("replica", time.monotonic()))

        # ---- kill 4: the ROUTER (bench plays watchdog) ------------
        time.sleep(1.5)
        chaos_mod.sigkill(p_router.pid)
        kills.append(("router", time.monotonic()))
        p_router, ann = launch_router()

        # ---- training completes; poison steps ride the conveyor ---
        deadline = time.time() + 240
        while time.time() < deadline \
                and not children.get("train_done"):
            time.sleep(0.2)
        t_last_commit = time.monotonic()
        steps_now = list_committed_steps(ck)
        last_good = steps_now[-1] if steps_now else None
        wide = MultiLayerNetwork(build_conf(hidden=16))
        wide.fit(feats[:192],
                 np.eye(3, dtype=np.float32)[labels[:192]], epochs=40)
        with ShardedModelSaver(ck, keep=50, sync=True) as s:
            # random weights: fails the absolute gate -> quarantine
            s.save(MultiLayerNetwork(build_conf()),
                   step=(last_good or 0) + 1000)
            # trained but arch-mismatched: PASSES the eval gate, then
            # fails the canary reload -> rollback + quarantine
            s.save(wide, step=(last_good or 0) + 2000)
        poison_eval = (last_good or 0) + 1000
        poison_canary = (last_good or 0) + 2000

        # ---- convergence: newest eval-passed COMMITTED step -------
        want_key = f"{os.path.abspath(ck)}@{last_good}"
        t_converged = None
        deadline = time.time() + 240
        while time.time() < deadline:
            try:
                served = get_json(router_url + "/stats")["fleet"][
                    "checkpoints_served"]
                q1 = os.path.exists(os.path.join(
                    ck, ckfmt.step_dir_name(poison_eval),
                    QUARANTINE_MARKER))
                q2 = os.path.exists(os.path.join(
                    ck, ckfmt.step_dir_name(poison_canary),
                    QUARANTINE_MARKER))
                if list(served) == [want_key] and q1 and q2:
                    t_converged = time.monotonic()
                    break
            except Exception:
                pass
            time.sleep(0.2)
        time.sleep(1.0)  # post-convergence traffic for the audit
        hammer_stop.set()
        for t in threads:
            t.join(timeout=5)

        final_served = get_json(router_url + "/stats")["fleet"][
            "checkpoints_served"]
        counters = scrape_pipeline_counters()
        pipe_status = get_json(status_url + "/status.json").get(
            "extra", {})

        # ---- the hammer audit -------------------------------------
        def excused(t_fail):
            # the documented readmission window after each kill: until
            # the first post-kill success, and never shorter than 5 s
            # (router relaunch + capacity-gap respawn + converge)
            for _, t_k in kills:
                if t_k <= t_fail:
                    if t_fail <= t_k + 5.0:
                        return True
                    t_ok = next((t for t, ok in results
                                 if ok and t > t_k), None)
                    if t_ok is None or t_fail <= t_ok:
                        return True
            return False

        failures = [t for t, ok in results if not ok]
        unexcused = [t for t in failures if not excused(t)]
        drill.update({
            "kills": [k for k, _ in kills],
            "requests": len(results),
            "failures": len(failures),
            "failures_outside_readmission": len(unexcused),
            "champion_step": (pipe_status.get("champion") or {}).get(
                "step"),
            "last_good_step": last_good,
            "checkpoints_served": final_served,
            "quarantined": pipe_status.get("quarantined"),
            "counters": counters,
            "incarnations": {k: len(v) for k, v in children.items()
                             if isinstance(v, list)},
            "commit_to_served_s": (round(t_converged - t_last_commit,
                                         3)
                                   if t_converged else None),
        })
    finally:
        hammer_stop.set()
        for p in (p_router, p_train, p_pipe):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()
        # the pipeline/train watchdog children + fleet replicas
        for pids in children.values():
            if isinstance(pids, list):
                for pid in pids:
                    try:
                        os.kill(pid, signal.SIGKILL)
                    except (OSError, ProcessLookupError):
                        pass
        for pid in replica_pids:
            try:
                os.killpg(pid, signal.SIGKILL)
            except (OSError, ProcessLookupError):
                try:
                    os.kill(pid, signal.SIGKILL)
                except (OSError, ProcessLookupError):
                    pass

    gate_converged = bool(
        drill.get("champion_step") is not None
        and drill["champion_step"] == drill.get("last_good_step")
        and list(drill.get("checkpoints_served") or {})
        == [f"{os.path.abspath(ck)}@{drill['last_good_step']}"])
    gate_one_champion = len(drill.get("checkpoints_served") or {}) == 1
    gate_quarantine = bool(
        drill.get("quarantined")
        and len(drill["quarantined"]) >= 2
        and drill.get("counters", {}).get(
            "dl4j_pipeline_quarantines_total", 0) >= 1
        and drill.get("counters", {}).get(
            "dl4j_pipeline_rollbacks_total", 0) >= 1)
    gate_promoted = drill.get("counters", {}).get(
        "dl4j_pipeline_promotions_total", 0) >= 1
    gate_hammer = drill.get("failures_outside_readmission") == 0
    gate_all_kills = len(drill.get("kills", [])) == 4

    return {
        "value": drill.get("commit_to_served_s"),
        "unit": "s_last_commit_to_fleet_serving_it",
        "lower_is_better": True,
        "drill": drill,
        "gate_all_four_kills_fired": gate_all_kills,
        "gate_zero_errors_outside_readmission": gate_hammer,
        "gate_no_torn_promotion_one_champion": gate_one_champion,
        "gate_converged_to_newest_eval_passed": gate_converged,
        "gate_regressor_quarantined_and_rolled_back": gate_quarantine,
        "gate_promotions_scraped_live": gate_promoted,
    }


def bench_checkpoint():
    """Checkpoint subsystem config (docs/CHECKPOINTS.md): (a) the
    per-autosave STEP-LOOP STALL — blocking single-file npz writer
    (serialize+write on the caller) vs the async sharded writer (the
    caller pays only the device→host snapshot; serialize+IO overlap
    training) — the acceptance gate is async < 20% of blocking; (b)
    committed save and restore bandwidth of the sharded format; (c)
    resharded restore: the same checkpoint reassembled from its
    per-device shards onto a single device (the 8→1 topology move)."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.checkpoint import (ShardedModelSaver,
                                               read_manifest,
                                               restore_network)
    from deeplearning4j_tpu.datasets.mnist import synthetic_mnist
    from deeplearning4j_tpu.scaleout.checkpoint import DefaultModelSaver

    net, batch_size = _mlp_net()
    # one tiny fit materializes updater state so checkpoints carry the
    # full production payload (params + hist + velocity)
    x_np, y_np = synthetic_mnist(batch_size)
    net.fit_scan(jnp.asarray(x_np), jnp.asarray(y_np),
                 batch_size=batch_size, epochs=1)
    _d2h(net.params())

    work = tempfile.mkdtemp(prefix="dl4j_bench_ckpt_")
    repeats = 3 if _fast() else 5
    try:
        # ---- (a) stall: blocking npz vs async sharded snapshot
        blocking = DefaultModelSaver(os.path.join(work, "block.ckpt"),
                                     keep_old=False)
        stalls_b = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            blocking.save(net)
            stalls_b.append(time.perf_counter() - t0)
        stall_blocking = statistics.median(stalls_b)

        saver = ShardedModelSaver(os.path.join(work, "sharded"),
                                  keep=2, max_in_flight=2)
        saver.save(net, iterator_position=0)  # warm the worker/dirs
        saver.flush()
        stalls_a, commits = [], []
        for i in range(repeats):
            t0 = time.perf_counter()
            saver.save(net, iterator_position=i + 1)
            stalls_a.append(time.perf_counter() - t0)
            saver.flush()  # outside the stall clock
            commits.append(time.perf_counter() - t0)
        stall_async = statistics.median(stalls_a)
        commit_s = statistics.median(commits)
        manifest = read_manifest(os.path.join(work, "sharded"))
        mb = manifest.get("total_bytes", 0) / 1e6
        saver.close()

        # ---- (b) restore bandwidth + (c) 8→1 resharded restore: the
        # shards were written per-device; restoring reassembles them and
        # places the tree on ONE device
        dev0 = jax.devices()[0]
        t0 = time.perf_counter()
        net2, _ = restore_network(os.path.join(work, "sharded"))
        net2._params = jax.device_put(net2._params, dev0)
        _d2h(net2.params())
        restore_s = time.perf_counter() - t0

        ratio = stall_async / stall_blocking if stall_blocking else None
        return {
            "value": round(stall_async * 1e3, 3), "unit": "ms/async_stall",
            "lower_is_better": True,
            "blocking_stall_ms": round(stall_blocking * 1e3, 3),
            "stall_ratio": round(ratio, 4) if ratio is not None else None,
            "stall_under_20pct": bool(ratio is not None and ratio < 0.20),
            "checkpoint_mb": round(mb, 2),
            "save_mb_s": round(mb / commit_s, 2) if commit_s else None,
            "restore_mb_s": round(mb / restore_s, 2) if restore_s else None,
            "reshard_restore_s": round(restore_s, 4),
            "n_devices": len(jax.devices()),
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)


def bench_telemetry():
    """Telemetry overhead config (docs/OBSERVABILITY.md): the same
    ragged iterator-driven fit as `feed` — the per-step dispatch loop is
    where the registry's counter incs / histogram observes / disabled
    spans land — run bare (registry kill switch off) vs instrumented
    (default). The delta is the whole telemetry cost of a train step;
    target <2% on the CPU smoke (asserted with a generous bound in
    tests/test_telemetry.py). Also reports registry scale and the
    /metrics render time, since scrapes run concurrently with serving.
    """
    import math

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.datasets import DeviceFeed, ListDataSetIterator
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.datasets.mnist import synthetic_mnist
    from deeplearning4j_tpu.telemetry.exposition import render_prometheus

    net, batch_size = _mlp_net()
    n_batches = 4 if _fast() else 16
    n = batch_size * n_batches + batch_size // 3  # ragged last batch
    x_np, y_np = synthetic_mnist(n)
    feed = DeviceFeed(ListDataSetIterator(DataSet(x_np, y_np), batch_size),
                      prefetch=2)
    epochs = 1 if _fast() else 4
    steps = epochs * math.ceil(n / batch_size)

    net.fit(feed, epochs=1)  # compile every bucket program
    _d2h(net.params())

    def window_instrumented():
        net.fit(feed, epochs=epochs)
        _d2h(net.params())

    def window_bare():
        telemetry.set_enabled(False)
        try:
            net.fit(feed, epochs=epochs)
            _d2h(net.params())
        finally:
            telemetry.set_enabled(True)

    rate_off, _ = _median_rate(window_bare, steps)
    rate_on, win_s = _median_rate(window_instrumented, steps)
    ms_on, ms_off = 1000.0 / rate_on, 1000.0 / rate_off
    overhead_pct = (ms_on - ms_off) / ms_off * 100.0

    t0 = time.perf_counter()
    text = render_prometheus()
    render_ms = (time.perf_counter() - t0) * 1e3
    n_series = sum(1 for ln in text.splitlines()
                   if ln and not ln.startswith("#"))
    return {"value": round(ms_on, 4), "unit": "ms/instrumented_step",
            "lower_is_better": True,
            "bare_ms": round(ms_off, 4),
            "overhead_pct": round(overhead_pct, 2),
            "registry": {"series": n_series,
                         "render_ms": round(render_ms, 3),
                         "bytes": len(text)},
            "steps_per_window": steps, "window_s": round(win_s, 3)}


def _flash_inputs():
    import jax
    import jax.numpy as jnp

    B, H, S, D = (2, 2, 512, 64) if _fast() else (4, 8, 2048, 64)
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, S, D), dtype=jnp.bfloat16)
    k = jax.random.normal(kk, (B, H, S, D), dtype=jnp.bfloat16)
    v = jax.random.normal(kv, (B, H, S, D), dtype=jnp.bfloat16)
    return q, k, v, (B, H, S, D)


def bench_flash():
    """Beyond-parity: Pallas flash-attention forward, compiled on the
    real chip, checked against the blockwise reference, then timed as a
    chained on-device scan. SURVEY §5 long-context."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.attention.blockwise import blockwise_attention
    from deeplearning4j_tpu.attention.flash_pallas import flash_attention

    fast = _fast()
    q, k, v, (B, H, S, D) = _flash_inputs()
    flash = lambda q, k, v: flash_attention(q, k, v, causal=True,  # noqa: E731
                                            interpret=fast)
    out = jax.block_until_ready(jax.jit(flash)(q, k, v))
    ref = blockwise_attention(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    if err > 0.05:  # bf16 tolerance
        raise AssertionError(f"flash vs blockwise max err {err}")

    steps = 2 if fast else 1500
    # keep-alive: scale by a tiny NON-zero constant — x*0 could legally be
    # folded to 0 by the algebraic simplifier, DCE-ing the kernel; 1e-8
    # rounds away in the bf16 add so the carry stays numerically fixed
    loop = jax.jit(lambda q, k, v: jax.lax.scan(
        lambda c, _: (q + jnp.bfloat16(1e-8) * flash(c, k, v)[0, 0, :1, :1],
                      None), q, None, length=steps)[0])
    jax.block_until_ready(loop(q, k, v))

    def window():
        _d2h(loop(q, k, v))

    rate, win_s = _median_rate(window, steps)
    ms = 1000.0 / rate
    useful_gflop = B * H * S * (S / 2) * D * 2 * 2 / 1e9  # causal fwd
    return {"value": round(ms, 4), "unit": "ms/step",
            "lower_is_better": True, "max_err_vs_blockwise": round(err, 4),
            "compiled_on": jax.devices()[0].platform,
            "shape": f"{B}x{H}x{S}x{D}",
            "tflops_useful": round(useful_gflop / ms, 1),
            "steps_per_window": steps, "window_s": round(win_s, 3)}


def bench_flash_bwd():
    """Beyond-parity: full flash-attention grad step (Pallas dQ + dK/dV
    kernels with saved-LSE recompute) as a chained on-device scan."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.attention.flash_pallas import flash_attention

    fast = _fast()
    q, k, v, (B, H, S, D) = _flash_inputs()

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, interpret=fast)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    grad = jax.grad(loss, argnums=(0, 1, 2))
    steps = 2 if fast else 500

    def body(c, _):
        dq, dk, dv = grad(c, k, v)
        probe = dq[0, 0, :1, :1] + dk[0, 0, :1, :1] + dv[0, 0, :1, :1]
        # non-zero scale so the probe dependence can't be constant-folded
        return q + jnp.bfloat16(1e-8) * probe, None

    loop = jax.jit(lambda q, k, v: jax.lax.scan(
        body, q, None, length=steps)[0])
    jax.block_until_ready(loop(q, k, v))

    def window():
        _d2h(loop(q, k, v))

    rate, win_s = _median_rate(window, steps)
    return {"value": round(1000.0 / rate, 4), "unit": "ms/grad_step",
            "lower_is_better": True,
            "compiled_on": jax.devices()[0].platform,
            "shape": f"{B}x{H}x{S}x{D}",
            "steps_per_window": steps, "window_s": round(win_s, 3)}


def bench_paged_kernel():
    """Paged-attention decode kernel config (docs/SERVING.md "Decode
    kernel"). Two deterministic gates that hold on any platform: (a)
    interpret-mode parity — the REAL Pallas kernel, run through the
    interpreter, against the dense-gather path on the same evolving
    pool, teacher-forced over ragged cursors including the max_len
    window edge; (b) per-step KV read-bytes reduction — a chat-shaped
    DecodeLoop drill whose dl4j_decode_kv_read_bytes counters give the
    streamed-pages vs dense-window traffic exactly (ISSUE 13 gate:
    >= 4x). The tokens/sec win itself is a TPU-lane number — interpret
    timing is meaningless, so it is reported only when this config
    compiled on a real chip."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.attention.paged_pallas import (
        resolve_decode_kernel)
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig, init_transformer_params)
    from deeplearning4j_tpu.serving import paged_kinds
    from deeplearning4j_tpu.serving.decode_loop import DecodeLoop
    from deeplearning4j_tpu.serving.paged_kv import (init_paged_pool,
                                                     pages_for_tokens,
                                                     pages_per_slot)

    fast = _fast()
    cfg = TransformerConfig(vocab_size=512, d_model=64, n_heads=4,
                            n_layers=2, d_ff=128, max_len=128,
                            interpret=fast)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    ps = 16
    rng = np.random.RandomState(0)

    # ---- (a) kernel vs gather parity on one evolving pool: ragged
    # prompts, teacher-forced steps crossing a page boundary, one slot
    # pinned AT the window edge (cursor == max_len -> trash write)
    P = pages_per_slot(cfg, ps)
    n_pages = 4 * P
    pool_g = init_paged_pool(cfg, n_pages, ps)
    trash = pool_g.trash_page
    t0s = [7, 16, 30, cfg.max_len]
    table = np.full((4, P), trash, np.int32)
    free = list(range(n_pages))
    lengths = np.asarray(t0s, np.int32)
    tb = 32
    padded = np.zeros((4, tb), np.int32)
    pids = np.full((4, tb // ps), trash, np.int32)
    for i, t in enumerate(t0s):
        pr = rng.randint(0, cfg.vocab_size, (min(t, tb),)).astype(np.int32)
        padded[i, :len(pr)] = pr
        need = pages_for_tokens(min(t, tb), ps)
        pages = [free.pop(0) for _ in range(need)]
        pids[i, :need] = pages
        table[i, :need] = pages
    # the window-edge slot owns its FULL reservation (all pages real)
    table[3] = [free.pop(0) for _ in range(P)]
    _, pool_g, _ = paged_kinds.prefill(
        params, jnp.asarray(padded), jnp.asarray(np.minimum(lengths, tb)),
        pool_g, {"full": jnp.asarray(pids)}, cfg)
    pool_p = pool_g
    active = np.asarray([True, True, True, False])
    max_err, steps = 0.0, 4
    for _ in range(steps):
        toks = rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
        for i in range(4):
            if active[i]:
                pidx = lengths[i] // ps
                if table[i, pidx] == trash:
                    table[i, pidx] = free.pop(0)
        args = (jnp.asarray(toks), jnp.asarray(table),
                jnp.asarray(lengths), jnp.asarray(active))
        lg_g, pool_g, _ = paged_kinds.decode_step(
            params, args[0], pool_g, {"full": args[1]}, args[2], args[3],
            cfg, kernel="gather")
        lg_p, pool_p, _ = paged_kinds.decode_step(
            params, args[0], pool_p, {"full": args[1]}, args[2], args[3],
            cfg, kernel="pallas")
        max_err = max(max_err, float(jnp.max(jnp.abs(lg_p - lg_g))))
        lengths = lengths + np.where(active, 1, 0).astype(np.int32)
    if max_err > 1e-5:
        raise AssertionError(
            f"pallas vs gather decode max err {max_err}")

    # ---- (b) chat-shaped KV traffic drill: short live contexts inside
    # wide max_len reservations — exactly where the dense gather
    # over-reads. The loop books BOTH lane figures every dispatch, so
    # the gather lane (CPU smoke) measures the identical reduction the
    # kernel lane realizes on-chip.
    n_streams = 8
    loop = DecodeLoop(params, cfg, slots=n_streams, page_size=ps,
                      horizon=4)
    prompts = [rng.randint(0, cfg.vocab_size,
                           (int(rng.choice([8, 16])),)).astype(np.int32)
               for _ in range(n_streams)]
    streams = [loop.submit(p, 16) for p in prompts]
    for s in streams:
        s.result(240)
    snap = loop.snapshot()
    loop.close()
    kv = snap["decode_kernel"]["kv_read_bytes"]
    reduction = kv["gather"] / kv["kernel"]
    return {"value": round(reduction, 2), "unit": "x_kv_read_reduction",
            "gate_4x": bool(reduction >= 4.0),
            "parity_max_err": round(max_err, 9),
            "parity_steps": steps,
            "kernel_read_bytes": kv["kernel"],
            "gather_read_bytes": kv["gather"],
            "path_selected": snap["decode_kernel"]["selected"],
            "auto_resolves_to": resolve_decode_kernel("auto", cfg, ps),
            "interpret_parity": fast,
            "tokens_per_sec": None if fast else "tpu_lane",
            "compiled_on": jax.devices()[0].platform,
            "n_streams": n_streams, "page_size": ps,
            "pages_per_slot": pages_per_slot(cfg, ps)}


def bench_warmup():
    """AOT warm start (docs/WARMUP.md): spawn `cli serve
    --compile-cache DIR --warmup-plan auto` replica processes against
    ONE cache directory — cold (empty cache: compile + persist + record
    the plan) then warm (plan replay: AOT loads, zero compiles) — and
    gate the subsystem's contract:

    - warm warmup_seconds (the /readyz-gating phase: socket-open to
      ready) >= 3x faster than cold;
    - warm boot reports recompiled_after_warmup == 0 on /stats with
      cache hits scraped LIVE off /metrics;
    - chaos leg: a replica with compile.cache_read faulted at every
      ordinal still reaches ready and serves correct predictions
      (cold-compile fallback, zero request errors);
    - trainer leg: cold-vs-warm first `fit()` wall in fresh
      subprocesses riding the same store.

    Spawn-to-ready wall is recorded too, but the gate rides the warmup
    phase: interpreter + jax import (identical both ways) would
    otherwise drown the signal on the CPU smoke."""
    import json as _json
    import shutil
    import tempfile
    import urllib.request

    from deeplearning4j_tpu.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.scaleout.checkpoint import DefaultModelSaver
    from deeplearning4j_tpu.serving.fleet import ReplicaSpawner
    from deeplearning4j_tpu.testing import chaos

    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(16).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(1).use_adagrad(False)
            .list(2).hidden_layer_sizes([32])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=4)
            .pretrain(False).build())
    work = tempfile.mkdtemp(prefix="dl4j_bench_warmup_")
    ckpt = os.path.join(work, "warm.ckpt")
    cache = os.path.join(work, "compile_cache")
    DefaultModelSaver(ckpt, keep_old=False).save(MultiLayerNetwork(conf))
    body = _json.dumps(
        {"inputs": np.random.RandomState(0).rand(4, 16).tolist()}
    ).encode()

    def _get(url, timeout=10):
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()

    def boot(extra_env=None):
        """Spawn one replica; returns its measurements and kills it."""
        env = dict(os.environ)
        if extra_env:
            env.update(extra_env)
        spawner = ReplicaSpawner(
            ckpt, env=env,
            serve_args=["--compile-cache", cache, "--warmup-plan",
                        "auto", "--max-delay-ms", "1"])
        t0 = time.perf_counter()
        proc, url = spawner.spawn()
        try:
            ready = None
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                try:
                    status, raw = _get(url + "/readyz", timeout=5)
                    if status == 200:
                        ready = _json.loads(raw)
                        break
                except Exception:  # noqa: BLE001 — 503 until warm
                    pass
                time.sleep(0.05)
            wall = time.perf_counter() - t0
            if ready is None:
                raise RuntimeError("replica never became ready")
            errors = 0
            for _ in range(8):
                try:
                    req = urllib.request.Request(
                        url + "/predict", data=body,
                        headers={"Content-Type": "application/json"})
                    with urllib.request.urlopen(req, timeout=30) as r:
                        out = _json.loads(r.read())
                    if len(out["outputs"]) != 4:
                        errors += 1
                except Exception:  # noqa: BLE001
                    errors += 1
            _, stats_raw = _get(url + "/stats", timeout=30)
            stats = _json.loads(stats_raw)
            _, metrics_raw = _get(url + "/metrics", timeout=30)
            scraped = {}
            for line in metrics_raw.decode().splitlines():
                for name in ("dl4j_compile_cache_hits_total",
                             "dl4j_compile_cache_misses_total"):
                    if line.startswith(name + " "):
                        scraped[name] = float(line.split()[-1])
            return {"spawn_to_ready_s": round(wall, 3),
                    "warmup_s": ready.get("warmup_seconds"),
                    "warmup": stats.get("warmup"),
                    "compile_cache": stats.get("compile_cache"),
                    "metrics": scraped,
                    "predict_errors": errors}
        finally:
            proc.kill()
            proc.wait(timeout=30)

    try:
        cold = boot()
        warm = boot()
        chaotic = boot(chaos.env_spec(
            [chaos.Rule("compile.cache_read", "error")], seed=0))

        ratio = (cold["warmup_s"] / warm["warmup_s"]
                 if cold["warmup_s"] and warm["warmup_s"] else None)
        warm_hits = warm["metrics"].get(
            "dl4j_compile_cache_hits_total", 0.0)
        recompiled = (warm.get("warmup") or {}).get(
            "recompiled_after_warmup")

        # trainer leg: first fit() in a fresh process, cold vs warm
        train_cache = os.path.join(work, "train_cache")
        script = (
            "import sys,time,numpy as np\n"
            "from deeplearning4j_tpu import compilecache as cc\n"
            "from deeplearning4j_tpu.config import "
            "NeuralNetConfiguration\n"
            "from deeplearning4j_tpu.nn.multilayer import "
            "MultiLayerNetwork\n"
            "conf=(NeuralNetConfiguration.builder().lr(0.1).n_in(16)"
            ".activation_function('tanh')"
            ".optimization_algo('iteration_gradient_descent')"
            ".num_iterations(1).use_adagrad(False).list(2)"
            ".hidden_layer_sizes([32])"
            ".override(1,layer='output',loss_function='mcxent',"
            "activation_function='softmax',n_out=4)"
            ".pretrain(False).build())\n"
            "cc.activate(sys.argv[1])\n"
            "x=np.random.RandomState(0).rand(32,16).astype('float32')\n"
            "y=np.eye(4,dtype='float32')"
            "[np.random.RandomState(1).randint(0,4,32)]\n"
            "t0=time.perf_counter()\n"
            "MultiLayerNetwork(conf).fit(x,y,epochs=1)\n"
            "print('FIT_S', time.perf_counter()-t0)\n"
            "print('HITS', cc.stats()['hits'])\n")

        def run_fit():
            import sys

            env = dict(os.environ)
            env["PYTHONPATH"] = HERE + os.pathsep + env.get(
                "PYTHONPATH", "")
            out = subprocess.run(
                [sys.executable, "-c", script, train_cache],
                capture_output=True, text=True, timeout=300, env=env)
            vals = dict(line.split() for line in out.stdout.splitlines()
                        if line.startswith(("FIT_S", "HITS")))
            return float(vals["FIT_S"]), int(vals["HITS"])

        fit_cold_s, _ = run_fit()
        fit_warm_s, fit_warm_hits = run_fit()

        return {
            "value": round(ratio, 2) if ratio else None,
            "unit": "x_warmup_speedup",
            "gate_3x": bool(ratio and ratio >= 3.0),
            "gate_zero_recompiles": recompiled == 0,
            "gate_live_hits": bool(warm_hits >= 1),
            "gate_chaos_clean": bool(
                chaotic["predict_errors"] == 0),
            "cold": cold, "warm": warm, "chaos": chaotic,
            "trainer": {"cold_fit_s": round(fit_cold_s, 3),
                        "warm_fit_s": round(fit_warm_s, 3),
                        "warm_hits": fit_warm_hits},
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)


CONFIGS = {
    "mlp": bench_mlp,
    "feed": bench_feed,
    "guardian": bench_guardian,
    "serve": bench_serve,
    "prefix_cache": bench_prefix_cache,
    "speculative": bench_speculative,
    "fleet": bench_fleet,
    "chaos": bench_chaos,
    "warmup": bench_warmup,
    "stream_failover": bench_stream_failover,
    "fleet_prefix": bench_fleet_prefix,
    "disagg": bench_disagg,
    "slo_tiers": bench_slo_tiers,
    "train_elastic": bench_train_elastic,
    "controlplane": bench_controlplane,
    "pipeline": bench_pipeline,
    "checkpoint": bench_checkpoint,
    "telemetry": bench_telemetry,
    "lenet": bench_lenet,
    "dbn": bench_dbn,
    "word2vec": bench_word2vec,
    "glove": bench_glove,
    "flash": bench_flash,
    "flash_bwd": bench_flash_bwd,
    "paged_kernel": bench_paged_kernel,
}

METRIC_NAMES = {
    "mlp": "mlp_mnist_train_samples_per_sec_per_chip",
    "feed": "device_feed_ragged_stream_steps_per_sec",
    "guardian": "guardian_guarded_step_time_ms",
    "serve": "serving_decode_tokens_per_sec_cached",
    "prefix_cache": "serving_prefix_cache_prefill_token_reduction",
    "speculative": "serving_speculative_tokens_per_dispatch_speedup",
    "fleet": "fleet_predict_rows_per_sec_4_replicas",
    "chaos": "chaos_sigstop_breaker_eviction_s",
    "warmup": "serving_warm_boot_warmup_speedup",
    "stream_failover": "serving_stream_failover_p99_ttnt_ms",
    "fleet_prefix": "fleet_prefix_prefill_token_reduction",
    "disagg": "serving_disagg_decode_p99_under_prefill_storm_ms",
    "slo_tiers": "serving_interactive_p99_under_batch_flood_ms",
    "train_elastic": "train_elastic_kill_recovery_s",
    "controlplane": "controlplane_router_restart_recovery_s",
    "pipeline": "pipeline_commit_to_served_s",
    "checkpoint": "checkpoint_async_save_stall_ms",
    "telemetry": "telemetry_instrumented_step_time_ms",
    "lenet": "lenet_mnist_step_time_ms",
    "dbn": "dbn_pretrain_finetune_samples_per_sec_per_chip",
    "word2vec": "word2vec_skipgram_pairs_per_sec",
    "glove": "glove_training_triples_per_sec",
    "flash": "flash_attention_causal_step_time_ms",
    "flash_bwd": "flash_attention_grad_step_time_ms",
    "paged_kernel": "serving_decode_kv_read_bytes_reduction",
}


# ----------------------------------------------------------------- history
def _load_history():
    try:
        with open(HIST_PATH) as f:
            hist = json.load(f)
    except (OSError, ValueError):
        hist = {}
    if hist.get("protocol") != PROTOCOL:
        # protocol change invalidates every pin: archive, start fresh
        hist = {"protocol": PROTOCOL,
                "baselines": {},
                "baselines_v1": hist.get("baselines", {}),
                "runs": hist.get("runs", [])[-20:]}
    if any(not isinstance(v, dict)
           for v in hist.get("baselines", {}).values()):
        hist["baselines"] = {}  # migrate flat pins (pre-platform-scoping)
    return hist


def _write_history(hist) -> None:
    try:
        with open(HIST_PATH, "w") as f:
            json.dump(hist, f, indent=1)
    except OSError:
        pass


def _summary_line(results, platform: str) -> str:
    primary_name = "mlp" if "mlp" in results else next(iter(results), None)
    primary = results.get(primary_name, {})
    summary = {}
    if platform != "tpu":
        summary["not_a_device_measurement"] = (
            f"platform={platform}: smoke-sized workloads; values are "
            "counts and host timings, not device metrics")
    summary.update({
        "metric": METRIC_NAMES.get(primary_name, primary_name or "none"),
        "value": primary.get("value"),
        "unit": primary.get("unit"),
        # null (not 1.0) when the primary config errored or was skipped —
        # a neutral ratio for a missing measurement would mislead gating
        "vs_baseline": primary.get("vs_baseline"),
        "protocol": PROTOCOL,
        "extra": {k: v for k, v in results.items() if k != primary_name},
    })
    for key in ("error", "skipped"):  # surface WHY the primary is null
        if key in primary:
            summary[key] = primary[key]
    return json.dumps(summary)


#: configs that start `cli serve` / worker child processes. Each child
#: needs the chip, and this process holds it once it has touched JAX.
SPAWNS_CHILDREN = frozenset({
    "fleet", "chaos", "warmup", "stream_failover", "fleet_prefix",
    "disagg", "slo_tiers", "train_elastic", "controlplane", "pipeline"})


def main() -> int:
    from deeplearning4j_tpu.utils import jaxenv

    jaxenv.configure()
    import jax

    platform = jax.devices()[0].platform
    chip_held = platform == "tpu"
    selected = os.environ.get("BENCH_CONFIGS")
    names = ([n.strip() for n in selected.split(",") if n.strip()]
             if selected else
             [n for n in CONFIGS
              if not (chip_held and n in SPAWNS_CHILDREN)])
    budget = float(os.environ.get("BENCH_BUDGET_S", "720"))
    # 720 s: a bad-weather full run measured 523 s of work — a 480 s
    # budget would have skipped the flash configs it was protecting

    hist = _load_history()
    run_entry = {"ts": time.time(), "protocol": PROTOCOL,
                 "platform": platform, "results": {}}
    try:
        run_entry["commit"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, cwd=HERE).stdout.strip()
    except OSError:
        run_entry["commit"] = ""
    hist["runs"].append(run_entry)
    hist["runs"] = hist["runs"][-50:]

    start = time.monotonic()
    results = {}
    for name in names:
        if results and time.monotonic() - start > budget:
            results[name] = {"skipped": f"BENCH_BUDGET_S={budget:g} spent"}
            run_entry["results"][name] = results[name]
            _write_history(hist)
            print(_summary_line(results, platform), flush=True)
            continue
        try:
            if chip_held and name in SPAWNS_CHILDREN:
                raise RuntimeError(
                    "spawns child processes that need the chip this "
                    "process already holds — not runnable from "
                    "bench.py on a TPU (ROADMAP S0/D4)")
            res = CONFIGS[name]()
        except Exception as e:  # a broken config must not hide the others
            res = {"error": f"{type(e).__name__}: {e}"}
        if res.get("value") is not None:
            # pins are per-platform: a CPU smoke run must never pin (or be
            # compared against) the TPU baselines the driver records
            pins = hist["baselines"].setdefault(platform, {})
            base = pins.get(name)
            if base is None:
                pins[name] = res["value"]
                base = res["value"]
            ratio = res["value"] / base
            if res.get("lower_is_better"):
                ratio = base / res["value"]
            res["vs_baseline"] = round(ratio, 4)
            # between-process spread recorded at pin time (BASELINE.md):
            # a vs_baseline inside the pin's spread band is run-to-run
            # noise, not signal
            spread = hist.get("pin_info", {}).get("spread", {}).get(name)
            if spread and platform == "tpu":
                res["pin_spread"] = spread
        results[name] = res
        run_entry["results"][name] = res
        _write_history(hist)
        print(_summary_line(results, platform), flush=True)
    return 1 if any("error" in r for r in results.values()) else 0


if __name__ == "__main__":
    sys.exit(main())
