"""InferenceEngine: compile-once-per-bucket forward for serving.

The training side already learned this lesson (datasets/device_feed.py):
a jitted program re-specializes per input shape, so ragged traffic must
be padded onto a small bucket ladder. An engine owns ONE jitted apply
function and the bucket ladder for its model; every request pads up to
the smallest bucket that holds it and slices the padding back off the
result. Since the forward is per-row independent (no cross-example
reductions at inference), padded rows never touch real outputs — no
mask needed, unlike the training loss.

The request input buffer is donated to the jitted call (it is freshly
device_put per request, so XLA reuses its HBM for the activations);
params are NOT donated — they serve every request.

Observability is first-class (`EngineStats`): requests, rows, batch
occupancy, p50/p99 wall latency (each timed window ends with the D2H
read of the result, so it covers the device work), and the
program-cache counter that pins "ragged stream compiles <= one program
per bucket" in tests and bench.
"""

from __future__ import annotations

import itertools
import time
from typing import Callable, Optional, Sequence

import numpy as np

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.datasets.device_feed import (DEFAULT_MIN_BUCKET,
                                                     bucket_for,
                                                     pow2_buckets)
from deeplearning4j_tpu.telemetry.trace import span
from deeplearning4j_tpu.utils.jitcache import jit_cache_size

__all__ = ["EngineStats", "InferenceEngine"]

_engine_seq = itertools.count()


class EngineStats:
    """Per-engine serving stats as a VIEW over the telemetry registry.

    Historically this class kept its own lock-and-dict counters in
    parallel with everything else's; now each engine owns a labeled set
    of registry series (`dl4j_serve_*{engine=...}`) and this object is
    just the typed accessor — the same numbers appear in `/metrics`, in
    `/stats`, and here, with no second code path. Latency percentiles
    come from the histogram's bounded reservoir; each timed window ends
    with the D2H read of the result, so it covers the device work.
    Note `telemetry.set_enabled(False)` blanks recording
    here too — the registry IS the storage.
    """

    def __init__(self, window: int = 2048, label: Optional[str] = None,
                 registry=None):
        reg = registry if registry is not None else telemetry.get_registry()
        self.label = label if label is not None else f"e{next(_engine_seq)}"
        lab = {"engine": self.label}
        self._requests = reg.counter(
            "dl4j_serve_requests", "inference requests served").labels(**lab)
        self._rows = reg.counter(
            "dl4j_serve_rows", "real request rows served").labels(**lab)
        self._padded = reg.counter(
            "dl4j_serve_padded_rows",
            "bucket-padding rows shipped alongside real rows").labels(**lab)
        self._errors = reg.counter(
            "dl4j_serve_errors", "failed inference requests").labels(**lab)
        self._latency = reg.histogram(
            "dl4j_serve_latency_seconds",
            "per-request wall latency incl. the result D2H read",
            window=window).labels(**lab)
        self._bucket_fam = reg.counter(
            "dl4j_serve_bucket_forwards",
            "compiled-bucket forwards by bucket size")
        # memoized per-bucket children: labels() takes the family lock
        # shared across ALL engines — not a per-request cost
        self._bucket_children: dict = {}

    # typed accessors (the historical attribute surface)
    @property
    def requests(self) -> int:
        return int(self._requests.value)

    @property
    def rows(self) -> int:
        return int(self._rows.value)

    @property
    def padded_rows(self) -> int:
        return int(self._padded.value)

    @property
    def errors(self) -> int:
        return int(self._errors.value)

    def record(self, rows: int, bucket: int, seconds: float) -> None:
        self._requests.inc()
        self._rows.inc(rows)
        self._padded.inc(bucket - rows)
        self._latency.observe(seconds)
        child = self._bucket_children.get(bucket)
        if child is None:  # benign race: labels() is get-or-create
            child = self._bucket_fam.labels(engine=self.label,
                                            bucket=str(bucket))
            self._bucket_children[bucket] = child
        child.inc()

    def record_error(self) -> None:
        self._errors.inc()

    def bucket_forwards(self) -> dict:
        """{bucket_size: forward_count} for this engine."""
        out = {}
        for labels, child in self._bucket_fam.children():
            if labels.get("engine") == self.label:
                out[int(labels["bucket"])] = int(child.value)
        return out

    def snapshot(self) -> dict:
        rows, padded = self.rows, self.padded_rows
        shipped = rows + padded
        return {
            "requests": self.requests,
            "rows": rows,
            "padded_rows": padded,
            "errors": self.errors,
            # fraction of shipped rows that were real work
            "occupancy": (rows / shipped) if shipped else 0.0,
            "latency_p50_ms": round(self._latency.percentile(0.50) * 1e3, 3),
            "latency_p99_ms": round(self._latency.percentile(0.99) * 1e3, 3),
            "bucket_forwards": self.bucket_forwards(),
        }


class InferenceEngine:
    """A jitted, bucket-padded forward for one model on one device.

    `apply_fn(params, x)` must be a pure per-row forward; `x`'s leading
    dim is the batch. Construct via the classmethods for the stock
    model families, or directly for anything functional.
    """

    def __init__(self, apply_fn: Callable, params, *,
                 max_batch_size: int = 64,
                 buckets: Optional[Sequence[int]] = None,
                 min_bucket: int = DEFAULT_MIN_BUCKET,
                 device=None,
                 generate_fn: Optional[Callable] = None,
                 cache_key: Optional[str] = None):
        import jax

        from deeplearning4j_tpu import compilecache

        if buckets is None:
            buckets = pow2_buckets(max_batch_size, min_bucket=min_bucket)
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"buckets must be positive, got {buckets}")
        self.max_batch_size = int(max_batch_size)
        self.device = device
        self._params = (jax.device_put(params, device)
                        if device is not None else params)
        # donate the request buffer (engine-owned: infer stages through
        # host + device_put, never the caller's array) so its HBM is
        # reused for activations; CPU ignores donation with a warning,
        # so gate it off there
        donate = () if jax.default_backend() == "cpu" else (1,)
        #: model identity for the persistent compile cache
        #: (docs/WARMUP.md); the full program key also pins the device,
        #: because serialized executables are device-bound — replica 3
        #: on cpu:3 must not load replica 0's programs
        dev = device if device is not None else jax.devices()[0]
        self.cache_key = (f"{cache_key}|dev={dev}"
                          if cache_key is not None else None)
        self._jit = compilecache.maybe_wrap(
            jax.jit(apply_fn, donate_argnums=donate), self.cache_key)
        self._generate_fn = generate_fn
        #: continuous-batching slot scheduler (transformer engines;
        #: start_decode_loop) — None until started
        self.decode_loop = None
        self._tf_cfg = None
        #: True once warmup() precompiled every bucket — the readiness
        #: surface (/readyz, docs/FLEET.md) reads it
        self.warmed_up = False
        #: wall seconds the last warmup()/warmup_from_plan() took (the
        #: cold-vs-warm spin-up number /stats and bench.py warmup pin)
        self.warmup_seconds: Optional[float] = None
        #: feature shape + dtype the engine warms with, captured from
        #: warmup() or the first infer() — what plan_fragment() records
        self._warm_shape: Optional[tuple] = None
        #: checkpoint identity this engine serves ({path, step} or None
        #: for constructor-installed params) — recorded by load_params,
        #: surfaced through /readyz and /stats so the deployment
        #: controller can verify a promotion landed (docs/PIPELINE.md)
        self.checkpoint: Optional[dict] = None
        #: identity of the speculative draft model's checkpoint, when
        #: one was hot-loaded via load_draft_params (None otherwise)
        self.draft_checkpoint: Optional[dict] = None
        self.stats = EngineStats()
        from deeplearning4j_tpu.telemetry import device as _tdev
        _tdev.watch_jit_cache("serving_engine", self.program_cache_size)

    # ----------------------------------------------------- constructors
    @classmethod
    def for_network(cls, net, **kw) -> "InferenceEngine":
        """Wrap a MultiLayerNetwork: apply = output-layer activations
        (the bucketed twin of `net.output`)."""
        from deeplearning4j_tpu.compilecache import config_digest

        kw.setdefault("cache_key",
                      "serve.net:" + config_digest(net.to_json()))
        return cls(lambda p, x: net.feed_forward_fn(p, x)[-1],
                   net.param_table, **kw)

    @classmethod
    def for_transformer(cls, params, cfg, *, decode_slots: int = 0,
                        page_size: int = 16,
                        kv_pages: Optional[int] = None,
                        max_waiting: Optional[int] = None,
                        prefix_cache: bool = True,
                        decode_kernel: str = "auto",
                        horizon: int = 1,
                        speculation: int = 0,
                        drafter: str = "ngram",
                        draft_params=None, draft_cfg=None,
                        draft_window: int = 32,
                        batch_share: float = 0.5,
                        batch_max_waiting: Optional[int] = None,
                        **kw) -> "InferenceEngine":
        """Wrap a transformer LM: apply = full logits (B, T, vocab);
        `generate()` runs the per-request KV-cached compiled scan.
        `decode_slots > 0` additionally starts the continuous-batching
        `DecodeLoop` (paged KV pool, `generate_stream()`); pass
        `page_size`/`kv_pages` to size the pool, `max_waiting` to
        bound its admission queue, `prefix_cache=False` to disable
        cross-request KV prefix sharing, and `decode_kernel` to pick
        the decode attention lane ("auto" = the Pallas paged kernel on
        TPU, dense gather elsewhere — docs/SERVING.md). `horizon > 1`
        chains K decode steps per dispatch; `speculation = k > 0`
        instead turns on draft-and-verify speculative decoding with
        the chosen `drafter` flavor ("ngram", or "model" with
        `draft_params`/`draft_cfg` — docs/SERVING.md "Speculative
        decoding")."""
        from deeplearning4j_tpu.compilecache import config_digest
        from deeplearning4j_tpu.models.transformer import transformer_logits
        from deeplearning4j_tpu.serving.kv_cache import generate_cached

        kw.setdefault("cache_key", "serve.tf:" + config_digest(cfg))
        eng = cls(lambda p, tok: transformer_logits(p, tok, cfg), params,
                  generate_fn=lambda p, prompt, n: generate_cached(
                      p, prompt, cfg, n),
                  **kw)
        eng._tf_cfg = cfg
        if decode_slots:
            eng.start_decode_loop(slots=decode_slots, page_size=page_size,
                                  n_pages=kv_pages,
                                  max_waiting=max_waiting,
                                  prefix_cache=prefix_cache,
                                  kernel=decode_kernel,
                                  horizon=horizon,
                                  speculation=speculation,
                                  drafter=drafter,
                                  draft_params=draft_params,
                                  draft_cfg=draft_cfg,
                                  draft_window=draft_window,
                                  batch_share=batch_share,
                                  batch_max_waiting=batch_max_waiting)
        return eng

    @classmethod
    def for_moe_transformer(cls, params, cfg, *, decode_slots: int = 0,
                            page_size: int = 16,
                            kv_pages: Optional[int] = None,
                            window_pages: Optional[int] = None,
                            prefill_tokens_per_pass: Optional[int] = None,
                            max_waiting: Optional[int] = None,
                            decode_kernel: str = "auto",
                            **kw) -> "InferenceEngine":
        """Wrap a model of `models/moe_transformer.py` (grouped K/V
        heads, window and full layers, an expert layer of which this
        chip holds a part): apply = full logits (B, T, vocab) with
        nothing cached; `decode_slots > 0` starts the `DecodeLoop`, which
        keeps pages by kind of layer (`kv_pages` for the full kind,
        `window_pages` for the window kind), and `generate_stream()`.
        Prefix sharing, speculation and a horizon above 1 are refused
        by name for what this model has and stay off; this engine has
        no per-request `generate()`."""
        from deeplearning4j_tpu.compilecache import config_digest
        from deeplearning4j_tpu.models import moe_transformer

        cfg.check()
        kw.setdefault("cache_key", "serve.moe:" + config_digest(cfg))
        eng = cls(lambda p, tok: moe_transformer.logits(p, tok, cfg),
                  params, **kw)
        eng._tf_cfg = cfg
        if decode_slots:
            eng.start_decode_loop(
                slots=decode_slots, page_size=page_size, n_pages=kv_pages,
                window_pages=window_pages,
                prefill_tokens_per_pass=prefill_tokens_per_pass,
                max_waiting=max_waiting, prefix_cache=False,
                kernel=decode_kernel)
        return eng

    @classmethod
    def for_hybrid_transformer(cls, params, cfg, *, decode_slots: int = 0,
                               page_size: int = 16,
                               kv_pages: Optional[int] = None,
                               prefill_tokens_per_pass: Optional[int] = None,
                               max_waiting: Optional[int] = None,
                               decode_kernel: str = "auto",
                               **kw) -> "InferenceEngine":
        """Wrap a model of `models/hybrid_transformer.py` (linear layers
        that keep a state a sequence beside full layers that keep K/V,
        an expert layer of which this chip holds a part): apply = full
        logits (B, T, vocab) with nothing cached; `decode_slots > 0`
        starts the `DecodeLoop`, whose cache holds `kv_pages` pages for
        the full kind and a state a slot for the linear kind. What
        counts on page reuse is refused by name and stays off; this
        engine has no per-request `generate()`."""
        from deeplearning4j_tpu.compilecache import config_digest
        from deeplearning4j_tpu.models import hybrid_transformer

        cfg.check()
        kw.setdefault("cache_key", "serve.hybrid:" + config_digest(cfg))
        eng = cls(lambda p, tok: hybrid_transformer.logits(p, tok, cfg),
                  params, **kw)
        eng._tf_cfg = cfg
        if decode_slots:
            eng.start_decode_loop(
                slots=decode_slots, page_size=page_size, n_pages=kv_pages,
                prefill_tokens_per_pass=prefill_tokens_per_pass,
                max_waiting=max_waiting, prefix_cache=False,
                kernel=decode_kernel)
        return eng

    @classmethod
    def for_lstm(cls, layer, params, **kw) -> "InferenceEngine":
        """Wrap an LSTM layer: apply = per-timestep decoded outputs over
        (B, T, n_in) input."""
        return cls(lambda p, x: layer.activate(p, x), params, **kw)

    # ------------------------------------------------------------ serve
    def infer(self, x) -> np.ndarray:
        """One request: (n, ...) -> np.ndarray of the first n output
        rows. Pads n up to the bucket ladder (requests beyond the top
        bucket take the pow2 escape ladder, still bounding program
        count), runs the compiled forward, slices the padding off. The
        returned array is host-resident — the D2H read is inside the
        latency window.

        Input is staged through the host (np.asarray + device_put), so
        the device buffer handed to the donated jit arg is always
        engine-owned — a caller's device array is never invalidated."""
        import jax

        x = np.asarray(x)
        if x.ndim < 2:
            raise ValueError(
                f"infer expects a (n, ...) batch, got shape {x.shape}")
        n = int(x.shape[0])
        if n == 0:
            raise ValueError("empty request")
        if self._warm_shape is None:
            self._warm_shape = (tuple(int(d) for d in x.shape[1:]),
                                x.dtype.str)
        start = time.perf_counter()
        try:
            with span("engine_infer", rows=n):
                b = bucket_for(n, self.buckets)
                if b != n:  # pad on host — the H2D copy ships once
                    x = np.concatenate(
                        [x, np.zeros((b - n, *x.shape[1:]), x.dtype)])
                xb = jax.device_put(x, self.device)
                out = np.asarray(self._jit(self._params, xb)[:n])
        except Exception:
            self.stats.record_error()
            raise
        self.stats.record(n, b, time.perf_counter() - start)
        return out

    def generate(self, prompt, n_tokens: int) -> np.ndarray:
        """KV-cached greedy decode (transformer engines only):
        prompt (B, T0) int tokens -> (B, T0 + n_tokens)."""
        import jax.numpy as jnp

        if self._generate_fn is None:
            raise ValueError(
                "this engine has no generate path (construct it with "
                "InferenceEngine.for_transformer)")
        prompt = jnp.asarray(prompt, jnp.int32)
        if prompt.ndim != 2:
            raise ValueError(
                f"prompt must be (B, T0) tokens, got shape {prompt.shape}")
        start = time.perf_counter()
        try:
            out = np.asarray(
                self._generate_fn(self._params, prompt, int(n_tokens)))
        except Exception:
            self.stats.record_error()
            raise
        self.stats.record(int(prompt.shape[0]), int(prompt.shape[0]),
                          time.perf_counter() - start)
        return out

    # ------------------------------------------- continuous batching
    def start_decode_loop(self, slots: int = 8, page_size: int = 16,
                          n_pages: Optional[int] = None,
                          horizon: int = 1,
                          max_waiting: Optional[int] = None,
                          prefix_cache: bool = True,
                          fleet_kv: str = "on",
                          kv_ship_timeout: float = 2.0,
                          kernel: str = "auto",
                          speculation: int = 0,
                          drafter: str = "ngram",
                          draft_params=None, draft_cfg=None,
                          draft_window: int = 32,
                          batch_share: float = 0.5,
                          batch_max_waiting: Optional[int] = None,
                          role: str = "unified",
                          window_pages: Optional[int] = None,
                          prefill_tokens_per_pass: Optional[int] = None):
        """Start the continuous-batching slot scheduler
        (serving/decode_loop.py) for this transformer engine: S slots
        over a paged KV pool riding ONE compiled decode step. `/generate`
        traffic routes here instead of the per-request compiled-scan
        path — requests join/leave at token boundaries and KV memory
        scales with written tokens. `kernel` picks the decode attention
        lane ("auto"|"pallas"|"gather", docs/SERVING.md);
        `speculation = k` turns on draft-and-verify with the chosen
        `drafter` ("ngram"|"model"). `batch_share`/`batch_max_waiting`
        tune the batch SLO tier's weighted-fair slot share and its
        (lower) admission-queue bound (docs/SERVING.md "Priority
        tiers"). `prefill_tokens_per_pass` bounds what one scheduler
        pass prefills; `window_pages` sizes the window kind's pool of a
        model with window layers (docs/SERVING.md "Kinds of layer")."""
        from deeplearning4j_tpu.serving.decode_loop import DecodeLoop

        if self._tf_cfg is None:
            raise ValueError(
                "decode loop needs a transformer engine (construct it "
                "with InferenceEngine.for_transformer)")
        if self.decode_loop is not None:
            raise RuntimeError("decode loop already started")
        self.decode_loop = DecodeLoop(self._params, self._tf_cfg,
                                      slots=slots, page_size=page_size,
                                      n_pages=n_pages, horizon=horizon,
                                      max_waiting=max_waiting,
                                      prefix_cache=prefix_cache,
                                      fleet_kv=fleet_kv,
                                      kv_ship_timeout=kv_ship_timeout,
                                      kernel=kernel,
                                      speculation=speculation,
                                      drafter=drafter,
                                      draft_params=draft_params,
                                      draft_cfg=draft_cfg,
                                      draft_window=draft_window,
                                      batch_share=batch_share,
                                      batch_max_waiting=batch_max_waiting,
                                      role=role,
                                      window_pages=window_pages,
                                      prefill_tokens_per_pass=(
                                          prefill_tokens_per_pass))
        return self.decode_loop

    def generate_stream(self, prompt, max_tokens: int,
                        eos_id: Optional[int] = None,
                        speculation: bool = True):
        """Submit one prompt (1-D token sequence) to the slot scheduler;
        returns a `GenerationStream` emitting tokens as they come off
        the chip, terminated by EOS or `max_tokens`. Requires
        `start_decode_loop` (or `decode_slots=` at construction).
        `speculation=False` opts this request out of speculative
        drafting (output is bit-identical either way)."""
        if self.decode_loop is None:
            raise ValueError(
                "this engine has no decode loop (pass decode_slots= to "
                "for_transformer or call start_decode_loop)")
        return self.decode_loop.submit(prompt, max_tokens, eos_id,
                                       speculation=speculation)

    def close(self) -> None:
        """Drain and stop the decode loop (no-op without one)."""
        if self.decode_loop is not None:
            self.decode_loop.close()

    # ------------------------------------------------------- hot reload
    def load_params(self, params, *,
                    checkpoint: Optional[dict] = None) -> None:
        """Swap this engine's weights in place — zero-downtime reload.

        Validates the new tree leaf-for-leaf (structure + shapes, error
        naming the first mismatched leaf) and device_puts it onto the
        engine's device BEFORE the swap, so the visible transition is a
        single reference assignment: requests in flight keep the old
        params they already closed over, later requests see the new ones
        — nothing is dropped and no lock sits on the request path. The
        compiled bucket programs are reused as-is (params are a traced
        argument, so same shapes = same program).

        `checkpoint` records the identity of what was just installed
        ({path, step}); it becomes visible only after the swap, so a
        reader never sees a new identity paired with old weights."""
        import jax

        from deeplearning4j_tpu.checkpoint.restore import validate_like

        validate_like(params, self._params, context="engine reload")
        if self.device is not None:
            params = jax.device_put(params, self.device)
        else:
            import jax.numpy as jnp

            params = jax.tree_util.tree_map(jnp.asarray, params)
        self._params = params  # atomic swap
        if self.decode_loop is not None:
            # same single-reference swap: in-flight decode steps keep
            # the params they closed over, the next step sees new ones
            self.decode_loop.params = params
        self.checkpoint = dict(checkpoint) if checkpoint else None

    def load_draft_params(self, params, *,
                          checkpoint: Optional[dict] = None) -> None:
        """Swap the speculative DRAFT model's weights in place — the
        `/reload {"target": "draft"}` path the deployment pipeline uses
        to canary a new draft model without touching serving weights.
        Requires a decode loop running a model drafter. Same contract
        as `load_params`: leaf-for-leaf validation against the current
        draft tree, then one reference assignment. A bad draft model
        can only cost acceptance rate, never correctness — the target
        verify step still decides every emitted token."""
        from deeplearning4j_tpu.checkpoint.restore import validate_like

        drafter = (None if self.decode_loop is None
                   else self.decode_loop._drafter)
        if drafter is None or drafter.kind != "model":
            raise ValueError(
                "no draft model to reload: the decode loop must be "
                "running with speculation > 0 and drafter='model'")
        validate_like(params, drafter.params, context="draft reload")
        drafter.load_params(params)
        self.draft_checkpoint = dict(checkpoint) if checkpoint else None

    # ---------------------------------------------------- observability
    def warmup(self, feature_shape: Sequence[int],
               dtype=np.float32) -> None:
        """Compile every bucket program up front so the first real
        requests don't pay compile latency. `feature_shape` is one
        example's shape (without the batch dim). Bypasses EngineStats —
        warmup compiles must not pollute the serving p50/p99/occupancy
        the bench and /stats report.

        With a persistent compile cache active, each bucket's program is
        loaded from disk instead of compiled when a prior run left it
        there (the execute below then just runs the loaded program on
        zeros)."""
        import jax

        start = time.perf_counter()
        for b in self.buckets:
            xb = jax.device_put(np.zeros((b, *feature_shape), dtype),
                                self.device)
            np.asarray(self._jit(self._params, xb))
        self.warmup_seconds = time.perf_counter() - start
        self._warm_shape = (tuple(int(d) for d in feature_shape),
                            np.dtype(dtype).str)
        self.warmed_up = True

    # ------------------------------------------------- warmup plans
    def plan_fragment(self) -> Optional[dict]:
        """The "engine" fragment of a warmup plan (docs/WARMUP.md):
        the buckets this engine compiled — ladder plus any pow2 escape
        buckets traffic actually forwarded — and the feature shape to
        build them with. None until a shape is known (no warmup and no
        traffic yet) or when the engine has no cache identity."""
        if self.cache_key is None or self._warm_shape is None:
            return None
        shape, dtype = self._warm_shape
        buckets = set(self.buckets) | set(self.stats.bucket_forwards())
        return {"cache_key": self.cache_key,
                "buckets": sorted(int(b) for b in buckets),
                "feature_shape": list(shape),
                "dtype": dtype}

    def warmup_from_plan(self, frag: dict) -> None:
        """Replay a recorded plan fragment: AOT load-or-compile every
        bucket program listed, WITHOUT executing anything (pure
        `lower().compile()` / deserialize via the persistent cache).
        Falls back to the standard execute-zeros warmup when the engine
        is not cache-wrapped or the fragment was recorded for a
        different model identity."""
        import jax

        shape = tuple(int(d) for d in frag.get("feature_shape", ()))
        dtype = np.dtype(frag.get("dtype", "float32"))
        if (frag.get("cache_key") != self.cache_key
                or not hasattr(self._jit, "warm")):
            self.warmup(shape, dtype)
            return
        start = time.perf_counter()
        sds = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype)
        params_spec = jax.tree_util.tree_map(sds, self._params)
        for b in frag.get("buckets", self.buckets):
            self._jit.warm(params_spec,
                           jax.ShapeDtypeStruct((int(b), *shape), dtype))
        self.warmup_seconds = time.perf_counter() - start
        self._warm_shape = (shape, dtype.str)
        self.warmed_up = True

    def program_cache_size(self) -> int:
        """Compiled-program count for the jitted forward — the serving
        twin of MultiLayerNetwork.train_step_cache_size(). With bucket
        padding this stays <= len(buckets) hit (+ escape buckets);
        -1 when the private jax counter API drifted."""
        return jit_cache_size(self._jit)

    def snapshot(self) -> dict:
        snap = self.stats.snapshot()
        snap["buckets"] = list(self.buckets)
        snap["compiled_programs"] = self.program_cache_size()
        if self.warmup_seconds is not None:
            snap["warmup_seconds"] = round(self.warmup_seconds, 4)
        snap["checkpoint"] = self.checkpoint
        if self.draft_checkpoint is not None:
            snap["draft_checkpoint"] = self.draft_checkpoint
        if self.device is not None:
            snap["device"] = str(self.device)
        if self.decode_loop is not None:
            snap["decode"] = self.decode_loop.snapshot()
        return snap
