"""The stacked network.

Parity: reference core/nn/multilayer/MultiLayerNetwork.java (1,596 LoC) —
init with nIn/nOut inference (:331-386), layer-wise `pretrain` (:142/:195),
`feedForward` (:457), `fit` (:1021/:1136), `finetune` (:1044), `output`/
`predict` (:1197/:1107), `score` (:1265), flat param pack/unpack
(params :784, setParameters :1420, pack :831, unPack :920), and the
parameter-averaging `merge` (:1361).

TPU-native design: parameters are a pytree ({layer index -> named-param
table}); forward/loss are pure functions of (params, batch, rng) so the
whole training step jits into one XLA program per config. The reference's
three hand-written backprop variants (computeDeltas/computeDeltas2/
computeDeltasR) are replaced by jax.grad / jax.jvp on the same loss.
"""

from __future__ import annotations

import logging
import time
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from deeplearning4j_tpu.config.multi_layer_configuration import MultiLayerConfiguration
from deeplearning4j_tpu.datasets.device_feed import (DEFAULT_MIN_BUCKET,
                                                     DeviceFeed, bucket_for,
                                                     feed_mask, pad_rows)
from deeplearning4j_tpu.nn.api import merge_params
from deeplearning4j_tpu.nn.layers import make_layer
from deeplearning4j_tpu.optimize.guardian import (GuardianAbort,
                                                  guarded_update, make_guard)
from deeplearning4j_tpu.optimize.solver import Solver
from deeplearning4j_tpu.optimize.updater import NetworkGradientUpdater
from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.telemetry.trace import span
from deeplearning4j_tpu.testing import chaos
from deeplearning4j_tpu.utils.jitcache import jit_cache_size
from deeplearning4j_tpu.utils.sanitize import validate_batch

log = logging.getLogger(__name__)

# telemetry (docs/OBSERVABILITY.md): host-side counters only — nothing
# here syncs a device value, so the training math is bit-identical with
# telemetry on or off. Loss is gauged only where a float(score) host
# sync already exists (listener dispatch / fit_scan's return).
_M_STEPS = telemetry.counter(
    "dl4j_train_steps", "supervised train steps dispatched")
_M_EXAMPLES = telemetry.counter(
    "dl4j_train_examples", "example rows dispatched (incl. bucket padding)")
_M_EPOCHS = telemetry.counter("dl4j_train_epochs", "training epochs run")
_M_STEP_S = telemetry.histogram(
    "dl4j_train_step_seconds",
    "wall time per train step; source=fit is per-step dispatch wall "
    "time, source=scan is the per-step average of a compiled epoch, "
    "source=parallel is the DP/ZeRO-1/TP trainer dispatch loop, "
    "source=listener is StepTimeListener's listener-to-listener time")
_M_LOSS = telemetry.gauge(
    "dl4j_train_loss", "last host-synced training score")


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration,
                 params: Optional[jnp.ndarray] = None):
        """`params`, if given, is a packed flat vector — the reference's
        canonical checkpoint constructor `MultiLayerNetwork(String confJson,
        INDArray params)` (MultiLayerNetwork.java:91)."""
        self.conf = conf
        self._infer_layer_sizes()
        self.layers = [make_layer(c) for c in conf.confs]
        self._params: Optional[Dict[str, dict]] = None
        self._unravel = None
        self._updater_state = None
        self._train_step = None
        self._train_step_guarded = None
        self._predict_step = None
        self._finetune_solver = None
        self._batch_solver = None
        self._scan_steps: Dict[tuple, object] = {}
        self._pretrain_solvers: Dict[int, Solver] = {}
        self._pending_params = params
        self._iteration_count = 0
        self.listeners: List = []
        self._key = jax.random.PRNGKey(conf.confs[0].seed if conf.confs else 0)
        self.init()
        # recompile counters surface as dl4j_jit_programs{cache=...}
        # (weak-ref'd: watching never extends this network's lifetime)
        from deeplearning4j_tpu.telemetry import device as _tdev
        _tdev.watch_jit_cache("train_step", self.train_step_cache_size)
        _tdev.watch_jit_cache("predict_step", self.predict_step_cache_size)

    # ------------------------------------------------------------- set-up
    def _infer_layer_sizes(self) -> None:
        """nIn/nOut inference from hiddenLayerSizes (reference init:331-386 —
        the reference mutates conf during init; we replicate the inference)."""
        sizes = self.conf.hidden_layer_sizes
        if not sizes:
            return
        confs = self.conf.confs
        if len(confs) != len(sizes) + 1:
            raise ValueError(
                f"hidden_layer_sizes of length {len(sizes)} requires "
                f"{len(sizes) + 1} layer confs, got {len(confs)}")
        n_in0, n_out_last = confs[0].n_in, confs[-1].n_out
        dims = [n_in0, *sizes, n_out_last]
        for i, c in enumerate(confs):
            c.n_in, c.n_out = dims[i], dims[i + 1]

    def init(self) -> None:
        """Initialize parameters (reference MultiLayerNetwork.init :331)."""
        self._key, init_key = jax.random.split(self._key)
        keys = jax.random.split(init_key, max(1, len(self.layers)))
        self._params = {
            str(i): layer.init_params(k)
            for i, (layer, k) in enumerate(zip(self.layers, keys))
        }
        _, self._unravel = ravel_pytree(self._params)
        self._updater_state = None
        self._train_step = None
        self._train_step_guarded = None
        self._predict_step = None
        self._finetune_solver = None
        self._batch_solver = None
        self._scan_steps = {}
        self._pretrain_solvers = {}
        if self._pending_params is not None:
            self.set_parameters(self._pending_params)
            self._pending_params = None

    def next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def set_listeners(self, listeners: Sequence) -> None:
        self.listeners = list(listeners)

    # ------------------------------------------------------------ forward
    def _layer_input(self, i: int, x, rng=None):
        pp = self.conf.input_preprocessors.get(i)
        return pp(x, rng=rng) if pp is not None else x

    def _layer_output(self, i: int, act, rng=None):
        pp = self.conf.output_preprocessors.get(i)
        return pp(act, rng=rng) if pp is not None else act

    def feed_forward_fn(self, params, x, rng: Optional[jax.Array] = None,
                        training: bool = False) -> List[jnp.ndarray]:
        """Pure feed-forward returning [input, act_0, ..., act_L]
        (reference feedForward :457)."""
        acts = [x]
        cur = x
        n = len(self.layers)
        keys = (jax.random.split(rng, 2 * n) if rng is not None
                else [None] * (2 * n))
        for i, layer in enumerate(self.layers):
            cur = self._layer_input(i, cur, rng=keys[2 * i])
            cur = layer.activate(params[str(i)], cur, rng=keys[2 * i + 1],
                                 training=training)
            cur = self._layer_output(i, cur)
            acts.append(cur)
        return acts

    def loss_fn(self, params, x, labels, rng: Optional[jax.Array] = None,
                training: bool = False, weights=None):
        """Full-network supervised loss: feed-forward into the output layer's
        configured loss (reference score :1265 via OutputLayer.score), plus
        per-layer L2 (the reference applies L2 per-variable in
        GradientAdjustment.java:66-113; defining it in the loss keeps every
        solver path — SGD, CG, LBFGS, HF — consistently regularized).

        `weights` (per-example over the batch dim) masks device-feed
        padding rows out of the data loss: zero-weight rows contribute
        zero loss/gradient and the mean divides by the real count, so
        shape bucketing never changes the math. None (the default) is the
        historical unweighted path, bit-identical to before."""
        n = len(self.layers)
        keys = (jax.random.split(rng, 2 * n) if rng is not None
                else [None] * (2 * n))
        cur = x
        for i, layer in enumerate(self.layers[:-1]):
            cur = self._layer_input(i, cur, rng=keys[2 * i])
            cur = layer.activate(params[str(i)], cur, rng=keys[2 * i + 1],
                                 training=training)
            cur = self._layer_output(i, cur)
        cur = self._layer_input(n - 1, cur, rng=keys[2 * n - 2])
        score = self.layers[-1].loss(params[str(n - 1)], cur, labels,
                                     rng=keys[2 * n - 1],
                                     training=training, weights=weights)
        for i, layer in enumerate(self.layers):
            c = layer.conf
            if c.use_regularization and c.l2 > 0:
                for name, value in params[str(i)].items():
                    if not layer.is_bias(name):
                        score = score + 0.5 * c.l2 * jnp.sum(jnp.square(value))
        return score

    # -------------------------------------------------------------- train
    def has_pretrain_layers(self) -> bool:
        return any(hasattr(layer, "pretrain_loss") for layer in self.layers)

    def _iter_batches(self, data):
        """Yield feature arrays from a DataSetIterator or a single array."""
        if hasattr(data, "reset"):
            data.reset()
            for ds in data:
                yield jnp.asarray(ds.features)
        else:
            yield jnp.asarray(data)

    def pretrain(self, data) -> None:
        """Layer-wise unsupervised pretraining (reference pretrain :142/:195):
        feed each batch through the already-trained lower layers, fit each
        pretrain-capable layer (RBM/AE) on the resulting activations.
        `data` is a DataSetIterator or a feature array."""
        for i, layer in enumerate(self.layers[:-1]):
            if not hasattr(layer, "pretrain_loss"):
                continue
            # One solver per layer, cached across pretrain() calls: the
            # batch is a traced argument of the jitted step, so every
            # mini-batch of this layer's phase (and every later pretrain
            # pass) reuses ONE compiled program instead of recompiling
            solver = self._pretrain_solvers.get(i)
            if solver is None:
                _, unravel_i = ravel_pytree(self._params[str(i)])

                def flat_loss(vec, key, batch, *, _l=layer, _u=unravel_i):
                    return _l.pretrain_loss(_u(vec), batch, key)

                solver = Solver(layer.conf, flat_loss,
                                listeners=self.listeners, model=self,
                                rng_key=self.next_key())
                self._pretrain_solvers[i] = solver
            # the optimizer snapshots its listener list; refresh it so
            # set_listeners() calls between fits reach cached solvers
            solver.get_optimizer().listeners = list(self.listeners)
            for x in self._iter_batches(data):
                cur = x
                for j in range(i):
                    cur = self._layer_input(j, cur)
                    cur = self.layers[j].activate(self._params[str(j)], cur)
                    cur = self._layer_output(j, cur)
                cur = self._layer_input(i, cur)
                # sync=False: the returned score stays a device scalar —
                # a per-optimize float() sync would stall the host on the
                # device once per layer-wise pretraining call, and the
                # lazy %s below only materializes it at INFO verbosity
                new_params, score = solver.optimize(
                    self._params[str(i)], cur, rng_key=self.next_key(),
                    sync=False)
                self._params[str(i)] = new_params
                log.info("Pretrained layer %d (score=%s)", i, score)

    def _resolve_feed(self, iterator, device_feed):
        """(feed, raw_source) for an iterator-driven fit."""
        if isinstance(iterator, DeviceFeed):
            return iterator, iterator.source
        if device_feed is False:
            return None, iterator
        return DeviceFeed(iterator), iterator

    def fit(self, x, labels=None, epochs: int = 1,
            device_feed: Optional[bool] = None,
            guardian=None, checkpoint_every: Optional[int] = None,
            saver=None, start_position: int = 0,
            start_epoch: int = 0, start_epoch_batch: int = 0) -> None:
        """Train. Accepts (x, labels) arrays or a DataSetIterator
        (reference fit(DataSet) :1172 / fit(DataSetIterator) :1021).
        Pretraining (if configured) runs ONCE over the data, then the
        supervised phase runs for `epochs`.

        Iterator-driven runs go through the device-feed pipeline by
        default (datasets/device_feed.py): ragged batches are padded to
        shape buckets with the real count threaded into the masked loss,
        so the jitted step compiles once per bucket instead of once per
        batch shape, and H2D transfers prefetch ahead of the step. Pass
        `device_feed=False` for the legacy per-shape path, or pass a
        DeviceFeed instance directly as `x` for custom buckets/prefetch.

        Fault tolerance (optimize/guardian.py, docs/FAULT_TOLERANCE.md):
        `guardian=` (a GuardianPolicy, or True for defaults) switches to
        the guarded train step — non-finite grad/loss steps are skipped
        on device, persistent trouble rolls back to a last-good snapshot
        with LR backoff, and `GuardianAbort` fires when the rollback
        budget runs out (the network is left on the last-good state).
        `checkpoint_every=N` autosaves a resumable checkpoint (params +
        updater state + batch cursor) every N batches through `saver`
        (default: rotating DefaultModelSaver); any configured saver also
        arms a SIGTERM hook that flushes a final checkpoint and raises
        `TrainingPreempted`. With everything off (the default) this is
        the historical code path, bit for bit. Guardian requires the
        iteration_gradient_descent backprop algorithm.

        Resuming a checkpointed run: `start_position`/`start_epoch`/
        `start_epoch_batch` seed the guard's cursors with the restored
        checkpoint's `iterator_position` and `metadata` epoch fields,
        so subsequent autosaves continue the step numbering (no
        collision with committed step dirs) and record a truthful
        within-epoch cursor (a SECOND resume fast-forwards correctly) —
        pair with `DeviceFeed.fast_forward(epoch_batch)` to position
        the data stream (docs/FAULT_TOLERANCE.md, `cli train
        --resume`)."""
        guard = make_guard(self, guardian, checkpoint_every, saver,
                           start_position=start_position,
                           start_epoch=start_epoch,
                           start_epoch_batch=start_epoch_batch)
        if guard is None:
            return self._fit_impl(x, labels, epochs, device_feed, None)
        with guard:
            return self._fit_impl(x, labels, epochs, device_feed, guard)

    def _fit_impl(self, x, labels, epochs, device_feed, guard) -> None:
        """One fit body for the guarded and historical paths — with
        guard=None every guard hook is skipped and this is the legacy
        code path, bit for bit."""
        if labels is None:  # iterator protocol
            iterator = x
            feed, raw = self._resolve_feed(iterator, device_feed)
            if self.conf.pretrain and self.has_pretrain_layers():
                self.pretrain(raw)  # host-driven per-layer: unguarded
            for _ in range(epochs):
                _M_EPOCHS.inc()
                if guard is not None:
                    guard.begin_epoch()
                if feed is not None:
                    for fb in feed:
                        self._fit_supervised(fb.features, fb.labels,
                                             n_valid=fb.n_valid, guard=guard)
                        if guard is not None:
                            guard.tick()
                else:
                    iterator.reset()
                    for ds in iterator:
                        self._fit_supervised(jnp.asarray(ds.features),
                                             jnp.asarray(ds.labels),
                                             guard=guard)
                        if guard is not None:
                            guard.tick()
            return
        x, labels = jnp.asarray(x), jnp.asarray(labels)
        validate_batch(x, labels, n_in=self.layers[0].conf.n_in
                       if not self.conf.input_preprocessors.get(0) else None,
                       n_out=self.layers[-1].conf.n_out, context="fit")
        if self.conf.pretrain and self.has_pretrain_layers():
            self.pretrain(x)
        for _ in range(epochs):
            _M_EPOCHS.inc()
            if guard is not None:
                guard.begin_epoch()
            self._fit_supervised(x, labels, guard=guard)
            if guard is not None:
                guard.tick()

    def _fit_supervised(self, x, labels, n_valid=None, guard=None) -> None:
        if self.conf.backprop:
            self._backprop_fit(x, labels, n_valid=n_valid, guard=guard)
        else:
            if guard is not None and guard.guarded:
                raise ValueError(
                    "guardian= requires the backprop iteration_gradient_"
                    "descent path; the finetune path is host-driven "
                    "(autosave via checkpoint_every= still works)")
            if n_valid is not None:
                # the finetune path is host-driven and per-layer; strip
                # the bucketing padding instead of threading a mask
                # through the frozen-feature solver (shape-specialized —
                # acceptable on this legacy non-backprop path)
                n = int(n_valid)
                x, labels = x[:n], labels[:n]
            self.finetune(x, labels)

    def fit_scan(self, x, labels, batch_size: int, epochs: int = 1,
                 pad_partial: bool = False, guardian=None,
                 checkpoint_every: Optional[int] = None,
                 saver=None) -> float:
        """Whole-epoch training as ONE compiled program: minibatches are
        a leading scan axis and `lax.scan` carries (params, updater
        state) through every step on-device — zero per-step host
        dispatch. Beyond-parity alternative path for the
        iteration_gradient_descent algorithm.

        This is the preferred training path whenever per-step host
        dispatch is a visible share of a step (small models, short
        steps); how large that share is on the current chip machine
        has not been measured (PERF.md, open questions).
        Caveat: `epochs` is a static arg — each distinct value compiles
        its own program.

        `x`: (N, features). When N is not a multiple of batch_size the
        tail is truncated (historical behavior) unless
        `pad_partial=True`, which zero-pads the last minibatch to
        batch_size and scans a per-batch example count alongside so the
        masked loss and the updater's ÷batchSize use the real counts —
        the device-feed masking semantics (docs/DEVICE_FEED.md), inside
        the scan. Returns the final batch's score.

        `guardian=` fuses the guarded commit INTO the scan body (a
        non-finite minibatch is skipped on device, the skip counter
        rides the scan carry) and drives epochs one compiled call each
        so the host-side ladder/autosave/preemption hooks run between
        epochs — one program either way. The ladder's cadences
        (check_every etc.) stay denominated in batches (each epoch
        advances them by n_batches); `checkpoint_every=` counts
        epochs."""
        conf0 = self.layers[-1].conf
        if conf0.optimization_algo.lower() != "iteration_gradient_descent":
            raise ValueError("fit_scan supports iteration_gradient_descent")
        x, labels = jnp.asarray(x), jnp.asarray(labels)
        validate_batch(x, labels, n_in=self.layers[0].conf.n_in
                       if not self.conf.input_preprocessors.get(0) else None,
                       n_out=self.layers[-1].conf.n_out, context="fit_scan")
        n_real = x.shape[0]
        tail = n_real % batch_size
        if pad_partial and tail:
            pad = batch_size - tail
            x = jnp.concatenate(
                [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)])
            labels = jnp.concatenate(
                [labels, jnp.zeros((pad, *labels.shape[1:]), labels.dtype)])
        n = x.shape[0] // batch_size * batch_size
        if n == 0:
            raise ValueError(
                f"batch_size {batch_size} exceeds {x.shape[0]} examples")
        n_batches = n // batch_size
        xb = x[:n].reshape(n_batches, batch_size, *x.shape[1:])
        yb = labels[:n].reshape(n_batches, batch_size,
                                *labels.shape[1:])
        # no tail -> every count would be batch_size: reuse the cheaper
        # unmasked program instead of compiling the masked epoch for it
        masked = bool(pad_partial and tail)
        counts = None
        if masked:  # masked implies a nonzero tail
            counts = np.full((n_batches,), batch_size, np.int32)
            counts[-1] = tail
            counts = jnp.asarray(counts)

        guard = make_guard(self, guardian, checkpoint_every, saver)
        guarded = guard is not None and guard.guarded
        key = (masked, guarded)
        if key not in self._scan_steps:
            self._scan_steps[key] = self._build_scan_step(masked, guarded)

        if self._updater_state is None:
            self._updater_state = NetworkGradientUpdater.for_network(
                self).init(self._params)
        if guard is None:
            args = ((xb, yb, counts, int(epochs)) if masked
                    else (xb, yb, int(epochs)))
            t0 = time.perf_counter()
            with span("fit_scan", epochs=int(epochs), batches=n_batches):
                (self._params, self._updater_state,
                 score) = self._scan_steps[key](
                    self._params, self._updater_state, *args,
                    self.next_key())
                self._iteration_count += epochs * n_batches
                score = float(score)  # the one host sync of this path
            steps = epochs * n_batches
            _M_STEP_S.labels(source="scan").observe(
                (time.perf_counter() - t0) / max(1, steps))
            _M_STEPS.inc(steps)
            _M_EXAMPLES.inc(epochs * n)
            _M_EPOCHS.inc(epochs)
            _M_LOSS.set(score)
            for listener in self.listeners:
                listener.iteration_done(self, self._iteration_count - 1,
                                        score)
            return score

        # guarded/autosaved: one single-epoch program, driven per epoch so
        # the host ladder and checkpoint/preemption hooks interleave
        with guard:
            if guarded:
                guard.arm_once((self._params, self._updater_state))
            args = ((xb, yb, counts, 1) if masked else (xb, yb, 1))
            score = None
            scan_child = _M_STEP_S.labels(source="scan")
            for _ in range(epochs):
                guard.begin_epoch()
                t0 = time.perf_counter()
                if guarded:
                    with span("fit_scan_epoch", guarded=True,
                              batches=n_batches):
                        (self._params, self._updater_state, gstate,
                         score) = self._scan_steps[key](
                            self._params, self._updater_state, guard.gstate,
                            *args, self.next_key())
                    self._iteration_count += n_batches
                    try:
                        # steps=n_batches: the ladder's cadences stay in
                        # BATCHES even though observation is per-epoch
                        live, _ = guard.post_step(
                            (self._params, self._updater_state), gstate,
                            score, steps=n_batches)
                    except GuardianAbort as e:
                        self._params, self._updater_state = e.last_good
                        raise
                    self._params, self._updater_state = live
                else:
                    with span("fit_scan_epoch", batches=n_batches):
                        (self._params, self._updater_state,
                         score) = self._scan_steps[key](
                            self._params, self._updater_state, *args,
                            self.next_key())
                    self._iteration_count += n_batches
                scan_child.observe(
                    (time.perf_counter() - t0) / max(1, n_batches))
                _M_STEPS.inc(n_batches)
                _M_EXAMPLES.inc(n)
                _M_EPOCHS.inc()
                guard.tick()
            score = float(score)
            _M_LOSS.set(score)
            for listener in self.listeners:
                listener.iteration_done(self, self._iteration_count - 1,
                                        score)
            return score

    def _build_scan_step(self, masked: bool, guarded: bool):
        """Compile the whole-epoch program for fit_scan: `masked` scans
        per-batch real counts alongside (device-feed masking), `guarded`
        fuses the guardian's finite-check commit into the scan body and
        carries (gstate, skip counter) on device."""
        updater = NetworkGradientUpdater.for_network(self)
        # static n_epochs position shifts with the leading gstate arg
        static = 4 + int(masked) + int(guarded)

        @partial(jax.jit, donate_argnums=(0, 1), static_argnums=(static,))
        def epoch(params, upd_state, *rest):
            if guarded:
                gstate, *rest = rest
            else:
                gstate = None
            if masked:
                xb, yb, bn, n_epochs, rng = rest
            else:
                xb, yb, n_epochs, rng = rest
                bn = None

            def body(carry, batch):
                params, upd_state, gstate, rng = carry
                if masked:
                    bx, by, bi = batch
                    weights, count = feed_mask(bx.shape[0], bi)
                else:
                    bx, by = batch
                    weights, count = feed_mask(bx.shape[0], None)
                rng, sub = jax.random.split(rng)
                score, grads = jax.value_and_grad(self.loss_fn)(
                    params, bx, by, rng=sub, training=True,
                    weights=weights)
                updates, new_state = updater.update(
                    grads, upd_state, params, count)
                if guarded:
                    params, upd_state, gstate = guarded_update(
                        params, upd_state, updates, new_state, gstate,
                        score, grads)
                else:
                    upd_state = new_state
                    params = jax.tree_util.tree_map(
                        lambda p, u: p - u, params, updates)
                return (params, upd_state, gstate, rng), score

            xs = (xb, yb, bn) if masked else (xb, yb)

            def one_epoch(carry, _):
                carry, scores = jax.lax.scan(body, carry, xs)
                return carry, scores[-1]

            (params, upd_state, gstate, _), last_scores = jax.lax.scan(
                one_epoch, (params, upd_state, gstate, rng), None,
                length=n_epochs)
            if guarded:
                return params, upd_state, gstate, last_scores[-1]
            return params, upd_state, last_scores[-1]

        from deeplearning4j_tpu import compilecache
        return compilecache.maybe_wrap(
            epoch,
            self._aot_key(f"fit_scan|m={int(masked)}|g={int(guarded)}"),
            static_argnums=(static,))

    def _backprop_fit(self, x, labels, n_valid=None, guard=None) -> None:
        # chaos numeric-fault point (docs/FAULT_TOLERANCE.md): a "nan"
        # rule poisons this batch on the host, producing the non-finite
        # grads the guardian's on-device defense exists for; a no-op
        # (one global check) without an active plan
        x = chaos.maybe_nan("train.batch", x)
        conf0 = self.layers[-1].conf
        algo = conf0.optimization_algo.lower()
        guarded = guard is not None and guard.guarded
        if algo == "iteration_gradient_descent":
            # Hot path: one fused XLA program per step, updater state carried
            # across batches (standard minibatch SGD when num_iterations=1).
            # n_valid (device-feed path) is a TRACED count — every bucket
            # shape shares one program regardless of how full it is.
            step = self._get_train_step(guarded=guarded)
            if self._updater_state is None:
                self._updater_state = NetworkGradientUpdater.for_network(
                    self).init(self._params)
            if guarded:
                guard.arm_once((self._params, self._updater_state))
            score = None
            step_child = _M_STEP_S.labels(source="fit")
            for i in range(conf0.num_iterations):
                t0 = time.perf_counter()
                if guarded:
                    with span("train_step", guarded=True):
                        (self._params, self._updater_state, gstate,
                         score) = step(self._params, self._updater_state,
                                       guard.gstate, x, labels,
                                       self.next_key(), n_valid)
                    self._iteration_count += 1
                    try:
                        live, _ = guard.post_step(
                            (self._params, self._updater_state), gstate,
                            score)
                    except GuardianAbort as e:
                        # leave the network on the last-good state the
                        # escalation ladder kept, then surface the report
                        self._params, self._updater_state = e.last_good
                        raise
                    self._params, self._updater_state = live
                else:
                    with span("train_step"):
                        self._params, self._updater_state, score = step(
                            self._params, self._updater_state, x, labels,
                            self.next_key(), n_valid)
                    self._iteration_count += 1
                step_child.observe(time.perf_counter() - t0)
                _M_STEPS.inc()
                _M_EXAMPLES.inc(x.shape[0])
            if self.listeners:  # float() only where it always was:
                score_f = float(score)  # no-listener fits stay sync-free
                _M_LOSS.set(score_f)
                for listener in self.listeners:
                    listener.iteration_done(self, self._iteration_count - 1,
                                            score_f)
        else:
            if guarded:
                raise ValueError(
                    "guardian= supports only the iteration_gradient_descent "
                    f"algorithm (got {algo!r}); the line-search solvers "
                    "drive their own inner loop")
            if self._batch_solver is None:
                _, unravel = ravel_pytree(self._params)

                def flat_loss(vec, key, bx, by, *rest, _u=unravel):
                    # rest, when present, is the device-feed row mask
                    w = rest[0] if rest else None
                    return self.loss_fn(_u(vec), bx, by, rng=key,
                                        training=True, weights=w)

                # cached: line-search solvers (CG/LBFGS/HF) compile once;
                # the batch is a traced argument (rng_key at construction
                # marks the loss stochastic; per-batch keys come from the
                # optimize override)
                self._batch_solver = Solver(conf0, flat_loss,
                                            listeners=self.listeners,
                                            model=self,
                                            rng_key=self.next_key())
            data = (x, labels)
            if n_valid is not None:
                data += (feed_mask(x.shape[0], n_valid)[0],)
            self._params, _ = self._batch_solver.optimize(
                self._params, *data, rng_key=self.next_key(), sync=False)

    def _aot_key(self, tag: str) -> Optional[str]:
        """Persistent-compile-cache key for this network's jitted steps
        (docs/WARMUP.md): the config JSON names the program family, the
        device binds the serialized executable. None (= stay a plain
        jit) when no cache is active or the config won't serialize."""
        from deeplearning4j_tpu import compilecache

        if compilecache.active_compiler() is None:
            return None
        try:
            digest = compilecache.config_digest(self.to_json())
        except Exception:
            return None
        return f"train.{tag}:{digest}|dev={jax.devices()[0]}"

    def _get_train_step(self, guarded: bool = False):
        if guarded:
            if self._train_step_guarded is None:
                self._train_step_guarded = self._build_train_step(True)
            return self._train_step_guarded
        if self._train_step is None:
            self._train_step = self._build_train_step(False)
        return self._train_step

    def _build_train_step(self, guarded: bool):
        updater = NetworkGradientUpdater.for_network(self)

        # params/updater-state buffers are donated: the step's outputs
        # alias their HBM instead of allocating fresh buffers each
        # iteration (~1.4x step throughput on v5e for the MLP config).
        # Callers must treat the passed-in trees as consumed — the fit
        # loop rebinds self._params/_updater_state from the outputs.
        # n_valid is None (arrays path: bit-identical legacy program)
        # or a traced int32 count (device-feed path: rows >= n_valid
        # are bucketing padding, masked out of loss and ÷batchSize).
        if not guarded:
            @partial(jax.jit, donate_argnums=(0, 1))
            def step(params, upd_state, x, labels, rng, n_valid=None):
                weights, count = feed_mask(x.shape[0], n_valid)
                score, grads = jax.value_and_grad(self.loss_fn)(
                    params, x, labels, rng=rng, training=True,
                    weights=weights)
                updates, upd_state = updater.update(grads, upd_state, params,
                                                    count)
                params = jax.tree_util.tree_map(lambda p, u: p - u, params,
                                                updates)
                return params, upd_state, score

            from deeplearning4j_tpu import compilecache
            return compilecache.maybe_wrap(step, self._aot_key("step"))

        # guarded variant: an all-leaves-finite predicate over grads+loss
        # is reduced on device and the whole update commits through
        # jnp.where — a poisoned step leaves params/updater state (and the
        # updater's iteration counter) untouched and bumps the skip
        # counter. gstate.lr_scale rescales committed updates so the
        # rollback ladder can back off LR without recompiling.
        @partial(jax.jit, donate_argnums=(0, 1))
        def gstep(params, upd_state, gstate, x, labels, rng, n_valid=None):
            weights, count = feed_mask(x.shape[0], n_valid)
            score, grads = jax.value_and_grad(self.loss_fn)(
                params, x, labels, rng=rng, training=True, weights=weights)
            updates, new_state = updater.update(grads, upd_state, params,
                                                count)
            params, upd_state, gstate = guarded_update(
                params, upd_state, updates, new_state, gstate, score, grads)
            return params, upd_state, gstate, score

        from deeplearning4j_tpu import compilecache
        return compilecache.maybe_wrap(gstep, self._aot_key("gstep"))

    def train_step_cache_size(self) -> int:
        """Number of XLA programs compiled for the jitted supervised train
        step so far (unguarded + guarded variants) — the device-feed
        recompile counter. With shape bucketing this stays at the number
        of buckets actually hit (the traced n_valid never re-specializes);
        without it, one program per distinct batch shape. Returns 0
        before the first backprop step."""
        total = 0
        for step in (self._train_step, self._train_step_guarded):
            if step is None:
                continue
            size = jit_cache_size(step)
            if size < 0:
                return -1
            total += size
        return total

    def finetune(self, x, labels=None) -> None:
        """Optimize only the output layer on top of frozen features
        (reference finetune :1044/:1079 -> OutputLayer.fit). Accepts
        (x, labels) arrays or a DataSetIterator; large arrays stream the
        frozen-feature computation in batch_size chunks rather than
        feed-forwarding the whole dataset in one device batch."""
        if labels is None:  # iterator protocol
            iterator = x
            iterator.reset()
            for ds in iterator:
                self.finetune(ds.features, ds.labels)
            return
        x = jnp.asarray(x)
        hidden = self._frozen_features(x)
        out_idx = str(len(self.layers) - 1)
        out_layer = self.layers[-1]
        if self._finetune_solver is None:
            _, unravel = ravel_pytree(self._params[out_idx])

            def flat_loss(vec, hid, lab, *, _u=unravel):
                return out_layer.loss(_u(vec), hid, lab)

            # cached: repeated finetune batches (fit over a DataSetIterator)
            # reuse one compiled step — hidden/labels are traced args
            self._finetune_solver = Solver(out_layer.conf, flat_loss,
                                           listeners=self.listeners,
                                           model=self)
        new_params, _ = self._finetune_solver.optimize(
            self._params[out_idx], hidden, jnp.asarray(labels), sync=False)
        self._params[out_idx] = new_params

    def _frozen_features(self, x, chunk_size: int = 4096) -> jnp.ndarray:
        """Features under the output layer, computed in chunks so only
        (chunk, features) activations are ever live on device."""
        if len(self.layers) < 2:
            return x
        if x.shape[0] <= chunk_size:
            return self.feed_forward_fn(self._params, x)[-2]
        outs = [self.feed_forward_fn(self._params, x[i:i + chunk_size])[-2]
                for i in range(0, x.shape[0], chunk_size)]
        return jnp.concatenate(outs, axis=0)

    # ----------------------------------------------------------- inference
    def feed_forward(self, x) -> List[jnp.ndarray]:
        x = jnp.asarray(x)
        validate_batch(x, n_in=self.layers[0].conf.n_in
                       if not self.conf.input_preprocessors.get(0) else None,
                       context="feed_forward")
        return self.feed_forward_fn(self._params, x)

    def _get_predict_step(self):
        """Cached jitted forward to the output layer — the serving-side
        twin of _get_train_step. Input batches pad to a pow2 bucket
        before the call (see output), so a ragged request/CSV stream
        compiles <= one program per bucket instead of one per shape."""
        if self._predict_step is None:
            from deeplearning4j_tpu import compilecache
            self._predict_step = compilecache.maybe_wrap(
                jax.jit(
                    lambda params, x: self.feed_forward_fn(params, x)[-1]),
                self._aot_key("predict"))
        return self._predict_step

    def output(self, x, bucketed: bool = True) -> jnp.ndarray:
        """Output-layer activations (reference output :1197).

        `bucketed=True` (default) zero-pads the batch up to the pow2
        bucket ladder and runs the cached jitted forward, slicing the
        padding back off — inference is per-row independent, so padded
        rows never touch real outputs. `bucketed=False` is the eager
        legacy path (also the escape hatch for layers with
        cross-example behavior at inference)."""
        if not bucketed:
            return self.feed_forward(x)[-1]
        x = jnp.asarray(x)
        validate_batch(x, n_in=self.layers[0].conf.n_in
                       if not self.conf.input_preprocessors.get(0) else None,
                       context="output")
        n = x.shape[0]
        b = bucket_for(n, (DEFAULT_MIN_BUCKET,))
        return self._get_predict_step()(self._params, pad_rows(x, b))[:n]

    def predict(self, x) -> np.ndarray:
        """Class predictions (reference predict :1107) — through the
        bucketed jitted forward."""
        return np.asarray(jnp.argmax(self.output(x), axis=-1))

    def predict_step_cache_size(self) -> int:
        """Compiled-program count for the jitted inference forward (the
        train_step_cache_size analogue): with bucketing this stays at
        the pow2 buckets actually hit, not one per batch shape. 0 before
        the first bucketed output/predict."""
        if self._predict_step is None:
            return 0
        return jit_cache_size(self._predict_step)

    def score(self, x, labels) -> float:
        """Mean loss on (x, labels) (reference score :1265)."""
        return float(self.loss_fn(self._params, jnp.asarray(x),
                                  jnp.asarray(labels)))

    # ------------------------------------------------- params as flat vector
    @property
    def param_table(self) -> Dict[str, dict]:
        """Live per-layer parameter tree (reference paramTable). NOTE: the
        hot fit path donates these buffers to the train step — snapshot
        with `params()` (which copies into a fresh packed vector) rather
        than holding this tree across a fit()."""
        return self._params

    def params(self) -> jnp.ndarray:
        """Packed flat parameter vector (reference params :784 / pack :831)."""
        flat, _ = ravel_pytree(self._params)
        return flat

    def set_parameters(self, flat: jnp.ndarray) -> None:
        """Install a packed vector (reference setParameters :1420 / unPack :920)."""
        self._params = self._unravel(jnp.asarray(flat))

    def num_params(self) -> int:
        return int(self.params().shape[0])

    def merge(self, other: "MultiLayerNetwork", n: int) -> None:
        """Parameter averaging: this += (other - this)/n (reference merge
        :1361 — the primitive under all distributed runtimes)."""
        self._params = merge_params(self._params, other._params, n)

    # -------------------------------------------------------- serialization
    def to_json(self) -> str:
        return self.conf.to_json()

    @classmethod
    def from_config_json(cls, s: str, params: Optional[jnp.ndarray] = None
                         ) -> "MultiLayerNetwork":
        return cls(MultiLayerConfiguration.from_json(s), params=params)

    def clone(self) -> "MultiLayerNetwork":
        net = MultiLayerNetwork(
            MultiLayerConfiguration.from_json(self.conf.to_json()))
        net.set_parameters(self.params())
        return net
