"""Batch optimizers.

Parity: reference core/optimize/solvers/ — `BaseOptimizer.optimize` main loop
(BaseOptimizer.java:128-195: gradientAndScore -> termination checks ->
line-search step -> listeners -> re-score), `IterationGradientDescent`,
`GradientAscent` (line-search gradient descent), `ConjugateGradient`
(Polak-Ribiere), `LBFGS` (two-loop recursion), `StochasticHessianFree`
(CG-minimized curvature, StochasticHessianFree.java:87-184).

TPU-native design: optimizers work on the FLAT parameter vector
(jax.flatten_util.ravel_pytree — the same representation as the reference's
params()/setParameters pack/unpack, MultiLayerNetwork.java:784/:831) with a
jitted value_and_grad; hand-written backprop and the hand-written R-op
(MultiLayerNetwork.backPropGradientR :1475) are replaced by jax.grad and
jvp-based Hessian/Gauss-Newton vector products.
"""

from __future__ import annotations

import logging
from functools import partial
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from deeplearning4j_tpu.optimize.line_search import backtrack_line_search
from deeplearning4j_tpu.optimize.listeners import IterationListener
from deeplearning4j_tpu.optimize.terminations import (
    EpsTermination,
    Norm2Termination,
    TerminationCondition,
    ZeroDirection,
)
from deeplearning4j_tpu.optimize.updater import GradientUpdater

log = logging.getLogger(__name__)


class BaseOptimizer:
    """Shared loop: iterate `step` until num_iterations or termination.

    `loss` is a pure fn (flat_params -> scalar score); subclasses implement
    `make_step` returning a jitted update on flat vectors.
    """

    def __init__(
        self,
        conf,
        loss: Callable[[jnp.ndarray], jnp.ndarray],
        listeners: Optional[Sequence[IterationListener]] = None,
        terminations: Optional[Sequence[TerminationCondition]] = None,
        model=None,
        rng_key: Optional[jax.Array] = None,
    ):
        self.conf = conf
        self.listeners: List[IterationListener] = list(listeners or [])
        self.terminations = list(
            terminations
            if terminations is not None
            else [EpsTermination(), ZeroDirection()]
        )
        self.model = model
        self.rng_key = rng_key
        self._step = None  # jitted step, compiled once per optimizer
        # Stochastic losses (CD Gibbs chains, denoising corruption, dropout)
        # take (x, key, *data) and get a FRESH key each iteration (fold_in
        # of the iteration index); deterministic losses take (x, *data) and
        # the key arg is ignored. The key AND the data batch are traced
        # arguments, so varying them never retriggers compilation — one
        # optimizer instance serves every mini-batch of a phase
        # (reference BaseOptimizer is likewise reused by its Solver).
        if rng_key is not None:
            self.loss = loss
        else:
            self.loss = lambda x, key, *data: loss(x, *data)

    # subclasses: raw traceable (x, state, key, *data) ->
    # (x, state, score, grad_norm); make_step/make_loop wrap it
    def _step_fn(self):
        raise NotImplementedError

    #: argnums make_step donates (IGD donates params+state buffers)
    _donate: tuple = ()

    def make_step(self):
        return jax.jit(self._step_fn(), donate_argnums=self._donate)

    def init_state(self, x):
        return ()

    # ---------------------------------------------- device-side fast loop
    #: optimizers whose _step_fn is a pure traced function (all five
    #: solvers here) run their WHOLE iteration loop as one compiled
    #: lax.while_loop when (a) no per-iteration listeners are attached
    #: and (b) every termination condition is one of the jittable
    #: reference trio. The eager loop costs a host round trip PER
    #: ITERATION (the float(score) sync), which dominates
    #: multi-iteration pretraining.
    _JITTABLE_TERMS = (EpsTermination, ZeroDirection, Norm2Termination)

    def _device_loop_eligible(self) -> bool:
        return (not self.listeners
                and all(isinstance(t, self._JITTABLE_TERMS)
                        for t in self.terminations))

    def _terminate_traced(self, new_score, old_score, gnorm):
        """The reference termination trio as traced predicates — same
        math as terminations.py, on device."""
        conds = []
        for t in self.terminations:
            if isinstance(t, EpsTermination):
                finite = jnp.isfinite(new_score) & jnp.isfinite(old_score)
                denom = (jnp.abs(old_score) + jnp.abs(new_score)
                         + t.tolerance)
                conds.append(finite & (
                    2.0 * jnp.abs(new_score - old_score) / denom < t.eps))
            elif isinstance(t, ZeroDirection):
                conds.append(gnorm == 0.0)
            elif isinstance(t, Norm2Termination):
                conds.append(gnorm < t.gradient_tolerance)
        out = jnp.asarray(False)
        for c in conds:
            out = out | c
        return out

    def make_loop(self, n_iters: int):
        """The whole optimize() loop as ONE compiled while_loop — identical
        iteration math and termination checks to the eager path (same
        per-iteration fold_in keys, same check-after-step schedule), minus
        the per-iteration host sync. Works for every solver whose step is
        a pure traced function (all five here)."""
        step = self._step_fn()
        terminate = self._terminate_traced

        @partial(jax.jit, donate_argnums=(0,))
        def run(x, base_key, *data):
            inf = jnp.float32(jnp.inf)

            def cond(carry):
                i, x, state, score, old, gnorm = carry
                # the eager loop checks terminations AFTER each step;
                # checking before the NEXT step is the same schedule —
                # guard i == 0 so the init sentinels never terminate
                return (i < n_iters) & ((i == 0)
                                        | ~terminate(score, old, gnorm))

            def body(carry):
                i, x, state, score, old, gnorm = carry
                new_x, new_state, new_score, new_gnorm = step(
                    x, state, jax.random.fold_in(base_key, i), *data)
                return (i + 1, new_x, new_state,
                        new_score.astype(jnp.float32), score,
                        new_gnorm.astype(jnp.float32))

            init = (jnp.int32(0), x, self.init_state(x), inf, inf,
                    jnp.float32(0.0))
            _, x, _, score, _, _ = jax.lax.while_loop(cond, body, init)
            return x, score

        return run

    def _has_device_loop(self) -> bool:
        # old-style subclasses that override make_step without providing
        # a raw _step_fn can't build the traced loop — fall back to eager
        return type(self)._step_fn is not BaseOptimizer._step_fn

    def optimize(self, params, *data, rng_key=None, sync: bool = True):
        """Run the loop; params is a pytree; returns (params, final_score).
        `data` arrays are forwarded to the loss as traced arguments;
        `rng_key` overrides the construction-time key (fresh stochasticity
        per mini-batch without recompiling).

        `sync` controls the return type of `final_score` when the device
        loop is taken (no listeners + jittable terminations +
        num_iterations > 1): the default True syncs it to a Python float,
        so the return type never varies by path; sync=False returns the
        live float32 DEVICE scalar and skips the host round-trip — that
        per-optimize sync stalls the host once per layer-wise
        pretraining call, so hot internal callers pass
        sync=False and float() only when they actually read the score."""
        x, unravel = ravel_pytree(params)
        # the jitted step/loop DONATE the params buffer; for single-leaf
        # pytrees ravel_pytree returns the caller's array itself, so
        # donate would delete it out from under the caller — hand the
        # optimizer its own copy (one device op per optimize() call)
        x = jnp.array(x, copy=True)
        if rng_key is None:
            rng_key = self.rng_key
        base_key = (rng_key if rng_key is not None
                    else jax.random.PRNGKey(0))
        if (self._has_device_loop() and self._device_loop_eligible()
                and self.conf.num_iterations > 1):
            # cache keyed on what optimize() itself reads per call
            # (iteration count + termination config): mutating those
            # between calls must recompile, not reuse the stale loop.
            # Hyperparameters (lr, momentum, history, ...) are baked at
            # first compile on BOTH paths — the cached eager self._step
            # closes over them the same way — so they are not keyed.
            loop_key = (self.conf.num_iterations,
                        tuple((type(t).__name__,
                               tuple(sorted(vars(t).items())))
                              for t in self.terminations))
            if getattr(self, "_loop_key", None) != loop_key:
                self._loop = self.make_loop(self.conf.num_iterations)
                self._loop_key = loop_key
            x, score = self._loop(x, base_key, *data)
            for listener in self.listeners:  # empty by eligibility, but
                done = getattr(listener, "optimization_done", None)
                if done is not None:  # keep the contract future-proof
                    done(self.model)
            return unravel(x), (float(score) if sync else score)
        if self._step is None:
            self._step = self.make_step()
        step = self._step
        state = self.init_state(x)
        old_score = float("inf")
        score = None
        for i in range(self.conf.num_iterations):
            x, state, score_arr, gnorm_arr = step(
                x, state, jax.random.fold_in(base_key, i), *data)
            score, gnorm = float(score_arr), float(gnorm_arr)
            for listener in self.listeners:
                listener.iteration_done(self.model, i, score)
            if any(t.terminate(score, old_score, gnorm) for t in self.terminations):
                log.debug("Terminated at iteration %d (score=%s)", i, score)
                break
            old_score = score
        for listener in self.listeners:
            # end-of-optimization hook (beyond-parity: lets stateful
            # listeners like ProfilerListener finalize deterministically
            # even when a termination condition cuts the loop short)
            done = getattr(listener, "optimization_done", None)
            if done is not None:
                done(self.model)
        return unravel(x), score


class IterationGradientDescent(BaseOptimizer):
    """Plain SGD with GradientAdjustment semantics (reference
    IterationGradientDescent + GradientAdjustment.java:66-113)."""

    # donate x/state: outputs alias their HBM instead of reallocating
    # per iteration (same win as MultiLayerNetwork._get_train_step);
    # optimize() rebinds both from the outputs every iteration
    _donate = (0, 1)

    def init_state(self, x):
        updater = GradientUpdater(self.conf)
        return updater.init(x)

    def _step_fn(self):
        updater = GradientUpdater(self.conf)
        sign = 1.0 if self.conf.minimize else -1.0

        def step(x, state, key, *data):
            score, g = jax.value_and_grad(self.loss)(x, key, *data)
            # data[0] (when present) is the mini-batch: its leading dim is
            # the reference's ÷batchSize denominator (adagrad branch)
            bs = data[0].shape[0] if data and hasattr(data[0], "shape") \
                and getattr(data[0], "ndim", 0) >= 1 else 1
            updates, state = updater.update(g, state, x, bs)
            return x - sign * updates, state, score, jnp.linalg.norm(g)

        return step


class GradientAscent(BaseOptimizer):
    """Line-search steepest descent (reference GradientAscent solver: the
    GRADIENT_DESCENT algorithm — normalized gradient direction + backtracking
    line search)."""

    def _step_fn(self):
        max_iters = self.conf.num_line_search_iterations

        def step(x, state, key, *data):
            score, g = jax.value_and_grad(self.loss)(x, key, *data)
            gnorm = jnp.linalg.norm(g)
            d = -g / (gnorm + 1e-12)
            res = backtrack_line_search(
                lambda xx: self.loss(xx, key, *data),
                x, score, g, d,
                initial_step=self.conf.lr,
                max_iterations=max_iters)
            return x + res.step * d, state, res.score, gnorm

        return step


class ConjugateGradient(BaseOptimizer):
    """Nonlinear CG, Polak-Ribiere+ (reference ConjugateGradient solver)."""

    def init_state(self, x):
        return (jnp.zeros_like(x), jnp.zeros_like(x), jnp.asarray(True))

    def _step_fn(self):
        max_iters = self.conf.num_line_search_iterations

        def step(x, state, key, *data):
            g_prev, d_prev, first = state
            score, g = jax.value_and_grad(self.loss)(x, key, *data)
            gnorm = jnp.linalg.norm(g)
            denom = jnp.vdot(g_prev, g_prev)
            beta = jnp.where(
                jnp.logical_or(first, denom < 1e-20),
                0.0,
                jnp.maximum(0.0, jnp.vdot(g, g - g_prev) / denom),
            )
            d = -g + beta * d_prev
            # Restart with steepest descent when d is not a descent direction
            descent = jnp.vdot(g, d) < 0
            d = jnp.where(descent, d, -g)
            res = backtrack_line_search(lambda xx: self.loss(xx, key, *data),
                                        x, score, g,
                                        d / (jnp.linalg.norm(d) + 1e-12),
                                        initial_step=1.0,
                                        max_iterations=max_iters)
            dn = d / (jnp.linalg.norm(d) + 1e-12)
            return (x + res.step * dn, (g, d, jnp.asarray(False)),
                    res.score, gnorm)

        return step


class LBFGS(BaseOptimizer):
    """Limited-memory BFGS with two-loop recursion (reference LBFGS solver).

    History is a fixed-size ring buffer of (s, y) pairs held in device arrays
    so the whole step jits (no Python-list history, unlike the reference's
    LinkedList-based implementation).
    """

    def __init__(self, *args, history: int = 10, **kwargs):
        super().__init__(*args, **kwargs)
        self.history = history

    def init_state(self, x):
        m, n = self.history, x.shape[0]
        return (
            jnp.zeros((m, n), x.dtype),  # S
            jnp.zeros((m, n), x.dtype),  # Y
            jnp.zeros((m,), x.dtype),  # rho
            jnp.asarray(0, jnp.int32),  # count
            x,  # x_prev
            jnp.zeros_like(x),  # g_prev
        )

    def _step_fn(self):
        m = self.history
        max_ls = self.conf.num_line_search_iterations

        def step(x, state, key, *data):
            S, Y, rho, count, x_prev, g_prev = state
            score, g = jax.value_and_grad(self.loss)(x, key, *data)
            gnorm = jnp.linalg.norm(g)

            # Update history with (s, y) from the last accepted step
            s = x - x_prev
            y = g - g_prev
            sy = jnp.vdot(s, y)
            valid = jnp.logical_and(count > 0, sy > 1e-10)

            def push(args):
                S, Y, rho = args
                S = jnp.roll(S, -1, axis=0).at[-1].set(s)
                Y = jnp.roll(Y, -1, axis=0).at[-1].set(y)
                rho = jnp.roll(rho, -1).at[-1].set(1.0 / sy)
                return S, Y, rho

            S, Y, rho = jax.lax.cond(valid, push, lambda a: a, (S, Y, rho))
            hist_len = jnp.minimum(count, m)

            # Two-loop recursion (newest entry is row m-1)
            def bwd(i, carry):
                q, alphas = carry
                idx = m - 1 - i
                use = i < hist_len
                a = jnp.where(use, rho[idx] * jnp.vdot(S[idx], q), 0.0)
                q = q - a * Y[idx]
                return q, alphas.at[idx].set(a)

            q, alphas = jax.lax.fori_loop(0, m, bwd, (g, jnp.zeros((m,), x.dtype)))
            gamma = jnp.where(valid, sy / (jnp.vdot(y, y) + 1e-12), 1.0)
            r = gamma * q

            def fwd(i, r):
                use = i < hist_len
                idx = m - jnp.minimum(hist_len, m) + i  # oldest valid -> newest
                b = jnp.where(use, rho[idx] * jnp.vdot(Y[idx], r), 0.0)
                return r + jnp.where(use, (alphas[idx] - b), 0.0) * S[idx]

            r = jax.lax.fori_loop(0, m, fwd, r)
            d = -r
            descent = jnp.vdot(g, d) < 0
            d = jnp.where(descent, d, -g)
            res = backtrack_line_search(lambda xx: self.loss(xx, key, *data),
                                        x, score, g, d,
                                        initial_step=1.0,
                                        max_iterations=max_ls)
            new_x = x + res.step * d
            new_count = jnp.where(valid, count + 1, count + 1)
            return new_x, (S, Y, rho, new_count, x, g), res.score, gnorm

        return step


class StochasticHessianFree(BaseOptimizer):
    """Hessian-free (truncated-Newton) optimization.

    Parity: reference StochasticHessianFree.java:87-184 — CG-minimize the local
    quadratic model with a curvature-vector product and Levenberg-Marquardt
    damping adjustment. The reference hand-codes an R-op Gauss-Newton product
    through MultiLayerNetwork (feedForwardR :1438 / backPropGradientR :1475);
    here the curvature product is a jvp-of-grad Hessian-vector product (or a
    caller-supplied Gauss-Newton product) — jax.jvp over jax.grad composes to
    the same mathematical object without hand-derivation.
    """

    def __init__(self, *args, matvec: Optional[Callable] = None,
                 cg_iterations: int = 30, initial_lambda: float = 1.0,
                 **kwargs):
        super().__init__(*args, **kwargs)
        self._user_matvec = matvec
        self.cg_iterations = cg_iterations
        self.initial_lambda = initial_lambda

    def init_state(self, x):
        return jnp.asarray(self.initial_lambda, x.dtype)

    def _step_fn(self):
        loss = self.loss
        cg_iters = self.cg_iterations
        user_matvec = self._user_matvec

        def hvp(x, v, key, *data):
            if user_matvec is not None:
                return user_matvec(x, v)
            return jax.jvp(jax.grad(lambda xx: loss(xx, key, *data)),
                           (x,), (v,))[1]

        def step(x, lam, key, *data):
            score, g = jax.value_and_grad(loss)(x, key, *data)
            gnorm = jnp.linalg.norm(g)

            def Av(v):
                return hvp(x, v, key, *data) + lam * v

            # Plain CG on A delta = -g (reference conjGradient :87)
            b = -g

            def cg_body(i, state):
                d, r, p = state
                Ap = Av(p)
                pAp = jnp.vdot(p, Ap)
                alpha = jnp.where(pAp > 1e-20, jnp.vdot(r, r) / pAp, 0.0)
                d_new = d + alpha * p
                r_new = r - alpha * Ap
                beta = jnp.where(jnp.vdot(r, r) > 1e-20,
                                 jnp.vdot(r_new, r_new) / jnp.vdot(r, r), 0.0)
                return (d_new, r_new, r_new + beta * p)

            zeros = jnp.zeros_like(x)
            delta, _, _ = jax.lax.fori_loop(0, cg_iters, cg_body,
                                            (zeros, b, b))

            # Backtrack over the CG solution (reference cgBackTrack :184)
            new_score = loss(x + delta, key, *data)

            def shrink_cond(s):
                scale, ns, it = s
                return jnp.logical_and(ns > score, it < 10)

            def shrink_body(s):
                scale, _, it = s
                scale = scale * 0.5
                return (scale, loss(x + scale * delta, key, *data), it + 1)

            scale, new_score, _ = jax.lax.while_loop(
                shrink_cond, shrink_body,
                (jnp.asarray(1.0, x.dtype), new_score, jnp.asarray(0)))

            # Levenberg-Marquardt damping update via reduction ratio
            pred = -(jnp.vdot(g, scale * delta)
                     + 0.5 * jnp.vdot(scale * delta, Av(scale * delta)))
            rho = jnp.where(pred > 1e-20, (score - new_score) / pred, 0.0)
            lam = jnp.where(rho > 0.75, lam * 2.0 / 3.0,
                            jnp.where(rho < 0.25, lam * 1.5, lam))
            improved = new_score < score
            x_new = jnp.where(improved, x + scale * delta, x)
            return x_new, lam, jnp.where(improved, new_score, score), gnorm

        return step
