"""Epoch-wave parameter averaging on a device mesh.

Parity: the reference's iterative-reduce semantics — each worker takes K
local fit steps on its own shard, then parameters are averaged
(`MultiLayerNetwork.merge` :1361 / INDArrayAggregator.java:35-59 /
Spark fold(Add)/÷n, SparkDl4jMultiLayer.java:172-174). The reference moves
packed parameter vectors through Hazelcast/Akka/Spark to a master; here each
replica's K-step inner loop is a `lax.scan` compiled into ONE XLA program
per wave, and the "averaging" is a `pmean` collective that rides ICI — no
host round-trip, no serialization.

This trainer exists for behavioral parity (coarse-grained averaging waves);
`DataParallelTrainer` (per-step gradient all-reduce) is the tighter-sync
mode that usually trains better per FLOP.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map

from deeplearning4j_tpu.optimize.updater import NetworkGradientUpdater
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, make_mesh


class ParameterAveragingTrainer:
    """K local steps per replica, then a pmean parameter average per wave."""

    def __init__(self, network, mesh: Optional[jax.sharding.Mesh] = None,
                 axis: str = DATA_AXIS, local_steps: int = 4):
        self.network = network
        self.mesh = mesh if mesh is not None else make_mesh()
        self.axis = axis
        self.local_steps = local_steps
        self.n_devices = int(np.prod(self.mesh.devices.shape))
        self.updater = NetworkGradientUpdater.for_network(network)
        self._wave = self._build_wave()

    def _build_wave(self):
        net, updater, axis = self.network, self.updater, self.axis

        def replica_wave(params, upd_state, xs, ys, keys):
            # per-device shapes: xs (1, K, b, f) — drop the shard dim
            xs, ys, keys = xs[0], ys[0], keys[0]

            def body(carry, xyk):
                p, s = carry
                x, y, k = xyk
                score, g = jax.value_and_grad(net.loss_fn)(
                    p, x, y, rng=k, training=True)
                upd, s = updater.update(g, s, p)
                p = jax.tree_util.tree_map(lambda pp, uu: pp - uu, p, upd)
                return (p, s), score

            (p, s), scores = lax.scan(body, (params, upd_state),
                                      (xs, ys, keys))
            # THE iterative-reduce average, as an ICI collective. Integer
            # leaves (e.g. the updater's iteration counter — identical on
            # every replica) use pmax to stay integer-typed; pmean would
            # drift them to float and retrigger compilation.
            def avg(a):
                if jnp.issubdtype(a.dtype, jnp.floating):
                    return lax.pmean(a, axis)
                return lax.pmax(a, axis)

            p = jax.tree_util.tree_map(avg, p)
            s = jax.tree_util.tree_map(avg, s)
            return p, s, lax.pmean(jnp.mean(scores), axis)

        fn = _shard_map(
            replica_wave, mesh=self.mesh,
            in_specs=(P(), P(), P(axis), P(axis), P(axis)),
            out_specs=(P(), P(), P()),
        )
        return jax.jit(fn)

    def fit(self, iterator, epochs: int = 1) -> None:
        """Consume the iterator in waves of n_devices*local_steps batches."""
        net = self.network
        params = net._params
        upd_state = (net._updater_state if net._updater_state is not None
                     else self.updater.init(params))
        score = None
        waves = 0
        for _ in range(epochs):
            iterator.reset()
            batch = []
            for ds in iterator:
                batch.append((np.asarray(ds.features), np.asarray(ds.labels)))
                if len(batch) == self.n_devices * self.local_steps:
                    params, upd_state, score = self._run_wave(
                        params, upd_state, batch)
                    waves += 1
                    batch = []
            if batch:  # tail wave: tile to fill the grid
                need = self.n_devices * self.local_steps
                idx = np.arange(need) % len(batch)
                params, upd_state, score = self._run_wave(
                    params, upd_state, [batch[i] for i in idx])
                waves += 1
        net._params = params
        net._updater_state = upd_state
        if waves:
            for listener in net.listeners:
                listener.iteration_done(net, waves - 1, float(score))

    @staticmethod
    def _pad_rows(arr: np.ndarray, rows: int) -> np.ndarray:
        """Tile a ragged tail batch up to the wave's uniform batch size."""
        if arr.shape[0] == rows:
            return arr
        idx = np.arange(rows) % arr.shape[0]
        return arr[idx]

    def _run_wave(self, params, upd_state, batch):
        d, k = self.n_devices, self.local_steps
        rows = max(b[0].shape[0] for b in batch)
        batch = [(self._pad_rows(x, rows), self._pad_rows(y, rows))
                 for x, y in batch]
        xs = np.stack([b[0] for b in batch]).reshape(
            d, k, *batch[0][0].shape)
        ys = np.stack([b[1] for b in batch]).reshape(
            d, k, *batch[0][1].shape)
        keys = jax.random.split(self.network.next_key(), d * k).reshape(
            d, k, -1)
        with self.mesh:
            return self._wave(params, upd_state, jnp.asarray(xs),
                              jnp.asarray(ys), keys)
