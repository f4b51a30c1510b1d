"""The training driver: `make_train_step` as it stands, steps back to
back, the host waiting on the loss every `sync_every`-th step and at the
end of the window; the input pipeline (token batches made on the host
from the seed and put on the device) runs inside the window.

Set-up builds ONE object, the compiled step with its state (the cell's
family's `make_train_step`), drives it through its first three steps on
the seed's first three batches (their losses, the first gradient as the
optimizer got it and the parameters' change are what `check.py` holds
against the reference), warms it up, and hands that same object to the
window.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Optional

import numpy as np

from benchmark import weights
from benchmark.manifest import Cell

TRAIN_STREAM = 1
CHECK_STEPS = 3


def batch_ids(seed: int, index: int, rows: int, seq_len: int,
              vocab: int) -> np.ndarray:
    """Batch `index`: `rows` rows that all differ, each one token longer
    than it trains on (the last target)."""
    flat = weights.token_ids(seed, TRAIN_STREAM, index,
                             rows * (seq_len + 1), vocab)
    return flat.reshape(rows, seq_len + 1)


def leaf_norms(tree, base=None) -> dict:
    """Euclidean norm of every leaf (of `tree - base` where a base is
    given), by its path, on the host; float32 sums, one fused program."""
    import jax
    import jax.numpy as jnp

    def norm(a, b=None):
        a = a.astype(jnp.float32)
        if b is not None:
            a = a - b.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(a)))

    trees = (tree,) if base is None else (tree, base)
    norms = jax.jit(lambda *t: jax.tree_util.tree_map(norm, *t))(*trees)
    flat, _ = jax.tree_util.tree_flatten_with_path(norms)
    return {jax.tree_util.keystr(path): float(v) for path, v in flat}


class TrainLoop:
    """The compiled step with its state and its feed."""

    def __init__(self, cell: Cell, seed: int):
        self.cell, self.seed = cell, seed
        self.vocab = cell.family.sizes(cell.config)["vocab_size"]
        self.rows = int(cell.traffic["batch"])
        self.seq_len = int(cell.traffic["seq_len"])
        self.params = weights.make_params(seed, cell.family, cell.config)
        self.step, self.state = cell.family.make_train_step(
            cell.config, self.params)
        self.index = 0

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.seq_len

    def feed(self):
        import jax

        with jax.profiler.TraceAnnotation("bench.feed"):
            ids = batch_ids(self.seed, self.index, self.rows,
                            self.seq_len, self.vocab)
            return jax.device_put(ids)

    def advance(self):
        """One step through the window's own call and feed; returns the
        loss, still on the device."""
        import jax

        batch = self.feed()
        with jax.profiler.TraceAnnotation("bench.train_step"):
            self.params, self.state, loss = self.step(
                self.params, self.state, batch)
        self.index += 1
        return loss

    def first_steps(self) -> dict:
        """Steps 1-3, with what the reference will be held against."""
        out = {"loss": []}
        for i in range(CHECK_STEPS):
            out["loss"].append(float(self.advance()))
            if i == 0:
                out["grad_norms"] = leaf_norms(
                    self.cell.family.first_gradient(self.state))
        base = weights.make_params(self.seed, self.cell.family,
                                   self.cell.config)
        out["change_norms"] = leaf_norms(self.params, base)
        return out

    def warm_up(self) -> None:
        """The fixed warm-up of the same loop, before the window."""
        for _ in range(int(self.cell.traffic["warmup_steps"])):
            loss = self.advance()
        float(loss)

    def free(self) -> None:
        self.params = self.state = None


def run_window(loop: TrainLoop, seconds: float, tracer=None) -> dict:
    every = int(loop.cell.traffic["sync_every"])
    gc.collect()
    gc.freeze()
    trace_box: dict = {}
    tracer_thread: Optional[threading.Thread] = None
    if tracer is not None:
        spec = loop.cell.traffic["trace"]

        def take():
            time.sleep(min(float(spec["start_s"]), seconds / 4))
            trace_box["trace"] = tracer.record(
                min(float(spec["seconds"]), seconds / 2))

        tracer_thread = threading.Thread(target=take, daemon=True)
        tracer_thread.start()
    steps, last = 0, float("nan")
    start = time.perf_counter()
    while True:
        for _ in range(every):
            loss = loop.advance()
            steps += 1
        last = float(loss)
        if time.perf_counter() - start >= seconds:
            break
    elapsed = time.perf_counter() - start
    gc.unfreeze()
    if tracer_thread is not None:
        tracer_thread.join()
    return {"train": {"steps": steps, "elapsed": elapsed,
                      "tokens_per_step": loop.tokens_per_step,
                      "rows": loop.rows, "seq_len": loop.seq_len,
                      "last_loss": last},
            "window": (start, start + elapsed),
            "trace": trace_box.get("trace")}
