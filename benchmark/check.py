"""What decides `correct`: the timed path's output against the plain
reference, each number compared beside its limit.

Serving: once the window has closed, a sample of the requests it
finished (drawn from the seed, the longest among them) goes through the
reference once, prompt and served tokens together, and the number
compared is the widest gap by which a served token's logit lies below
the reference's best at its position (`token_gap_max`). Valid for
greedy tokens, which is all this traffic sends.

Training: the reference follows the first three steps on the same
batches. Compared: the worst loss gap, the first gradient's norm and the
parameters' change after the three steps, both by the worst leaf: the
gap between the program's norm and the reference's (not the norm of
their difference) against the reference's norm of that leaf or of the
median leaf, whichever is larger. Leaves whose reference gradient is
under a thousandth of the median leaf's are left out of the change (they
move by round-off alone).

The reference takes nothing the program has made: it builds the weights
again from the seed. It runs after the window, after
`memory_peak_bytes` has been read and the program's state is freed.

`mode` other than "f32" puts the reference, computed in a lower
precision, in the program's place: the control that has to come out as
not correct (`benchmark/calibrate.py`, `tests/benchmark_suite`).
"""

from __future__ import annotations

import math
from statistics import median
from typing import Dict, List

import numpy as np

from benchmark import weights
from benchmark.manifest import Cell

PAD_TO = 512
SMALL_GRADIENT = 1e-3


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each number beside its limit; correct only if every limited
    number is finite and within it."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = (value is not None and math.isfinite(value)
                and value <= limit)
        ok = ok and good
        compared[name] = {"value": value, "limit": limit}
    return {"correct": bool(ok), "compared": compared}


# ---------------------------------------------------------------- serving
def pick_requests(requests: List[dict], window, n: int, seed: int
                  ) -> List[dict]:
    """A sample of the requests the window finished, the longest in
    it."""
    start, end = window
    done = [r for r in requests
            if r["finish"] == "max_tokens" and r["tokens"]
            and (start <= r["due"] < end or start <= r["times"][-1] < end)]
    if not done:
        return []
    done.sort(key=lambda r: r["index"])
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 99])
    take = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(take)]


def _position_gaps(lg, picks):
    """By position, how far the picked token's logit lies below the
    best: lg (T, V), picks (T,). One shape per padded length, so a run
    compiles nothing that the last run of the cell did not."""
    import jax.numpy as jnp

    got = jnp.take_along_axis(lg, picks[:, None], axis=1)[:, 0]
    return jnp.max(lg, axis=-1) - got


def serve_numbers(cell: Cell, seed: int, sample: List[dict],
                  control_modes=()) -> dict:
    """`token_gap_max` of the served tokens, and for each control mode
    the same number for the tokens that mode puts first at the same
    positions."""
    import jax
    import jax.numpy as jnp

    ref = cell.family.reference()
    max_len = cell.family.sizes(cell.config)["max_len"]
    params = weights.make_params(seed, cell.family, cell.config)
    position_gaps = jax.jit(_position_gaps)
    argmax = jax.jit(lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32))
    gaps, tokens = [], 0
    control = {m: [] for m in control_modes}
    for rec in sample:
        served = np.asarray(rec["tokens"], np.int32)
        seq = np.concatenate([np.asarray(rec["prompt"], np.int32),
                              served[:-1]])
        first = rec["prompt_len"] - 1
        last = first + len(served)
        width = min(-(-len(seq) // PAD_TO) * PAD_TO, max_len)
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(seq)] = seq
        picks = np.zeros((width,), np.int32)
        picks[first:last] = served
        lg = ref.logits(cell.config, params, jnp.asarray(padded), 0,
                        width)[0]
        gaps.append(np.asarray(
            position_gaps(lg, jnp.asarray(picks)))[first:last])
        tokens += len(served)
        for mode in control_modes:
            low = ref.logits(cell.config, params, jnp.asarray(padded),
                             0, width, mode=mode)[0]
            control[mode].append(np.asarray(
                position_gaps(lg, argmax(low)))[first:last])
    if not gaps:
        return {"token_gap_max": None, "tokens_compared": 0,
                "requests_compared": 0}
    allg = np.concatenate(gaps)
    out = {"token_gap_max": float(allg.max()),
           "token_gap_mean": float(allg.mean()),
           "tokens_off_best": int((allg > 0).sum()),
           "tokens_compared": tokens, "requests_compared": len(sample)}
    for mode, g in control.items():
        g = np.concatenate(g)
        out[f"control_{mode}_token_gap_max"] = float(g.max())
        out[f"control_{mode}_tokens_off_best"] = int((g > 0).sum())
    return out


# --------------------------------------------------------------- training
def leaf_gaps(got: Dict[str, float], want: Dict[str, float],
              leaves=None) -> Dict[str, float]:
    """By leaf, |got - want| against the larger of the reference's norm
    of that leaf and of the median leaf."""
    names = list(want) if leaves is None else list(leaves)
    mid = median(want[n] for n in names)
    out = {}
    for n in names:
        scale = max(want[n], mid)
        out[n] = abs(got[n] - want[n]) / scale if scale > 0 else math.inf
    return out


def worst_leaf_gap(got, want, leaves=None) -> float:
    return max(leaf_gaps(got, want, leaves).values())


def median_leaf_gap(got, want, leaves=None) -> float:
    return median(leaf_gaps(got, want, leaves).values())


def worst_leaves(got, want, leaves=None, n: int = 4) -> list:
    """The `n` leaves that read worst, each with both norms: what a
    builder looks at when a gap reads high."""
    gaps = leaf_gaps(got, want, leaves)
    return [{"leaf": k, "gap": gaps[k], "program": got[k],
             "reference": want[k]}
            for k in sorted(gaps, key=gaps.get, reverse=True)[:n]]


def moving_leaves(grad_norms: Dict[str, float]) -> List[str]:
    """Leaves the reference's gradient really moves: not under a
    thousandth of the median leaf's."""
    mid = median(grad_norms.values())
    return [n for n, v in grad_norms.items() if v >= SMALL_GRADIENT * mid]


def reference_steps(cell: Cell, seed: int, steps: int, mode: str = "f32",
                    rows=None) -> dict:
    """The reference through the first `steps` steps on the seed's
    batches: losses, the first gradient's and the change's norms."""
    import jax
    import jax.numpy as jnp

    from benchmark.train import batch_ids, leaf_norms

    ref = cell.family.reference()
    vocab = cell.family.sizes(cell.config)["vocab_size"]
    n_rows, seq_len = int(cell.traffic["batch"]), int(cell.traffic["seq_len"])
    per_block = max(1, 1024 // seq_len)
    base = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        weights.make_params(seed, cell.family, cell.config))
    params = jax.tree_util.tree_map(jnp.copy, base)
    state = ref.init_state(base)
    out = {"loss": []}
    for i in range(steps):
        ids = jnp.asarray(batch_ids(seed, i, n_rows, seq_len, vocab))
        loss, grads = ref.loss_and_grad(cell.config, params, ids,
                                        per_block, mode=mode, rows=rows)
        out["loss"].append(float(loss))
        if i == 0:
            out["grad_norms"] = leaf_norms(grads)
        params, state = ref.update(cell.config, params, state, grads)
    out["change_norms"] = leaf_norms(params, base)
    return out


def train_numbers(got: dict, want: dict) -> dict:
    """The program's first steps (`got`) against the reference's."""
    moving = moving_leaves(want["grad_norms"])
    return {
        "loss_gap_max": max(abs(a - b)
                            for a, b in zip(got["loss"], want["loss"])),
        "grad_norm_gap_worst_leaf": worst_leaf_gap(
            got["grad_norms"], want["grad_norms"]),
        "grad_norm_gap_median_leaf": median_leaf_gap(
            got["grad_norms"], want["grad_norms"]),
        "change_norm_gap_worst_leaf": worst_leaf_gap(
            got["change_norms"], want["change_norms"], moving),
        "change_norm_gap_median_leaf": median_leaf_gap(
            got["change_norms"], want["change_norms"], moving),
        "loss_reference_first": want["loss"][0],
    }


def train_detail(got: dict, want: dict) -> dict:
    moving = moving_leaves(want["grad_norms"])
    return {"loss_program": got["loss"], "loss_reference": want["loss"],
            "leaves": len(want["grad_norms"]), "leaves_moving": len(moving),
            "grad_worst": worst_leaves(got["grad_norms"],
                                       want["grad_norms"]),
            "change_worst": worst_leaves(got["change_norms"],
                                         want["change_norms"], moving)}
