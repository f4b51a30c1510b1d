"""The one traffic generator: a traffic file in, a schedule out.

A schedule is a pure function of the traffic file (and, for an open
loop, of how many seconds it has to cover): arrival offsets, prompt
lengths, output lengths, the order of requests, training batch shapes.
`--seed` never reaches this module: two runs with different seeds offer
requests of the same lengths at the same offsets, and only their token
ids differ (`weights.token_ids`). Lengths still come from the
distribution the file states, by stratified draws: item i of n takes the
quantile (i + 0.5) / n, and the file's `schedule_seed` shuffles the
order.

A traffic file states `driver` (`serve_closed`, `serve_open`, `train`)
and that driver's parameters; see `benchmark/traffic/*.json`.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, NamedTuple, Sequence, Tuple

import numpy as np


class Request(NamedTuple):
    index: int        # position in the schedule; names the token stream
    client: int       # closed loop: whose request; open loop: -1
    offset_s: float   # open loop: due time after the loop's start
    prompt_len: int
    output_len: int


def draw_lengths(spec: dict, n: int, rng: np.random.Generator
                 ) -> List[int]:
    """`n` whole lengths from the distribution `spec` states, stratified
    and then shuffled by `rng` (consts and cycles keep their order)."""
    dist = spec["dist"]
    if dist == "const":
        return [int(spec["value"])] * n
    if dist == "cycle":
        vals = [int(v) for v in spec["values"]]
        return [vals[i % len(vals)] for i in range(n)]
    q = (np.arange(n) + 0.5) / n
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        vals = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif dist == "uniform":
        vals = spec["min"] + (spec["max"] - spec["min"]) * q
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    vals = np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)
    return [int(v) for v in rng.permutation(vals)]


def closed_loop(traffic: dict) -> List[List[Request]]:
    """Per client, the requests it sends one after the other. Request j
    of client k takes entry (k + j) of the cycled lengths; the first
    request of client k is cut to ceil(L (k + 1) / clients) tokens where
    the file says `stagger_first`, so that ends are spread from the
    start."""
    clients = int(traffic["clients"])
    per = int(traffic["requests_per_client"])
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    prompts = draw_lengths(traffic["prompt_len"], clients * per, rng)
    outs = draw_lengths(traffic["output_len"], clients + per, rng)
    plan = []
    for k in range(clients):
        row = []
        for j in range(per):
            out = outs[k + j]
            if j == 0 and traffic.get("stagger_first"):
                out = math.ceil(out * (k + 1) / clients)
            row.append(Request(k * per + j, k, 0.0,
                               prompts[k * per + j], out))
        plan.append(row)
    return plan


def open_loop(traffic: dict, seconds: float) -> List[Request]:
    """Arrivals over `warmup_s + seconds`: a Poisson process at
    `rate_per_s` from `schedule_seed`, each with a stratified prompt and
    output length."""
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    horizon = float(traffic["warmup_s"]) + float(seconds)
    rate = float(traffic["rate_per_s"])
    offsets, t = [], 0.0
    while True:
        t += float(rng.exponential(1.0 / rate))
        if t >= horizon:
            break
        offsets.append(t)
    n = len(offsets)
    prompts = draw_lengths(traffic["prompt_len"], n, rng)
    outs = draw_lengths(traffic["output_len"], n, rng)
    return [Request(i, -1, offsets[i], prompts[i], outs[i])
            for i in range(n)]


def train_batches(traffic: dict) -> Tuple[int, int]:
    """(rows, tokens a row trains on); a batch holds one token more a
    row, the last target."""
    return int(traffic["batch"]), int(traffic["seq_len"])


# ------------------------------------------------------------ warm set
def pow2_at_least(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def bucket_of(plen: int, buckets: Sequence[int]) -> int:
    return next(b for b in buckets if b >= plen)


def length_range(spec: dict) -> Tuple[int, int]:
    if spec["dist"] == "const":
        return int(spec["value"]), int(spec["value"])
    if spec["dist"] == "cycle":
        return min(spec["values"]), max(spec["values"])
    return int(spec["min"]), int(spec["max"])


def max_arrivals(offsets: Sequence[float], span_s: float) -> int:
    """Most arrivals any interval of `span_s` seconds holds."""
    best, lo = 0, 0
    for hi, t in enumerate(offsets):
        while offsets[lo] <= t - span_s:
            lo += 1
        best = max(best, hi - lo + 1)
    return best


def warm_groups(traffic: dict, seconds: float, slots: int,
                buckets: Sequence[int]) -> Dict[str, list]:
    """Every prefill program the cell's schedule CAN reach, not those a
    replay happened to reach, given the prompt lengths the program has
    prefill programs for (`buckets`, the family's to say): `groups` is
    each (bb, tb) with tb a bucket the file's prompt range touches and
    bb a power of two up to the most requests one scheduler pass can
    admit; `sizes` is every count of requests 1..that, because the
    program's hand-over of first tokens compiles per (bb, count). Closed loop: all clients start at once, so
    up to the slots. Open loop: the most arrivals of its schedule in any
    half second, doubled, rounded up to a power of two, never above the
    slots."""
    lo, hi = length_range(traffic["prompt_len"])
    touched = [b for b in buckets
               if b >= bucket_of(lo, buckets) and b <= bucket_of(hi, buckets)]
    if traffic["driver"] == "serve_closed":
        top = min(slots, int(traffic["clients"]))
    else:
        offs = [r.offset_s for r in open_loop(traffic, seconds)]
        top = min(slots, pow2_at_least(2 * max_arrivals(offs, 0.5)))
    bbs = sorted({pow2_at_least(n) for n in range(1, top + 1)})
    return {"groups": [(bb, tb) for tb in touched for bb in bbs],
            "sizes": list(range(1, top + 1)),
            "buckets": touched}
