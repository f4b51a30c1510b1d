"""The rule that is new: a cell offers the same work in every run.
`--seed` makes weights and token ids and nothing else; the schedule is
a pure function of the traffic file."""

import glob
import json
import math
import os
from statistics import median

import numpy as np
import pytest

from benchmark import manifest, schedule, weights
from benchmark.families import gpt2

TRAFFIC_DIR = os.path.join(manifest.ROOT, "benchmark", "traffic")
FILES = sorted(glob.glob(os.path.join(TRAFFIC_DIR, "*.json")))
SECONDS = manifest.load_manifest()["run_seconds"]


def load(path):
    with open(path) as f:
        return json.load(f)


def plan_of(traffic):
    if traffic["driver"] == "serve_closed":
        return [r for row in schedule.closed_loop(traffic) for r in row]
    if traffic["driver"] == "serve_open":
        return schedule.open_loop(traffic, SECONDS)
    return [schedule.train_batches(traffic)]


def contents(traffic, seed):
    """What a run with `seed` would send: the harness's own calls."""
    from benchmark.train import batch_ids

    if traffic["driver"] == "train":
        rows, seq = schedule.train_batches(traffic)
        return [batch_ids(seed, i, rows, seq, 50257) for i in range(2)]
    return [weights.token_ids(seed, 0, r.index, r.prompt_len, 50257)
            for r in plan_of(traffic)[:8]]


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_two_seeds_one_schedule_other_contents(path):
    traffic = load(path)
    # the schedule takes no seed at all: generating it twice, as two
    # runs would, gives the same offsets, lengths and order
    assert plan_of(traffic) == plan_of(load(path))
    a, b = contents(traffic, 11), contents(traffic, 2 ** 31 + 12)
    assert [x.shape for x in a] == [x.shape for x in b]
    assert all(not np.array_equal(x, y) for x, y in zip(a, b))
    # and one seed gives the same contents again
    again = contents(traffic, 11)
    assert all(np.array_equal(x, y) for x, y in zip(a, again))


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_lengths_match_the_stated_distribution(path):
    traffic = load(path)
    if traffic["driver"] == "train":
        rows, seq = schedule.train_batches(traffic)
        assert rows * seq == 8192
        return
    plan = plan_of(traffic)
    for key, attr in (("prompt_len", "prompt_len"),
                      ("output_len", "output_len")):
        spec = traffic[key]
        got = [getattr(r, attr) for r in plan]
        if traffic.get("stagger_first") and key == "output_len":
            got = [r.output_len for r in plan
                   if r.index % traffic["requests_per_client"]]
        lo, hi = schedule.length_range(spec)
        assert min(got) >= lo and max(got) <= hi
        if spec["dist"] == "lognormal":
            assert abs(median(got) - spec["median"]) <= 0.05 * spec["median"]
            z = np.log(np.array(got) / spec["median"])
            inner = z[(np.array(got) > lo) & (np.array(got) < hi)]
            assert abs(np.std(z) - spec["sigma"]) < 0.15, np.std(z)
            assert len(inner) > 0.6 * len(got)
        elif spec["dist"] == "cycle":
            assert sorted(set(got)) == sorted(spec["values"])
        elif spec["dist"] == "const":
            assert set(got) == {spec["value"]}


def test_agent_sat_is_the_issue_s_loop():
    traffic = load(os.path.join(TRAFFIC_DIR, "agent-sat.json"))
    plan = schedule.closed_loop(traffic)
    lens = traffic["output_len"]["values"]
    assert len(plan) == 16
    for k, row in enumerate(plan):
        assert row[0].output_len == math.ceil(lens[k % 5] * (k + 1) / 16)
        for j, req in enumerate(row[1:], start=1):
            assert req.output_len == lens[(k + j) % 5]
            assert req.prompt_len == 1024
    # ends are spread from the start: first requests of 12 to 256
    # tokens, at most two of them alike
    firsts = [row[0].output_len for row in plan]
    assert len(set(firsts)) >= len(firsts) - 1
    assert min(firsts) == 12 and max(firsts) <= 448


def test_open_loop_arrivals_are_poisson_at_the_rate():
    traffic = load(os.path.join(TRAFFIC_DIR, "doc-p80.json"))
    plan = schedule.open_loop(traffic, SECONDS)
    horizon = traffic["warmup_s"] + SECONDS
    offs = [r.offset_s for r in plan]
    assert offs == sorted(offs) and 0 < offs[0] and offs[-1] < horizon
    want = traffic["rate_per_s"] * horizon
    assert abs(len(plan) - want) < 3 * math.sqrt(want)
    gaps = np.diff(offs)
    assert 0.7 < np.std(gaps) / np.mean(gaps) < 1.3   # exponential: 1


def brute_force_groups(traffic, slots, max_len, page_size):
    """Every (bb, tb) that requests admitted in ONE scheduler pass can
    form: closed loop, any number of clients up to all of them; open
    loop, every run of consecutive arrivals inside one second (twice the
    half second the rule reckons with), as many as the slots take."""
    buckets = gpt2.prompt_buckets(max_len, page_size)
    out = set()

    def add(reqs):
        by = {}
        for r in reqs:
            tb = schedule.bucket_of(r.prompt_len, buckets)
            by[tb] = by.get(tb, 0) + 1
        for tb, n in by.items():
            out.add((schedule.pow2_at_least(n), tb))

    if traffic["driver"] == "serve_closed":
        heads = [row[0] for row in schedule.closed_loop(traffic)]
        for n in range(1, min(slots, len(heads)) + 1):
            add(heads[:n])
    else:
        plan = schedule.open_loop(traffic, SECONDS)
        for i in range(len(plan)):
            for j in range(i, len(plan)):
                if plan[j].offset_s - plan[i].offset_s > 1.0 \
                        or j - i + 1 > slots:
                    break
                add(plan[i:j + 1])
    return out


@pytest.mark.parametrize("path", [p for p in FILES if load(p)["driver"]
                                  != "train"], ids=os.path.basename)
def test_warm_set_covers_every_group_the_schedule_can_form(path):
    traffic = load(path)
    warm = schedule.warm_groups(traffic, SECONDS, 16,
                                gpt2.prompt_buckets(2048, 16))
    reachable = brute_force_groups(traffic, 16, 2048, 16)
    assert reachable and reachable <= set(warm["groups"])
    most = max(bb for bb, _ in reachable)
    assert warm["sizes"] == list(range(1, max(warm["sizes"]) + 1))
    assert max(warm["sizes"]) >= most


def test_buckets_are_the_program_s():
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    from deeplearning4j_tpu.serving.paged_kv import prompt_buckets

    for max_len, page in ((2048, 16), (1024, 16), (100, 16), (64, 8)):
        cfg = TransformerConfig(vocab_size=8, max_len=max_len)
        assert gpt2.prompt_buckets(max_len, page) == \
            prompt_buckets(cfg, page)
