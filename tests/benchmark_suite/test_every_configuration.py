"""The two checks that `conftest.py` marks as expected failures, kept
running for EVERY configuration and EVERY serving mix, the accepted ones
first among them, with the two rules that a cut configuration of
another family cannot meet stated as they would have to read:

- `test_manifest.py::test_every_configuration_has_a_cell_and_its_files`
  is one test over all configurations and asserts `reduced == []`.
  Here it is one case a configuration, and `reduced` has to be the keys
  of the file's own `reduced_why` (none for the two uncut files, so for
  them the rule is the accepted one, letter for letter).
- `test_schedule.py::test_warm_set_covers_every_group_the_schedule_can_
  form` takes GPT-2's buckets for 2,048 positions and 16 slots whatever
  the mix. Here slots, positions, page and buckets are those of each
  cell that runs the mix, asked of its family (for `agent-sat` and
  `doc-p80` they are 16, 2,048 and 16: the accepted test's own).

A `benchmark` PR that edits the two accepted tests so deletes this file
with `conftest.py`."""

import json
import os

import pytest

from benchmark import manifest, schedule

M = manifest.load_manifest()
SECONDS = M["run_seconds"]
SERVING = [w["name"] for w in M["workloads"]
           if manifest.load_cell(w["name"]).traffic["driver"] != "train"]


@pytest.mark.parametrize("entry", M["configs"], ids=lambda c: c["name"])
def test_a_configuration_has_a_cell_and_its_files(entry):
    assert entry["name"] in {w["config"] for w in M["workloads"]}
    assert entry["file"].startswith(tuple(p + "/" for p in M["paths"]))
    with open(os.path.join(manifest.ROOT, entry["file"])) as f:
        raw = json.load(f)
    assert raw["source"] == entry["source"]
    # every cut is listed, in the manifest and in the file, with its
    # published value and its reason; nothing else is
    cuts = raw.get("reduced_why", {})
    assert sorted(entry["reduced"]) == sorted(cuts)
    assert sorted(raw.get("published", {})) == sorted(cuts)
    assert all(isinstance(why, str) and why for why in cuts.values())
    family = manifest.load_family(raw)
    assert callable(family.reference().logits)


def test_the_two_accepted_configurations_are_uncut():
    by = {c["name"]: c for c in M["configs"]}
    assert by["cerebras-gpt-1.3b"]["reduced"] == []
    assert by["gpt2-medium"]["reduced"] == []


def test_files_are_no_other_configuration_s_and_four_chips_are_few():
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(M["workloads"]) // 4)


def reachable_groups(traffic, slots, buckets):
    """Every (bb, tb) that requests admitted in ONE scheduler pass can
    form, as `test_schedule.py:brute_force_groups` reckons it, over the
    buckets given."""
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


@pytest.mark.parametrize("name", SERVING)
def test_a_cell_s_warm_set_covers_every_group_its_schedule_can_form(name):
    cell = manifest.load_cell(name)
    srv = cell.config["serving"]
    slots, page = int(srv["slots"]), int(srv["page_size"])
    max_len = cell.family.sizes(cell.config)["max_len"]
    buckets = cell.family.prompt_buckets(max_len, page)
    warm = schedule.warm_groups(cell.traffic, SECONDS, slots, buckets)
    reachable = reachable_groups(cell.traffic, slots, buckets)
    assert reachable and reachable <= set(warm["groups"])
    most = max(bb for bb, _ in reachable)
    assert warm["sizes"] == list(range(1, max(warm["sizes"]) + 1))
    assert max(warm["sizes"]) >= most
    # what the family really warms: every reachable group that a bound
    # on the tokens a pass prefills (where the cell sets one) admits
    bound = srv.get("prefill_tokens_per_pass")
    warmed = {(schedule.pow2_at_least(n),
               schedule.bucket_of(length, buckets))
              for n, length in cell.family.warm_requests(
                  cell.config, cell.traffic, SECONDS)}
    for bb, tb in reachable:
        if bound is None or bb <= max(1, int(bound) // tb):
            assert (bb, tb) in warmed, (bb, tb)
