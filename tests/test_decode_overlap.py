"""A second step in flight (`serving/decode_loop.py`, docs/SERVING.md
"The order of a pass"): the plain lane enqueues step N+1 before it reads
step N. What has to hold, on the CPU at a tiny size, for both families,
driven by hand (`start=False`) and by the scheduler thread:

1. the served tokens of every request are the contiguous reference's,
   whatever joins and leaves around it (the device is never overwritten
   from the host's mirrors, which lag a step);
2. an end-of-sequence token that arrives while the next step is in
   flight ends the stream at that token, leaves the pages balanced and
   the slot's next occupant served correctly; cancel, deadline and
   preemption retire the same way; a page-starved slot waits and goes on;
3. nothing is left in flight when the loop says it is idle;
4. `dispatches_overlapped` counts what it says.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import cohere2_moe as moe_family
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_transformer_params)
from deeplearning4j_tpu.serving.decode_loop import DecodeLoop
from deeplearning4j_tpu.serving.errors import (TIER_BATCH, Deadline,
                                               DeadlineExceededError)
from deeplearning4j_tpu.serving.kv_cache import generate_cached
from deeplearning4j_tpu.telemetry import exposition
from tests.benchmark_suite import tiny_moe
from tests.test_prefix_cache import _assert_balance

CFG = TransformerConfig(vocab_size=61, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_len=64, interpret=True)
#: the blocks' matrices 16 times their initial size: with a tied head a
#: tiny model at its initial weights repeats one token for ever, and a
#: step fed a token one step stale would serve the right tokens by luck.
#: At this size nine of ten successive tokens differ
PARAMS = init_transformer_params(jax.random.PRNGKey(0), CFG)
PARAMS = dict(PARAMS, blocks=jax.tree_util.tree_map(
    lambda a: a * 16 if a.ndim == 2 else a, PARAMS["blocks"]))
MOE_CONFIG = dict(tiny_moe.CONFIG, dtype="float32")


def _prompt(seed, t):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, (t,)).astype(np.int32)


def _ref(prompt, n):
    """The contiguous path's greedy tokens (prompt left out)."""
    prompt = np.asarray(prompt, np.int32)
    return np.asarray(generate_cached(
        PARAMS, jnp.asarray(prompt[None]), CFG, n))[0].tolist()[len(prompt):]


def _loop(**kw):
    kw.setdefault("slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("start", False)
    return DecodeLoop(PARAMS, CFG, **kw)


def _drain(loop, streams, threaded):
    if threaded:
        return [s.result(timeout=120) for s in streams]
    loop.run_until_idle()
    return [s.result(timeout=0) for s in streams]


#: five requests on two slots: every one joins a loop that is running
#: and leaves one that goes on (lengths and budgets all different)
JOBS = [(3, 9, 12), (4, 16, 5), (5, 5, 20), (6, 24, 9), (7, 11, 1)]


# ------------------------------------------------ (1) the same tokens
@pytest.mark.parametrize("threaded", [False, True],
                         ids=["by-hand", "threaded"])
@pytest.mark.parametrize("kw", [
    dict(prefix_cache=False), dict(prefix_cache=False, horizon=2),
    dict(prefix_cache=True), dict(prefix_cache=True, horizon=3)],
    ids=["plain", "horizon2", "prefix-cache", "prefix-cache-horizon3"])
def test_gpt2_slots_serve_the_contiguous_reference_s_tokens(kw, threaded):
    prompts = [_prompt(seed, t) for seed, t, _ in JOBS]
    budgets = [n for *_, n in JOBS]
    # the prefix cache gets something to share: two prompts repeat
    prompts += [prompts[1], prompts[3]]
    budgets += [7, 4]
    with _loop(start=threaded, **kw) as loop:
        streams = loop.submit_many(prompts, budgets)
        got = _drain(loop, streams, threaded)
        snap = loop.snapshot()
        if not threaded:
            _assert_balance(loop)
    assert got == [_ref(p, n) for p, n in zip(prompts, budgets)]
    assert all(s.finish_reason == "max_tokens" for s in streams)
    assert snap["decode_step_programs"] == 1
    assert snap["dispatches_overlapped"] > 0
    if kw["prefix_cache"]:
        assert snap["prefix_cache"]["hits"] >= 1


@pytest.mark.parametrize("threaded", [False, True],
                         ids=["by-hand", "threaded"])
def test_window_layers_and_held_experts_serve_the_reference_s_best(threaded):
    cfg = moe_family.model_config(MOE_CONFIG)
    params = weights.make_params(2 ** 31 + 29, moe_family, MOE_CONFIG)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 97, (n,)).astype(np.int32)
               for n in (21, 9, 30, 5, 17)]
    budgets = [20, 12, 25, 30, 3]
    with DecodeLoop(params, cfg, slots=2, page_size=4, n_pages=64,
                    window_pages=64, prefix_cache=False,
                    start=threaded) as loop:
        streams = loop.submit_many(prompts, budgets)
        got = _drain(loop, streams, threaded)
        snap = loop.snapshot()
    assert [len(g) for g in got] == budgets
    assert snap["dispatches_overlapped"] > 0
    assert snap["pages_by_kind"]["window"]["released"] > 0
    ref = moe_family.reference()
    for prompt, out in zip(prompts, got):
        seq = np.concatenate([prompt, out])
        lg = np.asarray(ref.logits(MOE_CONFIG, params,
                                   jnp.asarray(seq[None, :-1]),
                                   len(prompt) - 1, len(seq) - 1))[0]
        assert (lg.max(-1) - lg[np.arange(len(out)), out]).max() < 1e-4


def test_a_token_shows_a_pass_after_its_step_was_dispatched():
    prompt = _prompt(1, 9)
    with _loop(slots=1) as loop:
        stream = loop.submit(prompt, 6)
        seen = []
        for _ in range(7):
            loop.tick()
            seen.append((len(stream._generated), int(loop._lengths[0]),
                         loop._inflight is not None))
        # pass 1 prefills, enqueues step 1 and flushes the first token;
        # pass k reads step k-1. `_lengths` is the DISPATCHED cursor
        assert seen[:3] == [(1, 10, True), (2, 11, True), (3, 12, True)]
        # 5 steps for 6 tokens: pass 6 finds nothing to enqueue, reads
        # step 5 and retires the slot; pass 7 has nothing to do
        assert seen[4:] == [(5, 14, True), (6, 0, False), (6, 0, False)]
        assert stream.result(timeout=0) == _ref(prompt, 6)


def test_a_retired_slot_s_length_is_0_on_the_device():
    """An idle slot reads one trash block, not its old context: the
    host sets the row at retirement, whatever a step in flight made of
    it, and touches no other row."""
    with _loop() as loop:
        short = loop.submit(_prompt(2, 9), 3)
        long = loop.submit(_prompt(3, 12), 12)
        while not short.done:
            loop.tick()
        loop.tick()                       # the next dispatch sets the row
        assert loop._slot_state[0] is None
        lengths = np.asarray(loop._d_lengths)
        assert lengths[0] == 0 and lengths[1] == loop._lengths[1] > 12
        assert not loop._host_rows.any()
        loop.run_until_idle()
        assert long.result(timeout=0) == _ref(long.prompt, 12)


# ---------------------------------- (2) ends that arrive a step late
def _with_an_end_of_sequence(min_at=2, budget=14):
    """A prompt, its reference tokens and an `eos_id` that the reference
    first emits at index >= `min_at`, well before the budget."""
    for seed in range(100):
        prompt = _prompt(100 + seed, 10)
        ref = _ref(prompt, budget)
        for at in range(min_at, budget - 3):
            if ref[at] not in ref[:at]:
                return prompt, ref, at
    raise AssertionError("no prompt with a late first occurrence")


@pytest.mark.parametrize("threaded", [False, True],
                         ids=["by-hand", "threaded"])
@pytest.mark.parametrize("horizon", [1, 2])
def test_an_end_of_sequence_a_step_late_ends_the_stream_at_it(horizon,
                                                              threaded):
    prompt, ref, at = _with_an_end_of_sequence()
    other = _prompt(8, 13)
    with _loop(slots=2, n_pages=12, horizon=horizon,
               start=threaded) as loop:
        first = loop.submit(prompt, 14, eos_id=ref[at])
        beside = loop.submit(other, 18)
        # the slot's next occupant, and one that shares the prompt's
        # cached pages with what the ended stream left
        after = loop.submit_many([_prompt(9, 7), prompt], [6, 5])
        got = _drain(loop, [first, beside] + after, threaded)
        snap = loop.snapshot()
        if not threaded:
            _assert_balance(loop)
            assert loop._inflight is None
    assert first.finish_reason == "eos"
    assert got[0] == ref[:at + 1]        # ends AT the token, none after
    assert got[1] == _ref(other, 18)
    assert got[2] == _ref(after[0].prompt, 6)
    assert got[3] == ref[:5]
    assert snap["pages_in_use"] == 0
    # the step in flight behind the end was dispatched all the same
    assert snap["dispatches_overlapped"] > 0


def test_an_end_of_sequence_on_the_first_token_retires_at_the_flush():
    prompt = _prompt(4, 9)
    ref = _ref(prompt, 4)
    with _loop(slots=1) as loop:
        stream = loop.submit(prompt, 4, eos_id=ref[0])
        loop.tick()           # prefill, step 1 enqueued, first flushed
        assert stream.done and stream.finish_reason == "eos"
        assert loop._inflight is not None      # the wasted step
        nxt = loop.submit(_prompt(5, 6), 5)
        loop.run_until_idle()
        assert stream.result(timeout=0) == ref[:1]
        assert nxt.result(timeout=0) == _ref(nxt.prompt, 5)
        assert loop._inflight is None
        _assert_balance(loop)


@pytest.mark.parametrize("how", ["cancel", "deadline", "preempt"])
def test_a_slot_retired_with_a_step_in_flight(how):
    """The step in flight was dispatched for the retired request: its
    tokens reach no stream, the pages go back, the slot's next occupant
    and the stream beside it are served correctly."""
    victim_prompt, beside_prompt = _prompt(10, 9), _prompt(11, 14)
    with _loop(slots=2, n_pages=12) as loop:
        deadline = Deadline.from_ms(600_000) if how == "deadline" else None
        victim = loop.submit(victim_prompt, 40, deadline=deadline,
                             tier=TIER_BATCH)
        beside = loop.submit(beside_prompt, 16)
        for _ in range(3):
            loop.tick()
        assert loop._inflight is not None and not victim.done
        had = len(victim._generated)
        nxt_prompt = _prompt(12, 6)
        if how == "cancel":
            assert victim.cancel()
        elif how == "deadline":
            deadline._expires = time.monotonic()     # the budget dies
        nxt = loop.submit(nxt_prompt, 7)     # interactive: may preempt
        loop.tick()
        assert victim.done
        assert victim.finish_reason == {
            "cancel": "cancelled", "deadline": "deadline_exceeded",
            "preempt": "preempted"}[how]
        # what it got is a prefix of its own tokens: at most the step
        # that was read in the pass that retired it (preemption sits in
        # admission, after the read of the pass before)
        assert len(victim._generated) <= had + 1
        assert victim._generated == _ref(victim_prompt, 40)[
            :len(victim._generated)]
        loop.run_until_idle()
        if how == "deadline":
            with pytest.raises(DeadlineExceededError):
                victim.result(timeout=0)
        assert beside.result(timeout=0) == _ref(beside_prompt, 16)
        assert nxt.result(timeout=0) == _ref(nxt_prompt, 7)
        assert loop._inflight is None
        assert loop.snapshot()["pages_in_use"] == 0
        _assert_balance(loop)


def test_a_page_starved_slot_waits_and_goes_on():
    a, b = _prompt(13, 8), _prompt(14, 8)
    # 7 pages of 8: 2 each at admission, a third each at length 16; at
    # 24 the first takes the last page and the second waits for it to
    # retire
    with _loop(slots=2, n_pages=7, prefix_cache=False) as loop:
        streams = loop.submit_many([a, b], [20, 18])
        loop.run_until_idle()
        snap = loop.snapshot()
        assert [s.result(timeout=0) for s in streams] == [
            _ref(a, 20), _ref(b, 18)]
        assert snap["admission_waits"] > 0
        assert loop._inflight is None and snap["pages_in_use"] == 0


def test_a_pool_with_no_way_forward_fails_with_nothing_in_flight():
    with _loop(slots=2, n_pages=4, prefix_cache=False) as loop:
        streams = loop.submit_many([_prompt(15, 8), _prompt(16, 8)],
                                   [30, 30])
        loop.run_until_idle()
        assert all(s.finish_reason == "error" for s in streams)
        with pytest.raises(RuntimeError, match="pool exhausted"):
            streams[0].result(timeout=0)
        assert loop._inflight is None
        assert loop.snapshot()["pages_in_use"] == 0


# ------------------------------------- (3) idle means nothing in flight
def test_run_until_idle_and_close_leave_nothing_in_flight():
    prompt, ref, at = _with_an_end_of_sequence()
    with _loop(slots=1) as loop:
        stream = loop.submit(prompt, 14, eos_id=ref[at])
        while not stream.done:
            loop.tick()
        # the end came a step late: that step is still unread, and the
        # loop does not call itself idle
        assert loop._inflight is not None and loop.occupied_slots == 0
        with loop._cond:
            assert not loop._idle()
        loop.run_until_idle()
        assert loop._inflight is None
        with loop._cond:
            assert loop._idle()
    loop = _loop(slots=1, start=True)
    stream = loop.submit(prompt, 14, eos_id=ref[at])
    assert stream.result(timeout=120) == ref[:at + 1]
    loop.close()
    assert not loop._thread.is_alive()
    assert loop._inflight is None and loop.occupied_slots == 0


# ------------------------------------------------------ (4) the counter
def test_a_lone_stream_overlaps_every_dispatch_but_the_first():
    with _loop(slots=1, name="overlap-count") as loop:
        stream = loop.submit(_prompt(17, 9), 11)
        loop.run_until_idle()
        snap = loop.snapshot()
        assert stream.finish_reason == "max_tokens"
        assert snap["dispatches"] == 10      # the prefill gave the first
        assert snap["dispatches_overlapped"] == snap["dispatches"] - 1
        assert ('dl4j_decode_dispatches_overlapped_total'
                '{loop="overlap-count"} 9') in exposition.render_prometheus()


def test_the_speculative_lane_stays_in_turn():
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8], [7, 7, 7, 7]]
    with _loop(kernel="gather", speculation=3, drafter="ngram") as loop:
        streams = loop.submit_many(prompts, [16, 12])
        for _ in range(200):
            if all(s.done for s in streams):
                break
            loop.tick()
            assert loop._inflight is None    # read in the pass it ran
        snap = loop.snapshot()
    assert [s.result(timeout=0) for s in streams] == [
        _ref(p, n) for p, n in zip(prompts, [16, 12])]
    assert snap["dispatches"] > 0 and snap["dispatches_overlapped"] == 0


def test_step_seconds_do_not_overlap_and_sum_to_the_time_a_step_was_unread():
    """`dl4j_decode_step_seconds` starts at the read of the step before
    where the two overlapped: one observation a dispatch, and their sum
    no more than the wall time the steps were in flight (from the first
    dispatch to the last read), though every step but the first was
    enqueued before the one before it was read."""
    with _loop(slots=1, name="step-seconds") as loop:
        loop.submit(_prompt(19, 9), 11)
        loop.tick()                  # prefill, the first step enqueued
        first = loop._inflight.t0
        loop.run_until_idle()
        wall = loop._read_at - first
        hist = loop._m_step_s
        snap = loop.snapshot()
    assert snap["dispatches_overlapped"] == snap["dispatches"] - 1 == 9
    assert hist.count == snap["dispatches"]
    assert 0.0 < hist.sum <= wall * (1 + 1e-9)


def test_a_prefill_pass_is_counted_where_a_step_is_enqueued():
    """A pass that only read the step in flight enqueued nothing: it is
    no prefill pass even where a prefill ran in it (a request of one
    token admitted while the last step of another is unread)."""
    with _loop(slots=2) as loop:
        long = loop.submit(_prompt(23, 9), 3)
        while loop._lengths[0] < loop._stop[0] or loop._inflight is None:
            loop.tick()              # the last step of `long` is unread
        before = loop.snapshot()
        one = loop.submit(_prompt(29, 5), 1)
        assert loop.tick()           # prefills `one`, reads, enqueues none
        snap = loop.snapshot()
        assert one.done and long.done
    assert snap["dispatches"] == before["dispatches"]
    assert snap["prefill_passes"] == before["prefill_passes"] == 1
    assert (snap["phases"]["decode.prefill_dispatch"]["count"]
            == before["phases"]["decode.prefill_dispatch"]["count"] + 1)


# ------------------------------------------- (5) the books, every pass
def walk_the_books(loop):
    """What has to hold of the host's page books after EVERY pass,
    whatever is in flight: by kind, no physical page in two live slots'
    tables unless it is shared by count (the full kind under the prefix
    cache; a window page never), none both in a table and in that
    kind's free list, free + held (+ cached and unread) = the pool, and
    the table rows the steps are handed are the slots' own lists."""
    trash = loop._trash
    held = {}
    for i, slot in enumerate(loop._slot_state):
        row = loop._table[i]
        if slot is None:
            assert (row == trash).all(), (i, row)
            assert loop._lengths[i] == 0 and loop._stop[i] == 0
            continue
        n = len(slot.pages)
        assert row[:n].tolist() == slot.pages and (row[n:] == trash).all()
        assert len(set(slot.pages)) == n
        for page in slot.pages:
            held[page] = held.get(page, 0) + 1
    free = list(loop._free)
    assert len(set(free)) == len(free)
    assert not set(free) & set(held)
    for page, readers in held.items():
        # more readers than slots only while an export pins the page
        assert loop._ref[page] >= readers
        if readers > 1:
            assert loop._prefix is not None
    assert all(loop._ref[p] == 0 for p in free)
    _assert_balance(loop)
    win = loop._win
    if win is None:
        return
    from deeplearning4j_tpu.serving import paged_kinds
    columns = paged_kinds.window_table_pages(loop.cfg, loop.page_size)
    w_held = []
    for i, slot in enumerate(loop._slot_state):
        lo, hi = int(win.lo[i]), int(win.hi[i])
        row = win.table[i]
        if slot is None:
            assert lo == hi == 0
        assert (row[:lo] == win.trash).all() and (row[hi:] == win.trash).all()
        assert (row[lo:hi] != win.trash).all()
        assert hi - lo <= columns
        w_held += row[lo:hi].tolist()
    w_free = list(win.free)
    assert len(set(w_held)) == len(w_held), "a window page with two owners"
    assert len(set(w_free)) == len(w_free)
    assert not set(w_free) & set(w_held)
    assert len(w_free) + len(w_held) == win.n_pages


def _walked(loop):
    """Have the loop walk its books after every pass, whoever drives
    it (the scheduler thread calls `self.tick`); returns the counts the
    walk keeps: passes, and steps read for a slot that had been taken
    again since they were dispatched."""
    seen = {"passes": 0, "retaken_unread": 0, "late_for_empty": 0}
    tick, read = loop.tick, loop._read_step

    def read_step(step):
        for i, slot in step.members:
            now = loop._slot_state[i]
            if now is not slot:
                seen["retaken_unread" if now is not None
                     else "late_for_empty"] += 1
        read(step)

    def walked_tick():
        ran = tick()
        seen["passes"] += 1
        with loop._cond:
            walk_the_books(loop)
        return ran

    loop._read_step, loop.tick = read_step, walked_tick
    return seen


def _drain_walking(loop, streams):
    seen = _walked(loop)
    loop.run_until_idle()
    assert seen["passes"] > 0
    return [s.result(timeout=0) for s in streams]


@pytest.mark.parametrize("kw", [
    dict(prefix_cache=False), dict(prefix_cache=True, horizon=2),
    dict(prefix_cache=True, n_pages=9)],
    ids=["plain", "prefix-cache-horizon2", "prefix-cache-tight-pool"])
def test_the_books_balance_after_every_pass_gpt2(kw):
    prompts = [_prompt(seed, t) for seed, t, _ in JOBS]
    budgets = [n for *_, n in JOBS]
    prompts += [prompts[1], prompts[3], prompts[1]]
    budgets += [7, 4, 9]
    with _loop(**kw) as loop:
        streams = loop.submit_many(prompts, budgets)
        got = _drain_walking(loop, streams)
    assert got == [_ref(p, n) for p, n in zip(prompts, budgets)]


# --------------- (6) the geometry the refusal of PR 33 points at: window
# pages exactly tight, every page boundary a release and a grant in one
# pass, slots retired and taken again under an unread step
MOE_SLOTS, MOE_PAGE = 4, 4


def _moe_model():
    """The tiny window model with its blocks' matrices 16 times their
    initial size, for the reason given at `PARAMS`: at the initial size
    it repeats one token, and a key dropped from a window or a token a
    step stale would serve the right tokens by luck."""
    cfg = moe_family.model_config(MOE_CONFIG)
    params = weights.make_params(2 ** 31 + 29, moe_family, MOE_CONFIG)
    params = dict(params, blocks=jax.tree_util.tree_map(
        lambda a: a * 16 if a.ndim >= 2 else a, params["blocks"]))
    return cfg, params


def _tight_moe_loop(cfg, params, **kw):
    """The window kind's free list exactly tight (slots x (ceil((W - 1)
    / page) + 1) pages) and the full pool exactly slots x pages a
    slot."""
    from deeplearning4j_tpu.serving import paged_kinds
    columns = paged_kinds.window_table_pages(cfg, MOE_PAGE)
    return DecodeLoop(params, cfg, slots=MOE_SLOTS, page_size=MOE_PAGE,
                      n_pages=MOE_SLOTS * (cfg.max_len // MOE_PAGE),
                      window_pages=MOE_SLOTS * columns,
                      prefix_cache=False, **kw)


def _moe_jobs(clients, per_client, seed=7):
    """By client, (prompt, budget, eos_id or None): contexts of twice
    the window and more (prompt 12-30, up to 64 in all), budgets that
    share no period so that ends stay staggered, a third of the
    requests with an end-of-sequence token that may or may not come."""
    rng = np.random.RandomState(seed)
    budgets = (7, 19, 11, 29, 13, 23, 5, 17)
    plan = []
    for k in range(clients):
        row = []
        for j in range(per_client):
            n = budgets[(3 * k + j) % len(budgets)]
            if j == 0:
                n = max(2, n * (k + 1) // clients)
            plen = int(rng.randint(12, 31))
            n = min(n, 64 - plen)
            eos = int(rng.randint(0, 97)) if (k + j) % 3 == 0 else None
            row.append((rng.randint(0, 97, (plen,)).astype(np.int32), n,
                        eos))
        plan.append(row)
    return plan


def _check_against_the_reference(params, done):
    """Every served token is the float32 reference's best at its
    position, teacher-forced on the served tokens (one padded width, so
    the reference compiles once), and every stream ended where it had
    to."""
    ref = moe_family.reference()
    tokens_seen = set()
    for prompt, budget, eos, out, reason in done:
        assert out, "a request with no token"
        if reason == "eos":
            assert out[-1] == eos and eos not in out[:-1]
        else:
            assert reason == "max_tokens" and len(out) == budget
            assert eos is None or eos not in out
        seq = np.zeros((1, 64), np.int32)
        n = len(prompt) + len(out) - 1
        seq[0, :n] = np.concatenate([prompt, out])[:-1]
        lg = np.asarray(ref.logits(MOE_CONFIG, params, jnp.asarray(seq),
                                   0, 64))[0, len(prompt) - 1:n]
        gap = lg.max(-1) - lg[np.arange(len(out)), out]
        assert gap.max() < 1e-4, (len(prompt), out, gap.tolist())
        tokens_seen.update(out)
    # the model does not repeat one token: a wrong context would show
    assert len(tokens_seen) > 40


def _closed_loop_by_hand(loop, plan):
    """Clients that send their next request in the pass after the last
    one ended; returns [(prompt, budget, eos, tokens, finish_reason)]."""
    rows = [list(r) for r in plan]
    live = [None] * len(rows)
    done = []
    while any(rows) or any(s is not None for s in live):
        for k, cur in enumerate(live):
            if cur is not None and cur[0].done:
                stream, job = cur
                done.append((*job, stream.result(timeout=0),
                             stream.finish_reason))
                live[k] = cur = None
            if cur is None and rows[k]:
                job = rows[k].pop(0)
                live[k] = (loop.submit(job[0], job[1], eos_id=job[2]), job)
        loop.tick()
    return done


def _closed_loop_threaded(loop, plan):
    import threading
    done, errors = [], []

    def client(row):
        try:
            for job in row:
                stream = loop.submit(job[0], job[1], eos_id=job[2])
                done.append((*job, stream.result(timeout=300),
                             stream.finish_reason))
        except BaseException as e:  # noqa: BLE001 - shown by the test
            errors.append(e)

    threads = [threading.Thread(target=client, args=(row,)) for row in plan]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not errors, errors
    return done


@pytest.mark.parametrize("threaded,per_client", [(False, 75), (True, 40)],
                         ids=["by-hand-300-requests",
                              "threaded-160-requests"])
def test_tight_window_pages_under_a_closed_loop(threaded, per_client):
    cfg, params = _moe_model()
    plan = _moe_jobs(MOE_SLOTS, per_client)
    with _tight_moe_loop(cfg, params, start=False) as loop:
        seen = _walked(loop)
        if threaded:
            import threading
            loop._thread = threading.Thread(target=loop._run, daemon=True)
            loop._thread.start()
            done = _closed_loop_threaded(loop, plan)
        else:
            done = _closed_loop_by_hand(loop, plan)
            loop.run_until_idle()
        snap = loop.snapshot()
    assert len(done) == MOE_SLOTS * per_client
    _check_against_the_reference(params, done)
    win = snap["pages_by_kind"]["window"]
    # every slot held its full window's pages, and the kind never had a
    # page to spare: tight, and no slot ever waited for a page
    assert win["pages_per_slot_peak"] == win["table_pages"]
    assert win["peak_pages_in_use"] == win["pages_total"]
    assert win["released"] > 10 * len(done) / 4
    assert snap["pages_in_use"] == 0 and win["pages_in_use"] == 0
    assert snap["dispatches_overlapped"] > 0.9 * snap["dispatches"]
    # ends came a step late, and slots were taken again under a step
    # dispatched for their last occupant
    assert any(reason == "eos" for *_, reason in done)
    if threaded:
        # a client thread has to wake before it sends its next request:
        # the late step is mostly read before the slot is taken again
        assert seen["retaken_unread"] + seen["late_for_empty"] > 0
    else:
        assert seen["retaken_unread"] > 0
    assert seen["passes"] >= snap["dispatches"]


@pytest.mark.parametrize("fault", ["a-window-page-a-step-early",
                                   "mirrors-uploaded-over-the-device"])
def test_the_checks_see_the_faults_they_are_for(fault, monkeypatch):
    """Planted faults of the size the refusal of PR 33 would have been:
    a window page released one step before its last key leaves the
    window, and the host's lagging mirrors written over the device's
    tokens and lengths. The served tokens must leave the reference's."""
    cfg, params = _moe_model()
    plan = _moe_jobs(MOE_SLOTS, 6)
    with _tight_moe_loop(cfg, params, start=False) as loop:
        if fault == "a-window-page-a-step-early":
            release = loop._win.release_before
            monkeypatch.setattr(
                loop._win, "release_before",
                lambda slot, cursor: release(slot, cursor + 1))
        else:
            enqueue = loop._enqueue_step

            def reupload():
                loop._host_rows[:] = True
                return enqueue()

            monkeypatch.setattr(loop, "_enqueue_step", reupload)
        done = _closed_loop_by_hand(loop, plan)
        loop.run_until_idle()
    with pytest.raises(AssertionError):
        _check_against_the_reference(params, done)


# ---------------- (7) what a step writes for a slot already at its stop
@pytest.mark.parametrize("family", ["gpt2", "window-and-experts"])
def test_a_slot_at_its_stop_writes_to_the_trash_page_of_every_kind(family):
    """With a step in flight a slot that reached its `stop` still has
    its last occupant's pages in the table of the step enqueued behind
    (at the parent it always had the trash page there). `_row_dest`
    sends its row to the trash page of EVERY kind: no real page of any
    layer changes, whatever the table maps."""
    from deeplearning4j_tpu.serving import paged_kinds
    if family == "gpt2":
        cfg, params, ps = CFG, PARAMS, 8
    else:
        (cfg, params), ps = _moe_model(), MOE_PAGE
    pages = {k: 6 for k in paged_kinds.kinds_of(cfg)}
    pool = paged_kinds.init_pool(cfg, pages, ps)
    rng = np.random.RandomState(0)
    pool = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype), pool)
    # slot 0 advances; slot 1 sits at its stop with real pages mapped
    tables = {k: jnp.asarray([[0, 1, 6, 6], [2, 3, 4, 6]], jnp.int32)
              for k in pages}
    lengths = jnp.asarray([ps + 1, 2 * ps + 2], jnp.int32)
    stop = jnp.asarray([ps + 2, 2 * ps + 2], jnp.int32)
    tokens = jnp.asarray([5, 7], jnp.int32)
    _, out, _ = paged_kinds.decode_step(params, tokens, pool, tables,
                                        lengths, lengths < stop, cfg)
    for before, after in zip(pool.layers, out.layers):
        for name in ("k", "v"):
            b, a = np.asarray(before[name]), np.asarray(after[name])
            changed = {int(p) for p in np.nonzero(
                (b != a).reshape(b.shape[0], -1).any(axis=1))[0]}
            # slot 0 wrote its row in its second page; slot 1's row went
            # to the trash page (the last), not to page 4 at offset 2
            assert changed == {1, 6}, changed


# ------------------------------ (8) uploads are copies, not views
def test_uploads_are_copies_the_host_can_write_on():
    """The mirrors go up as copies: the host writes on in them while a
    step that took the upload is in flight, and nothing waits for that
    step before the next grant, release or retirement."""
    cfg, params = _moe_model()
    with _tight_moe_loop(cfg, params, start=False) as loop:
        loop.submit(np.arange(13, dtype=np.int32), 9)
        loop.tick()
        assert loop._inflight is not None
        uploads = [(loop._d_stop, loop._stop),
                   (loop._d_table["full"], loop._table),
                   (loop._d_table["window"], loop._win.table)]
        kept = [np.array(d) for d, _ in uploads]
        for (d, mirror), was in zip(uploads, kept):
            assert d.unsafe_buffer_pointer() != mirror.ctypes.data
            mirror += 1
            assert (np.asarray(d) == was).all()
            mirror -= 1
        loop.run_until_idle()
