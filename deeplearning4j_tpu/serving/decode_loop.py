"""Continuous-batching decode loop over a paged KV pool.

The per-request `generate_cached` path compiles one whole-decode scan
per (B, T0, n_tokens) signature and serves requests one at a time: one
slow request blocks everything behind it, and every request pays its
full `n_tokens` even after EOS. `DecodeLoop` replaces that with the
modern serving shape (the PagedAttention / continuous-batching lineage;
ROADMAP "Continuous batching + paged KV cache"):

- a fixed pool of **S slots** rides ONE jitted decode step
  (`paged_kinds.decode_step` + on-device argmax feedback). Slot
  membership is a traced per-slot `stop` bound, never a shape — the
  step compiles exactly once and requests join/leave without
  recompiling for the life of the server (`decode_step_programs()`
  pins this in tests and bench);
- KV lives in a **paged block pool**: a request holds
  `ceil(tokens/page_size)` pages, pages return to the free list the
  moment it completes, and admission is a free-page check — memory
  scales with tokens actually written, not `max_len × requests`;
- a **scheduler thread** admits queued prompts into freed slots between
  steps (bucketed compiled prefill scatters the prompt's K/V into the
  slot's pages), and emits tokens onto per-request `GenerationStream`s
  as they come off the chip — the HTTP layer streams them to clients
  (`server.py /generate`);
- per-slot **max_tokens / EOS** termination: a finished stream frees
  its slot and pages immediately; the other slots never notice.

The device carry — last tokens, pool, page table, lengths, stop bounds
— feeds straight back into the next dispatch. The page table and the
stop bounds are the host's: it uploads them whole after a visible event
(admission, completion, page grant). The tokens and the lengths are the
device's: a step hands them to the next one and the host sets a slot's
row only where it is the authority, at admission and at retirement
(`_host_rows`, one program of fixed shape). Steady-state per-token cost
is one dispatch slice plus the token D2H the streams need anyway.

**A second step in flight** (the plain lane, every model, every
horizon; docs/SERVING.md "The order of a pass"): a pass enqueues step
N+1 BEFORE it reads step N, so the host's pass and the read-back run
under the device's next step and not between two steps. The order of a
pass:

1. reap cancelled and expired slots, run page jobs, admit (a prefill is
   enqueued behind the step in flight; its slot joins the next step);
2. with step N enqueued and unread, prepare and enqueue step N+1:
   window pages released, pages granted, the copy-on-write fence run,
   stop bounds set, all against the DISPATCHED cursor, table and stop
   uploaded when they changed, the step called on the device's own
   tokens, lengths and pool;
3. only then read step N (`decode.d2h` waits for what is left of N),
   account, flush first tokens (their read waits on the prefill, under
   a `decode.d2h` span of its own), emit N's tokens and retire what
   finished, while the device runs N+1.

The dispatched cursor is `_lengths`: where a slot's length will stand
on the device once every enqueued step has run, i.e. what was emitted
plus what the step in flight will consume, `clip(stop - length, 0,
horizon)`, the device's own rule (`lengths < stop`, `horizon` times).
Nothing step N+1 needs comes from the host's reading of step N. What
has to hold (tests/test_decode_overlap.py has a test for each):

- The host's mirrors lag a step; the device is never overwritten from
  them. Tokens and lengths of a slot are set on the device only where
  the host is the authority (admission, retirement, after a speculative
  round: `_host_rows`) by ONE program of fixed shape over all slots
  (`_set_rows`, compiled by the first admission, so in warm-up). Uploads
  are copies of the mirrors (`_upload`), never views the host writes on:
  a transfer reads the host's buffer after the call returns, and with a
  step in flight nothing waits for it before the mirrors change again.
- A step's tokens reach a stream only if the slot still holds the
  request the step was dispatched for (the slot OBJECT is compared, not
  its index: the slot may have been taken again). An end-of-sequence
  token therefore costs one wasted step, never a wrong token: the slot
  has already run in step N+1, the stream ends at the token, and the
  extra position landed in the slot's own page (the fence made it
  private). A slot that ends by `max_tokens` overshoots nothing (the
  device stops it at `stop`); cancellation, deadline and preemption
  retire the same way.
- A page has one owner at every point of the device's order. Every
  program that touches the pool takes the pool the program before it
  returned (`self._pool`), so the device runs them in the order they
  were enqueued; and pages are only ever granted while work is being
  PREPARED, on the scheduler thread. So a page given back while a step
  that reads or writes it is in flight (a window release in
  `_release_window`, a retirement in `_retire`, a fork or an eviction in
  `_cow_guard` / `_alloc_page`) can be granted, in the same pass or a
  later one, only to work enqueued BEHIND that step: a prefill, a
  fork's copy, an install, the next step. Its new owner writes each
  position before it reads it, so what the step in flight left there
  (the wasted position of an end of sequence) counts for nothing.
- A step enqueued for a slot that had already reached its `stop` writes
  nothing a later owner can see: `paged_kinds._row_dest` sends the row
  of a slot with `lengths >= stop` (not `live`) to the trash page of
  EVERY kind, whatever its table still maps; at the parent such a
  slot's table held the trash page anyway, with a step in flight it may
  still map its last occupant's pages.
- Nothing is in flight when the loop says it is idle (`_drained`:
  `_idle`, `run_until_idle`, `close`; `_fail_all` drops the handle; the
  stuck-pool check runs only in a pass that neither enqueued nor read).
  No name keeps a device array of a step beyond `decode.d2h` but the one
  handle of the step in flight (`_inflight.out`; PERF.md section 6,
  PR 32).
- One order of a pass, not two: no option turns the overlap off, no
  model is asked its name. If the host's pass outlasts the device's
  step the read returns at once and the order degrades to one step at a
  time by itself. The speculative lane stays in turn because its
  drafter reads a slot's tokens on the host before it can propose
  (`spec_k`): a verify round, and the plain step it falls back to, are
  read in the pass that dispatched them.

`dl4j_decode_dispatches_overlapped` (`snapshot()
["dispatches_overlapped"]`) counts the steps enqueued while the step
before them was unread.

On accelerators the
pool is donated to the step and KV updates alias in place: that holds
because the step's write (`paged_kinds._write_rows`) keeps the pool in the
layout the paged kernel reads, so the compiled step holds no copy of
the pool (tests/test_paged_step_layout.py pins it; the earlier
two-index scatter was donated too and still copied each layer's pool
four times a step). CPU ignores donation (gated off to avoid the
warning, same as InferenceEngine).

**Decode horizon**: `horizon=K` runs K decode steps inside one compiled
dispatch (a `lax.scan` feeding each slot's argmax back on device). The
per-slot `stop` bound makes ragged membership exact — a slot never
writes past its token budget or its allocated pages, whatever K is —
and the host trims EOS overshoot (at most K-1 speculative tokens of
the chunk are discarded, and the chunk already in flight behind it;
admission waits at most one chunk). K=1 (the default) is
pure token-boundary scheduling; dispatch-bound hosts raise it to
amortize the per-step round trip (`bench.py serve` runs the CPU smoke
at K=8).

Backpressure: a request is admitted only when the pool can cover its
prompt plus the first decode write; a mid-flight slot that needs a page
with the pool empty simply stops advancing (its `stop` clamps to the
allocated frontier) until a completion frees pages. If every occupied
slot is stalled and nothing can ever free a page, the stalled streams
fail with a clear error instead of deadlocking — size the pool with
`paged_kv_bytes` (docs/SERVING.md).

**Prefix caching** (`prefix_cache=True`, the default): a
content-addressed index (`prefix_cache.PrefixIndex`, a radix trie over
page-aligned token-id chunks) sits in front of admission. Pages become
REFCOUNTED: a request whose prompt starts with cached chunks maps those
pool pages into its page table by reference and prefills only the
uncovered tail (`paged_kinds.prefill_ctx` — the tail attends to the shared
prefix through the pool); a fully-covered prompt skips prefill
entirely and replays its last prompt token through the decode step.
Shared pages are read-only: the first divergent write — the decode
cursor entering a page another reader or the cache retains —
copy-on-write forks it into a private page (`copy_page`, the one small
jitted helper sharing adds; `decode_step_programs()` stays 1 for the
life of the server). A page returns to the free list only when its
last reader retires; full PROMPT pages of a retiring request seed the
cache instead, and an LRU tier evicts unreferenced-but-cached pages on
demand — the cache never starves live admission or decode growth.
Because shared pages are read-only until forked, cached-prefix output
is bit-identical to the cold prefill's by construction for the shared
positions (tests pin whole-output equality). Per-request opt-out:
`submit*(..., prefix_cache=False)` neither matches nor seeds the cache
(secret-bearing prompts must not leak into shared pages).

**Decode kernel** (`kernel="auto"|"pallas"|"gather"`): the attention
read inside the compiled step. "pallas" streams each slot's WRITTEN
pages straight from the pool (`attention/paged_pallas.py` — per-step
KV traffic O(written pages)); "gather" materializes the dense
`S × max_len` window (the legacy path, O(reservation)). "auto"
resolves ONCE at construction — the kernel on TPU inside the
envelope checked on a chip, gather everywhere else (never a silent
interpret-mode slowdown off-TPU) — so the step stays one compiled
program either way. Both figures are exported every dispatch as
dl4j_decode_kv_read_bytes{path="kernel"|"gather"} so the traffic win
is visible whichever lane runs.

**Speculative decoding** (`speculation=k`, default off): each scheduler
round, a drafter (serving/speculation.py — "ngram" prompt-lookup fed by
the slot's own history and the prefix-cache trie, or "model" with a
small draft transformer) proposes up to k continuation tokens per slot,
and ONE widened verify dispatch (`paged_kinds.verify_step` — the
horizon idea turned sideways: k+1 positions of one step instead of k+1
chained steps) scores every position against the target model. The
longest prefix where the draft matches the target's own argmax is
accepted, plus the target's token at the first mismatch — so emitted
output is BIT-IDENTICAL to non-speculative greedy decode by
construction, and a wrong draft costs acceptance rate, never
correctness. Accept/rollback is pure host bookkeeping: the per-slot
length cursor advances by `accepted + 1`; rejected positions' K/V
writes landed in pages the slot privately owns (the CoW guard forks the
whole write range `[length, stop)` before dispatch, exactly as for
horizon), are never readable (attention masks key positions past every
query's cursor), and are overwritten before the cursor passes them.
Opt-out per request with `submit*(..., speculation=False)` (HTTP
`"speculation": false`) — that slot rides every verify at width 1,
i.e. a plain decode step. Speculation and `horizon>1` are mutually
exclusive: speculation is its own chunking. The compiled surface grows
by exactly one program (decode + verify; `decode_step_programs()`
counts both and tests/bench pin <= 2). Telemetry:
dl4j_spec_{proposed,accepted,rounds} counters and an acceptance-rate
gauge in snapshot()/stats (docs/SERVING.md "Speculative decoding").

**SLO tiers + preemption** (`tier="interactive"|"batch"` on submit):
every stream carries a priority tier. Interactive (the default) is the
latency tier; batch is the bulk lane riding the same slots and pages.
Admission is tier-priority (every interactive arrival goes ahead of
every batch one, FIFO within a tier), batch holds at most a
weighted-fair share of the slots while interactive work wants the
machine (`batch_share`, default half) and soaks ALL idle capacity when
none does, batch sheds at its own lower `batch_max_waiting` bound with
a Retry-After derived from the batch backlog, and a blocked interactive
admission PREEMPTS batch slots: the victim (fewest tokens emitted — the
cheapest resume) retires with finish_reason `"preempted"`, its pages
return to the pool, and its full prompt pages seed the prefix cache so
the router-side durable-stream resume replays the prefix nearly for
free. Preemption is pure host bookkeeping — slot retirement, exactly
the cancel/deadline path — so `decode_step_programs()` stays pinned
(docs/SERVING.md "Priority tiers").

Telemetry: dl4j_kv_pages_total / dl4j_kv_pages_in_use /
dl4j_kv_pages_shared / dl4j_kv_pages_cached /
dl4j_decode_active_slots gauges, dl4j_decode_requests /
dl4j_decode_tokens_streamed / dl4j_decode_admission_waits /
dl4j_kv_prefix_{hits,misses,forks,evictions} /
dl4j_decode_kv_read_bytes{path} counters, dl4j_decode_step_seconds
histogram (docs/OBSERVABILITY.md).

**Kinds of layer** (`cfg.layer_kinds`): a "full" layer holds every key
of a sequence, a "window" layer only ever reads the last `cfg.window`.
The loop does not know which model it serves. It keeps pages BY KIND:
each kind has its own pool size, page table and free list, the tables
and page ids it hands the programs are dicts by kind, and
`serving/paged_kinds.py` is the device side for every model
(`models.model_of(cfg)` finds the model's one forward; a model whose
layers are all full has one kind). The full kind rides the lists of this
class, a window kind `_WindowPages` (`self._win`, None where no layer has
a window). Admission checks every kind; a prompt longer than the window
claims, for the window kind, only the pages that still hold a key its
first decoded token can see; and every pass returns to the window kind's
free list the pages whose last key has left the window
(`decode.release_window`), the table taking the trash page in their
place. A stall for pages of any kind is the same stall.
`snapshot()["pages_by_kind"]` has each kind's pages; `pages_total` and
`peak_pages_in_use` are the one kind's pages or, where there are several
kinds, sums weighted by the kind's layers (one page of a kind spans all
layers of that kind). A step and a prefill hand back `(tokens, aux)`:
`aux` is what the model's layers count, the (token, expert) pairs by
held expert where the configuration has an expert layer
(`snapshot()["moe"]`), nothing otherwise, and it comes back in the read
the tokens make anyway. What the host's side cannot do yet for what a
model has is an error at construction that names it (`_check_refusals`):
prefix sharing, speculation, a horizon above 1 and the prefill role, for
a window kind (it gives pages back), an expert layer's counts, or a
kind held by slot.

**Kinds that are not pages** (`paged_kinds.SLOT_KINDS`). A `linear`
layer (`models/hybrid_transformer.py`) keeps a recurrent state and a few
convolution columns a sequence, however long, a `conv` layer only the
columns of its short convolution: arrays indexed by SLOT in the same
donated pool, `(slots, ...)` a layer, each kind its own
(`cfg.slot_state`). They need no grant, no table and no free list:
admission is bounded by slots, and by the full kind's pages as ever. A
cold prefill is told each row's slot beside its pages
(`page_ids[kind]`) and overwrites that slot's entry whole, so
a retired slot's state never reaches the next request; an idle slot's
update is masked in the step. The slot keeps ONE state, the newest: a
later piece of a prompt that is prefilled in pieces starts from it
(below), but whatever reuses a page would need the state at that page's
end, which nothing keeps (snapshots at a page boundary are not
written): prefix sharing (and with it copy-on-write and `/kv/export`),
speculation, a horizon above 1 and the prefill role are refused by
name. Preemption retires the slot and the router re-admits prompt +
delivered: an honest second scan. `snapshot()["state"]` says what the
kinds hold together, `snapshot()["state_by_kind"]` each kind's share.

**A bound on the tokens a pass prefills** (`prefill_tokens_per_pass`,
None = no bound): a pass stops claiming queued requests once the rows it
would prefill, each padded to its bucket, pass the bound (it always
claims one); the rest wait for the next pass, behind one decode step.
A burst of long prompts then neither needs the memory of one program
over all of them nor holds every running stream for the sum of their
prefills.

**A prompt longer than the bound is prefilled a piece a pass**
(`_continue_prefills`; where pages are not shared by content and no
layer has a window, else prompts stay whole). The piece is the largest
bucket within the bound. Admission claims the slot and ALL the prompt's
pages and prefills the first piece by the cold program; the slot then
rides no decode step (no table row, stop 0: the step masks it and its
state is its own). Every later pass first enqueues the next piece of
every such prompt, oldest first, as far as the bound allows (always
one), through ONE program a bucket whatever the context's length
(`prefill_chunk_fn`, `paged_kinds.prefill_ctx`): a linear layer scans on
from the state and the columns its slot kept, a full layer writes the
piece's K/V to its pages and attends over the pages of the pieces
before it and its own through the flash kernel with a query offset
(the loop's kernel lane), no array of scores. Then the pass admits what
the bound still leaves and enqueues the decode step: the running
streams wait for one piece between two of their tokens, never for the
whole prompt. The last piece (its bucket no narrower than a quarter of
a piece: three programs) gives the first token, and the slot joins the
next step. `snapshot()["prefill_chunks"]` counts the pieces of such
prompts, `first` from a zero state and `carried` on a kept one, and
their tokens (dl4j_prefill_chunks{loop,carried},
dl4j_prefill_chunk_tokens{loop}).

**Spans** (`telemetry.span`, names in `PHASES`): every scheduler pass
is one `decode.tick` whose children are the phases of the pass, so each
nanosecond of a pass lies in exactly one child or in the tick's self
time. Their seconds and counts are always on
(dl4j_decode_phase_seconds{loop,phase}, `snapshot()["phases"]`), and
`decode.tick` and `decode.d2h` also read the scheduler thread's own CPU
clock (`cpu_seconds` beside `seconds`; dl4j_decode_phase_cpu_seconds):
wall less CPU is time the thread did not run. The longest pass of each
of the last `SLOW_TICKS_KEPT` intervals is kept with what it was made of
(`snapshot()["slow_ticks"]`: its phases, `cpu_ms` and `d2h_cpu_ms`,
`offcpu_ms` = `dur_ms` - `d2h_ms` - (`cpu_ms` - `d2h_cpu_ms`), the time the
pass neither ran nor waited on the device, and from the process's
`telemetry.host` monitor the collector's `gc_ms` and highest `gc_gen`,
the heartbeat's worst delay `lag_ms`, the thread's context switches
`vcsw`, `ivcsw` and major faults `majflt`; `decode.prefill_dispatch`
carries `bb`, `tb`, `rows`, `tokens`, the context pages it reads `ctx`
and `carried`, whether it starts from a kept state), and every span is a
TraceMe of the same name, so a `jax.profiler` window holds the
scheduler's phases on the device trace's clock, beside the collector's
`host.gc.gen*`. `snapshot()["host"]` is the monitor's own record. A
request carries four stamps of its own life
(`GenerationStream.timeline()`); the wait in the queue also feeds
dl4j_decode_queue_wait_seconds.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
import weakref
from collections import deque
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.attention.paged_pallas import (
    block_pages, resolve_decode_kernel)
from deeplearning4j_tpu.models.moe_rows import rows_moved
from deeplearning4j_tpu.serving.errors import (TIER_BATCH,
                                               TIER_INTERACTIVE, TIERS,
                                               Deadline,
                                               DeadlineExceededError,
                                               OverloadedError,
                                               backlog_retry_ms)
from deeplearning4j_tpu.serving import fleetkv, paged_kinds
from deeplearning4j_tpu.serving.paged_kv import (copy_page, extract_page,
                                                 install_page,
                                                 pages_for_tokens,
                                                 pages_per_slot,
                                                 prompt_buckets)
from deeplearning4j_tpu.serving.prefix_cache import PrefixIndex
from deeplearning4j_tpu.serving.speculation import build_drafter
from deeplearning4j_tpu.telemetry import host
from deeplearning4j_tpu.telemetry.trace import (SLOWEST_INTERVAL_S,
                                               SLOWEST_KEPT, PhaseTotals,
                                               active_tracer, slowest,
                                               slowest_slot, span)
from deeplearning4j_tpu.testing import chaos
from deeplearning4j_tpu.utils.jitcache import jit_cache_size

__all__ = ["GenerationStream", "DecodeLoop", "ROLES", "ROLE_UNIFIED",
           "ROLE_PREFILL", "ROLE_DECODE"]

_DONE = object()
_loop_seq = itertools.count()


def _pow2(n: int) -> int:
    """The least power of two >= n: the ladder that batches of rows and
    tables of cached pages are padded to, so programs stay few."""
    p = 1
    while p < n:
        p *= 2
    return p

#: replica roles (docs/FLEET.md "Disaggregated roles"): a `unified`
#: loop serves prefill AND decode (the default — existing deployments
#: are unchanged); a `prefill` loop only computes prompt KV into its
#: trie for `/kv/export` handoff (submit/generate are refused, so its
#: compiled surface never grows a decode program); a `decode` loop is
#: a unified loop the fleet routes streams at — the tag exists so the
#: router/fleet can place work, not to change loop behavior.
ROLE_UNIFIED = "unified"
ROLE_PREFILL = "prefill"
ROLE_DECODE = "decode"
ROLES = (ROLE_UNIFIED, ROLE_PREFILL, ROLE_DECODE)

#: the scheduler's spans. `decode.tick` is one pass; `decode.idle_wait`
#: is the scheduler thread asleep between passes; `decode.prefill_dispatch`
#: is a child of `decode.admit`; every other one is a child of the tick
#: (`decode.d2h` once for a step's tokens and once more in a pass that
#: has prefill groups' first tokens to read).
TICK = "decode.tick"
IDLE_WAIT = "decode.idle_wait"
PREFILL_DISPATCH = "decode.prefill_dispatch"
RELEASE_WINDOW = "decode.release_window"
PHASES = (IDLE_WAIT, TICK, "decode.reap", "decode.kv_jobs", "decode.admit",
          PREFILL_DISPATCH, RELEASE_WINDOW, "decode.grant_pages",
          "decode.upload",
          "decode.draft", "decode.step_dispatch", "decode.d2h",
          "decode.account", "decode.flush_first", "decode.emit")
#: the phases that also count the scheduler thread's CPU time
CPU_PHASES = (TICK, "decode.d2h")

#: `snapshot()["slow_ticks"]` holds the longest pass of each of the last
#: SLOW_TICKS_KEPT intervals of SLOW_TICK_INTERVAL_S seconds: about the
#: last minute of service, however long start-up's passes were
SLOW_TICKS_KEPT = SLOWEST_KEPT
SLOW_TICK_INTERVAL_S = SLOWEST_INTERVAL_S

#: per-queued-item service estimate feeding the backlog-derived
#: Retry-After on a tier shed: interactive items are short user turns,
#: batch items long bulk rows — a deep batch backlog should tell its
#: client to come back much later than an interactive blip would
_TIER_ITEM_MS = {TIER_INTERACTIVE: 50.0, TIER_BATCH: 250.0}


#: what `DecodeLoop._check_refusals` says, by the kind of layer that
#: stands in the way and by what was asked. A window kind gives its
#: pages back as the cursor moves (and an expert layer's counts are read
#: back from the plain step and the cold prefill only); the state of a
#: kind held by slot is no page: a slot keeps the newest alone (enough to
#: go on from, as a prompt prefilled in pieces does), so whatever reuses
#: a page would need the state at that page's end, and snapshots of state
#: at a page boundary are not written
_REFUSALS = {
    paged_kinds.KIND_WINDOW: {
        "prefix_cache":
            "prefix sharing is not written for a model with window "
            "layers (a shared page would have to be shared in every "
            "kind, and a window layer gives its pages back): pass "
            "prefix_cache=False",
        "speculation":
            "speculation is not written for a model with window "
            "layers (a rejected draft's rows would need a window "
            "page back): pass speculation=0",
        "horizon":
            "horizon > 1 is not written for a model with window "
            "layers (pages are returned once a pass, between "
            "steps): pass horizon=1",
        "role":
            "a prefill-role loop ships pages over /kv/export, which "
            "is not written for a model with window layers"},
    **{kind: {
        "prefix_cache":
            f"prefix sharing (and with it copy-on-write forks and "
            f"/kv/export) is not written for a model with {kind} "
            f"layers (a shared prefix's pages say nothing of the "
            f"{what} at its end, and a slot keeps only its newest, no "
            f"snapshot at a page boundary): pass prefix_cache=False",
        "speculation":
            f"speculation is not written for a model with {kind} "
            f"layers (a rejected draft has already moved the slot's "
            f"{what}, and there is no snapshot to go back to): pass "
            f"speculation=0",
        "horizon":
            f"horizon > 1 is not written for a model with {kind} "
            f"layers (the chained step has not been checked against "
            f"{what} updated in place): pass horizon=1",
        "role":
            f"a prefill-role loop ships pages over /kv/export, which "
            f"is not written for a model with {kind} layers (a page "
            f"list says nothing of the {what})"}
       for kind, what in ((paged_kinds.KIND_LINEAR, "recurrent state"),
                          (paged_kinds.KIND_CONV, "kept columns"))},
}


class GenerationStream:
    """One in-flight generate request: a token queue the scheduler
    pushes into as the slot emits, plus the blocking `result()` the
    non-streaming path uses.

    `tokens()` yields generated token ids as they come off the chip
    (the HTTP streaming response iterates it); `result()` blocks until
    the stream finishes and returns the full generated list;
    `full_sequence()` is prompt + generated — the backward-compatible
    `/generate` response row. `finish_reason` is "eos", "max_tokens",
    "cancelled", "deadline_exceeded", "preempted" (a batch slot evicted
    for an interactive arrival — error stays None so already-emitted
    tokens relay, and the router re-admits the row as a durable-stream
    resume) or "error" once done."""

    def __init__(self, prompt: Sequence[int], max_tokens: int,
                 eos_id: Optional[int],
                 deadline: Optional[Deadline] = None):
        self.prompt: List[int] = [int(t) for t in prompt]
        self.max_tokens = int(max_tokens)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.deadline = deadline
        #: False = this request neither matches nor seeds the shared
        #: prefix cache (set by submit_many's per-request opt-out)
        self.prefix_cache = True
        #: False = no speculative drafts for this request (its slot
        #: rides every verify round at width 1 — a plain decode step).
        #: Output is bit-identical either way; the opt-out exists for
        #: latency A/Bs and for keeping draft-model compute off a
        #: request entirely (set by submit_many)
        self.speculation = True
        #: SLO tier (set by submit_many): "interactive" requests go
        #: ahead of "batch" ones at admission and may preempt their
        #: slots; "batch" rides the weighted-fair bulk lane
        #: (docs/SERVING.md "Priority tiers")
        self.tier = TIER_INTERACTIVE
        #: absolute index of the FIRST token this stream will emit —
        #: non-zero when the request is a failover continuation whose
        #: already-delivered tokens ride in as prompt context. The
        #: streaming front end adds it to each emitted token's
        #: `token_index`, which is the router's exactly-once dedupe key
        #: (docs/SERVING.md "Streaming", docs/FLEET.md failover)
        self.token_index_base = 0
        #: the loop's count of this request, and the four stamps of its
        #: life on `time.perf_counter` (None until reached): see
        #: `timeline()`
        self.request_id: Optional[int] = None
        self.submitted: Optional[float] = None
        self.admitted: Optional[float] = None
        self.first_token: Optional[float] = None
        self.finished: Optional[float] = None
        self.finish_reason: Optional[str] = None
        self.error: Optional[BaseException] = None
        self._generated: List[int] = []
        self._q: "queue.Queue" = queue.Queue()
        self._done = threading.Event()
        self._cancelled = threading.Event()
        self._loop_ref = None  # weakref to the owning loop, set at submit

    # ------------------------------------------------- scheduler side
    def _emit(self, token: int) -> None:
        if self.first_token is None:
            self.first_token = time.perf_counter()
        self._generated.append(int(token))
        self._q.put(int(token))

    def _finish(self, reason: str,
                error: Optional[BaseException] = None) -> None:
        self.finished = time.perf_counter()
        self.finish_reason = reason
        self.error = error
        tracer = active_tracer()
        if tracer is not None:
            self._record_life(tracer)
        self._q.put(_DONE)
        self._done.set()

    def _record_life(self, tracer) -> None:
        """The stages of this request as spans that share `request`:
        `request.queued`, `request.prefill` (admitted to first token)
        and `request.decode`, children of one `request`, on a lane of
        the trace of their own. A stage the request never reached is
        left out; the queue's ends where the request did."""
        loop = self._loop_ref() if self._loop_ref is not None else None
        args = {"request": self.request_id, "finish": self.finish_reason,
                "loop": None if loop is None else loop.label}
        lane = self.request_id or 0

        def ns(t: float) -> int:
            return int(t * 1e9)

        start, end = ns(self.submitted or self.finished), ns(self.finished)
        root = tracer.add("request", start, end, thread_id=lane, **args)
        stages = (("request.queued", self.submitted, self.admitted),
                  ("request.prefill", self.admitted, self.first_token),
                  ("request.decode", self.first_token, self.finished))
        for name, t0, t1 in stages:
            if t0 is None:
                break
            tracer.add(name, ns(t0), end if t1 is None else ns(t1),
                       parent_id=root, thread_id=lane, **args)

    def timeline(self) -> dict:
        """The request's life on `time.perf_counter`: when it was
        enqueued (`submitted`), when a pass of the scheduler claimed its
        slot (`admitted`), when its first token was emitted
        (`first_token`) and when it finished; None for what has not
        happened, or never did."""
        return {"request_id": self.request_id,
                "submitted": self.submitted, "admitted": self.admitted,
                "first_token": self.first_token, "finished": self.finished}

    # --------------------------------------------------- client side
    def tokens(self, timeout: Optional[float] = None) -> Iterator[int]:
        """Yield generated tokens as they are emitted; raises the
        stream's error (if it failed) after the last delivered token.
        `timeout` bounds the wait BETWEEN tokens (a stalled scheduler
        raises TimeoutError, matching result())."""
        while True:
            try:
                item = self._q.get(timeout=timeout)
            except queue.Empty:
                raise TimeoutError(
                    f"no token emitted within {timeout}s") from None
            if item is _DONE:
                if self.error is not None:
                    raise self.error
                return
            yield item

    def indexed_tokens(self, timeout: Optional[float] = None
                       ) -> Iterator[tuple]:
        """`tokens()` with each token's ABSOLUTE index attached:
        yields `(token_index_base + n, token)` for the n-th emitted
        token. The streaming HTTP front end relays the index on every
        NDJSON chunk so a resuming router can deduplicate replayed
        tokens by position (exactly-once delivery across failover)."""
        for n, tok in enumerate(self.tokens(timeout=timeout)):
            yield self.token_index_base + n, tok

    def __iter__(self) -> Iterator[int]:
        return self.tokens()

    @property
    def done(self) -> bool:
        return self._done.is_set()

    @property
    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    def cancel(self) -> bool:
        """Retire this request: its decode slot is released and its KV
        pages return to the pool at the scheduler's NEXT pass (one
        dispatch boundary — the disconnect-handling contract,
        docs/SERVING.md "Cancellation"). Idempotent; returns True when
        the cancel was accepted (the stream had not already finished).
        The stream then finishes with `finish_reason == "cancelled"`
        and `result()` returns the tokens generated so far."""
        if self._done.is_set():
            return False
        self._cancelled.set()
        loop = self._loop_ref() if self._loop_ref is not None else None
        if loop is not None:
            with loop._cond:
                loop._cond.notify_all()  # wake an idle scheduler now
        return True

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until finished; return the generated token ids (EOS
        included when it fired)."""
        if not self._done.wait(timeout):
            raise TimeoutError("generation still in flight")
        if self.error is not None:
            raise self.error
        return list(self._generated)

    def full_sequence(self, timeout: Optional[float] = None) -> List[int]:
        return self.prompt + self.result(timeout)


class _WindowPages:
    """The window kind's pages on the host: its own pool size, page
    table and free list. A slot holds the logical pages [lo, hi): pages
    are granted at the back as the cursor moves on and returned at the
    front as their last key leaves the window, so a slot never holds
    more than `paged_kinds.window_table_pages` of them. No page of this
    kind is ever shared. The scheduler thread owns it, under the
    loop's lock."""

    def __init__(self, n_pages: int, slots: int, columns: int,
                 page_size: int, window: int):
        self.n_pages = int(n_pages)
        self.trash = self.n_pages
        self.page_size, self.window = int(page_size), int(window)
        self.free: deque = deque(range(self.n_pages))
        self.table = np.full((slots, columns), self.trash, np.int32)
        self.lo = np.zeros((slots,), np.int64)
        self.hi = np.zeros((slots,), np.int64)
        self.released = 0          # pages returned because they fell out
        self.peak_in_use = 0
        self.peak_per_slot = 0

    @property
    def in_use(self) -> int:
        return self.n_pages - len(self.free)

    def first_page(self, cursor: int) -> int:
        """The first logical page that still holds a key the query at
        `cursor` sees."""
        return max(0, cursor - self.window + 1) // self.page_size

    def needed(self, plen: int) -> int:
        """Pages a prompt of `plen` needs at admission: what its first
        decoded token can see, and room for that token's own write."""
        return (pages_for_tokens(plen + 1, self.page_size)
                - self.first_page(plen))

    def claim(self, slot: int, plen: int) -> None:
        """The pages of a prompt's last window (the caller checked
        `needed` against `free`)."""
        self.lo[slot] = self.hi[slot] = self.first_page(plen)
        self.extend(slot, pages_for_tokens(plen, self.page_size))

    def extend(self, slot: int, want_hi: int) -> int:
        """Grant pages up to logical page `want_hi`; returns the end
        actually reached (short when the kind's free list is empty)."""
        while self.hi[slot] < want_hi and self.free:
            self.table[slot, self.hi[slot]] = self.free.popleft()
            self.hi[slot] += 1
        held = int(self.hi[slot] - self.lo[slot])
        self.peak_per_slot = max(self.peak_per_slot, held)
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return int(self.hi[slot])

    def release_before(self, slot: int, cursor: int) -> int:
        """Return the pages whose every key the query at `cursor` no
        longer sees; the table takes the trash page in their place."""
        upto = min(self.first_page(cursor), int(self.hi[slot]))
        n = 0
        while self.lo[slot] < upto:
            j = self.lo[slot]
            self.free.append(int(self.table[slot, j]))
            self.table[slot, j] = self.trash
            self.lo[slot] += 1
            n += 1
        self.released += n
        return n

    def drop(self, slot: int) -> None:
        """A retiring slot's pages, all of them (not counted as
        released: nothing fell out of a window)."""
        for j in range(int(self.lo[slot]), int(self.hi[slot])):
            self.free.append(int(self.table[slot, j]))
        self.table[slot, :] = self.trash
        self.lo[slot] = self.hi[slot] = 0


class _Slot:
    __slots__ = ("stream", "pages", "awaiting_first", "emitted",
                 "stop_len", "no_cache", "prefilled")

    def __init__(self, stream: GenerationStream, pages: List[int],
                 stop_len: int, prefilled: Optional[int] = None):
        self.stream = stream
        self.pages = pages        # physical page ids, in logical order
        #: prompt tokens whose K/V and state the cache holds: the whole
        #: prompt, or less while a long prompt is prefilled a piece a
        #: pass (the slot then rides no decode step yet)
        self.prefilled = (len(stream.prompt) if prefilled is None
                          else prefilled)
        #: prefill's first token is still ON DEVICE (in a group batch —
        #: DecodeLoop._deferred); admission never blocks on a D2H
        self.awaiting_first = True
        self.emitted = 0          # tokens pushed onto the stream so far
        self.stop_len = stop_len  # final length: prompt + max_tokens - 1
        #: pages whose bytes diverged from the pure prompt sequence
        #: (CoW forks) — they must never seed the prefix cache
        self.no_cache: set = set()

    @property
    def in_prefill(self) -> bool:
        return self.prefilled < len(self.stream.prompt)


class _Step:
    """A decode step that is enqueued and not read yet: the one handle
    on its device arrays, and what the host knew when it dispatched it."""
    __slots__ = ("out", "members", "before", "advance", "t0")

    def __init__(self, out, members, before, advance, t0: float):
        self.out = out            # device ((K, S) tokens, aux); None once read
        self.members = members    # [(slot index, its _Slot then)]
        self.before = before      # (S,) lengths the step started from
        self.advance = advance    # (S,) positions it consumes a slot
        self.t0 = t0


class DecodeLoop:
    """Owns the paged pool, the page tables, the single compiled decode
    step, and the scheduler thread. `submit()` is thread-safe and
    returns a `GenerationStream`."""

    def __init__(self, params, cfg, *, slots: int = 8,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 horizon: int = 1, max_waiting: Optional[int] = None,
                 prefix_cache: bool = True, fleet_kv: str = "on",
                 kv_ship_timeout: float = 2.0,
                 kernel: str = "auto",
                 speculation: int = 0, drafter: str = "ngram",
                 draft_params=None, draft_cfg=None,
                 draft_window: int = 32, ngram: int = 3,
                 batch_share: float = 0.5,
                 batch_max_waiting: Optional[int] = None,
                 role: str = ROLE_UNIFIED,
                 window_pages: Optional[int] = None,
                 prefill_tokens_per_pass: Optional[int] = None,
                 start: bool = True, name: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        if role not in ROLES:
            raise ValueError(
                f"role must be one of {ROLES}, got {role!r}")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if horizon < 1:
            raise ValueError(f"horizon must be >= 1, got {horizon}")
        if speculation < 0:
            raise ValueError(
                f"speculation must be >= 0, got {speculation}")
        if speculation and horizon > 1:
            raise ValueError(
                "speculation and horizon>1 are mutually exclusive: "
                "speculation replaces the horizon chain with "
                "draft-and-verify chunking (pick one)")
        if max_waiting is not None and max_waiting < 0:
            raise ValueError(
                f"max_waiting must be >= 0, got {max_waiting}")
        if not 0.0 < batch_share <= 1.0:
            raise ValueError(
                f"batch_share must be in (0, 1], got {batch_share}")
        if batch_max_waiting is not None and batch_max_waiting < 0:
            raise ValueError(
                f"batch_max_waiting must be >= 0, "
                f"got {batch_max_waiting}")
        if (prefill_tokens_per_pass is not None
                and prefill_tokens_per_pass < 1):
            raise ValueError(f"prefill_tokens_per_pass must be >= 1, "
                             f"got {prefill_tokens_per_pass}")
        self._check_refusals(cfg, prefix_cache, speculation, horizon, role)
        self.prefill_tokens_per_pass = (
            None if prefill_tokens_per_pass is None
            else int(prefill_tokens_per_pass))
        self.cfg = cfg
        self.params = params
        self.role = role
        self.slots = int(slots)
        self.page_size = int(page_size)
        self.horizon = int(horizon)
        #: drafts per verify round (0 = speculation off)
        self.spec_k = int(speculation)
        # resolve "auto" ONCE, before jitting: the lane is a
        # compile-time constant of the single step program
        self.kernel_requested = kernel
        self.decode_kernel = resolve_decode_kernel(
            kernel, cfg, self.page_size)
        self._pps = pages_per_slot(cfg, self.page_size)
        if n_pages is None:
            # safe default: worst case (every slot at max_len) — callers
            # chasing HBM set it lower and lean on the backpressure
            n_pages = self.slots * self._pps
        self.n_pages = int(n_pages)
        #: admission-queue bound: a submit that cannot start immediately
        #: while this many requests already wait sheds with
        #: OverloadedError (None = queue unboundedly, legacy behavior)
        self.max_waiting = None if max_waiting is None else int(max_waiting)
        #: weighted-fair share: while interactive work wants the
        #: machine, batch holds at most this many slots; with no
        #: interactive demand batch soaks everything (SLO tiers)
        self.batch_share = float(batch_share)
        self._batch_slot_cap = max(1, int(round(self.slots
                                                * self.batch_share)))
        #: the bulk lane's OWN (lower) admission-queue bound — batch
        #: sheds first; defaults to half the interactive bound
        if batch_max_waiting is not None:
            self.batch_max_waiting: Optional[int] = int(batch_max_waiting)
        elif self.max_waiting is not None:
            self.batch_max_waiting = self.max_waiting // 2
        else:
            self.batch_max_waiting = None
        #: live per-tier admission-queue depth (kept exact under the
        #: lock so the backlog gauge/shed math never iterates the deque
        #: racily)
        self._tier_waiting = {t: 0 for t in TIERS}
        self._buckets = prompt_buckets(cfg, self.page_size)
        #: the most tokens of ONE prompt a pass prefills: the largest
        #: bucket within the bound. A longer prompt keeps its slot and
        #: pages and is prefilled a piece a pass (`_continue_prefills`).
        #: None: prompts are prefilled whole, as where there is no
        #: bound, where pages are shared by content (a piece would have
        #: to start behind a cached prefix) and where a window kind
        #: claims only the pages a prompt's END can still see
        self._piece: Optional[int] = None
        if (self.prefill_tokens_per_pass is not None and not prefix_cache
                and paged_kinds.KIND_WINDOW not in cfg.layer_kinds):
            self._piece = max(
                (b for b in self._buckets
                 if b <= self.prefill_tokens_per_pass), default=None)

        # device state ------------------------------------------------
        #: the window kind's pages (None: every layer keeps all keys)
        self._win: Optional[_WindowPages] = None
        pages = {paged_kinds.KIND_FULL: self.n_pages}
        if paged_kinds.KIND_WINDOW in cfg.layer_kinds:
            columns = paged_kinds.window_table_pages(cfg, self.page_size)
            if window_pages is None:
                window_pages = self.slots * min(columns, self._pps)
            if window_pages < 1:
                raise ValueError(f"window_pages must be >= 1, got "
                                 f"{window_pages}")
            self._win = _WindowPages(window_pages, self.slots, self._pps,
                                     self.page_size, cfg.window)
            pages[paged_kinds.KIND_WINDOW] = self._win.n_pages
        self._kind_pages = pages
        self._kind_layers = paged_kinds.layers_of(cfg)
        #: layers of each kind held by SLOT, no pages (empty: every
        #: layer keeps keys)
        self._slot_layers = paged_kinds.slot_kinds(cfg)
        #: what a page of a kind counts for in `pages_total` and its
        #: like: the kind's layers where kinds have to be summed (a page
        #: of a kind spans every layer of that kind), 1 where there is
        #: one kind and the counts are plainly its pages
        self._kind_weight = (self._kind_layers if len(pages) > 1
                             else dict.fromkeys(pages, 1))
        self._pool = paged_kinds.init_pool(cfg, pages, self.page_size,
                                           slots=self.slots)
        self._trash = self.n_pages
        #: pairs by (layer, held expert) and what they are of, for a
        #: model with an expert layer (snapshot()["moe"])
        self._moe = None
        if cfg.n_held:
            self._moe = {
                "pairs": np.zeros((cfg.n_layers, cfg.n_held), np.int64),
                "tokens": 0, "decode_tokens": 0, "decode_pairs": 0,
                "decode_steps": 0, "experts_touched": 0, "rows_moved": 0}
        # host mirrors (scheduler-thread-owned) -----------------------
        self._table = np.full((self.slots, self._pps), self._trash,
                              np.int32)
        #: the DISPATCHED cursor: a slot's length on the device once
        #: every enqueued step has run (what was emitted plus what the
        #: step in flight will consume)
        self._lengths = np.zeros((self.slots,), np.int32)
        self._stop = np.zeros((self.slots,), np.int32)
        #: a slot's last token as the host knows it: a step behind the
        #: device while a step is in flight
        self._pending = np.zeros((self.slots,), np.int32)
        self._dirty = True          # table or stop changed since upload
        #: slots whose token and length the HOST sets on the device at
        #: the next dispatch (admission, retirement, a speculative
        #: round); every other row of the two is the device's own
        self._host_rows = np.zeros((self.slots,), bool)
        #: the decode step enqueued and not read yet (plain lane)
        self._inflight: Optional[_Step] = None
        #: when the last step was read, on `time.perf_counter`
        self._read_at = 0.0
        self._d_tokens = jnp.zeros((self.slots,), jnp.int32)
        self._d_lengths = jnp.zeros((self.slots,), jnp.int32)
        self._d_table = None        # by kind, (S, P) int32
        self._d_stop = None         # (S,) int32
        self._free: deque = deque(range(self.n_pages))
        self._slot_state: List[Optional[_Slot]] = [None] * self.slots
        #: prefill-group first tokens still on device:
        #: [(device (B,) array, [(row, slot_idx), ...])]
        self._deferred: List = []
        # prefix sharing: per-page reader refcounts + the chunk trie.
        # Every page is in exactly ONE of: the free list, in use
        # (ref > 0), or the cached tier (ref == 0 but trie-retained) —
        # snapshot()/tests pin that the three always sum to n_pages.
        self.prefix_cache_enabled = bool(prefix_cache)
        self._prefix: Optional[PrefixIndex] = (
            PrefixIndex(self.page_size) if self.prefix_cache_enabled
            else None)
        self._ref = np.zeros((self.n_pages,), np.int32)
        self._prefill_token_count = 0  # real tokens through prefill
        # fleet KV plane (serving/fleetkv.py, docs/FLEET.md): affinity
        # summaries + peer page shipping. The plane rides the prefix
        # trie, so without a trie it is forced off.
        if fleet_kv not in fleetkv.MODES:
            raise ValueError(
                f"fleet_kv must be one of {fleetkv.MODES}, "
                f"got {fleet_kv!r}")
        self.fleet_kv = (fleet_kv if self.prefix_cache_enabled
                         else fleetkv.MODE_OFF)
        if self.role == ROLE_PREFILL and self.fleet_kv != fleetkv.MODE_ON:
            # the trie + /kv/export wire ARE a prefill replica's whole
            # product: without them it could never hand pages to anyone
            raise ValueError(
                "a prefill-role loop needs prefix_cache=True and "
                "fleet_kv='on' — its only output is cached KV pages "
                "shipped over /kv/export")
        #: install jobs queued for the scheduler thread — pool swaps
        #: happen OUTSIDE the lock on that thread, so a shipped-page
        #: scatter from a handler thread would race a prefill's swap;
        #: routing installs through the tick serializes them for free
        self._kv_jobs: deque = deque()
        #: cumulative ship stats, reported in the /readyz summary so
        #: the fleet's probe can delta them into router-side counters
        self._ship_stats = {"page_ships": 0, "ship_bytes": 0,
                            "ship_failures": 0}
        #: default budget for one donor fetch + install (seconds);
        #: request deadlines cap it further (server._generate). Raise
        #: it when donors run compute-starved (interpret mode, shared
        #: cores) — a slow export is still far cheaper than a cold
        #: head prefill, and ANY expiry just falls back to prefill.
        if kv_ship_timeout <= 0:
            raise ValueError(f"kv_ship_timeout must be > 0, "
                             f"got {kv_ship_timeout}")
        self.kv_ship_timeout = float(kv_ship_timeout)

        # speculative decoding ----------------------------------------
        # the drafter proposes; the verify program below is the only
        # authority on emitted tokens (serving/speculation.py)
        self._drafter = None
        if self.spec_k:
            corpus = ((lambda: self._prefix.iter_sequences())
                      if self._prefix is not None else None)
            self._drafter = build_drafter(
                drafter, k=self.spec_k, cfg=cfg,
                draft_params=draft_params, draft_cfg=draft_cfg,
                draft_window=draft_window, ngram=ngram, corpus=corpus)

        # compiled programs -------------------------------------------
        # donation lets XLA update the pool in place on accelerators;
        # CPU ignores donation with a warning, so gate it off there
        donate_step = () if jax.default_backend() == "cpu" else (2,)
        donate_pre = () if jax.default_backend() == "cpu" else (3,)
        k_steps = self.horizon

        def step_fn(params, tokens, pool, table, lengths, stop):
            """K chained decode steps in one dispatch. Per-slot
            activity is `lengths < stop` — a slot out of budget or out
            of allocated pages stops advancing mid-chunk exactly where
            it should, so horizon never corrupts state. `table` is a
            dict by kind; what the model's layers count (`aux`: pairs
            by held expert, or nothing) rides beside the tokens."""
            def inner(carry, _):
                tokens, lengths, pool = carry
                act = lengths < stop
                logits, pool, aux = paged_kinds.decode_step(
                    params, tokens, pool, table, lengths, act, cfg,
                    kernel=self.decode_kernel)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                tokens = jnp.where(act, nxt, tokens)
                lengths = lengths + act.astype(lengths.dtype)
                return (tokens, lengths, pool), (nxt, aux)

            (tokens, lengths, pool), out = jax.lax.scan(
                inner, (tokens, lengths, pool), None, length=k_steps)
            return out, tokens, lengths, pool

        def prefill_fn(params, tokens, true_len, pool, page_ids):
            logits, pool, aux = paged_kinds.prefill(
                params, tokens, true_len, pool, page_ids, cfg)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    aux), pool

        # the two below run only for a model whose layers count nothing
        # (`_check_refusals`): their `aux` is empty
        def prefill_ctx_fn(params, tokens, true_len, pool, page_ids,
                           ctx_table, ctx_len):
            logits, pool, _ = paged_kinds.prefill_ctx(
                params, tokens, true_len, pool, page_ids, ctx_table,
                ctx_len, cfg)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), pool

        def prefill_chunk_fn(params, tokens, true_len, pool, page_ids,
                             ctx_table, ctx_len):
            """A later piece of a prompt prefilled in pieces: the
            context read through the loop's kernel lane, the linear
            kind's state taken from the row's slot and put back."""
            logits, pool, aux = paged_kinds.prefill_ctx(
                params, tokens, true_len, pool, page_ids, ctx_table,
                ctx_len, cfg, kernel=self.decode_kernel)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    aux), pool

        def verify_fn(params, tokens, pool, table, lengths, widths):
            """ONE widened step over (S, W) tokens: every real column
            writes K/V at `lengths + j` and the returned argmax row is
            the target model's own next-token choice after each draft
            prefix — the exact-accept rule's ground truth."""
            logits, pool, _ = paged_kinds.verify_step(
                params, tokens, pool, table, lengths, widths, cfg,
                kernel=self.decode_kernel)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), pool

        def set_rows(rows, tokens, lengths):
            """Where the host is the authority over a slot (`rows[0]`),
            its token (`rows[1]`) and length (`rows[2]`) replace the
            device's. A mask over all slots: one shape, one program."""
            host = rows[0] != 0
            return (jnp.where(host, rows[1], tokens),
                    jnp.where(host, rows[2], lengths))

        donate_copy = () if jax.default_backend() == "cpu" else (0,)
        self._set_rows = jax.jit(set_rows)
        self._step = jax.jit(step_fn, donate_argnums=donate_step)
        self._verify = jax.jit(verify_fn, donate_argnums=donate_step)
        self._prefill = jax.jit(prefill_fn, donate_argnums=donate_pre)
        self._prefill_ctx = jax.jit(prefill_ctx_fn,
                                    donate_argnums=donate_pre)
        self._prefill_chunk = jax.jit(prefill_chunk_fn,
                                      donate_argnums=donate_pre)
        # the one compiled surface sharing adds: scalar src/dst are
        # traced, so every CoW fork for the life of the server is ONE
        # program
        self._copy = jax.jit(copy_page, donate_argnums=donate_copy)
        # persistent compile cache (docs/WARMUP.md): no-op unless the
        # process activated one. The key pins every closure constant
        # that changes the program at identical input shapes — model
        # config, kernel lane, horizon, spec width — plus the device,
        # because serialized executables are device-bound.
        from deeplearning4j_tpu import compilecache as _cc

        self.cache_key = (
            f"decode:{_cc.config_digest(cfg)}|ps={self.page_size}"
            f"|k={self.decode_kernel}|h={self.horizon}"
            f"|spec={self.spec_k}|dev={jax.devices()[0]}")
        self._step = _cc.maybe_wrap(self._step, self.cache_key + "|step")
        self._verify = _cc.maybe_wrap(self._verify,
                                      self.cache_key + "|verify")
        self._prefill = _cc.maybe_wrap(self._prefill,
                                       self.cache_key + "|prefill")
        self._prefill_ctx = _cc.maybe_wrap(
            self._prefill_ctx, self.cache_key + "|prefill_ctx")
        self._prefill_chunk = _cc.maybe_wrap(
            self._prefill_chunk, self.cache_key + "|prefill_chunk")
        self._copy = _cc.maybe_wrap(self._copy, self.cache_key + "|copy")
        #: program-usage record for plan_fragment(): (bb, tb) /
        #: (bb, cb, tb) prefill groups actually dispatched, plus flags
        #: for the fixed-shape programs actually run — the plan must
        #: list exactly the programs a boot like this one compiles, or
        #: replay would add programs the record run never had
        self._plan_prefill: set = set()
        self._plan_prefill_ctx: set = set()
        self._plan_prefill_chunk: set = set()
        self._plan_step = False
        self._plan_verify = False
        self._plan_copy = False

        # queueing / lifecycle ----------------------------------------
        self._cond = threading.Condition()
        self._waiting: deque = deque()  # GenerationStreams not yet admitted
        self._closed = False
        self._peak_pages = 0
        self._thread: Optional[threading.Thread] = None

        # telemetry ----------------------------------------------------
        reg = telemetry.get_registry()
        self.label = name if name is not None else f"d{next(_loop_seq)}"
        lab = {"loop": self.label}
        self._m_requests = reg.counter(
            "dl4j_decode_requests",
            "generate requests submitted to the slot scheduler"
        ).labels(**lab)
        self._m_tokens = reg.counter(
            "dl4j_decode_tokens_streamed",
            "tokens emitted onto generation streams").labels(**lab)
        self._m_waits = reg.counter(
            "dl4j_decode_admission_waits",
            "scheduler passes where a queued request could not be "
            "admitted for lack of free pages or slots").labels(**lab)
        self._m_steps = reg.counter(
            "dl4j_decode_steps",
            "compiled decode dispatches run (each covers `horizon` "
            "token steps)").labels(**lab)
        self._m_overlapped = reg.counter(
            "dl4j_decode_dispatches_overlapped",
            "decode dispatches enqueued while the step before them was "
            "still unread: the host's pass ran under the device's step"
        ).labels(**lab)
        self._m_shed = reg.counter(
            "dl4j_decode_shed",
            "generate requests rejected at submit because the admission "
            "queue was at max_waiting").labels(**lab)
        self._m_deadline = reg.counter(
            "dl4j_decode_deadline_exceeded",
            "generate requests shed at submit/admission, or reaped "
            "mid-flight, because their deadline budget was spent"
        ).labels(**lab)
        self._m_cancelled = reg.counter(
            "dl4j_decode_cancelled",
            "generate requests cancelled (client disconnect or "
            "GenerationStream.cancel) — slot retired, pages freed"
        ).labels(**lab)
        self._m_hits = reg.counter(
            "dl4j_kv_prefix_hits",
            "admissions whose prompt matched >= 1 cached prefix chunk "
            "(shared pool pages mapped by reference)").labels(**lab)
        self._m_misses = reg.counter(
            "dl4j_kv_prefix_misses",
            "cache-eligible admissions that matched no cached chunk "
            "(full cold prefill)").labels(**lab)
        self._m_forks = reg.counter(
            "dl4j_kv_prefix_forks",
            "copy-on-write page forks (decode cursor entered a shared "
            "page; it was duplicated into a private one)").labels(**lab)
        self._m_evictions = reg.counter(
            "dl4j_kv_prefix_evictions",
            "unreferenced cached prefix pages evicted (LRU) to satisfy "
            "an allocation under page pressure").labels(**lab)
        _tier_req = reg.counter(
            "dl4j_tier_requests",
            "generate requests submitted per SLO tier (interactive "
            "goes ahead at admission; batch rides the weighted-fair "
            "bulk lane)")
        tscope = {"scope": f"loop:{self.label}"}
        self._m_tier_requests = {
            t: _tier_req.labels(tier=t, **tscope) for t in TIERS}
        _tier_shed = reg.counter(
            "dl4j_tier_shed",
            "generate requests shed at submit per SLO tier (batch "
            "sheds first, at its own lower batch_max_waiting bound)")
        self._m_tier_shed = {
            t: _tier_shed.labels(tier=t, **tscope) for t in TIERS}
        self._m_preempt = reg.counter(
            "dl4j_tier_preemptions",
            "batch decode slots preempted for a blocked interactive "
            "admission (lossless: the row resumes via the router's "
            "durable-stream record)").labels(tier=TIER_BATCH, **tscope)
        self._m_spec_proposed = reg.counter(
            "dl4j_spec_proposed",
            "draft tokens proposed to speculative verify rounds"
        ).labels(**lab)
        self._m_spec_accepted = reg.counter(
            "dl4j_spec_accepted",
            "draft tokens the target model's verify accepted (each one "
            "a decode dispatch saved)").labels(**lab)
        self._m_spec_rounds = reg.counter(
            "dl4j_spec_rounds",
            "widened verify dispatches run (speculative rounds; plain "
            "fallback rounds when no slot had a draft are not counted "
            "here)").labels(**lab)
        _kv_read = reg.counter(
            "dl4j_decode_kv_read_bytes",
            "KV bytes the decode attention read must touch, summed "
            "over token steps: path=\"kernel\" is the streamed-pages "
            "figure (written pages only — what the pallas lane reads), "
            "path=\"gather\" the dense-window figure (the full "
            "S x max_len reservation); their ratio is the paged "
            "kernel's traffic win")
        self._m_kv_read = {
            path: _kv_read.labels(path=path, **lab)
            for path in ("kernel", "gather")}
        self._m_step_s = reg.histogram(
            "dl4j_decode_step_seconds",
            "wall time of one compiled decode dispatch (covers "
            "`horizon` token steps) through its token D2H sync, from "
            "its dispatch or, where that came later, from the read of "
            "the step before it: the observations do not overlap, and "
            "their sum is the time some step was unread").labels(**lab)
        self._phases = PhaseTotals(reg.histogram(
            "dl4j_decode_phase_seconds",
            "wall time of the scheduler's spans by phase: decode.tick "
            "is one pass, the other decode.* are what a pass is made "
            "of, decode.idle_wait is the scheduler asleep between "
            "passes"), PHASES, cpu_family=reg.counter(
                "dl4j_decode_phase_cpu_seconds",
                "CPU seconds of the scheduler's thread in decode.tick and "
                "decode.d2h: the phase's wall seconds less these are the "
                "time the thread did not run"), cpu_names=CPU_PHASES,
            **lab)
        self._m_queue_wait = reg.histogram(
            "dl4j_decode_queue_wait_seconds",
            "time a generate request waited in the admission queue, "
            "submit to the scheduler pass that claimed its slot"
        ).labels(**lab)
        self._m_prefill_passes = reg.counter(
            "dl4j_decode_prefill_passes",
            "scheduler passes that enqueued a decode dispatch and at "
            "least one prefill: the token gaps a prefill lengthened"
        ).labels(**lab)
        _chunks = reg.counter(
            "dl4j_prefill_chunks",
            "pieces of prompts that are prefilled a piece a pass "
            "(prompts longer than prefill_tokens_per_pass): "
            "carried=\"0\" the first piece of such a prompt, from a "
            "zero state, carried=\"1\" a later one, on the state and "
            "the pages its slot kept")
        self._m_chunks = {c: _chunks.labels(carried=str(int(c)), **lab)
                          for c in (False, True)}
        self._m_chunk_tokens = reg.counter(
            "dl4j_prefill_chunk_tokens",
            "prompt tokens prefilled as such pieces").labels(**lab)
        self._request_ids = itertools.count()
        #: ring of the longest pass per interval (snapshot()
        #: ["slow_ticks"]); slot k holds interval number k mod its size
        self._slow_ticks: List[Optional[dict]] = [None] * SLOW_TICKS_KEPT
        #: the process's collector and heartbeat record
        self._host = host.start_host_monitor()
        reg.gauge(
            "dl4j_kv_pages_total",
            "usable KV pages in the block pool").labels(**lab).set(
                self.n_pages)
        ref = weakref.ref(self)
        reg.gauge(
            "dl4j_kv_pages_in_use",
            "KV pages currently held by in-flight requests"
        ).labels(**lab).set_function(
            lambda: (lambda o: o.pages_in_use if o else 0)(ref()))
        reg.gauge(
            "dl4j_kv_pages_shared",
            "KV pages an in-flight slot may not write without a CoW "
            "fork (>= 2 readers, or referenced while cache-retained)"
        ).labels(**lab).set_function(
            lambda: (lambda o: o.pages_shared if o else 0)(ref()))
        reg.gauge(
            "dl4j_kv_pages_cached",
            "KV pages retained by the prefix index (the unreferenced "
            "ones form the LRU-evictable tier)").labels(
                **lab).set_function(
            lambda: (lambda o: o.pages_cached if o else 0)(ref()))
        reg.gauge(
            "dl4j_decode_active_slots",
            "slots holding an in-flight request").labels(
                **lab).set_function(
            lambda: (lambda o: o.occupied_slots if o else 0)(ref()))
        _backlog = reg.gauge(
            "dl4j_tier_backlog",
            "generate requests queued for admission per SLO tier (the "
            "batch figure is the signal the autoscaler and the "
            "backlog-derived Retry-After key on)")
        for t in TIERS:
            _backlog.labels(tier=t, **tscope).set_function(
                (lambda _t: lambda: (lambda o: o._tier_waiting[_t]
                                     if o else 0)(ref()))(t))
        reg.gauge(
            "dl4j_spec_acceptance_rate",
            "accepted / proposed draft tokens over the loop's lifetime "
            "(0.0 while speculation is off or nothing was proposed)"
        ).labels(**lab).set_function(
            lambda: (lambda o: o.spec_acceptance_rate if o else 0.0)(
                ref()))

        self._register_kind_metrics(reg, lab, ref)
        #: pages a block of the paged kernel's sweep holds, by kind of
        #: layer: a constant of the step's shapes (0: the gather lane)
        self._block_pages = self._paged_block_pages()
        block = reg.gauge(
            "dl4j_paged_kernel_block_pages",
            "page-table columns the paged decode kernel sweeps as one "
            "block, derived from the pool's shapes (1: a page a grid "
            "step; 0: the step runs the gather lane)")
        for kind, n in self._block_pages.items():
            block.labels(kind=kind, **lab).set(n)

        if start:
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name=f"decode-loop-{self.label}")
            self._thread.start()

    # ---------------------------------------- kinds of layer, counts
    @staticmethod
    def _check_refusals(cfg, prefix_cache, speculation, horizon,
                        role) -> None:
        """What the host's side cannot do yet for what a model has is an
        error here, by name, never a silent wrong answer: a window kind
        gives its pages back as the cursor moves, what an expert layer
        counts is read back from the plain step and the cold prefill
        only, and the state of a kind held by slot is no page
        (`_REFUSALS` has the words)."""
        if paged_kinds.KIND_FULL not in cfg.layer_kinds:
            raise ValueError(
                "layer_kinds needs a full layer: a request's token "
                "budget rides the full kind's page table")
        held = paged_kinds.slot_kinds(cfg)
        if held:
            why = _REFUSALS[next(iter(held))]
        elif paged_kinds.KIND_WINDOW in cfg.layer_kinds or cfg.n_held:
            why = _REFUSALS[paged_kinds.KIND_WINDOW]
        else:
            return
        for asked, what in ((prefix_cache, "prefix_cache"),
                            (speculation, "speculation"),
                            (horizon > 1, "horizon"),
                            (role == ROLE_PREFILL, "role")):
            if asked:
                raise ValueError(why[what])

    def _paged_block_pages(self) -> Dict[str, int]:
        """`attention/paged_pallas.block_pages` of each kind's call in
        the decode step, from the pool as it was built."""
        columns = {paged_kinds.KIND_FULL: self._pps}
        if self._win is not None:
            columns[paged_kinds.KIND_WINDOW] = min(
                self._pps, paged_kinds.window_table_pages(
                    self.cfg, self.page_size))
        out = {}
        for kind, n in columns.items():
            layer = self._pool.layers[self.cfg.layer_kinds.index(kind)]
            _, heads, page_size, head_dim = layer["k"].shape
            out[kind] = (block_pages(page_size, heads, head_dim,
                                     layer["k"].dtype, n)
                         if self.decode_kernel == "pallas" else 0)
        return out

    def _register_kind_metrics(self, reg, lab: dict, ref) -> None:
        """Pages by kind, and for a model that has them the window
        kind's releases and the expert layer's pairs (the families with
        only a `loop` label keep their shape for every model)."""
        total = reg.gauge(
            "dl4j_kv_pages_total_by_kind",
            "usable KV pages by kind of layer (a page of a kind spans "
            "every layer of that kind)")
        in_use = reg.gauge(
            "dl4j_kv_pages_in_use_by_kind",
            "KV pages held by in-flight requests, by kind of layer")
        for kind, n in self._kind_pages.items():
            total.labels(kind=kind, **lab).set(n)
            in_use.labels(kind=kind, **lab).set_function(
                (lambda k: lambda: (lambda o: o._kind_in_use(k)
                                    if o else 0)(ref()))(kind))
        if self._win is not None:
            self._m_win_released = reg.counter(
                "dl4j_kv_window_pages_released",
                "window-layer KV pages returned to their free list "
                "because their last key left the window").labels(**lab)
        if self._slot_layers:
            state = reg.gauge(
                "dl4j_state_bytes",
                "bytes of per-slot state the cache holds for layers "
                "that keep no pages, by kind of layer (a linear layer's "
                "recurrent state and kept convolution columns, a conv "
                "layer's kept columns, every slot)")
            for kind in self._slot_layers:
                state.labels(kind=kind, **lab).set(self.state_bytes(kind))
            reg.gauge(
                "dl4j_state_slots_live",
                "slots whose per-slot state belongs to an in-flight "
                "request").labels(**lab).set_function(
                lambda: (lambda o: o.occupied_slots if o else 0)(ref()))
        if self._moe is None:
            return
        self._m_moe_tokens = reg.counter(
            "dl4j_moe_tokens",
            "tokens routed by the expert layer (prefill and decode, "
            "padding and idle slots left out)").labels(**lab)
        self._m_moe_touched = reg.counter(
            "dl4j_moe_experts_touched",
            "held experts with at least one pair, summed over decode "
            "steps and layers: the expert weights a step had to read"
        ).labels(**lab)
        self._m_moe_rows = reg.counter(
            "dl4j_moe_rows_moved",
            "rows the expert layer copied between token order and the "
            "order sorted by expert (a layer's pairs rounded up to whole "
            "grid steps of its kernels): over dl4j_moe_pairs, how much "
            "of the movement held no pair").labels(**lab)
        pairs = reg.counter(
            "dl4j_moe_pairs",
            "(token, expert) pairs that fell on an expert this chip "
            "holds, by layer and held expert")
        self._m_moe_pairs = [
            [pairs.labels(layer=str(i), expert=str(e), **lab)
             for e in range(self.cfg.n_held)]
            for i in range(self.cfg.n_layers)]

    def _kind_in_use(self, kind: str) -> int:
        if kind == paged_kinds.KIND_WINDOW:
            return self._win.in_use
        return self.pages_in_use

    def _weighted_in_use(self) -> int:
        """Pages in use over the kinds (`_kind_weight`): `pages_in_use`
        where there is one kind."""
        return sum(w * self._kind_in_use(k)
                   for k, w in self._kind_weight.items())

    def _note_peak(self) -> None:
        self._peak_pages = max(self._peak_pages, self._weighted_in_use())

    def _count_pairs(self, pairs: np.ndarray, tokens: int,
                     decode: bool) -> None:
        """Fold one program's pairs (layers, n_held) into the counters;
        `tokens` real tokens went through each layer's router."""
        moe = self._moe
        moe["pairs"] += pairs
        moe["tokens"] += tokens
        self._m_moe_tokens.inc(tokens)
        moved = int(rows_moved(pairs.sum(axis=1)).sum())
        moe["rows_moved"] += moved
        self._m_moe_rows.inc(moved)
        for i, e in zip(*np.nonzero(pairs)):
            self._m_moe_pairs[i][e].inc(int(pairs[i, e]))
        if decode:
            touched = int(np.count_nonzero(pairs))
            moe["decode_tokens"] += tokens
            moe["decode_pairs"] += int(pairs.sum())
            moe["decode_steps"] += 1
            moe["experts_touched"] += touched
            self._m_moe_touched.inc(touched)

    @staticmethod
    def _upload(mirror: np.ndarray):
        """A host mirror onto the device, as a COPY made on the host
        first. The transfer may read the host's buffer after this call
        returns (on the CPU the device array may BE that buffer, and
        `jnp.array` copies on the device, after the same late read), and
        with a step in flight the scheduler writes on in its mirrors
        (a grant, a release, a retirement) without waiting for any step
        that took the upload: a view would hand a step a table or a stop
        bound from a later pass."""
        import jax.numpy as jnp

        return jnp.asarray(mirror.copy())

    def _device_tables(self):
        """The page tables as the steps take them: a dict by kind, each
        a copy (`_upload`)."""
        tables = {paged_kinds.KIND_FULL: self._upload(self._table)}
        if self._win is not None:
            tables[paged_kinds.KIND_WINDOW] = self._upload(self._win.table)
        return tables

    # ----------------------------------------------------- public API
    @staticmethod
    def _per_row(value, n_rows: int, name: str) -> List[int]:
        """Normalize a scalar-or-per-row int parameter to one int per
        row (submit_many's max_tokens / token_index_base contract)."""
        if isinstance(value, (list, tuple, np.ndarray)):
            if len(value) != n_rows:
                raise ValueError(
                    f"per-row {name} needs {n_rows} entries, "
                    f"got {len(value)}")
            return [int(v) for v in value]
        return [int(value)] * n_rows

    def validate(self, prompt, max_tokens: int) -> np.ndarray:
        """Check one request without enqueueing it (raises ValueError);
        returns the normalized 1-D prompt. Callers submitting several
        rows as one unit (the HTTP /generate handler) validate ALL rows
        first, so a malformed row never orphans its row-mates'
        already-running streams."""
        prompt = np.asarray(prompt).ravel().astype(np.int64)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1, got {max_tokens}")
        if prompt.size + max_tokens > self.cfg.max_len:
            raise ValueError(
                f"generation would exceed max_len ({prompt.size} prompt "
                f"+ {max_tokens} new > {self.cfg.max_len})")
        need = pages_for_tokens(int(prompt.size) + 1, self.page_size)
        if need > self.n_pages:
            raise ValueError(
                f"prompt needs {need} pages but the pool only has "
                f"{self.n_pages}")
        if (self._win is not None
                and self._win.needed(int(prompt.size)) > self._win.n_pages):
            raise ValueError(
                f"prompt needs {self._win.needed(int(prompt.size))} window "
                f"pages but that pool only has {self._win.n_pages}")
        return prompt

    def submit(self, prompt, max_tokens: int,
               eos_id: Optional[int] = None,
               deadline: Optional[Deadline] = None,
               prefix_cache: bool = True,
               speculation: bool = True,
               tier: str = TIER_INTERACTIVE) -> GenerationStream:
        """Queue one prompt (1-D int sequence). The stream's first token
        arrives after admission + prefill; termination on EOS (when
        given), `max_tokens`, or the model window. `prefix_cache=False`
        opts this request out of the shared prefix cache — it neither
        reuses cached pages nor seeds new ones (benchmark cold runs;
        secret-bearing prompts). `speculation=False` opts it out of
        speculative drafting (plain one-token rounds; output is
        bit-identical either way). `tier="batch"` rides the bulk lane:
        admitted behind every interactive arrival, capped at the
        weighted-fair slot share under interactive demand, shed first,
        and preemptible (finish_reason "preempted")."""
        return self.submit_many([prompt], max_tokens, eos_id,
                                deadline=deadline,
                                prefix_cache=prefix_cache,
                                speculation=speculation,
                                tier=tier)[0]

    def submit_many(self, prompts, max_tokens,
                    eos_id: Optional[int] = None,
                    deadline: Optional[Deadline] = None,
                    prefix_cache: bool = True,
                    token_index_base=0,
                    speculation: bool = True,
                    tier: str = TIER_INTERACTIVE
                    ) -> List[GenerationStream]:
        """Admit several rows as ONE unit: all rows enqueue or none do.
        A shed that fired between a multi-row request's submits would
        orphan the already-queued row-mates in running slots (no
        consumer ever reads them), so the /generate handler routes
        every multi-row body through here. An already-expired `deadline`
        sheds the whole group here; one that expires while queued sheds
        at admission — either way before any prefill compute.

        `max_tokens` and `token_index_base` accept either one scalar
        for every row or a per-row sequence (length == len(prompts)).
        Per-row budgets are what a failover continuation needs: rows
        interrupted at different depths re-admit as one group, each
        with its own remaining budget and absolute-index offset. Both
        per-row lists are length- and value-checked UP FRONT with a
        named error — a short or negative list must fail before any
        row-mate is enqueued, not deep in slot admission.

        `tier` ("interactive" default, "batch") applies to the whole
        group. Batch sheds at its own `batch_max_waiting` bound — the
        bulk lane fills and sheds FIRST — and both tiers' shed replies
        carry the shed tier plus a Retry-After derived from that
        tier's backlog, so a bulk client backs off proportionally to
        the lane it actually waits in."""
        if self.role == ROLE_PREFILL:
            # a prefill replica owns no streams: its compiled surface
            # must never grow the decode/verify ladder (role-scoped
            # warmup plans pin key-set disjointness on exactly this)
            raise ValueError(
                "this replica has role 'prefill' — it computes prompt "
                "KV for handoff (/prefill) and serves /kv/export; "
                "generate streams belong on a decode/unified replica")
        if tier not in TIERS:
            raise ValueError(
                f"unknown tier {tier!r} (expected one of {TIERS})")
        if deadline is not None and deadline.expired:
            self._m_deadline.inc()
            deadline.check("decode admission")  # raises
        per_row_max = self._per_row(max_tokens, len(prompts),
                                    "max_tokens")
        per_row_base = self._per_row(token_index_base, len(prompts),
                                     "token_index_base")
        for base in per_row_base:
            if base < 0:
                raise ValueError(
                    f"per-row token_index_base must be >= 0, got {base}")
        prompts = [self.validate(p, mt)
                   for p, mt in zip(prompts, per_row_max)]
        streams = [GenerationStream(p, mt, eos_id, deadline=deadline)
                   for p, mt in zip(prompts, per_row_max)]
        loop_ref = weakref.ref(self)
        for stream, base in zip(streams, per_row_base):
            stream._loop_ref = loop_ref
            stream.prefix_cache = bool(prefix_cache)
            stream.speculation = bool(speculation)
            stream.token_index_base = base
            stream.tier = tier
        with self._cond:
            if self._closed:
                raise RuntimeError("decode loop is closed")
            bound = (self.batch_max_waiting if tier == TIER_BATCH
                     else self.max_waiting)
            if bound is not None:
                # free-page starvation / slot saturation sheds at the
                # door once the TIER's admission queue is at its bound
                # — a group that could start right now is never
                # rejected, and a deep bulk backlog never sheds the
                # interactive lane (those arrivals preempt instead)
                need = sum(pages_for_tokens(p.size + 1, self.page_size)
                           for p in prompts)
                free_slots = sum(1 for s in self._slot_state
                                 if s is None)
                can_now = (not self._waiting
                           and self._avail_pages() >= need
                           and free_slots >= len(prompts))
                tier_q = self._tier_waiting[tier]
                if not can_now and tier_q + len(prompts) > bound:
                    self._m_shed.inc()
                    self._m_tier_shed[tier].inc()
                    raise OverloadedError(
                        f"decode admission queue full for tier "
                        f"{tier!r} ({tier_q} waiting, "
                        f"{len(self._free)}/{self.n_pages} pages free)",
                        retry_after_ms=backlog_retry_ms(
                            tier_q + len(prompts),
                            _TIER_ITEM_MS[tier]),
                        tier=tier)
            now = time.perf_counter()
            for stream in streams:
                stream.request_id = next(self._request_ids)
                stream.submitted = now
                self._m_requests.inc()
                self._m_tier_requests[tier].inc()
                self._waiting.append(stream)
                self._tier_waiting[tier] += 1
            self._cond.notify_all()
        return streams

    def generate(self, prompt, max_tokens: int,
                 eos_id: Optional[int] = None,
                 timeout: Optional[float] = 120.0) -> List[int]:
        """Blocking convenience: submit + wait, returns prompt+generated
        (the `/generate` non-streaming row shape)."""
        return self.submit(prompt, max_tokens, eos_id).full_sequence(timeout)

    @property
    def pages_in_use(self) -> int:
        """Pages held by in-flight requests (reader refcount > 0).
        Cached-but-unreferenced prefix pages are NOT in use — they are
        reclaimable on demand (`pages_cached`)."""
        return int(np.count_nonzero(self._ref))

    @property
    def pages_cached(self) -> int:
        """Pages retained by the prefix index (shared prefix K/V)."""
        return 0 if self._prefix is None else len(self._prefix)

    @property
    def pages_shared(self) -> int:
        """Pages some in-flight slot may not write in place: >= 2
        readers, or >= 1 reader while the cache retains the page."""
        shared = int(np.count_nonzero(self._ref >= 2))
        if self._prefix is not None:
            shared += sum(1 for p in self._prefix.pages()
                          if self._ref[p] == 1)
        return shared

    def _cached_unref(self) -> int:
        """The evictable LRU tier: cache-retained pages no slot reads."""
        if self._prefix is None:
            return 0
        return sum(1 for p in self._prefix.pages() if self._ref[p] == 0)

    def _avail_pages(self) -> int:
        """Pages an allocation could obtain right now: free list plus
        the evictable cached tier (the cache never starves admission)."""
        return len(self._free) + self._cached_unref()

    def _alloc_page(self) -> Optional[int]:
        """Take one page for a new reader (ref -> 1): from the free
        list, else by LRU-evicting an unreferenced cached prefix page.
        None when neither has a page (callers stall, not crash)."""
        if self._free:
            page = self._free.popleft()
        elif self._prefix is not None:
            page = self._prefix.evict_lru(
                lambda p: self._ref[p] == 0)
            if page is not None:
                self._m_evictions.inc()
        else:
            page = None
        if page is not None:
            self._ref[page] += 1
        return page

    def _release_page(self, page: int) -> None:
        """Drop one reader; the page returns to the free list only when
        the LAST reader is gone AND the cache does not retain it."""
        self._ref[page] -= 1
        if self._ref[page] < 0:  # pragma: no cover — accounting bug
            raise AssertionError(f"page {page} refcount underflow")
        if (self._ref[page] == 0
                and (self._prefix is None
                     or not self._prefix.owns(page))):
            self._free.append(page)

    def _is_shared(self, page: int) -> bool:
        """True when a slot must CoW-fork before writing this page."""
        return (self._ref[page] > 1
                or (self._prefix is not None
                    and self._prefix.owns(page)))

    @property
    def occupied_slots(self) -> int:
        return sum(1 for s in self._slot_state if s is not None)

    @property
    def load(self) -> int:
        """Live in-flight pressure: queued + occupied slots. The
        replica-set and fleet least-loaded selectors key on this."""
        with self._cond:
            return len(self._waiting) + self.occupied_slots

    @property
    def alive(self) -> bool:
        """Scheduler thread running (readiness surface: a dead loop
        must flip /readyz, not hang clients)."""
        return (self._thread is not None and self._thread.is_alive()
                and not self._closed)

    @property
    def spec_acceptance_rate(self) -> float:
        """Accepted / proposed draft tokens over the loop's lifetime
        (0.0 while speculation is off or nothing was proposed yet)."""
        proposed = int(self._m_spec_proposed.value)
        if proposed <= 0:
            return 0.0
        return int(self._m_spec_accepted.value) / proposed

    def kv_pool_bytes(self) -> int:
        return paged_kinds.pool_bytes(self.cfg, self._kind_pages,
                                      self.page_size)

    def state_bytes(self, kind: Optional[str] = None) -> int:
        """HBM the per-slot state of `kind` pins (None: of every kind
        held by slot): every slot of every such layer. 0 for a model
        whose layers all keep pages."""
        return sum(self.slots * n
                   * paged_kinds.state_bytes_per_slot(self.cfg, k)
                   for k, n in self._slot_layers.items()
                   if kind in (None, k))

    def decode_step_programs(self) -> int:
        """Compiled-program count for the decode lane — the
        continuous-batching recompile guard. Plain mode: exactly 1
        after warmup, no matter how requests join/leave. Speculative
        mode: decode + widened verify, pinned <= 2 (both fixed-shape;
        membership is traced). -1 when the private jax counter API
        drifted."""
        n = jit_cache_size(self._step)
        if n < 0:
            return n
        if self.spec_k:
            nv = jit_cache_size(self._verify)
            if nv < 0:
                return -1
            n += nv
        return n

    def prefill_programs(self) -> int:
        """Compiled prefill programs — bounded by the prompt bucket
        ladder (one per bucket hit; the later pieces of long prompts
        one more a bucket, whatever the context's length)."""
        n, m = (jit_cache_size(self._prefill),
                jit_cache_size(self._prefill_chunk))
        return -1 if n < 0 or m < 0 else n + m

    # ---- warmup plans (docs/WARMUP.md)
    def plan_fragment(self) -> dict:
        """The "decode" fragment of a warmup plan: which of this loop's
        programs existed and at which prefill group shapes. Fixed-shape
        programs (step, verify, copy) are flags — their shapes are
        implied by the loop config; only the prefill groups are
        traffic-dependent."""
        frag = {
            "cache_key": self.cache_key,
            "role": self.role,
            "step": self._plan_step,
            "verify": self._plan_verify,
            "copy": self._plan_copy,
            "prefill": sorted(list(g) for g in self._plan_prefill),
            "prefill_ctx": sorted(list(g)
                                  for g in self._plan_prefill_ctx),
            "prefill_chunk": sorted(list(g)
                                    for g in self._plan_prefill_chunk),
        }
        if (self._drafter is not None
                and getattr(self._drafter, "kind", None) == "model"):
            frag["draft"] = {"rows": self.slots, "k": self.spec_k}
        return frag

    def warm_programs(self, frag: dict) -> int:
        """Replay a recorded plan fragment: AOT load-or-compile every
        listed program via `jax.ShapeDtypeStruct` placeholders, WITHOUT
        executing anything (execution would donate buffers and write
        the page pool). No-op unless this process has the persistent
        cache active (plain jits can't be preloaded) and the fragment
        matches this loop's program identity. Returns the number of
        programs warmed."""
        import jax

        if frag.get("cache_key") != self.cache_key:
            return 0
        if not hasattr(self._step, "warm"):
            return 0

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, np.int32)

        params_spec = jax.tree_util.tree_map(sds, self.params)
        pool_spec = jax.tree_util.tree_map(sds, self._pool)
        S, P, ps = self.slots, self._pps, self.page_size
        def by_kind(*shape):
            return {k: ints(*shape) for k in self._kind_pages}

        def rows_of(bb, columns):
            """A cold prefill's `page_ids`: pages by kind, and the
            rows' slots where a kind keeps its state by slot."""
            ids = by_kind(bb, columns)
            for kind in self._slot_layers:
                ids[kind] = ints(bb)
            return ids

        n = 0
        if frag.get("step"):
            n += self._step.warm(params_spec, ints(S), pool_spec,
                                 by_kind(S, P), ints(S), ints(S))
        if frag.get("verify") and self.spec_k:
            n += self._verify.warm(params_spec,
                                   ints(S, self.spec_k + 1), pool_spec,
                                   by_kind(S, P), ints(S), ints(S))
        if frag.get("copy"):
            n += self._copy.warm(pool_spec, ints(), ints())
        for bb, tb in frag.get("prefill", ()):
            n += self._prefill.warm(params_spec, ints(bb, tb), ints(bb),
                                    pool_spec, rows_of(bb, tb // ps))
        for bb, cb, tb in frag.get("prefill_ctx", ()):
            n += self._prefill_ctx.warm(
                params_spec, ints(bb, tb), ints(bb), pool_spec,
                by_kind(bb, tb // ps), by_kind(bb, cb), ints(bb))
        for bb, cb, tb in frag.get("prefill_chunk", ()):
            n += self._prefill_chunk.warm(
                params_spec, ints(bb, tb), ints(bb), pool_spec,
                rows_of(bb, tb // ps), by_kind(bb, cb), ints(bb))
        draft = frag.get("draft")
        if (draft and self._drafter is not None
                and hasattr(self._drafter, "warm")):
            n += int(self._drafter.warm(int(draft.get("rows", S)),
                                        int(draft.get("k", self.spec_k))))
        return n

    # ---- fleet KV plane (serving/fleetkv.py, docs/FLEET.md)
    def kv_summary(self) -> Optional[dict]:
        """The affinity summary piggybacked on /readyz: cumulative
        head-chunk fingerprints of every cached trie path (most recent
        first, capped), plus the cache/ship counters the fleet probe
        deltas into router-side series. None while the plane is off —
        the readiness payload then simply omits the key. Only tokens
        the trie RETAINS are fingerprinted; opted-out requests never
        seeded it, so nothing prompt-derived about them leaves this
        process."""
        if self._prefix is None or self.fleet_kv == fleetkv.MODE_OFF:
            return None
        # chaos: a summary-build fault must degrade the replica to
        # "no affinity signal", never fail the health probe
        chaos.hit("fleet.kv_summary")
        with self._cond:
            return {
                "v": 1,
                "mode": self.fleet_kv,
                "role": self.role,
                "page_size": self.page_size,
                "heads": fleetkv.summary_heads(self._prefix,
                                               self.page_size),
                "pages_cached": self.pages_cached,
                "hits": int(self._m_hits.value),
                "misses": int(self._m_misses.value),
                **self._ship_stats,
            }

    def kv_export(self, tokens: Sequence[int],
                  max_chunks: Optional[int] = None) -> Optional[bytes]:
        """Donor half of a page ship: serialize this replica's cached
        pages covering `tokens`' head chunks (crc-framed, no pickle —
        fleetkv.pack_pages). None when shipping is off. The matched
        pages are PINNED (reader refcount) for the duration of the
        read: eviction only takes refcount-zero pages, and any writer
        CoW-forks away from a trie-retained page, so the bytes each
        extract sees are frozen even while the pool keeps serving —
        and even across pool swaps, because a pinned page's content is
        immutable in every pool generation. Runs on the HTTP handler
        thread; only the bookkeeping takes the lock."""
        if self._prefix is None or self.fleet_kv != fleetkv.MODE_ON:
            return None
        with self._cond:
            matched = self._prefix.match(tokens)
            if max_chunks is not None:
                matched = matched[:int(max_chunks)]
            for page in matched:
                self._ref[page] += 1  # pin across the export read
        try:
            # chaos: donor faults mid-ship (a "hang" rule holds the
            # pins open — the export-vs-eviction drills ride this
            # window; "error"/"reset" drill the receiver's fallback)
            chaos.hit("fleet.kv_ship", role="export",
                      chunks=len(matched))
            pool = self._pool
            chunks = [extract_page(pool, page) for page in matched]
        finally:
            with self._cond:
                for page in matched:
                    self._release_page(page)
                self._cond.notify_all()
        meta = {
            "v": 1,
            "cache_key": self.cache_key,
            "page_size": self.page_size,
            "chunks": len(matched),
            "layers": self.cfg.n_layers,
            "shape": [self.cfg.n_kv_heads, self.page_size,
                      self.cfg.head_dim],
        }
        return fleetkv.pack_pages(meta, chunks)

    def kv_ship(self, donor_url: str, tokens: Sequence[int],
                timeout: Optional[float] = None) -> int:
        """Receiver half: fetch the donor's cached pages for `tokens`'
        head chunks and install whatever this trie is missing. Returns
        the number of pages installed; 0 on ANY failure — shipping is
        an optimization, the caller's admission prefills the same
        tokens regardless. Safe from any thread: the pool scatter is
        routed through the scheduler thread (`_kv_jobs`)."""
        if self._prefix is None or self.fleet_kv != fleetkv.MODE_ON:
            return 0
        n_full = len(tokens) // self.page_size
        if n_full == 0 or not donor_url:
            return 0
        with self._cond:
            covered = len(self._prefix.match(tokens))
        if covered >= n_full:
            return 0  # already warm locally — nothing worth a fetch
        if timeout is None:
            timeout = self.kv_ship_timeout
        try:
            # chaos: receiver-side fetch faults (transport flakes)
            chaos.hit("fleet.kv_ship", role="fetch", donor=donor_url)
            payload = fleetkv.fetch_pages(
                donor_url, tokens[:n_full * self.page_size], timeout,
                max_chunks=n_full)
            header, chunks = fleetkv.unpack_pages(payload)
            if header.get("cache_key") != self.cache_key:
                raise fleetkv.ShipError(
                    "donor/receiver decode identity mismatch — "
                    "refusing pages from a different model, page "
                    "size, kernel lane, or device")
            if not chunks:
                raise fleetkv.ShipError("donor had no cached pages")
            installed = self._kv_install(tokens, chunks, timeout)
        except Exception:
            # ANY failure — transport, framing, crc, identity, pool
            # pressure, chaos — falls back to plain prefill
            with self._cond:
                self._ship_stats["ship_failures"] += 1
            return 0
        if installed:
            with self._cond:
                self._ship_stats["page_ships"] += installed
                self._ship_stats["ship_bytes"] += len(payload)
        return installed

    def _kv_install(self, tokens, chunks, timeout: float) -> int:
        """Hand an install to the scheduler thread and wait: pool
        swaps happen outside the lock on that thread, so a scatter
        from this (handler) thread would race a prefill's swap. With
        no scheduler running (manual/test mode) the caller IS the
        scheduler — apply inline."""
        job = {"tokens": list(tokens), "chunks": chunks,
               "event": threading.Event(), "result": {}}
        self._enqueue_kv_job(job, timeout, "install did not complete "
                                           "within the ship budget")
        err = job["result"].get("error")
        if err is not None:
            raise err
        return int(job["result"].get("installed", 0))

    def _enqueue_kv_job(self, job: dict, timeout: float,
                        expiry_msg: str) -> None:
        """Route one pool-mutating job through the scheduler thread
        (or run it inline in manual/test mode) and wait it out."""
        if self.alive:
            with self._cond:
                if self._closed:
                    job["result"]["error"] = RuntimeError(
                        "decode loop is closed")
                    return
                self._kv_jobs.append(job)
                self._cond.notify_all()
            if not job["event"].wait(timeout=max(1.0, float(timeout))):
                job["result"].setdefault(
                    "error", fleetkv.ShipError(expiry_msg))
        else:
            self._run_kv_job(job)

    # ---- disaggregated prefill (docs/FLEET.md "Disaggregated roles")
    def prefill_only(self, tokens: Sequence[int],
                     timeout: Optional[float] = None) -> dict:
        """Handoff source: compute KV for `tokens`' FULL page-aligned
        head chunks into this replica's own pool and adopt the pages
        into the prefix trie as cached (refcount-zero, trie-retained)
        pages — exactly where `/kv/export` reads from — WITHOUT ever
        starting a stream. This is the whole job of a `prefill`-role
        replica: the router POSTs `/prefill` here, then names this
        replica as the `kv_donor` on the decode replica that owns the
        stream, whose existing `kv_ship` pulls the pages. No decode
        step, verify, or copy program is ever compiled by this path
        (role-scoped warmup plans pin that), and a fully-covered head
        is a cheap no-op — re-prefilling an already-hot prompt costs
        one trie match. Raises on pool pressure / chaos faults; the
        router treats ANY error as a failed handoff and falls back to
        plain unified prefill on the decode replica (bit-identical by
        the same causality argument the prefix cache rests on).
        Returns {"chunks", "covered", "cached", "kv_bytes"}."""
        if self._prefix is None:
            raise ValueError(
                "prefill_only needs the prefix cache: the trie is "
                "where handoff pages live until /kv/export ships them")
        n_full = len(tokens) // self.page_size
        if n_full == 0:
            # sub-page prompts have no trie key — nothing to hand off
            return {"chunks": 0, "covered": 0, "cached": 0,
                    "kv_bytes": 0}
        job = {"kind": "prefill", "tokens": [int(t) for t in tokens],
               "event": threading.Event(), "result": {}}
        if timeout is None:
            timeout = max(30.0, self.kv_ship_timeout)
        self._enqueue_kv_job(job, timeout, "prefill handoff did not "
                                           "complete within its budget")
        err = job["result"].get("error")
        if err is not None:
            raise err
        return job["result"]["report"]

    def _apply_prefill_only(self, tokens) -> dict:
        """Scheduler-thread half of `prefill_only`: pin the already-
        cached head run, allocate pages for the uncovered chunks, run
        the SAME bucketed prefill programs admission uses (bb=1 —
        recorded in the warmup plan like any other group), adopt the
        pages into the trie, release every pin. Mirrors
        `_kv_apply_install`'s pin/alloc/adopt/release discipline so
        the three-way page invariant holds at every exit."""
        ps = self.page_size
        head = [int(t) for t in tokens[:(len(tokens) // ps) * ps]]
        n_full = len(head) // ps
        # chaos: a handoff fault on the EXPORT side — the router sees
        # the /prefill error, counts a failed handoff, and the stream
        # proceeds with plain prefill on its decode replica
        chaos.hit("disagg.handoff", role="export", chunks=n_full)
        with self._cond:
            matched = self._prefix.match(head)
            covered = len(matched)
            need = n_full - covered
            page_bytes = paged_kinds.pool_bytes(
                self.cfg, dict.fromkeys(self._kind_pages, 1), ps)
            if need <= 0:
                return {"chunks": n_full, "covered": covered,
                        "cached": 0, "kv_bytes": n_full * page_bytes}
            for page in matched:
                self._ref[page] += 1
            fresh: List[int] = []
            if self._avail_pages() >= need:
                for _ in range(need):
                    page = self._alloc_page()
                    if page is None:  # pragma: no cover — availability
                        break         # was checked above
                    fresh.append(page)
        try:
            if len(fresh) < need:
                raise OverloadedError(
                    f"prefill handoff needs {need} pages but the pool "
                    f"has no headroom "
                    f"({len(self._free)}/{self.n_pages} free)",
                    retry_after_ms=1000)
            cov_tok = covered * ps
            tb = next(b for b in self._buckets if b >= len(head) - cov_tok)
            self._prefill_rows(
                [(head, matched + fresh, cov_tok)],
                min(_pow2(covered), self._pps) if covered else 0, tb)
            with self._cond:
                adopted = self._prefix.insert(head, matched + fresh)
                self._ship_stats["prefill_handoffs"] = (
                    self._ship_stats.get("prefill_handoffs", 0) + 1)
            return {"chunks": n_full, "covered": covered,
                    "cached": adopted, "kv_bytes": n_full * page_bytes}
        finally:
            with self._cond:
                for page in matched + fresh:
                    self._release_page(page)
                self._cond.notify_all()

    def _service_kv_jobs(self) -> None:
        """Scheduler-thread drain of queued shipped-page installs —
        runs at the top of every tick, before admission, so a ship
        that lands between ticks warms the very next `_admit` match."""
        while True:
            with self._cond:
                if not self._kv_jobs:
                    return
                job = self._kv_jobs.popleft()
            with span("decode.kv_jobs", self._phases):
                self._run_kv_job(job)

    def _run_kv_job(self, job: dict) -> None:
        try:
            if job.get("kind") == "prefill":
                job["result"]["report"] = self._apply_prefill_only(
                    job["tokens"])
            else:
                job["result"]["installed"] = self._kv_apply_install(
                    job["tokens"], job["chunks"])
        except Exception as e:
            job["result"]["error"] = e
        finally:
            job["event"].set()

    def _drain_kv_jobs(self, exc: BaseException) -> None:
        with self._cond:
            while self._kv_jobs:
                job = self._kv_jobs.popleft()
                job["result"]["error"] = exc
                job["event"].set()

    def _kv_apply_install(self, tokens, chunks) -> int:
        """Install shipped chunk K/V beyond this trie's current
        coverage: pin the existing matched path (an eviction during
        our own allocations must not consume it), allocate fresh pages
        through the normal ladder (free list first, LRU eviction
        second), scatter the bytes, adopt the pages into the trie,
        then drop every pin — adopted pages land in the cached
        (refcount-zero, trie-retained) tier exactly like a retired
        prompt's. Runs on the scheduler thread."""
        ps = self.page_size
        with self._cond:
            matched = self._prefix.match(tokens)
            covered = len(matched)
            depth = min(len(chunks), len(tokens) // ps)
            if depth <= covered:
                return 0
            need = depth - covered
            for page in matched:
                self._ref[page] += 1
            fresh: List[int] = []
            if self._avail_pages() >= need:
                for _ in range(need):
                    page = self._alloc_page()
                    if page is None:  # pragma: no cover — availability
                        break         # was checked above
                    fresh.append(page)
        try:
            if len(fresh) < need:
                raise fleetkv.ShipError(
                    "pool has no headroom for shipped pages")
            pool = self._pool
            for j, page in enumerate(fresh):
                pool = install_page(pool, page, chunks[covered + j])
            self._pool = pool  # scheduler thread: no concurrent swap
            with self._cond:
                adopted = self._prefix.insert(
                    tokens[:depth * ps], matched + fresh)
            return adopted
        finally:
            with self._cond:
                for page in matched + fresh:
                    self._release_page(page)
                self._cond.notify_all()

    def snapshot(self) -> dict:
        with self._cond:
            return {
                "role": self.role,
                "slots": self.slots,
                "occupied_slots": self.occupied_slots,
                "queued": len(self._waiting),
                "page_size": self.page_size,
                "horizon": self.horizon,
                **self._snapshot_pages(),
                "pool_bytes": self.kv_pool_bytes(),
                "max_waiting": self.max_waiting,
                "tiers": {
                    "batch_share": self.batch_share,
                    "batch_slot_cap": self._batch_slot_cap,
                    "batch_max_waiting": self.batch_max_waiting,
                    "preemptions": int(self._m_preempt.value),
                    "waiting": dict(self._tier_waiting),
                    "occupied": {
                        t: sum(1 for s in self._slot_state
                               if s is not None and s.stream.tier == t)
                        for t in TIERS},
                    "requests": {
                        t: int(self._m_tier_requests[t].value)
                        for t in TIERS},
                    "shed": {
                        t: int(self._m_tier_shed[t].value)
                        for t in TIERS},
                },
                "requests": int(self._m_requests.value),
                "tokens_streamed": int(self._m_tokens.value),
                "shed": int(self._m_shed.value),
                "deadline_exceeded": int(self._m_deadline.value),
                "cancelled": int(self._m_cancelled.value),
                "admission_waits": int(self._m_waits.value),
                "dispatches": int(self._m_steps.value),
                "dispatches_overlapped": int(self._m_overlapped.value),
                "prefill_passes": int(self._m_prefill_passes.value),
                "phases": self._phases.totals(),
                "queue_wait": {"seconds": self._m_queue_wait.sum,
                               "count": self._m_queue_wait.count},
                "slow_ticks": slowest(self._slow_ticks),
                "host": self._host.snapshot(),
                "decode_kernel": {
                    "requested": self.kernel_requested,
                    "selected": self.decode_kernel,
                    "kv_read_bytes": {
                        "kernel": int(self._m_kv_read["kernel"].value),
                        "gather": int(self._m_kv_read["gather"].value),
                    },
                },
                # by kind where there are kinds to tell apart
                "paged_block_pages": (
                    dict(self._block_pages) if len(self._block_pages) > 1
                    else self._block_pages[paged_kinds.KIND_FULL]),
                "decode_step_programs": self.decode_step_programs(),
                "prefill_programs": self.prefill_programs(),
                "prefill_ctx_programs": jit_cache_size(self._prefill_ctx),
                "prefill_tokens": self._prefill_token_count,
                "prefill_chunks": {
                    "first": int(self._m_chunks[False].value),
                    "carried": int(self._m_chunks[True].value),
                    "tokens": int(self._m_chunk_tokens.value)},
                "prefix_cache": {
                    "enabled": self.prefix_cache_enabled,
                    "hits": int(self._m_hits.value),
                    "misses": int(self._m_misses.value),
                    "forks": int(self._m_forks.value),
                    "evictions": int(self._m_evictions.value),
                    "pages_cached": self.pages_cached,
                    "pages_shared": self.pages_shared,
                    "cached_unreferenced": self._cached_unref(),
                    "nodes": (0 if self._prefix is None
                              else len(self._prefix)),
                },
                "fleet_kv": {
                    "mode": self.fleet_kv,
                    **self._ship_stats,
                },
                "speculation": {
                    "enabled": bool(self.spec_k),
                    "k": self.spec_k,
                    "drafter": (None if self._drafter is None
                                else self._drafter.kind),
                    "proposed": int(self._m_spec_proposed.value),
                    "accepted": int(self._m_spec_accepted.value),
                    "rounds": int(self._m_spec_rounds.value),
                    "acceptance_rate": self.spec_acceptance_rate,
                    "draft_programs": (
                        self._drafter.draft_programs()
                        if self._drafter is not None
                        and hasattr(self._drafter, "draft_programs")
                        else 0),
                },
            }

    def _snapshot_pages(self) -> dict:
        """`pages_total`, `pages_in_use` and `peak_pages_in_use`: pages
        of the one kind, or, where the model's layers are of several,
        sums over kinds weighted by the kind's layers (`_kind_weight`);
        each kind's own pages under `pages_by_kind`, what the kinds
        held by slot hold under `state` (summed) and `state_by_kind`,
        and the pairs of a model with
        an expert layer under `moe`. Caller holds the lock."""
        by_kind = {}
        for kind, n in self._kind_pages.items():
            by_kind[kind] = {"layers": self._kind_layers[kind],
                             "pages_total": n,
                             "pages_in_use": self._kind_in_use(kind)}
        win = self._win
        if win is not None:
            by_kind[paged_kinds.KIND_WINDOW].update(
                peak_pages_in_use=win.peak_in_use, released=win.released,
                pages_per_slot_peak=win.peak_per_slot,
                table_pages=paged_kinds.window_table_pages(
                    self.cfg, self.page_size))
        pages = {
            "pages_total": sum(self._kind_weight[k] * n
                               for k, n in self._kind_pages.items()),
            "pages_in_use": self._weighted_in_use(),
            "peak_pages_in_use": self._peak_pages,
            "pages_by_kind": by_kind}
        if self._slot_layers:
            by = {kind: {"bytes": self.state_bytes(kind),
                         "bytes_per_slot": n * paged_kinds.
                         state_bytes_per_slot(self.cfg, kind),
                         "layers": n}
                  for kind, n in self._slot_layers.items()}
            pages["state"] = {
                "bytes": self.state_bytes(),
                "bytes_per_slot": sum(v["bytes_per_slot"]
                                      for v in by.values()),
                "layers": sum(self._slot_layers.values()),
                "slots_live": self.occupied_slots}
            pages["state_by_kind"] = by
        moe = self._moe
        if moe is not None:
            pages["moe"] = {
                "held_first": self.cfg.held_first,
                "n_held": self.cfg.n_held,
                "n_experts": self.cfg.n_experts,
                "experts_per_token": self.cfg.experts_per_token,
                "pairs_by_layer_expert": moe["pairs"].tolist(),
                "pairs": int(moe["pairs"].sum()),
                **{k: v for k, v in moe.items() if k != "pairs"}}
        return pages

    def close(self, timeout: float = 30.0) -> None:
        """Stop accepting new requests, drain everything queued and in
        flight, stop the scheduler thread."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout)

    def __enter__(self) -> "DecodeLoop":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------ scheduler
    def _idle(self) -> bool:
        """Nothing queued, installed or in flight, no step unread.
        Caller holds the lock."""
        return (not self._closed and not self._waiting
                and not self._kv_jobs and self._drained())

    def _drained(self) -> bool:
        """No slot occupied and no step enqueued and unread (an
        end-of-sequence token leaves one behind it)."""
        return self.occupied_slots == 0 and self._inflight is None

    def _run(self) -> None:
        while True:
            with self._cond:
                if self._idle():
                    with span(IDLE_WAIT, self._phases):
                        while self._idle():
                            self._cond.wait(timeout=0.1)
                if (self._closed and not self._waiting
                        and self._drained()):
                    self._drain_kv_jobs(
                        RuntimeError("decode loop closed"))
                    return
            try:
                self.tick()
            except Exception as e:  # pragma: no cover — defensive: a
                # scheduler crash must fail the in-flight streams loudly
                # instead of hanging every waiting client
                self._fail_all(e)
                return

    def _fail_all(self, exc: BaseException) -> None:
        self._drain_kv_jobs(exc)
        with self._cond:
            self._deferred = []
            self._inflight = None  # its tokens have no stream to reach
            for i, slot in enumerate(self._slot_state):
                if slot is not None:
                    for page in slot.pages:
                        self._release_page(page)
                    if self._win is not None:
                        self._win.drop(i)
                    slot.stream._finish("error", exc)
                    self._slot_state[i] = None
            while self._waiting:
                stream = self._waiting.popleft()
                self._tier_waiting[stream.tier] -= 1
                stream._finish("error", exc)

    def tick(self) -> bool:
        """One scheduler pass: admit what fits, grant boundary pages,
        enqueue one compiled dispatch if any slot can advance, then read
        the dispatch of the pass BEFORE, emit its tokens and retire what
        finished: a token shows a pass after its step was dispatched.
        Returns True if a dispatch was enqueued or read. Public so tests
        (and `start=False` callers) can drive the loop
        deterministically."""
        phases = self._phases
        phases.begin_pass()
        gc_ns, usage = self._host.gc_ns, host.thread_usage()
        with span(TICK, phases) as tick:
            ran = tick.args["dispatched"] = self._pass()
        self._keep_if_slowest(tick, gc_ns, usage)
        return ran

    def _keep_if_slowest(self, tick, gc_ns: Optional[int] = None,
                         usage=None) -> None:
        """Keep this pass if it is the longest of its interval so far:
        its start on `time.perf_counter`, its length, the milliseconds
        of each phase that ran in it (what they leave of the length is
        the tick's self time), its CPU time and what the process did
        meanwhile (`host.HostMonitor.during`, given the collector's
        total and the thread's usage as read at the pass's start). A
        pass that is not kept allocates nothing."""
        start_s, dur_ms = tick.start_ns / 1e9, tick.dur_ns / 1e6
        at = slowest_slot(self._slow_ticks, start_s, dur_ms,
                          SLOW_TICK_INTERVAL_S)
        if at is None:
            return
        cpu = self._phases.pass_cpu_ns
        cpu_ms, d2h_cpu_ms = cpu[TICK] / 1e6, cpu["decode.d2h"] / 1e6
        d2h_ms = self._phases.pass_ns["decode.d2h"] / 1e6
        self._slow_ticks[at] = {
            "start_s": start_s, "dur_ms": dur_ms,
            "phases": {n: ns / 1e6
                       for n, ns in self._phases.pass_ns.items()
                       if ns and n != TICK},
            "cpu_ms": cpu_ms, "d2h_cpu_ms": d2h_cpu_ms,
            "offcpu_ms": dur_ms - d2h_ms - (cpu_ms - d2h_cpu_ms),
            **self._host.during(tick.start_ns, tick.start_ns + tick.dur_ns,
                                gc_ns, usage)}

    def _pass(self) -> bool:
        """The body of `tick()`."""
        with span("decode.reap", self._phases):
            self._reap()
        # shipped-page installs land before admission so the very next
        # `_admit` match sees them as cached chunks
        self._service_kv_jobs()
        # chaos point: a "delay" rule paces every scheduler pass (the
        # SLO drills use it to pin slot occupancy open long enough for
        # preemption to observably fire); an "error" drills the
        # fail-loudly path in _run
        chaos.hit("decode.step")
        with span("decode.admit", self._phases) as admit:
            admit.args["admitted"] = self._admit()
        # a piece of a long prompt is work done, step or no step
        ran = bool(self._phases.pass_ns[PREFILL_DISPATCH])
        ran = self._dispatch() or ran
        if self._deferred:
            # no step was read in this pass (the first of a busy spell,
            # or every admitted request has max_tokens=1): the firsts
            # still go out as soon as their prefill is over, after the
            # step that overlaid them was enqueued
            self._flush_first_tokens()
        if not ran:
            # nothing advanced and no step is unread: either idle, or
            # every occupied slot is starved of pages that can never
            # come — fail those rather than spin forever
            with self._cond:
                stuck = (self.occupied_slots > 0
                         and (self._avail_pages() == 0
                              or (self._win is not None
                                  and not self._win.free))
                         and all(s is None
                                 or (not s.in_prefill
                                     and self._stop[i] <= self._lengths[i])
                                 for i, s in enumerate(self._slot_state)))
            if stuck:
                self._fail_all(RuntimeError(
                    "KV page pool exhausted with every slot stalled — "
                    "no completion can free a page; size the pool with "
                    "paged_kv_bytes (docs/SERVING.md)"))
        return ran

    def run_until_idle(self, max_ticks: int = 100_000) -> None:
        """Drive the loop inline until nothing is queued or in flight
        (manual mode / tests)."""
        for _ in range(max_ticks):
            with self._cond:
                if not self._waiting and self._drained():
                    return
            self.tick()
        raise RuntimeError("decode loop did not drain")

    # ---- cancellation / expiry reaping
    def _reap(self) -> None:
        """Retire occupied slots whose stream was cancelled (client
        disconnect, explicit `cancel()`) or whose deadline budget died
        mid-flight: the slot is released and its pages return to the
        pool within THIS scheduler pass — an abandoned stream must not
        keep burning pages (docs/SERVING.md "Cancellation")."""
        with self._cond:
            for i, slot in enumerate(self._slot_state):
                if slot is None:
                    continue
                stream = slot.stream
                if stream.cancelled:
                    self._m_cancelled.inc()
                    self._retire(i, slot, "cancelled")
                elif (stream.deadline is not None
                      and stream.deadline.expired):
                    self._m_deadline.inc()
                    self._retire(i, slot, "deadline_exceeded",
                                 error=DeadlineExceededError(
                                     "deadline exceeded mid-generation",
                                     deadline_ms=stream.deadline.budget_ms,
                                     elapsed_ms=stream.deadline
                                     .elapsed_ms()))

    # ---- admission
    def _preempt_one(self, used: set) -> bool:
        """Evict ONE batch-held slot so a blocked interactive admission
        can proceed. The victim — the batch slot with the FEWEST tokens
        emitted, the cheapest to resume — retires with finish_reason
        "preempted" and error None: every token it already emitted was
        already streamed (and dedupable by absolute `token_index`), its
        pages return to the pool, and its full prompt pages seed the
        prefix cache so the router-side durable-stream resume replays
        the prefix nearly for free. Lossless by construction — the
        router re-admits `prompt + delivered` with the remaining budget
        exactly as a replica-failure resume would (docs/SERVING.md
        "Priority tiers"). Pure host bookkeeping: the retirement path
        is the cancel/deadline one, so `decode_step_programs()` never
        moves. Returns True when a victim was retired. Caller holds the
        lock."""
        victim = None
        for i, slot in enumerate(self._slot_state):
            if slot is None or slot.stream.tier != TIER_BATCH:
                continue
            if (victim is None or slot.emitted
                    < self._slot_state[victim].emitted):
                victim = i
        if victim is None:
            return False
        self._m_preempt.inc()
        self._retire(victim, self._slot_state[victim], "preempted")
        used.discard(victim)
        return True

    def _admit(self) -> int:
        """Prefill the next piece of the long prompts that hold a slot,
        claim slots and pages for what fits beside them, then prefill
        that by groups. Returns the number of requests admitted."""
        ps = self.page_size
        # claim everything that fits in one lock pass
        admitted = []  # (slot_idx, stream, pages, plen, covered)
        now = None  # one clock read for the requests this pass admits
        # bucket tokens left: the pieces of prompts already admitted go
        # first, what they leave of the bound is for new requests
        budget, continued = self._continue_prefills()
        win = self._win
        with self._cond:
            used = {i for i, s in enumerate(self._slot_state)
                    if s is not None}
            batch_held = sum(1 for s in self._slot_state
                             if s is not None
                             and s.stream.tier == TIER_BATCH)
            inter_held = len(used) - batch_held
            while self._waiting:
                # tier-priority scan: every interactive arrival goes
                # ahead of every batch one (FIFO within a tier) — a
                # head-of-line bulk prompt must never make the user who
                # is watching wait
                stream = next((s for s in self._waiting
                               if s.tier == TIER_INTERACTIVE),
                              self._waiting[0])
                interactive = stream.tier == TIER_INTERACTIVE
                # queue-expired or cancelled work is shed here, BEFORE
                # any prefill compute (the dispatch counters pin it)
                if stream.cancelled:
                    self._waiting.remove(stream)
                    self._tier_waiting[stream.tier] -= 1
                    self._m_cancelled.inc()
                    stream._finish("cancelled")
                    continue
                if (stream.deadline is not None
                        and stream.deadline.expired):
                    self._waiting.remove(stream)
                    self._tier_waiting[stream.tier] -= 1
                    self._m_deadline.inc()
                    stream._finish(
                        "deadline_exceeded", DeadlineExceededError(
                            "deadline exceeded while queued for a "
                            "decode slot",
                            deadline_ms=stream.deadline.budget_ms,
                            elapsed_ms=stream.deadline.elapsed_ms()))
                    continue
                if (not interactive
                        and batch_held >= self._batch_slot_cap
                        and inter_held > 0):
                    # weighted-fair share: while interactive work is
                    # live on the machine, batch holds at most its
                    # share of the slots — it soaks ALL idle capacity
                    # only when no user-facing work wants it
                    self._m_waits.inc()
                    break
                plen = len(stream.prompt)
                if budget is not None:
                    # the bound on what one pass prefills: rows count at
                    # their bucket's width, and a pass always takes one
                    tb = next(b for b in self._buckets
                              if b >= self._first_piece(plen))
                    if (admitted or continued) and tb > budget:
                        break
                    budget -= tb
                idx = next((i for i in range(self.slots)
                            if i not in used), None)
                while (idx is None and interactive
                       and self._preempt_one(used)):
                    batch_held -= 1
                    idx = next((i for i in range(self.slots)
                                if i not in used), None)
                if idx is None:
                    self._m_waits.inc()
                    break
                # longest cached prefix of FULL page-aligned chunks:
                # those pool pages are mapped by reference, only the
                # uncovered tail is prefilled
                use_cache = (self._prefix is not None
                             and stream.prefix_cache)
                matched = (self._prefix.match(stream.prompt)
                           if use_cache else [])
                covered = len(matched) * ps
                # reference the cached run FIRST, so the availability
                # check and any eviction below can never consume the
                # very pages this request is about to read
                for page in matched:
                    self._ref[page] += 1
                # uncovered prompt pages + room for the first decode
                # write (when fully covered, that is the CoW fork's
                # headroom) — the check that replaces the contiguous
                # path's whole-max_len reservation
                need = pages_for_tokens(plen + 1, ps) - len(matched)
                while (self._avail_pages() < need and interactive
                       and self._preempt_one(used)):
                    batch_held -= 1
                if (self._avail_pages() < need
                        or (win is not None
                            and len(win.free) < win.needed(plen))):
                    for page in matched:
                        self._release_page(page)
                    self._m_waits.inc()
                    break
                self._waiting.remove(stream)
                self._tier_waiting[stream.tier] -= 1
                if interactive:
                    inter_held += 1
                else:
                    batch_held += 1
                used.add(idx)
                alloc = pages_for_tokens(plen, ps) - len(matched)
                pages = list(matched)
                for _ in range(alloc):
                    page = self._alloc_page()
                    if page is None:  # pragma: no cover — availability
                        raise AssertionError(  # was checked above
                            "page allocation failed after availability "
                            "check")
                    pages.append(page)
                if win is not None:
                    win.claim(idx, plen)
                if use_cache:
                    (self._m_hits if matched else self._m_misses).inc()
                if now is None:
                    now = time.perf_counter()
                stream.admitted = now
                self._m_queue_wait.observe(now - stream.submitted)
                admitted.append((idx, stream, pages, plen, covered))
            if admitted:
                self._note_peak()
        if not admitted:
            return 0
        # what this pass prefills of each: the whole prompt, or the
        # first piece of one that is longer than a piece
        admitted = [a + (self._first_piece(a[3]),) for a in admitted]
        cold = [a for a in admitted if a[4] == 0]
        warm = [a for a in admitted if 0 < a[4] < a[3]]
        full = [a for a in admitted if a[4] >= a[3]]
        # fully-covered prompts skip prefill entirely: the slot starts
        # ONE position early with its last prompt token pending, so the
        # first compiled decode dispatch recomputes position plen-1 —
        # its K/V write re-enters the last shared page, which the CoW
        # guard forks before the dispatch — and emits the first token.
        for idx, stream, pages, plen, covered, _upto in full:
            slot = _Slot(stream, pages,
                         stop_len=plen + stream.max_tokens - 1)
            slot.awaiting_first = False
            with self._cond:
                self._slot_state[idx] = slot
                self._table[idx, :len(pages)] = pages
                self._lengths[idx] = plen - 1
                self._pending[idx] = stream.prompt[-1]
                self._stop[idx] = 0  # set by _grant_pages
                self._dirty = self._host_rows[idx] = True
        # one compiled prefill per (cached pages, prompt-bucket,
        # batch-bucket) group: an admission burst costs O(groups)
        # dispatches, not O(streams). Cold prompts first, then the warm
        # tails, which start on a page boundary by construction (only
        # FULL chunks match) and ride the ctx-aware prefill.
        groups: dict = {}
        for item in cold + warm:
            _idx, _stream, _pages, _plen, covered, upto = item
            cb = min(_pow2(covered // ps), self._pps) if covered else 0
            tb = next(b for b in self._buckets if b >= upto - covered)
            groups.setdefault((cb, tb), []).append(item)
        for (cb, tb), group in groups.items():
            self._prefill_group(group, cb, tb)
        return len(admitted)

    def _first_piece(self, plen: int) -> int:
        """Tokens of a prompt of `plen` that its admission's pass
        prefills: all of them, or one piece of a longer prompt."""
        return plen if self._piece is None else min(plen, self._piece)

    def _continue_prefills(self):
        """The next piece of every prompt that holds a slot and is not
        prefilled to its end, oldest admission first, as far as the
        bound on a pass's prefill allows (one is always taken): the
        piece's K/V goes to the pages the slot already holds, its
        linear layers start from the state and columns the slot kept,
        its full layers read the earlier pieces through the slot's
        pages. A piece is a whole bucket of pages but the last, whose
        bucket is no narrower than a quarter of a piece (three programs,
        one width of context table, whatever the prompt). The decode
        step of the streams that are running is enqueued behind it, in
        this pass, and the prompt's next piece in the next: a long
        prompt holds them for one piece a step, never for its whole
        length. Returns (what is left of the bound, pieces enqueued)."""
        budget, n = self.prefill_tokens_per_pass, 0
        if self._piece is None:
            return budget, n
        with self._cond:
            todo = sorted(
                ((i, s) for i, s in enumerate(self._slot_state)
                 if s is not None and s.in_prefill),
                key=lambda item: item[1].stream.admitted)
        for idx, slot in todo:
            plen, at = len(slot.stream.prompt), slot.prefilled
            upto = min(plen, at + self._piece)
            tb = next(b for b in self._buckets
                      if b >= max(upto - at, self._piece // 4))
            if n and tb > budget:
                break
            budget -= tb
            n += 1
            self._prefill_group(
                [(idx, slot.stream, slot.pages, plen, at, upto)],
                self._pps, tb, slot=slot)
        return budget, n

    def _prefill_group(self, group, cb: int, tb: int, slot=None) -> None:
        """Enqueue one prefill program for `group`, items of (slot
        index, stream, pages, prompt length, tokens the cache already
        covers, tokens it covers after this program), and install what
        it leaves. `slot`: the group is the next piece of that slot's
        prompt (one row over a context table of `cb` columns)."""
        ps = self.page_size
        carried = slot is not None
        with self._prefill_span(group, _pow2(len(group)), tb, ctx=cb,
                                carried=carried):
            first = self._prefill_rows(
                [(stream.prompt[:upto],
                  pages[:pages_for_tokens(upto, ps)], cov)
                 for _, stream, pages, _, cov, upto in group], cb, tb,
                slots=[item[0] for item in group], piece=carried)
        for *_, plen, cov, upto in group:
            if upto < plen or carried:
                self._m_chunks[carried].inc()
                self._m_chunk_tokens.inc(upto - cov)
        self._install_prefilled(group, first, slot)

    def _prefill_rows(self, rows, cb: int, tb: int, slots=None,
                      piece: bool = False):
        """Pack one prefill group and enqueue its program: `rows` of
        (a prompt's tokens, its pages in logical order, how many of the
        tokens cached pages cover), padded to a power of two of rows of
        `tb` tokens. `cb` is the width of the table of cached pages a
        warm group reads (`prefill_ctx`); 0 is a cold group (`prefill`),
        whose `slots` say where a window kind claimed pages for the
        rows and where a linear kind's state goes. `piece`: the rows
        are later pieces of prompts prefilled in pieces, the context
        the slot's own pages (`prefill_chunk_fn`, which also reads the
        slot's state). The program is dispatched but NOT synced — back-to-back
        groups queue without a host round trip between them. Returns
        its (first tokens, aux), still on the device."""
        import jax.numpy as jnp

        ps, win = self.page_size, self._win
        bb = _pow2(len(rows))
        padded = np.zeros((bb, tb), np.int32)
        lens = np.ones((bb,), np.int32)  # pad rows: true_len 1
        pids = np.full((bb, tb // ps), self._trash, np.int32)
        ctab = np.full((bb, cb), self._trash, np.int32)
        clen = np.zeros((bb,), np.int32)
        for row, (tokens, pages, cov) in enumerate(rows):
            cp, tl = cov // ps, len(tokens) - cov
            padded[row, :tl] = tokens[cov:]
            lens[row] = tl
            pids[row, :len(pages) - cp] = pages[cp:]
            ctab[row, :cp] = pages[:cp]
            clen[row] = cov
            self._prefill_token_count += tl
        d_pids = {paged_kinds.KIND_FULL: jnp.asarray(pids)}
        if self._slot_layers and slots is not None:
            # where each row's state is (a piece) and goes: its slot; a
            # padding row names a slot past the last, and its write is
            # dropped
            at = np.full((bb,), self.slots, np.int32)
            at[:len(slots)] = slots
            for kind in self._slot_layers:
                d_pids[kind] = jnp.asarray(at)
        if piece:
            self._plan_prefill_chunk.add((bb, cb, tb))
            first, self._pool = self._prefill_chunk(
                self.params, jnp.asarray(padded), jnp.asarray(lens),
                self._pool, d_pids,
                {paged_kinds.KIND_FULL: jnp.asarray(ctab)},
                jnp.asarray(clen))
            return first
        if cb:
            self._plan_prefill_ctx.add((bb, cb, tb))
            first, self._pool = self._prefill_ctx(
                self.params, jnp.asarray(padded), jnp.asarray(lens),
                self._pool, d_pids,
                {paged_kinds.KIND_FULL: jnp.asarray(ctab)},
                jnp.asarray(clen))
            return first, ()
        if win is not None and slots is not None:
            # only the pages the first decoded token can still see; the
            # rest of the prompt's K/V in the window layers goes to that
            # kind's trash page
            wids = np.full(pids.shape, win.trash, np.int32)
            for row, idx in enumerate(slots):
                lo, hi = int(win.lo[idx]), int(win.hi[idx])
                wids[row, lo:hi] = win.table[idx, lo:hi]
            d_pids[paged_kinds.KIND_WINDOW] = jnp.asarray(wids)
        self._plan_prefill.add((bb, tb))
        first, self._pool = self._prefill(
            self.params, jnp.asarray(padded), jnp.asarray(lens),
            self._pool, d_pids)
        return first

    def _prefill_span(self, group, bb: int, tb: int, ctx: int,
                      carried: bool = False) -> span:
        """The span of one prefill group, host packing and enqueue: the
        program's batch and token buckets, its real rows and tokens, the
        context pages it reads (0 = a cold prefill), whether it starts
        from the state a slot kept (`carried`: a later piece of a long
        prompt) and the requests in it."""
        return span(PREFILL_DISPATCH, self._phases, bb=bb, tb=tb,
                    rows=len(group), ctx=ctx, carried=carried,
                    tokens=sum(upto - cov for *_, cov, upto in group),
                    requests=[a[1].request_id for a in group])

    def _install_prefilled(self, group, first, slot=None) -> None:
        """Install slots for one prefill group (`slot`: the group is
        the next piece of that slot's prompt, installed already).
        `first` is the program's (first tokens, aux): both stay on the
        device until the next flush (`self._deferred`) and come back in
        one read. A prompt whose end this program did not reach keeps
        its slot out of the decode step (no table row, length and stop
        0: the step masks it, and its state is its own to move) and has
        no first token yet."""
        members = []
        for row, (idx, stream, pages, plen, _cov, upto) in enumerate(group):
            with self._cond:
                if slot is None:
                    self._slot_state[idx] = _Slot(
                        stream, pages,
                        stop_len=plen + stream.max_tokens - 1,
                        prefilled=upto)
                else:
                    slot.prefilled = upto
                if upto < plen:
                    continue
                members.append((row, idx))
                self._table[idx, :len(pages)] = pages
                self._lengths[idx] = plen
                self._pending[idx] = 0  # real value still on device
                self._stop[idx] = 0  # set by _grant_pages
                self._dirty = self._host_rows[idx] = True
        # a piece that ends no prompt is read back like any other: the
        # tokens of the step before it are emitted once it is over, so
        # the running streams' gaps are one piece and one step each,
        # not none and then two (`itl_p98_ms` 381 for 205 on
        # `olmohyb7b-docs-chunked`; PERF.md section 6, PR 37)
        self._deferred.append(
            (first, members, sum(upto - cov for *_, cov, upto in group)))

    # ---- page granting
    def _grant_pages(self) -> None:
        """Before a dispatch: give every occupied slot pages covering
        the next advance-window positions past its dispatched cursor
        (`horizon` plain steps, or the `spec_k`-draft + 1 verify width
        in speculative mode, capped at its token budget) and set its
        device `stop` bound to the granted frontier — a slot the pool
        cannot extend simply stops advancing there. Because the CoW
        guard fences the WHOLE [length, stop) window, every position a
        speculative verify may
        write — including draft tokens that get rejected — lands in
        private pages: rollback is just the host cursor not moving."""
        adv = (self.spec_k + 1) if self.spec_k else self.horizon
        with span("decode.grant_pages", self._phases), self._cond:
            for i, slot in enumerate(self._slot_state):
                if slot is None or slot.in_prefill:
                    continue  # a prompt not prefilled to its end: no step
                length = int(self._lengths[i])
                target = min(length + adv, slot.stop_len)
                want = pages_for_tokens(target, self.page_size)
                granted = False
                while len(slot.pages) < want:
                    page = self._alloc_page()
                    if page is None:
                        break
                    self._table[i, len(slot.pages)] = page
                    slot.pages.append(page)
                    granted = True
                alloc_end = len(slot.pages) * self.page_size
                if self._win is not None:
                    # the window kind grants from its own free list; the
                    # slot advances as far as BOTH kinds have pages
                    held = self._win.hi[i]
                    w_end = self._win.extend(i, want)
                    granted = granted or w_end > held
                    alloc_end = min(alloc_end, w_end * self.page_size)
                if granted:
                    self._note_peak()
                stop = min(slot.stop_len, alloc_end)
                if stop > length:
                    stop = self._cow_guard(i, slot, length, stop)
                if stop <= length and slot.stop_len > length:
                    self._m_waits.inc()  # page-starved this pass
                if stop != self._stop[i]:
                    self._stop[i] = stop
                    self._dirty = True

    def _cow_guard(self, i: int, slot: _Slot, length: int,
                   stop: int) -> int:
        """Copy-on-write fence, run before every dispatch: positions
        [length, stop) are about to be WRITTEN, so any page in that
        range that is still shared — mapped by another slot, or
        retained by the prefix index — is forked into a private copy
        first (`copy_page` duplicates the exact bytes, so outputs are
        unchanged). When no page can be obtained for the fork, the
        slot's stop bound clamps to the shared frontier: the same
        stall-until-a-retirement-frees-pages backpressure as page
        granting. Chaos point `decode.fork` fires inside the fork so
        drills can prove a mid-fork fault leaves page accounting
        balanced. Caller holds the lock.

        With a step in flight `length` is the dispatched cursor. The
        step in flight wrote this slot at `length - 1`, in a page its own
        fence made private, so a page found shared here is one no
        enqueued step writes; the copy is enqueued behind that step, and
        the page the fork gives up keeps a reader or the cache (it was
        shared) and never reaches the free list from here. The page the
        fork TAKES may be one `_alloc_page` evicts from the cache: its
        only possible reader in flight is a step dispatched for a slot
        that has retired since, whose tokens reach no stream."""
        import jax.numpy as jnp

        ps = self.page_size
        for j in range(length // ps, (stop - 1) // ps + 1):
            page = slot.pages[j]
            if not self._is_shared(page):
                continue
            new = self._alloc_page()
            if new is None:
                # fork-under-pressure: hold just before the shared page
                return max(length, j * ps)
            try:
                chaos.hit("decode.fork")
                self._plan_copy = True
                self._pool = self._copy(
                    self._pool, jnp.asarray(page, jnp.int32),
                    jnp.asarray(new, jnp.int32))
            except BaseException:
                # balance the books before propagating: the fresh page
                # goes straight back (nothing was mapped into it), the
                # shared page keeps all its readers
                self._release_page(new)
                raise
            slot.pages[j] = new
            self._table[i, j] = new
            slot.no_cache.add(new)
            self._release_page(page)
            self._m_forks.inc()
            self._dirty = True
        return stop

    # ---- one compiled dispatch
    def _dispatch(self) -> bool:
        """Route one dispatch round: draft-and-verify when speculation
        is on, the horizon chain otherwise."""
        if self.spec_k:
            return self._dispatch_spec()
        return self._dispatch_plain()

    # ---- plain dispatch (horizon token steps), a second step in flight
    def _dispatch_plain(self) -> bool:
        """Enqueue the next step, THEN read the one before it: the rest
        of the pass runs under the device's next step. The one order of
        a pass of the plain lane, for every model and horizon."""
        step = self._enqueue_step()
        prev, self._inflight = self._inflight, step
        if prev is not None:
            self._read_step(prev)
        return step is not None or prev is not None

    def _enqueue_step(self) -> Optional[_Step]:
        """Prepare and enqueue one step against the dispatched cursor
        (`_lengths`), whether or not the step before it was read: window
        pages released, pages granted, the fence run, stop bounds set,
        what changed uploaded, the step called on the device's own
        tokens, lengths and pool. None where no slot can advance."""
        import jax.numpy as jnp

        phases = self._phases
        if self._win is not None:
            self._release_window()
        self._grant_pages()
        with self._cond:
            members = [(i, s) for i, s in enumerate(self._slot_state)
                       if s is not None
                       and self._stop[i] > self._lengths[i]]
            if not members:
                return None
            before = self._lengths.copy()
            # what the step consumes a slot, the device's own rule
            # (`lengths < stop`, `horizon` times): 0 for an idle slot
            advance = np.clip(self._stop - before, 0, self.horizon)
            with span("decode.upload", phases):
                if self._host_rows.any():
                    # the host's mirrors lag a step for every other
                    # slot: the device is never overwritten from them
                    self._d_tokens, self._d_lengths = self._set_rows(
                        np.stack((self._host_rows, self._pending,
                                  self._lengths)),
                        self._d_tokens, self._d_lengths)
                    self._host_rows[:] = False
                if self._dirty:
                    self._d_table = self._device_tables()
                    self._d_stop = self._upload(self._stop)
                    self._dirty = False
                # overlay deferred prefill tokens (still
                # device-resident) into the feedback array — ONE scatter
                # per prefill group, no sync
                for (arr, _aux), group, _n in self._deferred:
                    if not group:
                        continue  # a piece that ended no prompt
                    rows = jnp.asarray([r for r, _ in group])
                    idxs = jnp.asarray([i for _, i in group])
                    self._d_tokens = self._d_tokens.at[idxs].set(
                        arr[rows])
            self._lengths += advance
        t0 = time.perf_counter()
        self._plan_step = True
        with span("decode.step_dispatch", phases, runnable=len(members)):
            out, self._d_tokens, self._d_lengths, self._pool = self._step(
                self.params, self._d_tokens, self._pool, self._d_table,
                self._d_lengths, self._d_stop)
        self._count_dispatch()
        if self._inflight is not None:
            self._m_overlapped.inc()
        return _Step(out, members, before, advance, t0)

    def _count_dispatch(self) -> None:
        """A dispatch was enqueued in this pass; with a prefill enqueued
        in the same pass it is one whose token gap the prefill
        lengthens."""
        self._m_steps.inc()
        if self._phases.pass_ns[PREFILL_DISPATCH]:
            self._m_prefill_passes.inc()

    def _read_step(self, step: _Step) -> None:
        """Read an enqueued step's tokens, account for it, flush first
        tokens, emit its tokens and retire what finished."""
        import jax

        phases = self._phases
        # the (K, S) token D2H is the sync the streams need anyway; what
        # the layers count travels beside it, and nothing more is read
        # where they count nothing. With a later step enqueued this
        # waits only for what is left of `step`. The handle is given up
        # INSIDE the span, and no other name may keep a device array of
        # a step: held to the end of the pass, its release cost 0.6 ms
        # (sat) to 1.3 ms (ep8) a pass outside every span (PERF.md
        # section 6, PR 32).
        with span("decode.d2h", phases):
            toks, aux = jax.device_get(step.out)
            step.out = None
        # from the read of the step before, where the two overlapped:
        # the sum stays the time some step was unread
        now = time.perf_counter()
        self._m_step_s.observe(now - max(step.t0, self._read_at))
        self._read_at = now
        # per-token-step KV read accounting, host math mirroring the
        # device chain: inner step k runs at cursor before+k, clamped
        # at each slot's stop bound (stalled/idle slots hold still).
        # Both figures are recorded each dispatch — the selected lane
        # is in snapshot()["decode_kernel"]
        with span("decode.account", phases):
            for k in range(self.horizon):
                if self._moe is not None:
                    self._count_pairs(aux[k], len(step.members),
                                      decode=True)
                self._count_read_bytes(
                    step.before + np.minimum(k, step.advance))
        self._flush_first_tokens()  # emit firsts BEFORE chunk tokens
        with span("decode.emit", phases) as emit:
            emitted = 0
            for i, slot in step.members:
                # retired since the step was enqueued (an end of
                # sequence a step ago or at the flush, a cancel, a
                # deadline, a preemption), the slot maybe taken again:
                # the step's tokens for it reach no stream
                if self._slot_state[i] is not slot:
                    continue
                for j in range(int(step.advance[i])):
                    tok = int(toks[j, i])
                    self._pending[i] = tok
                    slot.emitted += 1
                    emitted += 1
                    self._emit_and_maybe_finish(i, slot, tok)
                    if self._slot_state[i] is None:
                        break  # retired: discard speculative overshoot
            emit.args["tokens"] = emitted

    def _release_window(self) -> None:
        """Before the grants of a pass: return to the window kind's
        free list every page whose last key the slot's next query no
        longer sees (the query at the dispatched cursor); the table
        takes the trash page in its place. The pages a pass returns are
        the pass's to grant. The step in flight may still read them:
        whatever writes them next is enqueued behind it, and the device
        runs its programs in order."""
        with span(RELEASE_WINDOW, self._phases) as rel, self._cond:
            n = 0
            for i, slot in enumerate(self._slot_state):
                if slot is not None:
                    n += self._win.release_before(i,
                                                  int(self._lengths[i]))
            rel.args["pages"] = n
            if n:
                self._m_win_released.inc(n)
                self._dirty = True

    def _read_bytes(self, cursors) -> tuple:
        """The K/V bytes ONE token step at `cursors` (a slot each, idle
        ones included: they read the trash page) must read for
        attention, both ways: (streamed, dense). Streamed is whole pages
        from the first a layer's kind lets the cursor see to the
        cursor's own, `min(pos // page_size + 1, table width)`: exactly
        what `paged_attention`'s sweep fetches. Dense is every slot's
        whole table in every layer, however little was written. Their
        ratio is the kernel's traffic win, counted every dispatch as
        dl4j_decode_kv_read_bytes{path="kernel"|"gather"}."""
        ps = self.page_size
        page = paged_kinds.page_bytes(self.cfg, ps)
        streamed = 0
        for kind, layers in self._kind_layers.items():
            for pos in cursors:
                last = min(int(pos) // ps + 1, self._pps)
                first = (self._win.first_page(int(pos))
                         if kind == paged_kinds.KIND_WINDOW else 0)
                streamed += layers * page * (last - first)
        return streamed, (sum(self._kind_layers.values()) * page
                          * len(cursors) * self._pps)

    def _count_read_bytes(self, cursors) -> None:
        streamed, dense = self._read_bytes(cursors)
        self._m_kv_read["kernel"].inc(streamed)
        self._m_kv_read["gather"].inc(dense)

    # ---- speculative dispatch (draft k on the host, verify k+1 wide)
    def _dispatch_spec(self) -> bool:
        """One draft-and-verify round. Per runnable slot the drafter
        proposes up to k continuation tokens; ONE widened verify step
        feeds `[pending, d_1..d_k]` at cursors `length..length+k` and
        returns the target model's argmax after every prefix. The
        accepted run is the longest m with `d_j == argmax_{j-1}`, and
        the emitted tokens are `argmax_0..argmax_m` — the first
        disagreement (or the tail when all agree) is the verify step's
        OWN next token, so each round delivers m+1 tokens and the
        stream is bit-identical to plain decode by induction. Rollback
        of rejected positions is pure host bookkeeping: the cursor just
        doesn't advance past m, and the garbage K/V beyond it sits in
        CoW-private pages (see `_grant_pages`), masked by `key_pos <=
        query_pos`, and overwritten by the next round before any query
        can see it."""
        import jax.numpy as jnp

        phases = self._phases
        # drafting extends each slot's last token on the HOST, so any
        # deferred prefill firsts flush (one D2H per group) and emit
        # now — same firsts-before-chunk order as the plain lane
        if self._deferred:
            self._flush_first_tokens()
            self._host_rows[:] = True  # firsts never reached the carry
        self._grant_pages()
        with self._cond:
            runnable = [i for i, s in enumerate(self._slot_state)
                        if s is not None
                        and self._stop[i] > self._lengths[i]]
            if not runnable:
                return False
            before = self._lengths.copy()
        W = self.spec_k + 1
        tokens = np.zeros((self.slots, W), np.int32)
        widths = np.zeros((self.slots,), np.int32)
        proposals = {}
        model_rows = []
        with span("decode.draft", phases):
            for i in runnable:
                slot = self._slot_state[i]
                tokens[i, 0] = self._pending[i]
                widths[i] = 1
                # room for length-advance this round; >= 2 means at
                # least one draft position fits under the
                # granted/budget frontier
                room = int(self._stop[i] - before[i])
                if room < 2 or not slot.stream.speculation:
                    continue
                if self._drafter.kind == "model":
                    model_rows.append(i)
                else:
                    history = slot.stream.prompt + slot.stream._generated
                    prop = self._drafter.propose(
                        history, min(self.spec_k, room - 1))
                    if prop:
                        proposals[i] = [int(t) for t in prop]
            if model_rows:
                # one fixed-shape (S, window) batch through the draft
                # program — idle rows ride along and are ignored
                win = self._drafter.window
                windows = np.zeros((self.slots, win), np.int32)
                for i in model_rows:
                    slot = self._slot_state[i]
                    hist = (slot.stream.prompt
                            + slot.stream._generated)[-win:]
                    windows[i, win - len(hist):] = hist
                drafted = self._drafter.propose_all(windows, self.spec_k)
                for i in model_rows:
                    room = int(self._stop[i] - before[i])
                    prop = [int(t) for t in
                            drafted[i, :min(self.spec_k, room - 1)]]
                    if prop:
                        proposals[i] = prop
        if not proposals:
            # nothing drafted — run the plain width-1 chain instead so
            # an idle/unluckly round costs exactly what it always did
            # and the plain program stays warm. This lane stays in turn
            # (the drafter reads a slot's tokens on the host before it
            # can propose), so the step is read at once
            step = self._enqueue_step()
            if step is not None:
                self._read_step(step)
            return step is not None
        for i, prop in proposals.items():
            n = len(prop)
            tokens[i, 1:1 + n] = prop
            widths[i] = 1 + n
            self._m_spec_proposed.inc(n)
        t0 = time.perf_counter()
        self._plan_verify = True
        with span("decode.upload", phases):
            d_tokens, d_table = jnp.asarray(tokens), self._device_tables()
            d_before, d_widths = jnp.asarray(before), jnp.asarray(widths)
        with span("decode.step_dispatch", phases, runnable=len(runnable)):
            out, self._pool = self._verify(
                self.params, d_tokens, self._pool, d_table, d_before,
                d_widths)
        self._count_dispatch()
        self._m_spec_rounds.inc()
        with span("decode.d2h", phases):
            out = np.asarray(out)  # (S, W) argmax — the sync streams need
        self._m_step_s.observe(time.perf_counter() - t0)
        # KV read accounting mirrors the widened step: column j of slot
        # i attends at cursor before+j (clamped to its real width)
        with span("decode.account", phases):
            for j in range(int(widths.max())):
                self._count_read_bytes(
                    before + np.minimum(j, np.maximum(widths - 1, 0)))
        with span("decode.emit", phases) as emit:
            emitted = 0
            for i in runnable:
                slot = self._slot_state[i]
                if slot is None:
                    continue
                prop = proposals.get(i, [])
                m = 0
                while m < len(prop) and prop[m] == int(out[i, m]):
                    m += 1
                with self._cond:
                    self._lengths[i] = before[i] + m + 1
                if prop:
                    self._m_spec_accepted.inc(m)
                for j in range(m + 1):
                    tok = int(out[i, j])
                    self._pending[i] = tok
                    slot.emitted += 1
                    emitted += 1
                    self._emit_and_maybe_finish(i, slot, tok)
                    if self._slot_state[i] is None:
                        break  # retired (eos/budget): overshoot discarded
            emit.args["tokens"] = emitted
        # host cursors moved without touching the plain device carry —
        # a later plain-lane dispatch sets every row from the host's
        self._host_rows[:] = True
        return True

    def _flush_first_tokens(self) -> None:
        """Read deferred prefill tokens (one D2H per prefill group) and
        emit them. The prefill was enqueued behind the step this pass
        read, so the read waits for it: a wait on the device like the
        step's own, under the same span, so that `decode.tick` less
        `decode.d2h` stays the host's own time."""
        import jax

        deferred, self._deferred = self._deferred, []
        firsts = []
        if deferred:
            with span("decode.d2h", self._phases):
                firsts = jax.device_get([first for first, *_ in deferred])
        with span("decode.flush_first", self._phases,
                  groups=len(deferred)):
            for (_, members, n_tokens), (host, aux) in zip(deferred,
                                                           firsts):
                if self._moe is not None:
                    self._count_pairs(aux, n_tokens, decode=False)
                for row, i in members:
                    slot = self._slot_state[i]
                    if slot is None or not slot.awaiting_first:
                        continue  # failed/cleared meanwhile
                    tok = int(host[row])
                    slot.awaiting_first = False
                    self._pending[i] = tok
                    slot.emitted += 1
                    self._emit_and_maybe_finish(i, slot, tok)

    # ---- emission / retirement
    def _emit_and_maybe_finish(self, idx: int, slot: _Slot,
                               token: int) -> None:
        stream = slot.stream
        stream._emit(token)
        self._m_tokens.inc()
        if (stream.eos_id is not None and token == stream.eos_id):
            self._retire(idx, slot, "eos")
        elif slot.emitted >= stream.max_tokens:
            self._retire(idx, slot, "max_tokens")

    def _retire(self, idx: int, slot: _Slot, reason: str,
                error: Optional[BaseException] = None) -> None:
        with self._cond:
            self._slot_state[idx] = None
            self._table[idx, :] = self._trash
            # length back to 0 on the device too, whatever a step in
            # flight makes of it: an idle slot reads one trash block and
            # not its old context
            self._lengths[idx] = 0
            self._stop[idx] = 0
            self._pending[idx] = 0
            self._host_rows[idx] = True
            if (self._prefix is not None and slot.stream.prefix_cache
                    and reason in ("eos", "max_tokens", "preempted")):
                # seed the cache with the FULL prompt pages only —
                # decode pages hold this request's continuation, and a
                # partial prompt page would be rewritten by the next
                # reader's cursor. Forked pages never seed (no_cache):
                # their bytes diverged from the pure token sequence.
                # "preempted" seeds too: the durable-stream resume
                # re-sends this prompt as a prefix, and the cache is
                # what makes that replay near-free.
                n_full = len(slot.stream.prompt) // self.page_size
                self._prefix.insert(slot.stream.prompt,
                                    slot.pages[:n_full],
                                    skip=slot.no_cache)
            # a step in flight may still read these pages, or write the
            # one position an end-of-sequence token came too late to
            # stop (in the slot's own page: the fence made it private):
            # whatever writes them next is enqueued behind that step
            for page in slot.pages:
                self._release_page(page)
            if self._win is not None:
                self._win.drop(idx)
            self._dirty = True
            self._cond.notify_all()  # admissions may proceed
        slot.stream._finish(reason, error)
