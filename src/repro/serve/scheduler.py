"""Continuous-batching scheduler: slot-based request engine with chunked
prefill.

A fixed pool of ``slots`` decode lanes over one set of live cache buffers
(static shapes, allocated once).  Requests queue FIFO; every round runs ONE
unified ``Engine.step`` dispatch carrying ``prefill_chunk`` prompt tokens
(the chunk lane — page-aligned slices of the prompts currently admitting,
served FIFO: mid-prefill slots first, then new admissions from the queue
head) followed by ``chunk`` decode tokens for every slot.  A prompt's last
chunk entry samples its first output token in the same dispatch and the
slot joins the decode lane immediately, so admission never stalls decoding:
long prompts admit over several rounds at a fixed per-round cost (flat p99
decode latency) instead of monopolizing a whole admission round, and the
chunk budget is filled with real prompt tokens (padding waste ~1.0).
Batch slots are never idle while work is queued — the request-level
analogue of keeping the LUT fabric saturated.

Models whose prompt state cannot be built one token at a time fall back to
*monolithic admission* (``Engine.admit_monolithic``: one batched
exact-length prefill dispatch, stitched into the masked slots): recurrent
(SSM/RWKV) layers, MoE routing, int8-KV — and, per-request, SWA prompts
longer than the attention window (see ``Engine.chunk_eligible``).
Monolithic rounds group equal-length requests and prefill at exact prompt
length — no padding buckets anywhere.

Static-shape invariants (TPU-friendly, no retrace after warmup):
  * live caches are ``[G, slots, max_len, ...]``; the unified step compiles
    once per (has-chunk-entries, chunk, greedy) — chunk entries are fixed
    ``[prefill_chunk]`` vectors padded with no-op entries, and slot state
    (token, position, done, EOS id, sampling params) are all traced
    ``[slots]`` vectors; free slots carry the negative-position sentinel,
    which keeps every one of their keys masked;
  * mid-prefill slots park done=True on their latest chunk entry's (token,
    position) — iterations that don't target them re-run that cache write
    idempotently, so interleaving is bit-transparent (fresh admissions are
    parked on their FIRST entry host-side before the dispatch, replacing
    the free-slot sentinel whose clamped write would corrupt page 0).

With a paged engine (``ServeConfig(paged=True)``) the scheduler also runs
the block accounting: admission is gated on free pool pages (FIFO, no
skip-ahead), every decode round first maps pages for the chunk ahead, and
when the pool runs dry a slot is deterministically preempted and requeued
at the queue head with its emitted tokens intact — its re-admission
prefills prompt + emitted and continues bit-exactly, so temperature-0
transcripts match an uncontended run.  Page tables are fixed ``[slots,
entries]`` int32 arrays whose VALUES change round to round, so none of the
executors above ever retrace.

Fault tolerance (serve.faults + serve.request):

  * **Logical time only.**  Every robustness decision — deadline expiry,
    shed ordering, preemption slack — reads the ``now=`` values the caller
    threads through ``submit``/``step``/``run``, never wall clock, so a
    transcript replays bit-for-bit.
  * **Deadlines**: requests whose ``deadline`` passed finish ``timed_out``
    (queued or mid-decode) instead of emitting forever.
  * **Load shedding**: when the page pool (or, dense, the slot map)
    saturates past ``shed_watermark`` and more than ``overload_queue``
    requests wait, the excess is shed deterministically — lowest priority
    first, then least deadline slack, then latest submitted.
  * **Preemption ordering**: when the pool exhausts mid-decode and any
    active request carries a deadline, the victim is the MOST-slack slot
    (it can be requeued and still make its deadline); youngest-first is
    the tie-break and the no-deadline fallback.
  * **Detection + recovery**: the engine's finite-logits guard and
    ``PagePool.validate()`` surface corrupted state as
    :class:`~repro.serve.faults.CacheCorruption`; with
    ``snapshot_interval > 0`` the scheduler keeps a host-side rolling
    :meth:`snapshot` and on any :class:`~repro.serve.faults.EngineFault`
    restores it and replays — in-flight requests carry a bounded
    ``retries`` count and are dropped (status ``failed``) past
    ``max_retries``.  Injected dispatch failures roll back locally and
    simply re-dispatch.  Streaming callbacks never observe poisoned
    tokens (detection precedes ``emit``), but a recovery may replay
    tokens already streamed before the snapshot — at-least-once delivery.
  * **Crash recovery**: :meth:`save` / :meth:`load` round-trip the whole
    serving state (caches, slot vectors, queue, page tables, allocator,
    PRNG step) through ``ckpt.checkpoint``, so a fresh process resumes
    mid-stream and continues token-identically.
"""
from __future__ import annotations

import collections
import math
import warnings
from typing import Deque, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint as ckpt_lib
from repro.serve.engine import Engine
from repro.serve.faults import CacheCorruption, EngineFault, InjectedFault
from repro.serve.request import Request, RequestStatus

_UNSET = object()

# stats keys newer than some saved snapshots (restored as 0)
_LATER_STATS = ("spec_rounds", "spec_drafted", "spec_accepted",
                "experts_hit", "moe_pairs", "dropped_pairs")

# host spans of a round, on the profiler's clock (one TraceMe check each when
# no trace is being taken): serve.step holds serve.admin, serve.assemble,
# serve.dispatch, serve.wait and serve.deliver
_span = jax.profiler.TraceAnnotation


class _Round(NamedTuple):
    """What a round's assembly decided, for its dispatch and delivery."""
    entries: Optional[dict]     # chunk-lane arrays, None without prefill work
    plan: dict                  # slot -> chunk-lane progress after the round
    fresh: list                 # (slot, request) admitted this round
    completing: set             # slots whose last prompt token lands now
    C: int                      # chunk-lane width (0 without prefill work)
    greedy: bool
    spec: bool


class Scheduler:
    """FIFO admission over a fixed slot map; ``Engine`` executes the batch."""

    def __init__(self, engine: Engine, slots: int = 4, chunk: int = 8,
                 prompt_bucket=_UNSET, *, max_retries: int = 2,
                 snapshot_interval: int = 0,
                 shed_watermark: Optional[float] = None,
                 overload_queue: Optional[int] = None):
        if engine.is_encdec:
            raise NotImplementedError(
                "continuous batching serves decoder-only LMs")
        self.engine = engine
        self.n_slots = slots
        self.chunk = chunk
        if prompt_bucket is not _UNSET:
            # one-release deprecation shim: the bucket machinery is gone —
            # prompts admit in page-aligned chunks (ServeConfig.prefill_chunk)
            # and the monolithic fallback prefills at exact length
            warnings.warn(
                "Scheduler(prompt_bucket=...) is deprecated and ignored: "
                "admission is chunked — size it with "
                "ServeConfig.prefill_chunk; the monolithic fallback "
                "(recurrent/MoE/int8-KV models) prefills at exact prompt "
                "length", DeprecationWarning, stacklevel=2)
        # fault tolerance / overload policy
        self.max_retries = max_retries
        self.snapshot_interval = snapshot_interval
        self.shed_watermark = shed_watermark
        self.overload_queue = slots if overload_queue is None else \
            overload_queue
        scfg = engine.scfg
        self.cache = engine.init_cache(slots)
        # per-slot device state ([slots] vectors; free slot: pos=-1, done);
        # placed by the engine (sharded: pinned along the data axis)
        self.tok = engine.place_slot_state(jnp.zeros((slots,), jnp.int32))
        self.pos = engine.place_slot_state(jnp.full((slots,), -1, jnp.int32))
        self.done = engine.place_slot_state(jnp.ones((slots,), bool))
        # per-slot sampling state is mirrored host-side so admission can
        # rebuild the vectors without device reads
        self._eos_h = [-1] * slots
        self._temp_h = [scfg.temperature] * slots
        self._topk_h = [scfg.top_k] * slots
        self._topp_h = [scfg.top_p] * slots
        self._push_sampling_state()
        self._step = 0                      # global token step (PRNG fold-in)
        self.queue: Deque[Request] = collections.deque()
        self.slots: List[Optional[Request]] = [None] * slots
        self.finished: List[Request] = []
        # paged block accounting: admission order per slot (preemption
        # tie-breaks pick the youngest), monotone admission counter
        self._admit_seq = [0] * slots
        self._admit_counter = 0
        # chunked-prefill bookkeeping: per-slot tokens already fed through
        # the chunk lane and the total the admission must feed (progress <
        # target = mid-prefill; monolithic admissions set both at once)
        self._progress = [0] * slots
        self._target = [0] * slots
        # fault-recovery state: rolling snapshot + requests submitted since
        # it was taken (restore re-queues them so no submission is lost)
        self._snap = None
        self._submit_log: List[Request] = []
        self._submit_count = 0
        self._ticks = 0
        self._retries_since_progress = 0
        # serving telemetry (the bench commits these): admission padding
        # waste = prefill_tokens / admitted_tokens (the chunk lane always
        # dispatches its fixed [prefill_chunk] width), per-round slot
        # occupancy as a running sum (bounded state — a long-running server
        # never grows it)
        self.stats = {"rounds": 0, "admission_rounds": 0,
                      "prefill_tokens": 0, "admitted_tokens": 0,
                      "emitted_tokens": 0, "occupancy_sum": 0.0,
                      "preemptions": 0, "shed": 0, "timed_out": 0,
                      "recoveries": 0, "dispatch_retries": 0, "failed": 0,
                      "spec_rounds": 0, "spec_drafted": 0,
                      "spec_accepted": 0,
                      # MoE decode-lane counters, summed over forwards and
                      # layers: experts that got a token, (token, expert)
                      # pairs computed, pairs dropped by capacity
                      "experts_hit": 0, "moe_pairs": 0, "dropped_pairs": 0}

    # -- paged helpers -------------------------------------------------------

    @staticmethod
    def _seq(req: Request) -> List[int]:
        """The token sequence a (re-)admission must prefill: the prompt plus
        everything already emitted (non-empty only on a preemption resume)."""
        return list(req.prompt) + [int(t) for t in req.tokens]

    def _free_on_device(self, freed: List[int]) -> None:
        """Mark freed slots done with the negative-position sentinel."""
        fm = np.zeros((self.n_slots,), bool)
        fm[freed] = True
        fm = self.engine.place_slot_state(jnp.asarray(fm))
        self.done = self.done | fm
        self.pos = jnp.where(fm, -1, self.pos)

    def _preempt_victim(self, now_v) -> tuple[int, Request]:
        """Deterministic preemption: evict the slot with the MOST deadline
        slack (it can be requeued and still make its deadline; no-deadline
        requests have infinite slack and go first), tie-broken — and, when
        nothing carries a deadline, replaced — by youngest-first.  The
        victim's pages are released and the request keeps its emitted
        tokens: re-admission prefills prompt + emitted and continues, so
        temperature-0 transcripts match an uncontended run."""
        victim = max((s for s, r in enumerate(self.slots) if r is not None),
                     key=lambda s: (self.slots[s].slack(now_v),
                                    self._admit_seq[s]))
        req = self.slots[victim]
        self.slots[victim] = None
        self.engine.pool.release(victim)
        self._reset_slot_sampling(victim)
        self._progress[victim] = self._target[victim] = 0
        req.status = RequestStatus.QUEUED
        req.slot = None
        self.stats["preemptions"] += 1
        self.engine.pool.preemptions += 1
        return victim, req

    def _ensure_chunk_pages(self, now_v=None) -> None:
        """Grow every active slot's page mapping to cover the next decode
        chunk; when the pool runs dry, preempt-and-requeue (most-slack /
        youngest first) until the remaining slots fit (or one sequence
        alone exhausts the pool, which is a configuration error)."""
        pool = self.engine.pool
        scfg = self.engine.scfg
        max_len = scfg.max_len
        # a speculative round writes a draft_k+1-token block per slot, so
        # reserve for whichever lane this round ends up running (the spec
        # fallback decision happens after assembly; over-reservation trims
        # back after the round)
        W = max(self.chunk, scfg.draft_k + 1) if scfg.spec_decode \
            else self.chunk
        freed, evicted = [], []
        while True:
            active = [(s, r) for s, r in enumerate(self.slots)
                      if r is not None]
            # a decoding slot's pending token (sampled, unwritten) is the
            # first of the chunk's writes, so it needs chunk-1 positions past
            # its residency; a mid-prefill slot that completes this round
            # decodes a FULL chunk past its sequence (which may include
            # previously emitted tokens after a preempt-and-resume), so it
            # needs one more
            need = [(s, min(len(r.prompt) + len(r.tokens) + W
                            - (0 if self._progress[s] < self._target[s]
                               else 1), max_len))
                    for s, r in active]
            failed = next((s for s, n in need if not pool.ensure(s, n)),
                          None)
            if failed is None:
                break
            if len(active) == 1:
                raise RuntimeError(
                    "KV page pool exhausted by a single sequence — "
                    "raise ServeConfig.num_pages (or lower max_len)")
            slot, req = self._preempt_victim(now_v)
            evicted.append(req)
            freed.append(slot)
        if evicted:
            # requeue so original FIFO order survives: we evicted in
            # decreasing expendability, so appendleft in eviction order puts
            # the least expendable evictee at the queue head
            for req in evicted:
                self.queue.appendleft(req)
            self._free_on_device(freed)

    # -- admission -----------------------------------------------------------

    def submit(self, request: Request, now=None) -> Request:
        """Validate and queue a request.  ``now`` (here and in ``step``/
        ``run``) may be a timestamp or a zero-arg clock callable — the
        callable is read at the bookkeeping moment, so finish times stamp
        after the decode chunk that produced the final token.  Malformed
        requests are rejected HERE with a clear ``ValueError`` — not as a
        shape error (or a silent hang) deep inside admission."""
        L = len(request.prompt)
        max_len = self.engine.scfg.max_len
        if request.max_new_tokens < 0:
            raise ValueError(
                f"max_new_tokens must be >= 0, got {request.max_new_tokens}")
        if L > max_len:
            raise ValueError(
                f"prompt length ({L}) exceeds max_len ({max_len})")
        if L + request.max_new_tokens > max_len:
            raise ValueError(
                f"prompt ({L}) + max_new_tokens ({request.max_new_tokens}) "
                f"exceeds max_len ({max_len})")
        if request.deadline is not None and (
                not isinstance(request.deadline, (int, float))
                or not math.isfinite(request.deadline)):
            raise ValueError(
                f"deadline must be a finite logical time, got "
                f"{request.deadline!r}")
        if not isinstance(request.priority, (int, float)) or \
                not math.isfinite(request.priority):
            raise ValueError(
                f"priority must be finite, got {request.priority!r}")
        request.arrival_time = now() if callable(now) else now
        request.status = RequestStatus.QUEUED
        self._submit_count += 1
        request._seq = self._submit_count
        if self.snapshot_interval:
            self._submit_log.append(request)
        self.queue.append(request)
        return request

    def _sampling_for(self, req: Request):
        scfg = self.engine.scfg
        temp = scfg.temperature if req.temperature is None else req.temperature
        top_k = scfg.top_k if req.top_k is None else req.top_k
        top_p = scfg.top_p if req.top_p is None else req.top_p
        return float(temp), int(top_k), float(top_p)

    def _reset_slot_sampling(self, slot: int) -> None:
        """Freed slots fall back to the engine defaults so a past sampling
        request doesn't keep the greedy decode fast path disabled."""
        scfg = self.engine.scfg
        self._eos_h[slot] = -1
        (self._temp_h[slot], self._topk_h[slot],
         self._topp_h[slot]) = (scfg.temperature, scfg.top_k, scfg.top_p)

    def _admit(self, now=None, only_ineligible: bool = False) -> int:
        """Monolithic admission: fill free slots from the queue head in ONE
        fused dispatch (batched exact-length prefill + masked stitch +
        first-token sampling + slot-state merge); returns #admissions.
        Prompt state that cannot be built a token at a time is never
        pad-invariant either (recurrent integration, MoE capacity), so the
        dispatch takes only the leading run of EQUAL-length requests and
        prefills unpadded — a prefill retrace per distinct length, zero
        padding.  With ``only_ineligible`` (chunk-capable engines) the run
        additionally stops at the first chunk-eligible request, which
        admits through the chunk lane instead.

        Paged engines gate admission on free pool pages — candidates that
        don't fit go back to the queue head in FIFO order (no skip-ahead,
        so ordering stays deterministic).  An injected dispatch failure
        rolls the admission back locally (pages released, candidates
        requeued in order) and re-raises for the retry path."""
        free = [s for s in range(self.n_slots) if self.slots[s] is None]
        take: List[Request] = []
        for r in self.queue:
            if len(take) >= len(free):
                break
            if only_ineligible and self.engine.chunk_eligible(
                    len(self._seq(r))):
                break
            if take and len(self._seq(r)) != len(self._seq(take[0])):
                break
            take.append(r)
        for _ in take:
            self.queue.popleft()
        admitted = list(zip(free, take))
        if self.engine.paged and admitted:
            fits = []
            for i, (slot, req) in enumerate(admitted):
                if self.engine.pool.admit(slot, self._seq(req)) is None:
                    if (not fits
                            and not any(r is not None for r in self.slots)
                            and self.engine.pool.allocated_pages == 0):
                        raise RuntimeError(
                            "request needs more KV pages than the whole "
                            "pool holds — raise ServeConfig.num_pages")
                    for _, r in reversed(admitted[i:]):
                        self.queue.appendleft(r)
                    admitted = fits
                    break
                fits.append((slot, req))
        if not admitted:
            return 0
        R = self.n_slots
        # exact length: every admitted request is L0 tokens (equal-length
        # run), and submit() guarantees L0 <= max_len
        P = len(self._seq(admitted[0][1]))
        prompts = np.zeros((R, P), np.int32)
        lengths = np.ones((R,), np.int32)
        mask = np.zeros((R,), bool)
        budget_one = np.zeros((R,), bool)
        for slot, req in admitted:
            seq = self._seq(req)
            L = len(seq)
            prompts[slot, :L] = seq
            lengths[slot] = L
            mask[slot] = True
            # <=1: budget-0 requests also finish at admission (their slot is
            # never occupied; the sampled token is simply not emitted).
            # ``remaining`` (not max_new_tokens) so preemption resumes with
            # a partially spent budget admit correctly.
            budget_one[slot] = req.remaining <= 1
            (self._temp_h[slot], self._topk_h[slot],
             self._topp_h[slot]) = self._sampling_for(req)
            self._eos_h[slot] = -1 if req.eos_id is None else int(req.eos_id)
        self._push_sampling_state()
        try:
            (self.cache, self.tok, self.pos, self.done, tok0, done0,
             ok0) = self.engine.admit_monolithic(
                self.cache, prompts, lengths, mask, budget_one, self.eos,
                self.temperature, self.top_k, self.top_p, self.tok, self.pos,
                self.done, self._step)
        except InjectedFault:
            # the dispatch never ran: release this admission's pages, put
            # the candidates back at the queue head in FIFO order, and let
            # the retry path re-dispatch an identical round
            for slot, _ in admitted:
                if self.engine.paged:
                    self.engine.pool.release(slot)
                self._reset_slot_sampling(slot)
            self._push_sampling_state()
            for _, req in reversed(admitted):
                self.queue.appendleft(req)
            raise
        self._step += 1
        self.stats["admission_rounds"] += 1
        self.stats["prefill_tokens"] += R * P
        self.stats["admitted_tokens"] += int(
            sum(lengths[s] for s, _ in admitted))
        if self.engine.scfg.guards:
            ok0_h = np.asarray(ok0)
            bad = [s for s, _ in admitted if not ok0_h[s]]
            if bad:
                raise CacheCorruption(
                    f"non-finite logits at admission for slots {bad}")
        tok0_h, done0_h = np.asarray(tok0), np.asarray(done0)
        if callable(now):
            now = now()
        for slot, req in admitted:
            req.status = RequestStatus.RUNNING
            req.slot = slot
            self._admit_counter += 1
            self._admit_seq[slot] = self._admit_counter
            L = int(lengths[slot])
            self._progress[slot] = self._target[slot] = L
            cb_ok = True
            if req.remaining >= 1:
                cb_ok = self._deliver(req, int(tok0_h[slot]))
            if not cb_ok:
                # a raising streaming callback fails only ITS request; the
                # rest of the admission round stands
                self._retire(req, "failed", now)
                self.stats["failed"] += 1
                self._free_on_device([slot])
            elif done0_h[slot]:
                eos = self._eos_h[slot]
                req.finish("eos" if eos >= 0 and req.tokens
                           and req.tokens[-1] == eos
                           else "length", now)
                self.finished.append(req)
                self._reset_slot_sampling(slot)
                self._progress[slot] = self._target[slot] = 0
                if self.engine.paged:
                    self.engine.pool.release(slot)
            else:
                self.slots[slot] = req
        return len(admitted)

    def _push_sampling_state(self) -> None:
        place = self.engine.place_slot_state
        self.eos = place(jnp.asarray(self._eos_h, jnp.int32))
        self.temperature = place(jnp.asarray(self._temp_h, jnp.float32))
        self.top_k = place(jnp.asarray(self._topk_h, jnp.int32))
        self.top_p = place(jnp.asarray(self._topp_h, jnp.float32))

    # -- deadlines & load shedding (logical time only) ------------------------

    def _retire(self, req: Request, reason: str, now_v) -> None:
        """Terminal bookkeeping shared by expiry/shed/failure paths."""
        slot = req.slot
        req.finish(reason, now_v)
        self.finished.append(req)
        if slot is not None:
            self.slots[slot] = None
            self._reset_slot_sampling(slot)
            self._progress[slot] = self._target[slot] = 0
            if self.engine.paged:
                self.engine.pool.release(slot)

    def _expire_deadlines(self, now_v) -> None:
        """Finish every request whose logical deadline passed — queued ones
        without running, mid-decode ones with their partial transcript —
        with status ``timed_out``.  No-op when the caller runs clockless."""
        if now_v is None:
            return
        expired = [r for r in self.queue
                   if r.deadline is not None and r.deadline <= now_v]
        if expired:
            gone = set(map(id, expired))
            self.queue = collections.deque(
                r for r in self.queue if id(r) not in gone)
        freed = []
        for s, r in enumerate(self.slots):
            if r is not None and r.deadline is not None \
                    and r.deadline <= now_v:
                expired.append(r)
                freed.append(s)
        for r in expired:
            self._retire(r, "timed_out", now_v)
            self.stats["timed_out"] += 1
        if freed:
            self._free_on_device(freed)

    def _shed_overload(self, now_v) -> None:
        """Deterministic admission control: when the page pool (or, dense,
        the slot map) saturates past ``shed_watermark`` and more than
        ``overload_queue`` requests wait, shed the excess — lowest priority
        first, then least deadline slack (it was going to miss anyway),
        then latest submitted.  Same state + same watermark => same shed
        set, replayable bit-for-bit."""
        if self.shed_watermark is None or not self.queue:
            return
        if self.engine.paged:
            saturation = self.engine.pool.saturation
        else:
            saturation = sum(r is not None for r in self.slots) / self.n_slots
        if saturation < self.shed_watermark:
            return
        excess = len(self.queue) - self.overload_queue
        if excess <= 0:
            return
        order = sorted(self.queue,
                       key=lambda r: (r.priority, r.slack(now_v),
                                      -getattr(r, "_seq", 0)))
        victims = set(map(id, order[:excess]))
        self.queue = collections.deque(
            r for r in self.queue if id(r) not in victims)
        for r in order[:excess]:
            self._retire(r, "shed", now_v)
            self.stats["shed"] += 1

    # -- snapshot / restore / crash recovery ----------------------------------

    def snapshot(self) -> dict:
        """Host-side copy of the COMPLETE serving state: decode caches,
        slot vectors, sampling mirrors, PRNG step, queue/slot request
        states, page-pool allocator, telemetry.  Everything a restore needs
        to replay token-identically; per-request ``retries`` deliberately
        stays OUT (it must survive restores, or the retry bound would reset
        with every recovery)."""
        reqs = [r for r in self.queue] + \
               [r for r in self.slots if r is not None]
        return {
            "cache": ckpt_lib.tree_to_host(self.cache),
            "tok": np.asarray(self.tok), "pos": np.asarray(self.pos),
            "done": np.asarray(self.done),
            "eos_h": list(self._eos_h), "temp_h": list(self._temp_h),
            "topk_h": list(self._topk_h), "topp_h": list(self._topp_h),
            "step": self._step,
            "admit_seq": list(self._admit_seq),
            "admit_counter": self._admit_counter,
            "progress": list(self._progress),
            "target": list(self._target),
            "queue": list(self.queue),
            "slots": list(self.slots),
            "finished_len": len(self.finished),
            "req_state": [(r, r.status, list(r.tokens), r.finish_reason,
                           r.finish_time, r.slot) for r in reqs],
            "pool": (self.engine.pool.state_dict()
                     if self.engine.paged else None),
            "stats": dict(self.stats),
        }

    def restore(self, snap: dict) -> None:
        """Reinstate a :meth:`snapshot` — device state re-placed through the
        engine (sharded placements pinned, so executors never retrace),
        request objects mutated back in place, allocator reloaded.
        Requests submitted AFTER the snapshot rejoin the queue tail in
        submit order, so recovery never drops a submission."""
        eng = self.engine
        self.cache = eng.place_cache(snap["cache"])
        self.tok = eng.place_slot_state(jnp.asarray(snap["tok"]))
        self.pos = eng.place_slot_state(jnp.asarray(snap["pos"]))
        self.done = eng.place_slot_state(jnp.asarray(snap["done"]))
        self._eos_h = list(snap["eos_h"])
        self._temp_h = list(snap["temp_h"])
        self._topk_h = list(snap["topk_h"])
        self._topp_h = list(snap["topp_h"])
        self._push_sampling_state()
        self._step = snap["step"]
        self._admit_seq = list(snap["admit_seq"])
        self._admit_counter = snap["admit_counter"]
        self._progress = list(snap["progress"])
        self._target = list(snap["target"])
        self.queue = collections.deque(snap["queue"])
        self.slots = list(snap["slots"])
        del self.finished[snap["finished_len"]:]
        for r, status, toks, reason, ftime, slot in snap["req_state"]:
            r.status = status
            r.tokens = list(toks)
            r.finish_reason = reason
            r.finish_time = ftime
            r.slot = slot
        if snap["pool"] is not None:
            eng.pool.load_state(snap["pool"])
        self.stats = dict(snap["stats"])
        for k in _LATER_STATS:
            self.stats.setdefault(k, 0)
        for r in self._submit_log:       # post-snapshot submissions survive
            r.status = RequestStatus.QUEUED
            r.tokens = []
            r.finish_reason = None
            r.finish_time = None
            r.slot = None
            self.queue.append(r)

    def _recover(self, err: EngineFault, now_v) -> None:
        """Bounded-retry fault recovery.  Dispatch failures already rolled
        back locally — count and re-dispatch next round.  Corruption
        restores the rolling snapshot, charges one retry to every
        in-flight request, and drops (status ``failed``) any that crossed
        ``max_retries`` — deterministic, since the charge set and the
        restore are both functions of the replayed state."""
        self._retries_since_progress += 1
        if self._retries_since_progress > self.max_retries:
            raise err
        if isinstance(err, InjectedFault):
            self.stats["recoveries"] += 1
            self.stats["dispatch_retries"] += 1
            return
        if self._snap is None:
            raise RuntimeError(
                "corrupted serving state detected but snapshots are "
                "disabled — construct Scheduler(snapshot_interval=1) to "
                "enable recovery") from err
        affected = [r for r in self.slots if r is not None]
        self.restore(self._snap)     # also rewinds stats to the snapshot
        self.stats["recoveries"] += 1
        for r in affected:
            r.retries += 1
            if r.retries > self.max_retries:
                # Request is a value-eq dataclass: filter by IDENTITY
                if any(q is r for q in self.queue):
                    self.queue = collections.deque(
                        q for q in self.queue if q is not r)
                if r.slot is not None and self.slots[r.slot] is r:
                    self._free_on_device([r.slot])
                self._retire(r, "failed", now_v)
                self.stats["failed"] += 1

    def save(self, ckpt_dir: str, step: Optional[int] = None):
        """Write the whole serving state as a committed ``ckpt.checkpoint``
        (atomic dir, msgpack+zstd arrays, JSON manifest): the crash-
        recovery path.  Streaming callbacks (``on_token``) are process-
        local and are NOT serialized — a restored request streams only
        from its restore point on."""
        tree = {"cache": self.cache, "tok": self.tok, "pos": self.pos,
                "done": self.done}
        recs = {
            "queue": [_req_record(r) for r in self.queue],
            "slots": [None if r is None else _req_record(r)
                      for r in self.slots],
            "finished": [_req_record(r) for r in self.finished],
        }
        extra = {"serving": {
            "step": self._step, "ticks": self._ticks,
            "eos_h": self._eos_h, "temp_h": self._temp_h,
            "topk_h": self._topk_h, "topp_h": self._topp_h,
            "admit_seq": self._admit_seq,
            "admit_counter": self._admit_counter,
            "progress": self._progress,
            "target": self._target,
            "submit_count": self._submit_count,
            "stats": self.stats,
            "pool": (self.engine.pool.state_dict()
                     if self.engine.paged else None),
            "geometry": {"slots": self.n_slots, "chunk": self.chunk,
                         "max_len": self.engine.scfg.max_len,
                         "paged": self.engine.paged,
                         "prefill_chunk": self.engine.prefill_chunk},
            **recs,
        }}
        return ckpt_lib.save(ckpt_dir, self._ticks if step is None
                               else step, tree, extra=extra)

    def load(self, ckpt_dir: str, step: Optional[int] = None) -> None:
        """Restore :meth:`save` state into this (freshly constructed)
        scheduler — same engine config / slot count / chunk.  Requests are
        rebuilt as new ``Request`` objects (find them in ``queue`` /
        ``slots`` / ``finished``); decode then continues token-identically
        to the uninterrupted run."""
        tree = {"cache": self.cache, "tok": self.tok, "pos": self.pos,
                "done": self.done}
        restored, extra = ckpt_lib.restore(
            ckpt_dir, tree, step=step,
            shardings=self.engine.serving_state_shardings())
        s = extra["serving"]
        geo = s["geometry"]
        if (geo["slots"], geo["chunk"], geo["max_len"], geo["paged"],
                geo.get("prefill_chunk", self.engine.prefill_chunk)) != \
                (self.n_slots, self.chunk, self.engine.scfg.max_len,
                 self.engine.paged, self.engine.prefill_chunk):
            raise ValueError(
                f"serving-checkpoint geometry {geo} does not match this "
                "scheduler/engine")
        self.cache = self.engine.place_cache(restored["cache"])
        self.tok = self.engine.place_slot_state(restored["tok"])
        self.pos = self.engine.place_slot_state(restored["pos"])
        self.done = self.engine.place_slot_state(restored["done"])
        self._eos_h = list(s["eos_h"])
        self._temp_h = list(s["temp_h"])
        self._topk_h = list(s["topk_h"])
        self._topp_h = list(s["topp_h"])
        self._push_sampling_state()
        self._step = s["step"]
        self._ticks = s["ticks"]
        self._admit_seq = list(s["admit_seq"])
        self._admit_counter = s["admit_counter"]
        self._progress = list(s.get("progress", [0] * self.n_slots))
        self._target = list(s.get("target", [0] * self.n_slots))
        self._submit_count = s["submit_count"]
        self.stats = dict(s["stats"])
        for k in _LATER_STATS:
            self.stats.setdefault(k, 0)
        if s["pool"] is not None:
            self.engine.pool.load_state(s["pool"])
        self.queue = collections.deque(
            _req_from_record(d) for d in s["queue"])
        self.slots = [None if d is None else _req_from_record(d)
                      for d in s["slots"]]
        self.finished = [_req_from_record(d) for d in s["finished"]]

    # -- the scheduling loop -------------------------------------------------

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slots)

    @property
    def padding_waste(self) -> float:
        """prefill_tokens / admitted_tokens across all rounds with prefill
        work — chunk-lane iterations spent per useful prompt token (1.0 =
        every iteration carried a real token; under backlog the fixed
        [prefill_chunk] lane fills completely, so this sits at ~1.0).
        Monolithic fallback rounds count their full [slots, L] dispatch
        against the real prompt tokens admitted."""
        a = self.stats["admitted_tokens"]
        return self.stats["prefill_tokens"] / a if a else 0.0

    @property
    def mean_occupancy(self) -> float:
        """Mean fraction of slots holding live requests per decode round."""
        n = self.stats["rounds"]
        return self.stats["occupancy_sum"] / n if n else 0.0

    def step(self, now=None) -> int:
        """One scheduling round: expire deadlines, shed overload, (maybe)
        snapshot, admit into free slots, decode one chunk, retire finished
        sequences.  Returns the number of useful tokens emitted this round
        (0 on a recovered fault — the retry replays next round)."""
        with jax.profiler.StepTraceAnnotation("serve.step",
                                              step_num=self._ticks):
            now_v = now() if callable(now) else now
            with _span("serve.admin"):
                self._expire_deadlines(now_v)
                self._shed_overload(now_v)
                if self.snapshot_interval and \
                        self._ticks % self.snapshot_interval == 0:
                    self._snap = self.snapshot()
                    self._submit_log.clear()
            self._ticks += 1
            try:
                emitted = self._step_inner(now, now_v)
            except EngineFault as err:
                self._recover(err, now_v)
                return 0
            self._retries_since_progress = 0
            return emitted

    def _assemble_chunk(self, allow_admission: bool):
        """Build this round's chunk-lane entries: continue mid-prefill slots
        in admission order, then admit from the queue head (no skip-ahead)
        while budget, free slots, and pool pages last.  New admissions are
        committed host-side here (slot assigned, pool mapped, sampling
        mirrors set) and recorded in ``fresh`` so an injected dispatch
        failure can roll them back; ``plan`` (slot -> new progress) is only
        applied after the dispatch commits.

        Returns (entries | None, plan, fresh, completing) — entries is the
        [prefill_chunk] arrays dict ``Engine.step`` consumes (None when the
        round has no prefill work), completing the slots whose last prompt
        token lands this round (their first output token is in tok0)."""
        C = self.engine.prefill_chunk
        e_slot: List[int] = []
        e_tok: List[int] = []
        e_pos: List[int] = []
        e_first: List[bool] = []
        e_b1: List[bool] = []
        plan: dict = {}
        fresh: List[tuple] = []
        completing: set = set()
        parks: dict = {}

        def feed(slot, req, p0):
            seq, L = self._seq(req), self._target[slot]
            take = min(C - len(e_slot), L - p0)
            for p in range(p0, p0 + take):
                last = p == L - 1
                e_slot.append(slot)
                e_tok.append(int(seq[p]))
                e_pos.append(p)
                e_first.append(last)
                e_b1.append(last and req.remaining <= 1)
                if last:
                    completing.add(slot)

            plan[slot] = p0 + take

        for slot in sorted(
                (s for s in range(self.n_slots)
                 if self.slots[s] is not None
                 and self._progress[s] < self._target[s]),
                key=lambda s: self._admit_seq[s]):
            if len(e_slot) >= C:
                break
            feed(slot, self.slots[slot], self._progress[slot])
        while allow_admission and len(e_slot) < C and self.queue:
            req = self.queue[0]
            seq = self._seq(req)
            L = len(seq)
            if not self.engine.chunk_eligible(L):
                break               # head takes the monolithic fallback
            slot = next((s for s in range(self.n_slots)
                         if self.slots[s] is None), None)
            if slot is None:
                break
            p0 = 0
            if self.engine.paged:
                # SWA admissions are isolated (share=False): they replay
                # the window from position 0, and their chunk-lane page
                # bits must never mix with a monolithic sharer's
                share = self.engine.chunk_window_limit is None
                start = self.engine.pool.admit(slot, seq, fills_now=False,
                                               share=share)
                if start is None:
                    if (not any(r is not None for r in self.slots)
                            and self.engine.pool.allocated_pages == 0):
                        raise RuntimeError(
                            "request needs more KV pages than the whole "
                            "pool holds — raise ServeConfig.num_pages")
                    break
                # a fully-shared prompt still replays its last token: the
                # completion entry's logits are the first-token logits
                p0 = min(start, L - 1)
                if (L - p0 <= C - len(e_slot)
                        and not self.engine.pool.ensure(
                            slot, min(L + self.chunk,
                                      self.engine.scfg.max_len))):
                    # completes this round but decode growth doesn't fit:
                    # undo the mapping and wait (no skip-ahead)
                    self.engine.pool.release(slot)
                    break
            self.queue.popleft()
            req.status = RequestStatus.RUNNING
            req.slot = slot
            self.slots[slot] = req
            self._target[slot] = L
            self._progress[slot] = p0
            (self._temp_h[slot], self._topk_h[slot],
             self._topp_h[slot]) = self._sampling_for(req)
            self._eos_h[slot] = -1 if req.eos_id is None else int(req.eos_id)
            fresh.append((slot, req))
            parks[slot] = (int(seq[p0]), p0)
            feed(slot, req, p0)
        if not e_slot:
            return None, plan, fresh, completing, parks
        if fresh:
            self._push_sampling_state()
        pad = C - len(e_slot)
        entries = {"slot": e_slot + [-1] * pad,
                   "tok": e_tok + [0] * pad,
                   "pos": e_pos + [0] * pad,
                   "first": e_first + [False] * pad,
                   "budget_one": e_b1 + [False] * pad}
        return entries, plan, fresh, completing, parks

    def _step_inner(self, now, now_v) -> int:
        with _span("serve.assemble"):
            rnd = self._assemble_round(now, now_v)
        if rnd is None:
            return 0
        scfg = self.engine.scfg
        with _span("serve.dispatch"):
            outs = self._dispatch_round(rnd)
            # spec rounds burn draft_k draft + draft_k+1 verify sampling
            # streams
            self._step += rnd.C + (2 * scfg.draft_k + 1 if rnd.spec
                                   else self.chunk)
        _, _, toks, _, ok, _, _ = outs
        with _span("serve.wait"):
            # the one place the host waits on the round's program: its
            # outputs become ready together, so later reads find them
            first_h = np.asarray(ok if scfg.guards else toks)
        with _span("serve.deliver"):
            return self._deliver_round(rnd, outs, first_h, now)

    def _assemble_round(self, now, now_v) -> Optional[_Round]:
        """Admit, map pages and build the chunk lane; park fresh rows and
        decide the round's program variant.  None when no slot is live."""
        entries, plan, fresh, completing = None, {}, [], set()
        parks: dict = {}
        if self.engine.requires_monolithic_admission:
            self._admit(now)
            if not any(r is not None for r in self.slots):
                return None
            if self.engine.paged:
                # block accounting: map pages for the chunk ahead; preempts
                # most-slack/youngest-first when the pool is exhausted
                self._ensure_chunk_pages(now_v)
        else:
            allow = True
            if self.queue and not self.engine.chunk_eligible(
                    len(self._seq(self.queue[0]))):
                # the head needs the monolithic fallback (SWA prompt past
                # the window): admit its equal-length run first; chunk
                # admissions follow only if the new head is eligible
                # (FIFO — no skip-ahead past a blocked head)
                self._admit(now, only_ineligible=True)
                allow = (not self.queue or self.engine.chunk_eligible(
                    len(self._seq(self.queue[0]))))
            if self.engine.paged:
                self._ensure_chunk_pages(now_v)
            entries, plan, fresh, completing, parks = \
                self._assemble_chunk(allow)
        if not any(r is not None for r in self.slots):
            return None
        C = self.engine.prefill_chunk if entries is not None else 0
        if parks:
            # freshly admitted rows must park at their first entry BEFORE
            # the dispatch: chunk iterations preceding the row's first
            # target iteration re-run its held (tok, pos), and the free-slot
            # sentinel pos=-1 would clamp the paged KV write onto page 0 of
            # the row's table — a SHARED page under prefix reuse.  Parking
            # at (seq[p0], p0) makes every such pre-write the same bits the
            # entry itself writes.
            tok_h, pos_h = np.asarray(self.tok).copy(), \
                np.asarray(self.pos).copy()
            for s, (t, p) in parks.items():
                tok_h[s], pos_h[s] = t, p
            place = self.engine.place_slot_state
            self.tok = place(jnp.asarray(tok_h))
            self.pos = place(jnp.asarray(pos_h))
        # host mirrors let us pick the argmax-only decode variant statically
        greedy = all(t <= 0.0 and k == 0 and p >= 1.0 for t, k, p in
                     zip(self._temp_h, self._topk_h, self._topp_h))
        scfg = self.engine.scfg
        use_spec = scfg.spec_decode
        if use_spec:
            # a speculative block writes draft_k+1 positions from every
            # occupied row's post-chunk-lane held position; fall back to a
            # plain round whenever any row sits too close to max_len for
            # the block to land unclamped (the decision is a pure function
            # of host state, so fault replays re-derive it identically)
            lim = scfg.max_len - (scfg.draft_k + 1)
            for slot, req in enumerate(self.slots):
                if req is None:
                    continue
                p = plan.get(slot, self._progress[slot])
                if p < self._target[slot]:
                    held = p - 1                  # parks on its latest entry
                elif slot in completing:
                    held = self._target[slot]     # becomes a decoder at L
                else:
                    held = len(req.prompt) + len(req.tokens) - 1
                if held > lim:
                    use_spec = False
                    break
        return _Round(entries, plan, fresh, completing, C, greedy, use_spec)

    def _dispatch_round(self, rnd: _Round) -> tuple:
        """Enqueue the round's one program; returns its outputs (tok0,
        done0, toks, dones, ok, n_valid, moe) without waiting on them."""
        fresh = rnd.fresh
        try:
            (self.cache, self.tok, self.pos, self.done, tok0, done0, toks,
             dones, ok, n_valid, moe) = self.engine.step(
                self.cache, rnd.entries, self.tok, self.pos, self.done,
                self.eos, self.temperature, self.top_k, self.top_p,
                self._step, self.chunk, greedy=rnd.greedy, spec=rnd.spec)
        except InjectedFault:
            # the dispatch never ran: roll back this round's fresh chunk
            # admissions (pages released, candidates back at the queue head
            # in FIFO order) and re-raise for the retry path
            for slot, req in reversed(fresh):
                if self.engine.paged:
                    self.engine.pool.release(slot)
                self.slots[slot] = None
                self._reset_slot_sampling(slot)
                self._progress[slot] = self._target[slot] = 0
                req.status = RequestStatus.QUEUED
                req.slot = None
                self.queue.appendleft(req)
            if fresh:
                self._push_sampling_state()
                # restore the free-slot sentinel the parks overwrote
                self._free_on_device([slot for slot, _ in fresh])
            raise
        return tok0, done0, toks, dones, ok, n_valid, moe

    def _deliver_round(self, rnd: _Round, outs: tuple, first_h: np.ndarray,
                       now) -> int:
        """Commit the chunk lane and the stats, emit the round's tokens,
        retire finished requests.  ``first_h`` is the host copy of the
        round's first read output: ``ok`` with guards on, else ``toks``."""
        entries, plan, fresh, completing, C, _, use_spec = rnd
        tok0, done0, toks, dones, _, n_valid, moe = outs
        scfg = self.engine.scfg
        if scfg.guards and not first_h.all():
            # poisoned logits never reach a streaming callback:
            # detection precedes every emit below
            raise CacheCorruption(
                "non-finite logits in decode for slots "
                f"{np.flatnonzero(~first_h).tolist()}")
        # commit the chunk lane: progress applied, freshly covered pages
        # become prefix-shareable, admission bookkeeping recorded
        for slot, p in plan.items():
            self._progress[slot] = p
            if self.engine.paged:
                self.engine.pool.mark_filled(slot, p)
        for slot, req in fresh:
            self._admit_counter += 1
            self._admit_seq[slot] = self._admit_counter
        if entries is not None:
            self.stats["admission_rounds"] += 1
            self.stats["prefill_tokens"] += C
            self.stats["admitted_tokens"] += sum(
                1 for s in entries["slot"] if s >= 0)
        self.stats["rounds"] += 1
        self.stats["occupancy_sum"] += (
            sum(r is not None for r in self.slots) / self.n_slots)
        toks_h = np.asarray(toks) if scfg.guards else first_h
        dones_h = np.asarray(dones)
        tok0_h, done0_h = np.asarray(tok0), np.asarray(done0)
        nv_h = np.asarray(n_valid)
        for name, n in zip(("experts_hit", "moe_pairs", "dropped_pairs"),
                           np.asarray(moe).tolist()):
            self.stats[name] += n
        if use_spec:
            # accept-rate telemetry: every live decode row drafted draft_k
            # tokens and committed n_valid-1 of them (the last committed
            # token is the verifier's own bonus/correction sample)
            self.stats["spec_rounds"] += 1
            self.stats["spec_drafted"] += int((nv_h > 0).sum()) * \
                scfg.draft_k
            self.stats["spec_accepted"] += int(
                np.maximum(nv_h - 1, 0).sum())
        if callable(now):      # stamp finish times after the round completed
            now = now()
        emitted, freed = 0, []
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            if self._progress[slot] < self._target[slot]:
                continue            # still mid-prefill: nothing to emit yet
            cb_ok = True
            if slot in completing:
                # the slot's last prompt token landed this round: its first
                # output token was sampled in the same dispatch
                if req.remaining >= 1:
                    cb_ok = self._deliver(req, int(tok0_h[slot]))
                    emitted += 1 if cb_ok else 0
                if cb_ok and done0_h[slot]:
                    eos = self._eos_h[slot]
                    req.finish("eos" if eos >= 0 and req.tokens
                               and req.tokens[-1] == eos else "length", now)
            if cb_ok and not req.done:
                # only the first n_valid columns of the row are real (all
                # of them on a plain round; the accepted prefix + bonus
                # token on a speculative one)
                for j in range(int(nv_h[slot])):
                    cb_ok = self._deliver(req, int(toks_h[slot, j]))
                    if not cb_ok:
                        break
                    emitted += 1
                    if dones_h[slot, j]:
                        req.finish("eos", now)
                        break
                    if req.remaining <= 0:
                        req.finish("length", now)
                        break
            if not cb_ok:
                # a raising streaming callback fails only ITS request —
                # every other slot's tokens this round still commit
                req.finish("failed", now)
                self.stats["failed"] += 1
            if req.done:
                self.finished.append(req)
                self.slots[slot] = None
                self._reset_slot_sampling(slot)
                self._progress[slot] = self._target[slot] = 0
                if self.engine.paged:
                    self.engine.pool.release(slot)
                freed.append(slot)
        if use_spec and self.engine.paged:
            # paged-KV rollback of rejected speculation: drop page mappings
            # grown for the draft_k+1 block past the accepted sequence (the
            # pending token's slot stays resident)
            for slot, req in enumerate(self.slots):
                if req is None or self._progress[slot] < self._target[slot]:
                    continue
                self.engine.pool.trim(
                    slot, len(req.prompt) + len(req.tokens))
        if freed:
            self._free_on_device(freed)
        self.stats["emitted_tokens"] += emitted
        return emitted

    @staticmethod
    def _deliver(req: Request, token: int) -> bool:
        """Emit one token; False when the streaming callback raised (the
        token itself is already on the transcript — at-least-once delivery
        ends at the callback boundary)."""
        try:
            req.emit(token)
            return True
        except Exception:
            return False

    def check_drained(self) -> None:
        """Leak telemetry at drain: with no work left, the page pool must
        hold ZERO allocated pages outside the reserved null pages, and no
        page may be referenced without a slot mapping reaching it."""
        if self.has_work or not self.engine.paged:
            return
        pool = self.engine.pool
        leaked = pool.leaked_pages()
        assert pool.allocated_pages == 0 and not leaked, (
            f"page leak at drain: {pool.allocated_pages} pages still "
            f"allocated, unreachable={leaked}")

    def run(self, requests: Sequence[Request] = (), now=None,
            max_rounds: int = 100_000) -> List[Request]:
        """Submit ``requests`` and drive rounds until everything finishes."""
        for r in requests:
            self.submit(r, now)
        rounds = 0
        while self.has_work:
            self.step(now)
            rounds += 1
            if rounds > max_rounds:
                raise RuntimeError("scheduler failed to drain "
                                   f"({len(self.queue)} queued)")
        if self.engine.scfg.guards:
            self.check_drained()
        return self.finished


def _req_record(r: Request) -> dict:
    """JSON-able snapshot of one request (``on_token`` dropped)."""
    return {"prompt": [int(t) for t in r.prompt],
            "max_new_tokens": r.max_new_tokens,
            "eos_id": r.eos_id, "temperature": r.temperature,
            "top_k": r.top_k, "top_p": r.top_p,
            "deadline": r.deadline, "priority": r.priority,
            "status": r.status.value, "tokens": list(r.tokens),
            "finish_reason": r.finish_reason, "slot": r.slot,
            "arrival_time": r.arrival_time, "finish_time": r.finish_time,
            "retries": r.retries, "seq": getattr(r, "_seq", 0)}


def _req_from_record(d: dict) -> Request:
    r = Request(prompt=d["prompt"], max_new_tokens=d["max_new_tokens"],
                eos_id=d["eos_id"], temperature=d["temperature"],
                top_k=d["top_k"], top_p=d["top_p"],
                deadline=d["deadline"], priority=d["priority"])
    r.status = RequestStatus(d["status"])
    r.tokens = list(d["tokens"])
    r.finish_reason = d["finish_reason"]
    r.slot = d["slot"]
    r.arrival_time = d["arrival_time"]
    r.finish_time = d["finish_time"]
    r.retries = d["retries"]
    r._seq = d["seq"]
    return r
