"""Continuous-batching request scheduler with preemption + fault recovery.

COMET's end-to-end win (paper Fig. 11/12) comes from KV4 admitting larger
decode batches under a fixed memory budget; this scheduler is where that
batch is formed. Policy (vLLM-style):

* FCFS admission: a waiting request is admitted when the paged pool can
  hold its *first prefill chunk* — plus one decode token of headroom
  once the whole prompt is resident (chunked prefill — pass
  ``first_chunk_tokens``; whole-prompt admission reserves the full
  prompt). Prompts that can never fit ``max_pages_per_seq``, or whose
  prompt + one decode token exceeds the whole pool, are failed
  immediately with ``stop_reason="prompt_too_long"``.
* with ``prefix_cache=True`` admission first consults the cache's
  prefix index (``cache.match_prefix``): a request whose prompt prefix
  is already published adopts the shared pages, is charged only its
  un-cached pages against the pool, and starts with ``prefill_pos`` at
  the end of the shared prefix — only the suffix streams through
  ``plan_prefill``. A *preempted* request re-admits through the same
  path, so its own previously-published prompt pages are a warm hit;
* requests track ``prefill_pos`` (prompt tokens already through the
  model) so prefill proceeds chunk-by-chunk and preemption can fire
  mid-prefill — a preempted request simply restarts at ``prefill_pos=0``;
* decode batch = all running sequences (up to ``max_batch``);
* on pool exhaustion the *youngest* running sequence is preempted back to
  the waiting queue (its pages freed — recomputed on re-admission).
  Preemption is the LAST resort: the allocator drains the reclaimable
  prefix LRU first (``PagedKV4Cache._acquire_page``), so cached-but-idle
  prefix pages are always shed before any in-flight work is;
* graceful degradation under pressure (``max_waiting``): the waiting
  queue is bounded — the engine rejects at submit when it is full
  (``FAILED("queue_full")``), and a preemption victim that cannot be
  re-queued without overflowing the bound is *shed* instead
  (``FAILED("shed")``) — bounded queues turn overload into explicit,
  counted outcomes instead of unbounded latency;
* per-request deadlines (``SamplingParams.deadline_ms`` / ``ttft_ms``)
  are enforced at every step boundary by ``expire_deadlines``: expired
  requests — waiting or running — move to ``TIMED_OUT`` with partial
  output retained and pages freed refcount-exactly;
* step-level failures quarantine via ``fail`` — same page accounting as
  ``abort``, state ``FAILED`` with the error in ``stop_reason``;
* ``snapshot``/``restore`` serialize scheduler state so an engine restart
  (node failure) resumes with pending work intact. The legacy mode
  (``full=False``) demotes running requests to waiting (their device KV
  is lost with the node) and folds generated text into the prompt; the
  ``full=True`` mode keeps the exact waiting/running split, slots,
  prefill positions, and the free-slot order — paired with the KV-pool
  snapshot in ``PagedKV4Cache.snapshot_state`` it supports bitwise
  replay of the remaining work (``serving/recovery.py``).
"""

from __future__ import annotations

import dataclasses
import json
from collections import deque
from typing import Callable, Optional

from repro.serving.api import RequestState, SamplingParams

__all__ = ["Request", "Scheduler"]


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: list                   # token ids
    max_new_tokens: int
    arrived_at: float = 0.0
    admitted_at: float = 0.0       # clock at first admission (kept across
    #                                preemptions): queue wait is
    #                                admitted_at - arrived_at
    generated: list = dataclasses.field(default_factory=list)
    seq_slot: int = -1             # cache slot when running
    prefill_pos: int = 0           # prompt tokens already through the model
    stop_reason: Optional[str] = None   # None = ran to max_new_tokens
    first_token_at: float = 0.0    # wall clock of first generated token
    finished_at: float = 0.0       # wall clock of the terminal event —
    #                                with first_token_at this brackets
    #                                the decode window, so the serve CLI
    #                                derives TTFT/TPOT without polling
    params: Optional[SamplingParams] = None   # None → engine defaults
    state: RequestState = RequestState.QUEUED
    cached_tokens: int = 0         # prefix-cache hit tokens, last admission
    uid: int = -1                  # incarnation-qualified id: request_ids
    #                                are reusable after release(), so the
    #                                recovery journal and replica-group
    #                                routing key by this engine-lifetime
    #                                monotonic counter instead
    emitted: int = 0               # lifetime token events (survives the
    #                                preemption fold — the journal's
    #                                per-request delivery cursor)
    terminal_emitted: bool = dataclasses.field(   # exactly-one-terminal
        default=False, repr=False, compare=False)
    events: list = dataclasses.field(          # RequestOutput stream log
        default_factory=list, repr=False, compare=False)
    on_event: Optional[Callable] = dataclasses.field(
        default=None, repr=False, compare=False)

    def deadline_status(self, now: float) -> Optional[str]:
        """The stop_reason this request owes at wall-clock ``now``
        (``"deadline"`` / ``"ttft_budget"``), or ``None`` if within
        budget. Measured from ``arrived_at``; preemption keeps the
        arrival stamp, so a deadline survives re-queueing."""
        p = self.params
        if p is None:
            return None
        waited_ms = (now - self.arrived_at) * 1000.0
        if p.deadline_ms is not None and waited_ms > p.deadline_ms:
            return "deadline"
        if (p.ttft_ms is not None and not self.first_token_at
                and waited_ms > p.ttft_ms):
            return "ttft_budget"
        return None

    @property
    def prefilled(self) -> bool:
        return self.prefill_pos >= len(self.prompt)

    @prefilled.setter
    def prefilled(self, value: bool):
        self.prefill_pos = len(self.prompt) if value else 0

    @property
    def done(self) -> bool:
        return (self.stop_reason is not None
                or len(self.generated) >= self.max_new_tokens)

    @property
    def total_len(self) -> int:
        return len(self.prompt) + len(self.generated)


class Scheduler:
    def __init__(self, max_batch: int, max_seqs: int,
                 max_waiting: Optional[int] = None):
        self.max_batch = max_batch
        self.max_seqs = max_seqs
        self.max_waiting = max_waiting   # None = unbounded waiting queue
        self.waiting: deque[Request] = deque()
        self.running: list[Request] = []
        self.finished: list[Request] = []
        self._free_slots = list(range(max_seqs - 1, -1, -1))
        self.preemptions = 0
        self.released_count = 0     # terminal requests dropped via release
        self._plan_cursor = 0       # round-robin start for prefill plans

    @property
    def waiting_full(self) -> bool:
        """True when the bounded waiting queue cannot take another
        request — the engine's reject-at-submit backpressure signal."""
        return (self.max_waiting is not None
                and len(self.waiting) >= self.max_waiting)

    # ----------------------------------------------------------------- queue

    def submit(self, req: Request):
        self.waiting.append(req)

    def admit(self, cache, first_chunk_tokens: Optional[int] = None,
              prefix_cache: bool = False) -> list[Request]:
        """Admit waiting requests while pages + slots are available.

        ``first_chunk_tokens``: with chunked prefill, admission only
        needs pages for the first chunk (later chunks acquire pages via
        ``cache.grow_to``); ``None`` reserves the whole prompt (the
        whole-prompt baseline path).

        ``prefix_cache``: consult ``cache.match_prefix`` first — the
        matched pages are adopted rather than allocated, only the
        UN-CACHED pages are charged against ``pages_free``, and the
        request starts at ``prefill_pos = matched`` so just the suffix
        streams through the prefill plan."""
        admitted = []
        while (self.waiting and self._free_slots
               and len(self.running) < self.max_batch):
            req = self.waiting[0]
            if (cache.pages_needed(len(req.prompt))
                    > cache.pcfg.max_pages_per_seq
                    or cache.pages_needed(len(req.prompt) + 1)
                    > cache.pcfg.num_pages):
                # can never fit the per-seq page budget, or prompt + one
                # decode token can never fit the whole pool — fail fast
                # instead of livelocking admission/preemption (chunked
                # would stream until the pool is exhausted, self-preempt,
                # and restart forever). Token-granular: a prompt whose
                # last page has slack for its decode tokens is servable.
                self.waiting.popleft()
                req.stop_reason = "prompt_too_long"
                req.state = RequestState.FINISHED
                self.finished.append(req)
                continue
            pages, matched = (cache.match_prefix(req.prompt)
                              if prefix_cache else ([], 0))
            reserve = (len(req.prompt) if first_chunk_tokens is None
                       else min(len(req.prompt),
                                matched + first_chunk_tokens))
            # token-granular decode headroom: one extra TOKEN (not a
            # whole extra page) once the full prompt is resident — a
            # prompt whose last page has slack admits into an exactly-
            # sized pool
            headroom = reserve + 1 if reserve == len(req.prompt) else reserve
            if (cache.pages_needed(headroom) - len(pages)
                    > cache.pages_available_for(pages)):
                break
            slot = self._free_slots.pop()
            if not cache.allocate_seq(slot, reserve, prefix_pages=pages,
                                      prefix_tokens=matched):
                self._free_slots.append(slot)
                break
            req.seq_slot = slot
            req.prefill_pos = matched     # shared prefix is already resident
            req.cached_tokens = matched
            req.state = RequestState.PREFILLING
            self.waiting.popleft()
            self.running.append(req)
            admitted.append(req)
        return admitted

    def plan_prefill(self, cache,
                     token_budget: int) -> list[tuple[Request, int, int]]:
        """Plan this step's prefill chunk: ``[(req, start, take), ...]``.

        Packs up to ``token_budget`` prompt tokens across the running
        requests that still have prompt left, acquiring pages chunk-by-
        chunk (``cache.grow_to``); a request that can't get pages this
        step is simply skipped (decode keeps draining the pool).

        The scan start **round-robins** across the candidates (persistent
        cursor): a long prompt at the head of ``running`` would otherwise
        claim the whole budget every step and starve later arrivals of
        their first token. The engine passes a budget already debited for
        this step's decode rows, so chunk rows and decode rows share one
        per-step token budget — the unified forward's shape stays bounded
        by ``prefill_chunk_tokens`` regardless of the decode batch."""
        cands = [r for r in self.running if r.prefill_pos < len(r.prompt)]
        if not cands or token_budget <= 0:
            return []
        rot = self._plan_cursor % len(cands)
        self._plan_cursor += 1
        budget = token_budget
        plan: list[tuple[Request, int, int]] = []
        for req in cands[rot:] + cands[:rot]:
            if budget <= 0:
                break
            rem = len(req.prompt) - req.prefill_pos
            want = req.prefill_pos + min(rem, budget)
            cap = cache.grow_to(req.seq_slot, want)
            take = min(rem, budget, cap - req.prefill_pos)
            if take <= 0:
                continue
            plan.append((req, req.prefill_pos, take))
            budget -= take
        return plan

    def preempt_one(self, cache) -> Optional[Request]:
        """Evict the youngest running sequence to the waiting queue.

        Only the victim's own references are dropped (``cache.free_seq``
        is refcount-exact): pages it shared with other sequences stay
        mapped for them, and its own *published* prompt pages stay
        cached — re-admission goes back through ``match_prefix``, so a
        warm prefix cache turns the re-prefill into a page-table copy
        plus the un-cached tail.

        Finished requests (done but not yet completed by the engine's
        end-of-step sweep) are never victims: preempting one would fold
        its generated text back into the prompt and silently destroy its
        output. Their pages are released at completion instead."""
        candidates = [r for r in self.running if not r.done]
        if not candidates:
            return None
        req = max(candidates, key=lambda r: r.arrived_at)
        self.running.remove(req)
        cache.free_seq(req.seq_slot)
        self._free_slots.append(req.seq_slot)
        req.seq_slot = -1
        self.preemptions += 1
        if self.waiting_full:
            # load shed: re-queueing would overflow the bounded waiting
            # queue, so the victim is dropped terminally instead of
            # churning — pages are already freed, partial output kept.
            # The caller (engine) counts shed_count + emits the event.
            req.stop_reason = "shed"
            req.state = RequestState.FAILED
            self.finished.append(req)
            return req
        # keep generated text: re-admission prefills prompt+generated.
        # Mid-prefill victims (generated == []) simply restart at 0.
        req.prompt = req.prompt + req.generated
        req.max_new_tokens -= len(req.generated)
        req.generated = []
        req.prefill_pos = 0
        req.state = RequestState.QUEUED
        self.waiting.appendleft(req)
        return req

    def complete(self, req: Request, cache):
        self.running.remove(req)
        cache.free_seq(req.seq_slot)
        self._free_slots.append(req.seq_slot)
        req.seq_slot = -1
        req.state = RequestState.FINISHED
        self.finished.append(req)

    def _drop(self, req: Request, cache):
        """Detach ``req`` from wherever it lives (running: free pages
        refcount-exactly + return the slot; waiting: leave the queue).
        The shared teardown under abort / fail / timeout."""
        if req in self.running:
            self.running.remove(req)
            cache.free_seq(req.seq_slot)
            self._free_slots.append(req.seq_slot)
            req.seq_slot = -1
        elif req in self.waiting:
            self.waiting.remove(req)

    def abort(self, req: Request, cache) -> bool:
        """Cancel ``req`` wherever it is in the lifecycle. Running
        sequences (mid-prefill or mid-decode) drop their page references
        refcount-exactly; queued requests just leave the queue. Returns
        False if the request already reached a terminal state."""
        if req.state.terminal:
            return False
        self._drop(req, cache)
        req.stop_reason = "aborted"
        req.state = RequestState.ABORTED
        self.finished.append(req)
        return True

    def fail(self, req: Request, cache, reason: str) -> bool:
        """Quarantine ``req`` after a step-level failure: same exact
        page accounting as :meth:`abort`, terminal state ``FAILED`` with
        the error in ``stop_reason``. Partial output is retained (the
        tokens already streamed are real). Returns False if already
        terminal (a request cannot fail twice)."""
        if req.state.terminal:
            return False
        self._drop(req, cache)
        req.stop_reason = reason
        req.state = RequestState.FAILED
        self.finished.append(req)
        return True

    def reject(self, req: Request, reason: str = "queue_full"):
        """Refuse a request at submit (bounded-queue backpressure): it
        never enters the waiting queue — straight to ``FAILED`` with a
        policy reason, holding no pages or slots."""
        req.stop_reason = reason
        req.state = RequestState.FAILED
        self.finished.append(req)

    def expire_deadlines(self, cache, now: float) -> list[Request]:
        """Expire every waiting/running request past its deadline or
        TTFT budget to ``TIMED_OUT`` — pages freed refcount-exactly,
        partial output retained. Runs at each step boundary BEFORE
        admission, so a dead-on-arrival request never acquires pages.
        Returns the expired requests (the engine emits their terminal
        events and counts ``timeout_count``)."""
        expired = []
        for req in list(self.running) + list(self.waiting):
            why = req.deadline_status(now)
            if why is None:
                continue
            self._drop(req, cache)
            req.stop_reason = why
            req.state = RequestState.TIMED_OUT
            self.finished.append(req)
            expired.append(req)
        return expired

    def drain_waiting(self) -> list[Request]:
        """Hand off the ENTIRE waiting queue (FCFS order) — the replica-
        group migration seam. Waiting requests hold no pages or slots,
        so draining them off a recovered replica and resubmitting them
        to a survivor is pure bookkeeping: the drained requests leave
        this scheduler entirely (they are not failed, not finished —
        their lifecycle continues on whichever engine readmits them)."""
        drained = list(self.waiting)
        self.waiting.clear()
        return drained

    def release(self, req: Request) -> bool:
        """Forget a terminal request (bounded retention): drop it from
        ``finished`` so scheduler state scales with in-flight work, not
        lifetime traffic. Double-release is explicit, not silent: a
        request no longer in ``finished`` returns False and does not
        bump ``released_count``."""
        if req not in self.finished:
            return False
        self.finished.remove(req)
        self.released_count += 1
        return True

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # ------------------------------------------------------- fault tolerance

    @staticmethod
    def _req_entry(r: Request) -> dict:
        """Full-fidelity request record for the ``full=True`` snapshot:
        nothing folded, nothing demoted — enough to resume the exact
        incarnation (slot, prefill cursor, state, lifetime event count)."""
        entry = {
            "request_id": r.request_id,
            "prompt": list(r.prompt),
            "generated": list(r.generated),
            "max_new_tokens": r.max_new_tokens,
            "arrived_at": r.arrived_at,
            "admitted_at": r.admitted_at,
            "first_token_at": r.first_token_at,
            "finished_at": r.finished_at,
            "cached_tokens": r.cached_tokens,
            "emitted": r.emitted,
            "uid": r.uid,
            "seq_slot": r.seq_slot,
            "prefill_pos": r.prefill_pos,
            "state": r.state.value,
            "stop_reason": r.stop_reason,
        }
        if r.params is not None:
            entry["params"] = dataclasses.asdict(r.params)
        return entry

    @staticmethod
    def _req_from_entry(e: dict) -> Request:
        params = e.get("params")
        req = Request(
            request_id=e["request_id"], prompt=list(e["prompt"]),
            max_new_tokens=e["max_new_tokens"],
            arrived_at=e.get("arrived_at", 0.0),
            admitted_at=e.get("admitted_at", 0.0),
            first_token_at=e.get("first_token_at", 0.0),
            finished_at=e.get("finished_at", 0.0),
            cached_tokens=e.get("cached_tokens", 0),
            emitted=e.get("emitted", 0),
            uid=e.get("uid", -1),
            params=SamplingParams(**params) if params else None)
        req.generated = list(e.get("generated", []))
        req.seq_slot = e.get("seq_slot", -1)
        req.prefill_pos = e.get("prefill_pos", 0)
        req.state = RequestState(e.get("state", "queued"))
        req.stop_reason = e.get("stop_reason")
        req.terminal_emitted = req.state.terminal
        return req

    def snapshot(self, full: bool = False) -> str:
        """Serialize scheduler state.

        Legacy mode (default): running sequences are demoted to waiting
        — their device KV is lost with the node and is recomputed on
        restore — with generated text folded into the prompt.

        ``full=True``: the journaled-recovery mode. The exact
        waiting/running split, slot assignments, prefill cursors,
        free-slot order, and plan cursor are all captured, so a restore
        paired with :meth:`PagedKV4Cache.restore_state` resumes the
        very next step bit-identically (nothing re-prefills)."""
        if full:
            return json.dumps({
                "format": "full",
                "waiting": [self._req_entry(r) for r in self.waiting],
                "running": [self._req_entry(r) for r in self.running],
                "finished": [self._req_entry(r) for r in self.finished],
                "free_slots": list(self._free_slots),
                "plan_cursor": self._plan_cursor,
                "preemptions": self.preemptions,
                "released_count": self.released_count,
            })
        reqs = []
        for r in list(self.waiting) + self.running:
            entry = {
                "request_id": r.request_id,
                "prompt": list(r.prompt) + list(r.generated),
                "max_new_tokens": r.max_new_tokens - len(r.generated),
                "arrived_at": r.arrived_at,
                "admitted_at": r.admitted_at,
                # TTFT / prefix-hit accounting must survive the restart:
                # a request that already produced its first token keeps
                # its stamp (restore must not re-measure TTFT against
                # the recomputed prefill), and cached_tokens keeps the
                # prefix-hit counters honest across the crash
                "first_token_at": r.first_token_at,
                "cached_tokens": r.cached_tokens,
                "emitted": r.emitted,
            }
            if r.params is not None:
                entry["params"] = dataclasses.asdict(r.params)
            reqs.append(entry)
        done = [{
            "request_id": r.request_id,
            "prompt": list(r.prompt),
            "generated": list(r.generated),
            "stop_reason": r.stop_reason,
            "state": r.state.value,
            "arrived_at": r.arrived_at,
            "admitted_at": r.admitted_at,
            "first_token_at": r.first_token_at,
            "cached_tokens": r.cached_tokens,
            "emitted": r.emitted,
        } for r in self.finished]
        return json.dumps({"pending": reqs, "finished": done})

    @classmethod
    def restore(cls, blob: str, max_batch: int, max_seqs: int,
                max_waiting: Optional[int] = None) -> "Scheduler":
        state = json.loads(blob)
        sched = cls(max_batch, max_seqs, max_waiting)
        if state.get("format") == "full":
            for e in state["waiting"]:
                sched.waiting.append(cls._req_from_entry(e))
            for e in state["running"]:
                sched.running.append(cls._req_from_entry(e))
            for e in state["finished"]:
                sched.finished.append(cls._req_from_entry(e))
            sched._free_slots = list(state["free_slots"])
            sched._plan_cursor = state.get("plan_cursor", 0)
            sched.preemptions = state.get("preemptions", 0)
            sched.released_count = state.get("released_count", 0)
            return sched
        for r in state["pending"]:
            params = r.get("params")
            sched.submit(Request(
                request_id=r["request_id"], prompt=r["prompt"],
                max_new_tokens=r["max_new_tokens"],
                arrived_at=r["arrived_at"],
                admitted_at=r.get("admitted_at", 0.0),
                first_token_at=r.get("first_token_at", 0.0),
                cached_tokens=r.get("cached_tokens", 0),
                emitted=r.get("emitted", 0),
                params=SamplingParams(**params) if params else None))
        for r in state["finished"]:
            req = Request(request_id=r["request_id"], prompt=r["prompt"],
                          max_new_tokens=0,
                          arrived_at=r.get("arrived_at", 0.0),
                          admitted_at=r.get("admitted_at", 0.0))
            req.generated = r["generated"]
            req.stop_reason = r.get("stop_reason")
            req.state = RequestState(r.get("state", "finished"))
            req.first_token_at = r.get("first_token_at", 0.0)
            req.cached_tokens = r.get("cached_tokens", 0)
            req.emitted = r.get("emitted", 0)
            req.terminal_emitted = req.state.terminal
            sched.finished.append(req)
        return sched
