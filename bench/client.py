"""The load generator's client: drives ``Engine.submit``/``step`` in
process and keeps its own timestamps.

Open loop: every request is sent at its due time (or, if the client is
busy inside a step, as soon as that step returns; the lateness is
recorded). Closed loop: ``concurrency`` clients, each sending its next
request when its previous one has finished. Every timestamp is taken by
the client's clock after ``Engine.step`` returns (the step ends in the
logits copy-back, so its tokens are then visible to a client).

The same loop runs on a real clock (the measured window) and on a
virtual one (the warm-up replay, which advances time by each step's
measured duration and jumps idle gaps, so it walks the window's traffic
through the same batch shapes without waiting for arrivals).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Optional

import jax

from bench.traffic import Request

__all__ = ["Record", "StepRecord", "Client", "RealClock", "VirtualClock"]


@dataclasses.dataclass
class Record:
    rid: int
    due: float
    sent: float = float("nan")
    prompt_len: int = 0
    max_new: int = 0
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    chunks: list = dataclasses.field(default_factory=list)  # prompt spans
    end_state: Optional[str] = None
    handle: object = None


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    decode_rows: int
    rows: list            # [(ctx, q_len), ...] of this step's forward
    traces: int           # new jit traces during the step


class RealClock:
    def now(self) -> float:
        return time.perf_counter()

    def idle_until(self, t: float):
        dt = t - time.perf_counter()
        if dt > 0:
            time.sleep(dt)

    def stepped(self, t0: float, traced: bool):
        pass


class VirtualClock:
    """Time that moves by each step's measured duration; a step that
    traced (compiled or loaded a program) is charged the median of the
    steps that did not (nothing before the first such step), so a
    compile does not reshape the replay."""

    def __init__(self, scale: float = 1.0):
        self.t = 0.0
        self.scale = scale
        self._plain: list = []

    def now(self) -> float:
        return self.t

    def idle_until(self, t: float):
        self.t = max(self.t, t)

    def stepped(self, wall_s: float, traced: bool):
        if traced:
            plain = sorted(self._plain) or [0.0]
            wall_s = plain[len(plain) // 2]
        else:
            self._plain.append(wall_s)
        self.t += wall_s * self.scale


def _span(name: str, on: bool):
    if on:
        return jax.profiler.TraceAnnotation(name)
    return _NULL


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL = _Null()


class Client:
    def __init__(self, engine, requests: list, loop: str, concurrency: int,
                 sampling: Callable, clock, spans: bool = False):
        self.eng = engine
        self.loop = loop
        self.concurrency = concurrency
        self.sampling = sampling
        self.clock = clock
        self.spans = spans
        self.pending = deque(requests)
        self.records: dict = {}
        self.by_engine_id: dict = {}
        self.steps: list = []
        self.t0 = 0.0
        self.in_flight = 0

    # ------------------------------------------------------------ submit

    def _send(self, req: Request, due: float):
        now = self.clock.now()
        rec = Record(rid=req.rid, due=due, sent=now,
                     prompt_len=len(req.prompt), max_new=req.max_new)
        with _span("Engine.submit", self.spans):
            h = self.eng.submit(list(req.prompt), self.sampling(req))
        rec.handle = h
        self.records[req.rid] = rec
        self.by_engine_id[h.request_id] = rec
        self.in_flight += 1

    def send_due(self, until: float):
        """Send what is due now (open loop: due before ``until``; closed
        loop: a request per free client)."""
        with _span("generator", self.spans):
            if self.loop == "open":
                now = self.clock.now()
                while (self.pending and self.t0 + self.pending[0].due_s <= now
                       and self.t0 + self.pending[0].due_s < until):
                    req = self.pending.popleft()
                    self._send(req, self.t0 + req.due_s)
            else:
                while self.in_flight < self.concurrency and self.pending:
                    req = self.pending.popleft()
                    self._send(req, self.clock.now())

    # -------------------------------------------------------------- step

    def _rows_before(self):
        """Each request the step may run (a waiting one is admitted and
        given its first chunk inside the step), with its progress."""
        eng = self.eng
        return [(r, r.prefill_pos, len(r.prompt),
                 int(eng.cache.seq_len[r.seq_slot]) if r.seq_slot >= 0
                 else 0, len(r.generated))
                for r in list(eng.sched.running) + list(eng.sched.waiting)]

    def _step(self):
        eng = self.eng
        before = self._rows_before()
        decode_rows = sum(1 for r in eng.sched.running
                          if r.prefilled and not r.done)
        traces = eng.trace_count
        t0 = self.clock.now()
        w0 = time.perf_counter()
        with _span("Engine.step", self.spans):
            eng.step()
        wall = time.perf_counter() - w0
        traced = eng.trace_count != traces
        self.clock.stepped(wall, traced)
        t1 = self.clock.now()
        rows = []
        for r, pos0, plen, seq0, ngen0 in before:
            if pos0 < plen:
                if r.prefill_pos > pos0:
                    rows.append((pos0, r.prefill_pos - pos0))
                    rec = self.by_engine_id.get(r.request_id)
                    if rec is not None:
                        rec.chunks.append((pos0, r.prefill_pos))
            elif len(r.generated) > ngen0:
                rows.append((seq0, 1))
        self.steps.append(StepRecord(t0, t1, decode_rows, rows,
                                     eng.trace_count - traces))
        with _span("event drain", self.spans):
            for ev in eng.events():
                rec = self.by_engine_id.get(ev.request_id)
                if rec is None:
                    continue
                if ev.token is not None:
                    rec.token_times.append(t1)
                    rec.tokens.append(int(ev.token))
                if ev.finished:
                    rec.end_state = ev.state.name
                    self.in_flight -= 1
                    self.eng.release(rec.handle)

    # --------------------------------------------------------------- run

    def run_until(self, until: float, send_until: Optional[float] = None):
        """Serve until the clock reads ``until``; send nothing due at or
        after ``send_until`` (default: ``until``)."""
        send_until = until if send_until is None else send_until
        while True:
            now = self.clock.now()
            if now >= until:
                return
            self.send_due(send_until)
            if self.eng.sched.has_work:
                self._step()
                continue
            if (self.loop == "open" and self.pending
                    and self.t0 + self.pending[0].due_s < send_until):
                self.clock.idle_until(
                    min(until, self.t0 + self.pending[0].due_s))
                continue
            return

    def drain(self, until: float, wanted: Callable):
        """Keep stepping (sending nothing new) while ``wanted()`` and the
        clock is before ``until``."""
        while (self.eng.sched.has_work and wanted()
               and self.clock.now() < until):
            self._step()
