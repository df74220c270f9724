"""The client loop on a stub engine (no model, no device): open-loop
sends on schedule and records lateness; closed-loop keeps its clients
busy; the virtual clock advances by step times and jumps idle gaps."""

import types

import pytest

from bench.client import Client, VirtualClock
from bench.traffic import Request


class _Req:
    def __init__(self, rid, prompt, max_new):
        self.request_id, self.prompt, self.max_new = rid, prompt, max_new
        self.generated, self.prefill_pos, self.seq_slot = [], 0, rid
        self.state = types.SimpleNamespace(name="RUNNING")

    @property
    def prefilled(self):
        return self.prefill_pos == len(self.prompt)

    @property
    def done(self):
        return len(self.generated) >= self.max_new


class StubEngine:
    """Each step prefills every new prompt whole and gives every running
    request one token; a request ends when it has max_new tokens."""

    def __init__(self):
        self.sched = types.SimpleNamespace(running=[], waiting=[],
                                           finished=[], has_work=False)
        self.cache = types.SimpleNamespace(seq_len=[0] * 1000)
        self.trace_count, self._events, self._next = 0, [], 0

    def submit(self, prompt, params):
        r = _Req(self._next, prompt, params)
        self._next += 1
        self.sched.running.append(r)
        self.sched.has_work = True
        return types.SimpleNamespace(request_id=r.request_id)

    def step(self):
        for r in list(self.sched.running):
            r.prefill_pos = len(r.prompt)
            r.generated.append(7)
            fin = r.done
            self._events.append(types.SimpleNamespace(
                request_id=r.request_id, token=7, finished=False,
                state=r.state))
            if fin:
                r.state = types.SimpleNamespace(name="FINISHED")
                self._events.append(types.SimpleNamespace(
                    request_id=r.request_id, token=None, finished=True,
                    state=r.state))
                self.sched.running.remove(r)
        self.sched.has_work = bool(self.sched.running)

    def events(self):
        ev, self._events = self._events, []
        return ev

    def release(self, handle):
        return True


def _reqs(n, gap, max_new=3):
    return [Request(i, i * gap, (1, 2, 3), max_new) for i in range(n)]


def test_open_loop_on_a_virtual_clock():
    clock = VirtualClock()
    cl = Client(StubEngine(), _reqs(5, 1.0), "open", 0, lambda r: r.max_new,
                clock)
    cl.run_until(10.0)
    recs = sorted(cl.records.values(), key=lambda r: r.rid)
    assert [r.due for r in recs] == [0.0, 1.0, 2.0, 3.0, 4.0]
    assert all(r.end_state == "FINISHED" and len(r.tokens) == 3
               for r in recs)
    assert all(r.sent >= r.due for r in recs)
    assert cl.in_flight == 0 and len(cl.steps) == 15


def test_closed_loop_keeps_clients_busy():
    cl = Client(StubEngine(), _reqs(12, 0.0), "closed", 3,
                lambda r: r.max_new, VirtualClock())
    cl.run_until(1e9)
    assert len(cl.records) == 12
    assert max(s.decode_rows for s in cl.steps) <= 3


def test_virtual_clock_charges_traced_steps_the_median():
    clock = VirtualClock(scale=2.0)
    clock.stepped(100.0, traced=True)     # no plain step yet: nothing
    assert clock.now() == 0.0
    for w in (0.01, 0.03, 0.02):
        clock.stepped(w, traced=False)
    t = clock.now()
    clock.stepped(100.0, traced=True)     # a compile: charged the median
    assert clock.now() - t == pytest.approx(2.0 * 0.02)
    clock.idle_until(50.0)
    assert clock.now() == 50.0


class _ShapeStub(StubEngine):
    """A stub whose step traces once per batch size it has not seen
    (the stand-in for a forward shape), or on every step with
    ``always``."""

    def __init__(self, always=False):
        super().__init__()
        self.seen, self.always = set(), always

    def submit(self, prompt, params):
        return super().submit(prompt, params.max_new_tokens)

    def step(self):
        n = len(self.sched.running)
        if self.always or n not in self.seen:
            self.seen.add(n)
            self.trace_count += 1
        super().step()

    def abort(self, rid):
        self.sched.running = [r for r in self.sched.running
                              if r.request_id != rid]


def _mix(loop="open"):
    return types.SimpleNamespace(loop=loop)


def test_warm_up_replays_until_no_new_shape():
    from bench import run as RUN
    eng = _ShapeStub()
    traced = RUN.warm_up(eng, _reqs(12, 0.01, max_new=5), _mix(), 0, 1.0,
                         vocab=16)
    assert traced[0] > 0 and traced[-1] == 0
    assert sum(traced) == len(eng.seen)


def test_warm_up_stops_at_its_cap():
    from bench import run as RUN
    traced = RUN.warm_up(_ShapeStub(always=True), _reqs(4, 0.01), _mix(), 0,
                         1.0, vocab=16, max_replays=3)
    assert len(traced) == 3 and all(t > 0 for t in traced)


def test_replay_scales_alternate_around_one():
    from bench import run as RUN
    assert [round(RUN.replay_scale(i), 2) for i in range(5)] == [
        1.0, 0.92, 1.08, 0.84, 1.16]
