"""Step-phase spans and the admission stamp: the unified step marks its
phases in order and tiles the step with them, one dispatch span per
forward; a shape bucket is ``engine.dispatch.new_bucket`` once per
engine whether spans were on or off when it first ran; spans off build
no ``TraceAnnotation``; ``admitted_at`` is stamped once and survives a
preemption and both snapshot formats."""
import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.models.lm import LM, QuantConfig
from repro.serving import tracing
from repro.serving.engine import Engine, EngineConfig, SamplingParams
from repro.serving.tracing import Phases

ORDER = ["engine.schedule", "engine.pack", "engine.upload", "dispatch",
         "engine.readback", "engine.sample"]


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama3_8b")
    qc = QuantConfig(int4_fraction=0.75, impl="ref")
    lm = LM(cfg)
    params, axes = lm.init(jax.random.PRNGKey(0))
    qparams, _ = LM(cfg, quant=qc).quantize(params, axes)
    return cfg, qc, qparams


def make_engine(setup, clock=None, **kw):
    cfg, qc, qparams = setup
    ecfg = dict(max_batch=4, num_pages=64, page_size=8,
                max_pages_per_seq=16, prefill_chunk_tokens=16)
    ecfg.update(kw)
    extra = {} if clock is None else {"clock": clock}
    return Engine(cfg, qparams, qc, EngineConfig(**ecfg), **extra)


def submit(eng, lens, max_new, seed=0):
    rng = np.random.default_rng(seed)
    for n in lens:
        eng.submit(rng.integers(1, eng.cfg.vocab_size, n).tolist(),
                   SamplingParams(max_new_tokens=max_new, temperature=0.0))


class Recorder:
    """Stands in for ``tracing.span``: logs each span's enter and exit."""

    def __init__(self):
        self.log = []

    def __call__(self, name, on):
        assert on
        rec = self

        class _Span:
            def __enter__(self):
                rec.log.append(("enter", name))

            def __exit__(self, *a):
                rec.log.append(("exit", name))
        return _Span()


def _phase(name):
    return "dispatch" if name.startswith("engine.dispatch") else name


def test_step_phases_tile_the_step_in_order(setup, monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(tracing, "span", rec)
    eng = make_engine(setup)
    eng.trace_spans = True
    submit(eng, (30, 5, 12), 4)        # prompt chunks beside decode rows
    while eng.sched.has_work:
        rec.log.clear()
        fwd0 = eng.forward_calls
        eng.step()
        entered = [n for k, n in rec.log if k == "enter"]
        assert all(_phase(n) in ORDER for n in entered)
        # each span ends before the next begins, and the last one ends
        # inside the step
        assert rec.log == [e for n in entered
                           for e in (("enter", n), ("exit", n))]
        if eng.forward_calls > fwd0:
            assert [_phase(n) for n in entered] == ORDER
            assert eng.forward_calls - fwd0 == sum(
                1 for n in entered if _phase(n) == "dispatch")
        else:
            assert entered == ["engine.schedule"]
    assert eng.internal_errors == 0


def test_new_bucket_once_per_bucket(setup, monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(tracing, "span", rec)

    def serve(eng, lens, max_new, seed):
        rec.log.clear()
        traces = eng.trace_count
        submit(eng, lens, max_new, seed)
        eng.run()
        new = [n for k, n in rec.log
               if k == "enter" and n == "engine.dispatch.new_bucket"]
        return len(new), eng.trace_count - traces

    # spans on from the start: a new bucket is exactly a new jit trace
    eng = make_engine(setup)
    eng.trace_spans = True
    new, traced = serve(eng, (30, 5, 12), 4, seed=0)
    assert new == traced > 0
    # the same shapes again: no bucket is new
    assert serve(eng, (30, 5, 12), 4, seed=1) == (0, 0)

    # a warm-up with spans off still fills the seen-set, so the window
    # with spans on marks nothing new
    rec.log.clear()
    eng = make_engine(setup)
    submit(eng, (30, 5, 12), 4)
    eng.run()
    assert rec.log == []
    eng.trace_spans = True
    assert serve(eng, (30, 5, 12), 4, seed=2) == (0, 0)
    # a shape the warm-up never met is new once, then never again
    new, traced = serve(eng, (60,), 1, seed=3)
    assert new == traced > 0
    assert serve(eng, (60,), 1, seed=4) == (0, 0)


def test_spans_off_build_no_annotation(setup, monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("TraceAnnotation built with spans off")
    eng = make_engine(setup)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    submit(eng, (30, 5), 3)
    eng.run()
    assert eng.internal_errors == 0
    assert eng.last_forward_rows == []
    assert all(len(r.generated) == 3 for r in eng.sched.finished)


def test_span_helper():
    assert tracing.span("x", False) is tracing.span("y", False)
    assert isinstance(tracing.span("x", True), jax.profiler.TraceAnnotation)
    ph = Phases()
    ph.enter("engine.schedule")
    ph.enter("engine.pack")
    ph.end()
    ph.end()                            # ending twice is harmless


def test_last_forward_rows(setup):
    eng = make_engine(setup)
    eng.trace_spans = True
    submit(eng, (30, 5), 3)
    eng.step()          # a 16-token budget: the first prompt's chunk
    assert eng.last_forward_rows == [(0, 16)]
    eng.step()          # the plan's start rotates to the second prompt
    assert eng.last_forward_rows == [(0, 5), (16, 11)]


def test_admitted_at_once_across_preemption_and_restore(setup):
    t = {"now": 1.0}

    def clock():
        return t["now"]

    # a pool too small for three long generations forces preemption
    eng = make_engine(setup, clock=clock, max_batch=3, num_pages=6,
                      page_size=4, max_pages_per_seq=8)
    for i in range(3):
        eng.add_request(i, [1, 2, 3, 4, 5], 8)
        t["now"] += 1.0
    first: dict = {}
    blobs = []
    while eng.sched.has_work and eng.steps < 200:
        t["now"] += 1.0
        eng.step()
        for r in eng.sched.running:
            first.setdefault(r.request_id, r.admitted_at)
        if eng.steps == 4:
            blobs = [eng.snapshot(full=True), eng.snapshot()]
    assert eng.sched.preemptions > 0
    done = {r.request_id: r for r in eng.sched.finished}
    assert sorted(done) == [0, 1, 2]
    for rid, r in done.items():
        assert r.admitted_at == first[rid]
        assert r.arrived_at <= r.admitted_at
    for blob in blobs:
        eng2 = Engine.restore(blob, eng.cfg, eng.params, eng.quant,
                              eng.ecfg, clock=clock)
        reqs = (list(eng2.sched.waiting) + eng2.sched.running
                + eng2.sched.finished)
        stamped = {r.request_id: r.admitted_at for r in reqs
                   if r.admitted_at}
        assert stamped and all(first[rid] == a
                               for rid, a in stamped.items())
