"""The engine's step phases as the benchmark reads them: the idle split
and the clock check on synthetic ops and spans, a recorded trace with
the engine's phases, and the rows the client rebuilds against the rows
the engine ran.

``data/engine_phases.xplane.pb`` was recorded on a CPU backend (so it
has no device plane): the tiny cell's engine, warmed for two steps, then
four steps of two requests through ``bench.client.Client`` with client
spans and ``Engine.trace_spans`` on, under ``jax.profiler`` with host
tracer level 1, the Python tracer off and no HLO protos. The third step
met a shape bucket the warm-up had not."""

from pathlib import Path

import pytest

from bench import phases as PH
from bench import run as RUN
from bench import trace as TRC
from bench import traffic as TR
from bench.client import Client, VirtualClock

from tiny import CELL, DATA

FIXTURE = Path(__file__).parent / "data" / "engine_phases.xplane.pb"

# (name, long name, start ns, duration ns) as reduce() returns them
OPS = [("fusion.1", "", 120.0, 30.0), ("fusion.2", "", 140.0, 40.0),
       ("fusion.3", "", 330.0, 50.0)]
# two steps tiled by phases, with a gap between them and one uncovered
# stretch inside the second step ([300, 310) precedes its first phase)
SPANS = sorted([
    ("Engine.step", 0.0, 200.0),
    ("engine.schedule", 0.0, 40.0), ("engine.pack", 40.0, 30.0),
    ("engine.upload", 70.0, 20.0), ("engine.dispatch.new_bucket", 90.0,
                                    20.0),
    ("engine.readback", 110.0, 80.0), ("engine.sample", 190.0, 10.0),
    ("Engine.step", 300.0, 150.0),
    ("engine.schedule", 310.0, 10.0), ("engine.pack", 320.0, 5.0),
    ("engine.upload", 325.0, 3.0), ("engine.dispatch", 328.0, 2.0),
    ("engine.readback", 330.0, 100.0), ("engine.sample", 430.0, 20.0),
], key=lambda s: s[1])
WINDOW = 500.0


def test_idle_split_sums_to_the_idle_share():
    shares = PH.idle_by_span(OPS, SPANS, WINDOW)
    busy = TRC.busy_ns(OPS)                       # [120,180) + [330,380)
    assert busy == 110.0
    assert sum(shares.values()) == pytest.approx(
        100.0 * (1 - busy / WINDOW))
    # readback [110,190) holds 60 busy of 80, then [330,430) 50 of 100
    assert shares["readback"] == pytest.approx(100.0 * (20 + 50) / WINDOW)
    assert shares["new_bucket"] == pytest.approx(100.0 * 20 / WINDOW)
    assert shares["dispatch"] == pytest.approx(100.0 * 2 / WINDOW)
    assert shares["unphased"] == pytest.approx(100.0 * 10 / WINDOW)
    # [200,300) between the steps and [450,500) after the last
    assert shares["outside_step"] == pytest.approx(100.0 * 150 / WINDOW)


def test_idle_outside_every_span_lands_outside_step():
    shares = PH.idle_by_span(OPS, [], WINDOW)
    assert shares["outside_step"] == pytest.approx(100.0 * 390 / WINDOW)
    assert sum(v for k, v in shares.items() if k != "outside_step") == 0


def test_clock_check():
    assert PH.clock_check(OPS, SPANS) == (2, 2)
    # device ops 100 ns late: none shows before the first readback
    # ends, and the second step's first op precedes its dispatch span
    late = [(n, ln, s + 100.0, d) for n, ln, s, d in OPS]
    assert PH.clock_check(late, SPANS) == (0, 2)
    assert PH.clock_check([], SPANS) == (0, 0)


def test_recorded_trace_with_engine_phases():
    red = TRC.reduce(str(FIXTURE))
    # reduce() reads the client's spans under their plain names
    assert [s[0] for s in red["spans"]] == (
        ["generator", "Engine.submit", "Engine.submit"]
        + ["Engine.step", "event drain", "generator"] * 3
        + ["Engine.step", "event drain"])
    spans = PH.host_spans(str(FIXTURE))
    steps = [s for s in spans if s[0] == "Engine.step"]
    assert len(steps) == 4
    for i, (_, s0, d0) in enumerate(steps):
        inner = [s for s in spans
                 if s[0] in PH.PHASES and s0 <= s[1] < s0 + d0]
        assert [n for n, _, _ in inner] == [
            "engine.schedule", "engine.pack", "engine.upload",
            "engine.dispatch.new_bucket" if i == 2 else "engine.dispatch",
            "engine.readback", "engine.sample"]
        # the phases tile the step: each begins where the last ended
        assert all(inner[j][1] >= inner[j - 1][1] + inner[j - 1][2]
                   for j in range(1, len(inner)))
        assert inner[-1][1] + inner[-1][2] <= s0 + d0
    window = spans[-1][1] + spans[-1][2] - spans[0][1]
    shares = PH.idle_by_span([], spans, window)
    assert sum(shares.values()) == pytest.approx(100.0)
    assert shares["new_bucket"] > 0


def test_client_rows_match_the_engine_rows():
    """``Client._step`` rebuilds each forward's [(ctx, q)] rows from
    request state (they feed ``decode_rows_mean``, ``step_mfu`` and the
    rooflines); the engine keeps the rows it packed."""
    sess = RUN.build(CELL, 11, DATA)
    eng = sess.eng
    eng.trace_spans = True
    ran = []
    step = eng.step

    def recording_step():
        fwd = eng.forward_calls
        step()
        ran.append(eng.last_forward_rows if eng.forward_calls > fwd
                   else [])
    eng.step = recording_step
    reqs = TR.generate(sess.mix, 11, 8, sess.vocab)
    cl = Client(eng, reqs, sess.mix.loop, sess.concurrency, RUN._sampling,
                VirtualClock())
    cl.run_until(1e9)
    assert len(cl.steps) == len(ran) > 10
    assert any(len(r) > 1 for r in ran)
    for rec, rows in zip(cl.steps, ran):
        assert sorted(rec.rows) == sorted(rows)
