"""End-to-end metric arithmetic on synthetic client timestamps."""

import types

import pytest

from bench import e2e as E
from bench import registry as REG
from bench.client import Record


def _steady(stalls=(), stall_s=0.0, n=20, itl=0.05, rate=2.0):
    """n requests due every 1/rate s, first token 0.2 s after due, then
    a token every ``itl`` s for 10 tokens; each stall (a time) delays
    every event after it by ``stall_s``."""
    def t(x):
        return x + stall_s * sum(1 for s in stalls if x >= s)
    recs = []
    for i in range(n):
        due = i / rate
        times = [t(due + 0.2 + k * itl) for k in range(10)]
        recs.append(Record(rid=i, due=due, sent=due, token_times=times,
                           tokens=list(range(10)), end_state="FINISHED"))
    return recs


def test_steady_numbers():
    m = E.end_to_end(_steady(), 0.0, 10.0, 10.0)
    assert m["ttft_p90_ms"] == pytest.approx(200.0)
    assert m["itl_p50_ms"] == pytest.approx(50.0)
    assert m["itl_p95_ms"] == pytest.approx(50.0)
    # the last request (due 9.5) shows 6 of its 10 tokens before 10 s
    assert m["output_tok_s"] == pytest.approx(196 / 10.0)


def test_stall_moves_tails():
    """Stalls inside the window (a step that compiles, say) show in the
    gap tail and in the TTFT tail: the tokens in flight and the requests
    that fall due during a stall all wait for it."""
    base = E.end_to_end(_steady(), 0.0, 10.0, 20.0)
    stalls = [0.33 + 0.8 * i for i in range(12)]
    stalled = E.end_to_end(_steady(stalls, stall_s=0.3), 0.0, 10.0, 20.0)
    assert stalled["itl_p95_ms"] > base["itl_p95_ms"]
    assert stalled["ttft_p90_ms"] > base["ttft_p90_ms"]
    assert stalled["itl_p50_ms"] == pytest.approx(base["itl_p50_ms"])


def test_failed_request_is_a_miss():
    recs = _steady()
    recs[3].token_times, recs[3].tokens = [], []
    recs[3].end_state = "FAILED"
    ttft = E.ttft_s(recs, 0.0, 10.0, t_end=30.0)
    assert max(ttft) == pytest.approx(30.0 - recs[3].due)


def test_only_window_requests_and_gaps_count():
    recs = _steady()
    m = E.end_to_end(recs, 2.0, 4.0, 10.0)
    assert len(E.ttft_s(recs, 2.0, 4.0, 10.0)) == 4
    assert m["output_tok_s"] == pytest.approx(
        E.tokens_in(recs, 2.0, 4.0) / 2.0)


def test_empty_window_is_an_error():
    with pytest.raises(ValueError):
        E.end_to_end(_steady(), 100.0, 110.0, 120.0)


def test_first_token_reader_is_the_window_ttft_p90():
    """The per-layer reader takes TTFT's p90 as the end-to-end arithmetic
    does, misses included."""
    recs = _steady(stalls=[3.1], stall_s=0.7)
    recs[5].token_times, recs[5].tokens = [], []
    ctx = types.SimpleNamespace(records=recs, w0=0.0, w1=10.0, t_end=12.0)
    got = REG.reader("first_token_p90_ms")(ctx)
    assert got == pytest.approx(E.end_to_end(recs, 0.0, 10.0, 12.0)
                                ["ttft_p90_ms"])
    ctx.w0, ctx.w1 = 100.0, 110.0
    assert REG.reader("first_token_p90_ms")(ctx) is None
