"""The arithmetic of ``correct`` on synthetic logits: the requests it
chooses, the rows it compares and the numbers it reads."""

import types

import numpy as np
import pytest

from bench import check as CK
from bench.traffic import Request


def _reqs():
    return [Request(i, 0.5 * i, tuple(range(10 + 30 * (i % 4))), 8 + i)
            for i in range(20)]


def test_choose_takes_the_longest_and_is_seeded():
    reqs = _reqs()
    a = CK.choose(reqs, 1.0, 9.0, seed=2**33 + 1)
    longest = max((r for r in reqs if 1.0 <= r.due_s < 9.0),
                  key=lambda r: len(r.prompt) + r.max_new)
    assert longest.rid in a
    assert all(1.0 <= reqs[i].due_s < 9.0 for i in a)
    assert a == CK.choose(reqs, 1.0, 9.0, seed=2**33 + 1)
    assert sum(reqs[i].max_new for i in a) <= CK.ROWS


def test_choose_keeps_to_the_packed_budget(monkeypatch):
    monkeypatch.setattr(CK, "PACK", 120)
    reqs = _reqs()
    a = CK.choose(reqs, 0.0, 10.0, seed=3)
    assert sum(len(reqs[i].prompt) + reqs[i].max_new - 1 for i in a) <= 120


def test_readings_of_exact_rows():
    rng = np.random.default_rng(0)
    ref = rng.standard_normal((6, 50))
    r = CK.readings(ref.copy(), ref, ref.argmax(-1))
    assert r["logit_err_median"] == 0.0
    assert r["served_not_argmax"] == 0 and r["served_tokens"] == 6


def test_readings_relative_l2_and_misses():
    rng = np.random.default_rng(1)
    ref = rng.standard_normal((4, 50))
    got = ref.copy()
    got[0] = 1.5 * ref[0]                       # 0.5 off in relative L2
    got[3] = np.nan                             # no row was recorded
    served = ref.argmax(-1)
    served[1] = (served[1] + 1) % 50            # not the row's first choice
    r = CK.readings(got, ref, served)
    assert r["logit_err_median"] == pytest.approx(0.25)   # 0, 0, .5, miss
    assert r["served_not_argmax"] == 2


def test_verdict_holds_every_limit():
    lim = {"logit_err_median": 0.1, "served_not_argmax": 0,
           "served_tokens": 10}
    ok = CK.verdict({"logit_err_median": 0.05, "served_not_argmax": 0,
                     "served_tokens": 10}, lim)
    assert ok["correct"] is True
    for bad in ({"logit_err_median": 0.2}, {"served_not_argmax": 1},
                {"served_tokens": 9}):
        vals = {"logit_err_median": 0.05, "served_not_argmax": 0,
                "served_tokens": 10, **bad}
        assert CK.verdict(vals, lim)["correct"] is False


def test_recorder_keeps_only_the_chosen_rows():
    class Eng:
        def _sample_batch(self, logits, reqs, positions):
            return [int(np.argmax(r)) for r in logits]
    eng = Eng()
    client = types.SimpleNamespace(by_engine_id={
        10: types.SimpleNamespace(rid=1), 11: types.SimpleNamespace(rid=2)})
    rec = CK.LogitsRecorder(eng, client, wanted={2})
    logits = np.eye(3, dtype=np.float32)
    reqs = [types.SimpleNamespace(request_id=i) for i in (10, 11, 12)]
    assert eng._sample_batch(logits, reqs, [5, 7, 9]) == [0, 1, 2]
    assert set(rec.rows) == {(2, 7)}
    np.testing.assert_array_equal(rec.rows[(2, 7)], logits[1])
    rec.remove()
    assert eng._sample_batch.__name__ == "_sample_batch"
