"""The load generator: the seed fixes every request; the mix fixes the
work."""

import numpy as np
import pytest

from bench import traffic as TR


@pytest.fixture(params=["chat", "longdoc"])
def mix(request):
    return TR.load_mix(request.param)


def test_same_seed_same_requests(mix):
    a = TR.generate(mix, 2**33 + 17, 40, 1000)
    b = TR.generate(mix, 2**33 + 17, 40, 1000)
    assert a == b


def test_other_seed_other_requests(mix):
    a = TR.generate(mix, 11, 40, 1000)
    b = TR.generate(mix, 12, 40, 1000)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert all(x.prompt != y.prompt for x, y in zip(a, b))


def test_every_seed_offers_the_same_work(mix):
    """Sizes, their order and the gaps come from the mix's own seed:
    another run seed draws other token ids on the same schedule."""
    a = TR.generate(mix, 1, 64, 1000)
    b = TR.generate(mix, 987654321987, 64, 1000)
    shape = lambda rs: [(len(r.prompt), r.max_new, r.due_s) for r in rs]
    assert shape(a) == shape(b)
    assert len(set(np.diff([r.due_s for r in a]))) > 1


def test_lengths_inside_the_mix_bounds(mix):
    reqs = TR.generate(mix, 5, 200, 1000)
    assert all(mix.prompt["min"] <= len(r.prompt) <= mix.prompt["max"]
               for r in reqs)
    assert all(mix.output["min"] <= r.max_new <= mix.output["max"]
               for r in reqs)
    assert all(0 <= t < 1000 for r in reqs for t in r.prompt)


def test_open_loop_due_times_ascend(mix):
    reqs = TR.generate(mix, 3, 100, 1000)
    due = [r.due_s for r in reqs]
    assert due[0] == 0.0 and due == sorted(due)
    assert len(reqs) / due[-1] == pytest.approx(mix.rate_rps, rel=0.35)


def test_every_seed_offers_the_window_the_same_requests():
    mix = TR.load_mix("chat")
    n = TR.request_count(mix, 40.0, 0)
    seen = []
    for seed in (1, 2**33 + 5, 987654321987):
        reqs = TR.generate(mix, seed, n, 1000)
        assert all(a.due_s <= b.due_s for a, b in zip(reqs, reqs[1:]))
        seen.append([(len(r.prompt), r.max_new, r.due_s) for r in reqs
                     if 10.0 <= r.due_s < 40.0])
    assert seen[0] == seen[1] == seen[2] and len(seen[0]) > 20
