"""The harness with the timed path broken underneath: each fault a served
model can have makes ``correct`` come out false (one chip, so there is
no exchange between chips to leave out)."""

import pytest

from bench import check as CK
from bench import faults as F
from bench import run as RUN

from tiny import DATA, SECONDS

# a busier mix than tiny_chat, so steps carry several rows
CELL = {"name": "tiny_dense.tiny_busy", "config": "tiny_dense",
        "traffic": "tiny_busy", "chips": 1}


@pytest.mark.parametrize("fault", sorted(F.FAULTS))
def test_fault_is_not_correct(fault):
    """Serve, free and check as a run does (the window's own metrics are
    left out: on a loaded CPU a short window may hold no request)."""
    seed = 2**33 + 9
    sess, run = RUN.serve(CELL, seed, SECONDS, False, DATA,
                          engine_hook=F.FAULTS[fault])
    RUN.free(sess)
    res = CK.check(sess.cfg, seed, run.compared, run.prompts, run.logits)
    assert res["correct"] is False, res["compared"]
