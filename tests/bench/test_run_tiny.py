"""One run of the test-only cell through the whole harness (no chip
check): the contract's result line, and the control of ``correct``."""

import json

import pytest

from bench import control as CTL
from bench import run as RUN

from tiny import BENCH, CELL, DATA, SECONDS


@pytest.fixture(scope="module")
def result():
    # a first run in the checkout lowers the schedule's forward shapes, as
    # a cell's first run does; the measured run finds them compiled, so
    # a loaded CPU does not spend its short window lowering
    sess, _ = RUN.serve(CELL, 2**33 + 6, SECONDS, False, DATA)
    RUN.free(sess)
    return RUN.run_cell(BENCH, CELL, 2**33 + 7, SECONDS, False, 0.0, DATA)


def test_result_line_parses(result):
    line = json.dumps(result)
    back = json.loads(line)
    assert list(back)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(back)[-1] == "compared"
    assert back["correct"] is True and back["failed"] == 0
    assert back["attempted"] > 0
    assert set(back["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    for m in back["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    dev = back["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    for v in back["compared"].values():
        assert set(v) == {"value", "limit"}


def test_no_tpu_means_no_result(capsys, monkeypatch):
    """On a CPU backend the entry exits non-zero and prints nothing to
    standard output."""
    for k in ("JAX_COMPILATION_CACHE_DIR", "TPU_LOG_DIR",
              "JAX_COMPILATION_CACHE_MAX_SIZE",
              "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
              "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"):
        monkeypatch.delenv(k, raising=False)
    rc = RUN.main(["--workload", "mistral_nemo_12b.chat", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""
    assert RUN.main(["--workload", "no.such", "--seed", "1",
                     "--seconds", "1"]) != 0


def test_control_is_not_correct():
    """The control — the reference at int4_fraction 1.0, the step below
    the configuration's int8 activation blocks — in the program's place
    on the same requests: it fails the limits the program keeps."""
    r = CTL.readings(CELL, 2**33 + 8, SECONDS, bench_dir=DATA)
    assert r["correct"] == {"program": True, "control": False}, r
