"""Discovery by name, and BENCHMARK.json against the benchmark's
contract: every name in it resolves to files of its own under bench/,
and a new configuration, mix or metric is new files plus new entries."""

import json
import re
from pathlib import Path

import pytest

from bench import model as M
from bench import registry as REG
from bench import traffic as TR

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_contract_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    assert (ROOT / BENCH["command"][1]).is_file()
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_name_resolves_to_its_files():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for c in BENCH["configs"]:
        cfg = M.load_config(c["name"])
        assert (ROOT / c["file"]).is_file() and cfg["name"] == c["name"]
        assert c["source"] == cfg["source"]
    for w in BENCH["workloads"]:
        assert w["config"] in {c["name"] for c in BENCH["configs"]}
        TR.load_mix(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert callable(REG.reader(m["name"]))
        if m["name"].endswith("_roofline"):
            mod = REG.cost(m["name"][:-len("_roofline")])
            assert mod.PEAK in REG.peaks("TPU v5 lite") and mod.MATCH


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        REG.peaks("TPU v9 imaginary")


def test_a_test_only_entry_needs_only_new_files():
    """tests/bench/data holds a configuration, a mix and a peaks row that
    no file of bench/ names; the same loaders find them by name."""
    cfg = M.load_config("tiny_dense", DATA / "configs")
    mix = TR.load_mix("tiny_chat", DATA / "traffic")
    assert cfg["num_hidden_layers"] >= 1 and mix.loop == "open"
    assert REG.peaks("cpu", DATA)["int8_ops_s"] > 0


def test_a_new_metric_file_is_found(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "steps_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.window_steps))\n")
    read = REG.reader("steps_in_window", tmp_path)
    ctx = type("Ctx", (), {"window_steps": [1, 2, 3]})()
    assert read(ctx) == 3.0
