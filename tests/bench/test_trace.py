"""Trace reduction: the host spans of a recorded trace (a fixture,
recorded on a CPU backend, so it has no device plane) and the device-op
arithmetic on synthetic op lists."""

from pathlib import Path

import pytest

from bench import trace as TRC

FIXTURE = Path(__file__).parent / "data" / "host_spans.xplane.pb"

# (name, long name, start ns, duration ns) as reduce() returns them
OPS = [
    ("fusion.1", "fusion.1 = f32[8] fusion(...)", 0.0, 100.0),
    ("custom-call.2", "backend_config=_w4a4_kernel", 50.0, 100.0),
    ("custom-call.3", "backend_config=_w4a8_kernel", 400.0, 100.0),
    ("fusion.1", "fusion.1 = f32[8] fusion(...)", 1000.0, 50.0),
]
SPANS = [("Engine.step", 0.0, 600.0), ("event drain", 600.0, 50.0),
         ("generator", 700.0, 300.0)]


def test_recorded_trace_spans():
    red = TRC.reduce(str(FIXTURE))
    names = [s[0] for s in red["spans"]]
    assert names == ["generator", "Engine.submit", "Engine.step",
                     "event drain", "Engine.step"]
    assert all(d > 0 for _, _, d in red["spans"])
    assert red["devices"] == {}


def test_find_xplane(tmp_path):
    with pytest.raises(FileNotFoundError):
        TRC.find_xplane(str(tmp_path))
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(FIXTURE.read_bytes())
    assert TRC.find_xplane(str(tmp_path)).endswith("h.xplane.pb")


def test_busy_is_the_union_of_op_intervals():
    # [0,150) + [400,500) + [1000,1050)
    assert TRC.busy_ns(OPS) == pytest.approx(300.0)


def test_op_totals_and_kernel_time():
    tot = TRC.op_totals(OPS)
    assert tot == {"fusion.1": 150.0, "custom-call.2": 100.0,
                   "custom-call.3": 100.0}
    hlo = ("%_unified_forward.56 = f32[8,5120]{1,0:T(8,128)S(1)} "
           "custom-call(u8[8,6272]{1,0} %p), custom_call_target=\"x\"")
    assert TRC.short_name(hlo) == "%_unified_forward.56 custom-call"
    cp = ("%copy-start.3 = (u8[1,2]{1,0:S(1)}, u32[]{:S(2)}) "
          "copy-start(u8[1,2]{1,0} %w)")
    assert TRC.short_name(cp) == "%copy-start.3 copy-start"
    assert TRC.kernel_ns(OPS, r"_w4a4_kernel|_w4a8_kernel") == 200.0
    assert TRC.kernel_ns(OPS, r"no_such_kernel") == 0.0


def test_idle_gaps_named_by_the_covering_span():
    gaps = TRC.idle_gaps(OPS, SPANS)
    # gaps: [150,400) 250 ns inside Engine.step; [500,1000) 500 ns, its
    # midpoint 750 inside the generator span
    assert gaps[0] == ("generator", pytest.approx(500e-9))
    assert gaps[1] == ("Engine.step", pytest.approx(250e-9))
