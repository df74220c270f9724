"""Reduction of a JAX profiler trace to device busy time, per-op time and
idle gaps named by what the client was doing.

``reduce(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
and returns::

    {"devices": {device: [(name, start_ns, dur_ns), ...]},   # XLA ops
     "spans": [(name, start_ns, dur_ns), ...]}               # host spans

Device planes are those named ``/device:TPU:<n>``; their ops are the
events of the plane's ``XLA Ops`` line. Host spans are the events of the
host plane whose names are the client's own ``TraceAnnotation`` names.
Everything after that is arithmetic on those lists, kept here so every
run reduces a trace the same way.
"""

from __future__ import annotations

import glob
import os
import re

__all__ = ["SPAN_NAMES", "find_xplane", "reduce", "busy_ns",
           "op_totals", "idle_gaps", "kernel_ns"]

SPAN_NAMES = ("generator", "Engine.submit", "Engine.step", "event drain")
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stat(ev, key):
    try:
        for k, v in ev.stats:
            if k == key:
                return v
    except Exception:  # noqa: BLE001 — stats are optional per event
        return None
    return None


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    name = _stat(ev, "long_name") or ev.name
                    ops.append((str(ev.name), str(name), float(ev.start_ns),
                                float(ev.duration_ns)))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPAN_NAMES:
                        spans.append((ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(ops) -> float:
    """Time in which at least one op ran on the device."""
    return sum(e - s for s, e in _union((o[2], o[2] + o[3]) for o in ops))


_SHORT = re.compile(r"^(\S+) = .*?\s([a-z][\w-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` → ``%fusion.3 fusion``: a
    TPU op's trace name is its whole HLO instruction."""
    m = _SHORT.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def op_totals(ops) -> dict:
    """Summed device time per op, by short name."""
    out: dict = {}
    for name, _, _, dur in ops:
        key = short_name(name)
        out[key] = out.get(key, 0.0) + dur
    return out


def kernel_ns(ops, pattern: str) -> float:
    """Summed device time of the ops whose name or long name matches."""
    rx = re.compile(pattern)
    return sum(o[3] for o in ops if rx.search(o[0]) or rx.search(o[1]))


def idle_gaps(ops, spans, top: int = 10):
    """The longest gaps between device ops, each named by the client span
    that covers its midpoint (``idle`` where none does)."""
    merged = _union((o[2], o[2] + o[3]) for o in ops)
    gaps = [(merged[i][1], merged[i + 1][0])
            for i in range(len(merged) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = 0.5 * (s + e)
        name = "idle"
        for sn, ss, sd in spans:
            if ss <= mid <= ss + sd:
                name = sn
                if sn != "Engine.step":
                    break
        out.append((name, (e - s) * 1e-9))
    return out
