"""The engine's step phases in a device trace.

With ``Engine.trace_spans`` set, every unified step marks its phases as
host spans on the profiler's clock (``repro.serving.tracing``), nested
inside the client's ``Engine.step`` span. ``bench/trace.py``'s
``reduce()`` keeps only the client's spans; :func:`host_spans` reads the
engine's phases beside them. :func:`idle_by_span` splits the device's
idle time among the phases, and :func:`clock_check` tells whether the
host spans and the device ops share one clock.

This module reads program output only by span name, so it runs against
an engine that emits no phases (every phase then reads 0 and the idle
time falls outside them).
"""

from __future__ import annotations

import numpy as np

from bench import trace as TRC

__all__ = ["PHASES", "STEP", "host_spans", "idle_by_span", "clock_check"]

STEP = "Engine.step"
# span name → the key of its share
PHASES = {
    "engine.schedule": "schedule",
    "engine.pack": "pack",
    "engine.upload": "upload",
    "engine.dispatch": "dispatch",
    "engine.dispatch.new_bucket": "new_bucket",
    "engine.readback": "readback",
    "engine.sample": "sample",
}
_DISPATCH = ("engine.dispatch", "engine.dispatch.new_bucket")


def host_spans(path: str) -> list:
    """[(name, start_ns, dur_ns)] of the client's spans and the engine's
    phases on the host planes of the trace at ``path``, by start."""
    from jax.profiler import ProfileData
    names = set(TRC.SPAN_NAMES) | set(PHASES)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    spans.append((ev.name, float(ev.start_ns),
                                  float(ev.duration_ns)))
    return sorted(spans, key=lambda s: s[1])


def _busy_until(ops):
    """t → device busy time before t (union of the op intervals)."""
    merged = TRC._union((o[2], o[2] + o[3]) for o in ops)
    if not merged:
        return lambda t: np.zeros_like(np.asarray(t, float))
    starts, ends = np.asarray(merged).T
    cum = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def busy(t):
        t = np.asarray(t, float)
        k = np.searchsorted(starts, t, side="right")   # intervals begun
        tail = np.where(k > 0, np.maximum(ends[k - 1] - t, 0.0), 0.0)
        return cum[k] - tail
    return busy


def idle_by_span(ops, spans, window_ns: float) -> dict:
    """Device idle time over a window of ``window_ns``, split by what the
    host was doing, in percent of the window: one key per phase
    (:data:`PHASES`), ``unphased`` for idle time inside an ``Engine.step``
    span that no phase covers, and ``outside_step`` for the rest. The idle
    time is the window less the union of ``ops`` (as
    ``device_idle_share`` takes it), so the keys sum to that share."""
    busy = _busy_until(ops)

    def idle(names):
        iv = np.asarray([(s, s + d) for n, s, d in spans if n in names],
                        float).reshape(-1, 2)
        return float(np.sum(iv[:, 1] - iv[:, 0])
                     - np.sum(busy(iv[:, 1]) - busy(iv[:, 0])))

    out = {key: idle((name,)) for name, key in PHASES.items()}
    in_steps = idle((STEP,))
    out["unphased"] = in_steps - sum(out.values())
    out["outside_step"] = window_ns - TRC.busy_ns(ops) - in_steps
    return {k: 100.0 * v / window_ns for k, v in out.items()}


def clock_check(ops, spans) -> tuple:
    """(aligned, steps): of the steps that dispatched a forward, those
    whose device ops fall inside the host's view of the forward. A
    step's ops are those starting after the previous step's readback
    ended and up to its own readback's end; it is aligned when it has
    some and the first starts no earlier than its dispatch span and the
    last ends no later than its readback span."""
    if not ops:
        return 0, 0
    ops = sorted(ops, key=lambda o: o[2])
    starts = np.asarray([o[2] for o in ops])
    ends = np.asarray([o[2] + o[3] for o in ops])
    aligned = steps = 0
    prev_end, dispatch = -np.inf, None
    for name, s, d in spans:
        if name in _DISPATCH:
            dispatch = s
        elif name == "engine.readback" and dispatch is not None:
            lo = np.searchsorted(starts, prev_end, side="right")
            hi = np.searchsorted(starts, s + d, side="right")
            steps += 1
            aligned += bool(hi > lo and starts[lo] >= dispatch
                            and ends[lo:hi].max() <= s + d)
            prev_end, dispatch = s + d, None
    return aligned, steps
