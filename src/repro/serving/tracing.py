"""Host spans on the profiler's clock.

``span(name, on)`` is a ``jax.profiler.TraceAnnotation`` when ``on`` and
one shared no-op context otherwise. :class:`Phases` keeps at most one
span open, so the engine marks where each phase of its step begins
without nesting its code in ``with`` blocks: entering a phase ends the
one before it.

The engine's step phases, in the order one unified step runs them (a
forward of a shape bucket this engine has not run before is
``engine.dispatch.new_bucket`` instead of ``engine.dispatch``):

* ``engine.schedule``: deadline expiry, admission, decode-slot
  reservation, speculation planning and the prefill plan;
* ``engine.pack``: the host layout of the forward (destinations, shape
  buckets, work-queue descriptors, block tables, attention counters);
* ``engine.upload``: that layout written into one int32 buffer and its
  one host-to-device copy;
* ``engine.dispatch`` / ``engine.dispatch.new_bucket``: the calls of the
  jitted slicing of that buffer and of the jitted forward, which lower
  and compile a new bucket;
* ``engine.readback``: the logits' copy back to the host, where the host
  waits for the forward;
* ``engine.sample``: host state, the finite guard, sampling,
  verification and the completions with their events.
"""

from __future__ import annotations

import jax

__all__ = ["span", "Phases"]


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL = _Null()


def span(name: str, on: bool):
    """A profiler span named ``name`` when ``on``, else a no-op context."""
    if on:
        return jax.profiler.TraceAnnotation(name)
    return _NULL


class Phases:
    """Consecutive spans: :meth:`enter` ends the open span and opens the
    next; :meth:`end` ends the open one, if any."""

    def __init__(self):
        self._open = None

    def enter(self, name: str):
        self.end()
        self._open = span(name, True)
        self._open.__enter__()

    def end(self):
        if self._open is not None:
            sp, self._open = self._open, None
            sp.__exit__(None, None, None)
