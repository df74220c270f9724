"""Faults planted in the timed path, each of which ``correct`` has to
catch. Each takes a built engine, breaks it in place and returns a
function that undoes the break. ``bench/control.py`` reads them at the
cell's own size; ``tests/bench`` at a tiny one.
"""

from __future__ import annotations

import numpy as np

__all__ = ["FAULTS", "state_unchanged", "half_batch", "token_altered"]


def state_unchanged(eng):
    """Every step returns its KV pools unchanged: nothing is ever paged.
    (The pools are donated to the step, so it hands back copies.)"""
    import jax.numpy as jnp
    fwd = eng._fwd

    def broken(*a):
        k0, v0 = jnp.copy(a[4]), jnp.copy(a[5])
        logits, _, _ = fwd(*a)
        return logits, k0, v0
    eng._fwd = broken

    def undo():
        eng._fwd = fwd
    return undo


def half_batch(eng):
    """The second half of each step's rows is left out and served the
    first half's logits."""
    fwd = eng._fwd

    def broken(*a):
        logits, k, v = fwd(*a)
        n = int((np.asarray(a[15]) > 0).sum())      # rows with a chunk
        if n > 1:
            logits = logits.at[n // 2:n].set(logits[: n - n // 2])
        return logits, k, v
    eng._fwd = broken

    def undo():
        eng._fwd = fwd
    return undo


def token_altered(eng):
    """Every third sampled token is replaced where it is produced."""
    sample = eng._sample_batch
    count = {"n": 0}

    def broken(logits, reqs, positions):
        out = sample(logits, reqs, positions)
        for i in range(len(out)):
            count["n"] += 1
            if count["n"] % 3 == 0:
                out[i] = int((out[i] + 1) % logits.shape[-1])
        return out
    eng._sample_batch = broken

    def undo():
        del eng._sample_batch
    return undo


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  token_altered)}
