"""The engine's compiled forward programs, kept inside the checkout so
that a later run loads them instead of tracing and lowering them again.

The engine jits its unified forward once per shape bucket, and every
process traces and lowers each bucket anew: about 3 s per unrolled layer
per bucket at the configurations' widths, which the persistent
compilation cache does not save (it skips only the XLA compile), while a
chat run meets 50 to 100 buckets. :class:`Programs` puts itself in front
of the engine's jitted forward (``Engine._fwd``): the first call of a
bucket in a checkout lowers and compiles it on the engine's own jit and
writes the executable to ``<checkout>/.bench_programs/<cell>/``; every
later call, in this run or a later one, runs that executable. The engine's
host code and its compiled programs are unchanged; only their tracing is
not repeated. ``Engine.trace_count`` still counts every bucket lowered,
so a bucket first met inside the measured window shows there.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
from pathlib import Path

__all__ = ["Programs", "key_of"]


def _static(a) -> bool:
    return isinstance(a, (bool, int, float, str))


def key_of(args) -> tuple:
    """A forward call's bucket: its static arguments' values and the shape
    and dtype of every array it is given."""
    import jax
    statics = tuple(a for a in args if _static(a))
    leaves = jax.tree_util.tree_leaves([a for a in args if not _static(a)])
    return statics, tuple((tuple(x.shape), str(x.dtype)) for x in leaves)


class Programs:
    """Loads the checkout's executables for the engine's forward and puts
    itself in front of it. ``lowered`` counts the buckets this run had
    to lower."""

    def __init__(self, eng, directory: Path):
        self.jit, self.dir = eng._fwd, Path(directory)
        self.exes: dict = {}
        self.lowered = 0
        for path in sorted(self.dir.glob("*.bin")):
            self._load(path)
        eng._fwd = self.__call__

    def __call__(self, *args):
        key = key_of(args)
        exe = self.exes.get(key)
        if exe is None:
            exe = self._lower(key, args)
        if exe is False:                 # this bucket cannot be serialized
            return self.jit(*args)
        return exe(*[a for a in args if not _static(a)])

    def _path(self, key) -> Path:
        digest = hashlib.sha256(json.dumps(key).encode()).hexdigest()
        return self.dir / f"{digest[:32]}.bin"

    def _load(self, path: Path):
        from jax.experimental import serialize_executable as SE
        try:
            key, payload, in_tree, out_tree = pickle.loads(path.read_bytes())
            self.exes[key] = SE.deserialize_and_load(
                payload, in_tree, out_tree)
        except Exception:  # noqa: BLE001 — an unreadable file is relowered
            path.unlink(missing_ok=True)

    def _lower(self, key, args):
        from jax.experimental import serialize_executable as SE
        self.lowered += 1
        exe = self.jit.lower(*args).compile()
        try:
            payload, in_tree, out_tree = SE.serialize(exe)
        except (ValueError, NotImplementedError):
            self.exes[key] = False
            return False
        self.dir.mkdir(parents=True, exist_ok=True)
        path = self._path(key)
        tmp = path.with_suffix(".tmp")
        tmp.write_bytes(pickle.dumps((key, payload, in_tree, out_tree)))
        os.replace(tmp, path)
        self.exes[key] = exe
        return exe
