"""The checkout's forward programs: a bucket is lowered once, written,
and run from the file by a later engine."""

import types

import jax
import jax.numpy as jnp
import numpy as np

from bench.programs import Programs, key_of


def _engine():
    def fwd(n, x, y):
        return x * n + y["w"].sum()
    counter = {"traces": 0}

    def traced(n, x, y):
        counter["traces"] += 1
        return fwd(n, x, y)
    eng = types.SimpleNamespace(_fwd=jax.jit(traced, static_argnames="n"))
    return eng, counter


def test_a_bucket_is_lowered_once_and_loaded_later(tmp_path):
    x, y = jnp.arange(4.0), {"w": jnp.ones((2, 3))}
    eng, counter = _engine()
    progs = Programs(eng, tmp_path)
    np.testing.assert_allclose(eng._fwd(3, x, y), x * 3 + 6.0)
    eng._fwd(3, x + 1, y)                           # same bucket
    assert progs.lowered == 1 and counter["traces"] == 1
    assert len(list(tmp_path.glob("*.bin"))) == 1

    eng2, counter2 = _engine()
    progs2 = Programs(eng2, tmp_path)
    assert len(progs2.exes) == 1
    np.testing.assert_allclose(eng2._fwd(3, x, y), x * 3 + 6.0)
    assert progs2.lowered == 0 and counter2["traces"] == 0
    eng2._fwd(2, x, y)                              # a new static value
    eng2._fwd(3, jnp.arange(8.0), y)                # a new shape
    assert progs2.lowered == 2


def test_key_of_names_statics_shapes_and_dtypes():
    k = key_of((3, True, "wq", {"a": jnp.zeros((2, 5), jnp.int32)},
                jnp.zeros((7,), jnp.float32)))
    assert k == ((3, True, "wq"), (((2, 5), "int32"), ((7,), "float32")))


def test_an_unreadable_file_is_dropped(tmp_path):
    (tmp_path / "junk.bin").write_bytes(b"not a program")
    eng, _ = _engine()
    assert Programs(eng, tmp_path).exes == {}
    assert not (tmp_path / "junk.bin").exists()
