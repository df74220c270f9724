"""Property tests for the §4.3 zero-extension correction algebra.

Per 128-channel block with biased nibbles a' = a+8, w' = w+8:

    dot(a, w) = dot(a', w') − 8·Σa' − 8·Σw' + 8·8·128      (+8192)

Randomized over shapes and nb4/nb8 splits via hypothesis (or the
hermetic fixed-seed stub when hypothesis isn't installed).
"""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:            # hermetic env — fixed-seed sampled fallback
    from _hypothesis_stub import given, settings, strategies as st

from repro.core import quantizer as Q
from repro.kernels import ref
from repro.kernels import w4ax_matmul as WK

settings.register_profile("ci", max_examples=20, deadline=None)
settings.load_profile("ci")

BK = WK.BLOCK_K


@given(st.integers(1, 16), st.integers(1, 32), st.integers(0, 2**31 - 1))
def test_zeroext_block_identity(m, n, seed):
    """The raw integer identity the kernels rely on, one 128-block."""
    rng = np.random.default_rng(seed)
    a = rng.integers(-8, 8, size=(m, BK)).astype(np.int32)
    w = rng.integers(-8, 8, size=(BK, n)).astype(np.int32)
    ab, wb = a + 8, w + 8                      # biased, as stored
    corrected = (ab @ wb
                 - 8 * ab.sum(axis=1, keepdims=True)
                 - 8 * wb.sum(axis=0, keepdims=True)
                 + 8 * 8 * BK)
    np.testing.assert_array_equal(corrected, a @ w)
    assert 8 * 8 * BK == 8192                  # the constant in the docs


@given(st.integers(1, 8), st.integers(0, 3), st.integers(0, 3),
       st.integers(1, 3), st.integers(0, 2**31 - 1))
def test_zeroext_ref_gemm_random_splits(m, nb4, nb8, nblk_n, seed):
    """w4ax ref GEMM over random nb4/nb8 splits == exact fp math on the
    dequantized operands (the correction algebra is exact, not approx)."""
    if nb4 + nb8 == 0:
        nb4 = 1
    rng = np.random.default_rng(seed)
    k4, k8, n = nb4 * BK, nb8 * BK, nblk_n * 64
    if k4:
        a4i = rng.integers(-8, 8, size=(m, k4)).astype(np.int8)
        s4 = rng.uniform(0.01, 0.1, size=(m, nb4)).astype(np.float32)
        a4 = Q.pack_int4_interleaved(jnp.asarray(a4i), axis=1, block_size=BK)
    else:
        a4i = np.zeros((m, 0), np.int8)
        a4 = jnp.zeros((m, 0), jnp.uint8)
        s4 = np.zeros((m, 0), np.float32)
    if k8:
        a8 = rng.integers(-128, 128, size=(m, k8)).astype(np.int8)
        s8 = rng.uniform(0.01, 0.1, size=(m, nb8)).astype(np.float32)
    else:
        a8 = np.zeros((m, 0), np.int8)
        s8 = np.zeros((m, 0), np.float32)
    wi = rng.integers(-8, 8, size=(k4 + k8, n)).astype(np.int8)
    ws = rng.uniform(0.01, 0.1, size=(nb4 + nb8, n)).astype(np.float32)
    wp = Q.pack_int4_interleaved(jnp.asarray(wi), axis=0, block_size=BK)

    out = np.asarray(ref.w4ax_matmul_ref(
        a4, jnp.asarray(s4), jnp.asarray(a8), jnp.asarray(s8),
        wp[: k4 // 2], jnp.asarray(ws[:nb4]),
        wp[k4 // 2:], jnp.asarray(ws[nb4:])))

    ad = np.concatenate(
        [a4i.reshape(m, -1, BK) * s4[:, :, None],
         a8.reshape(m, -1, BK) * s8[:, :, None]] if k4 and k8 else
        ([a4i.reshape(m, -1, BK) * s4[:, :, None]] if k4 else
         [a8.reshape(m, -1, BK) * s8[:, :, None]]), axis=1).reshape(m, -1)
    wd = (wi.reshape(-1, BK, n) * ws[:, None, :]).reshape(-1, n)
    # f64 reference: an f32 numpy matmul's own rounding under
    # cancellation (~3e-5 seen) would exceed the tolerance by itself
    exact = ad.astype(np.float64) @ wd.astype(np.float64)
    np.testing.assert_allclose(out, exact, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("nb4,nb8", [(1, 0), (0, 1), (2, 1), (1, 2)])
def test_zeroext_kernel_matches_signext(rng, nb4, nb8):
    """Pallas split schedule: corrected zero-extension == explicit
    sign-extension unpack, across nb4/nb8 splits (interpret mode)."""
    m, n = 16, 128
    k4, k8 = nb4 * BK, nb8 * BK
    x = rng.normal(size=(m, k4 + k8)).astype(np.float32)
    w = (rng.normal(size=(k4 + k8, n)) * 0.05).astype(np.float32)
    if k4:
        q4, s4 = Q.quantize_act_groupwise(jnp.asarray(x[:, :k4]), BK, bits=4)
        a4 = Q.pack_int4_interleaved(q4, axis=1, block_size=BK)
    else:
        a4 = jnp.zeros((m, 0), jnp.uint8)
        s4 = jnp.zeros((m, 0), jnp.float32)
    if k8:
        a8, s8 = Q.quantize_act_groupwise(jnp.asarray(x[:, k4:]), BK, bits=8)
    else:
        a8 = jnp.zeros((m, 0), jnp.int8)
        s8 = jnp.zeros((m, 0), jnp.float32)
    wq = Q.quantize_weight_int4(jnp.asarray(w), group_size=BK)
    outs = {
        conv: np.asarray(WK.w4ax_matmul_split(
            a4, s4, a8, s8, wq.data, wq.scale,
            conversion=conv, interpret=True))
        for conv in ("zeroext", "signext")
    }
    np.testing.assert_allclose(outs["zeroext"], outs["signext"],
                               rtol=1e-5, atol=1e-5)
