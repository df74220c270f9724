"""On-the-fly activation quantization kernel vs oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("m,k", [(8, 128), (16, 512), (33, 256), (256, 1024)])
@pytest.mark.parametrize("bits", [4, 8])
def test_pallas_matches_oracle(rng, m, k, bits):
    x = jnp.asarray(rng.normal(size=(m, k)) * 3, jnp.float32)
    p_ref, s_ref = ref.act_quant_ref(x, bits=bits)
    p_pal, s_pal = ops.act_quant(x, bits=bits, impl="pallas")
    np.testing.assert_array_equal(np.asarray(p_pal), np.asarray(p_ref))
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref),
                               rtol=1e-6)


def test_roundtrip_error_bound(rng):
    from repro.core import quantizer as Q
    x = jnp.asarray(rng.normal(size=(16, 256)), jnp.float32)
    q, s = Q.quantize_act_groupwise(x, 128, bits=4)
    deq = np.asarray(q, np.float32).reshape(16, 2, 128) * \
        np.asarray(s)[:, :, None]
    err = np.abs(deq.reshape(16, 256) - np.asarray(x))
    bound = np.repeat(np.asarray(s), 128, axis=1) * 0.5 + 1e-6
    assert (err <= bound).all()


@pytest.mark.parametrize("k", [640, 12544, 1792])
@pytest.mark.parametrize("bits", [4, 8])
def test_ragged_k_tail_is_written(rng, k, bits):
    """K not a multiple of 512 (an odd block count at 640): every block,
    including the tail, matches the oracle. 12544 / 1792 are the W4A4 /
    W4A8 splits of LLaMA-3-8B's w_down input (d_ff=14336, fraction
    0.875)."""
    x = jnp.asarray(rng.normal(size=(8, k)) * 3, jnp.float32)
    p_ref, s_ref = ref.act_quant_ref(x, bits=bits)
    p_pal, s_pal = ops.act_quant(x, bits=bits, impl="pallas")
    np.testing.assert_array_equal(np.asarray(p_pal), np.asarray(p_ref))
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref),
                               rtol=1e-6)
