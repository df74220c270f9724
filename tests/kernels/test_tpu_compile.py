"""Ahead-of-time compiles of the serving hot path for a TPU v5e.

Interpret mode cannot see what Mosaic refuses: block shapes off the
(8, 128) tiling, 8-bit vector ops, scoped-VMEM overruns. These tests
compile every hot-path kernel at LLaMA-3-8B widths (d_model 4096,
Hq 32 / Hkv 8, head_dim 128, d_ff 14336) for a described v5e chip and a
two-layer full-width unified engine step, through the same ``ops``
wrappers the engine calls. Nothing runs; a passing compile says the
chip's compiler accepts the program, not that its results are right.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and test collection
must be identical in every worker.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.llama3_8b import CONFIG as LLAMA3_8B
from repro.kernels import ops

D_MODEL, D_FF, HQ, HKV, HEAD_DIM = (
    LLAMA3_8B.d_model, LLAMA3_8B.d_ff, LLAMA3_8B.num_heads,
    LLAMA3_8B.num_kv_heads, LLAMA3_8B.head_dim)
PAGES, PAGE_SIZE = 64, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # AOT executables cannot be read back from the persistent cache
    # without a chip; keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def compiled_pallas(monkeypatch):
    """Make the ``ops`` wrappers pick compiled Pallas as on a TPU (the
    CPU backend would otherwise select the ref path or interpret)."""
    monkeypatch.setattr(ops, "_resolve", lambda impl: (True, False))


def _specs(sharding):
    """shape, dtype → an abstract array placed on the described chip."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _split(k, frac=0.875):
    nb = k // 128
    nb4 = int(round(frac * nb))
    return nb4 * 128, (nb - nb4) * 128


@pytest.mark.parametrize("m", [8, 512])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_MODEL),      # wq / wo
                                 (D_MODEL, HKV * HEAD_DIM),  # wk / wv
                                 (D_MODEL, D_FF),         # w_up / w_gate
                                 (D_FF, D_MODEL)])        # w_down
def test_w4ax_split_gemm_compiles(one_chip, compiled_pallas, m, k, n):
    k4, k8 = _split(k)
    s = _specs(one_chip)
    compiled = _compile(
        lambda a4, s4, a8, s8, w, ws: ops.w4ax_matmul(a4, s4, a8, s8, w, ws),
        s((m, k4 // 2), jnp.uint8), s((m, k4 // 128), jnp.float32),
        s((m, k8), jnp.int8), s((m, k8 // 128), jnp.float32),
        s((k // 2, n), jnp.uint8), s((k // 128, n), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", [8, 512])
@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("k", [D_MODEL, D_FF])
def test_act_quant_compiles(one_chip, compiled_pallas, m, bits, k):
    k4, k8 = _split(k)
    kk = k4 if bits == 4 else k8
    s = _specs(one_chip)
    compiled = _compile(lambda x: ops.act_quant(x, bits=bits),
                        s((m, kk), jnp.bfloat16))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b,c,w", [(8, 1, 256), (4, 64, 512)])
def test_wq_prefill_attention_compiles(one_chip, compiled_pallas, b, c, w):
    """The unified step's attention: qlen-1 decode rows (c=1) and ragged
    prompt chunks (c=64) over int4 history, split-KV combine included."""
    s = _specs(one_chip)
    pool = s((PAGES, HKV, PAGE_SIZE, HEAD_DIM // 2), jnp.uint8)
    stat = s((HKV, 1, HEAD_DIM), jnp.float32)
    compiled = _compile(
        ops.paged_kv4_prefill_attention_wq,
        s((b, c, HQ, HEAD_DIM), jnp.float32),
        s((b, c, HKV, HEAD_DIM), jnp.float32),
        s((b, c, HKV, HEAD_DIM), jnp.float32),
        pool, stat, stat, pool, stat, stat, s((w, 4), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_wq_decode_attention_compiles(one_chip, compiled_pallas):
    s = _specs(one_chip)
    pool = s((PAGES, HKV, PAGE_SIZE, HEAD_DIM // 2), jnp.uint8)
    stat = s((HKV, 1, HEAD_DIM), jnp.float32)
    compiled = _compile(
        ops.paged_kv4_decode_attention_wq,
        s((8, HQ, HEAD_DIM), jnp.float32), pool, stat, stat, pool, stat,
        stat, s((256, 4), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_unified_step_compiles(one_chip, compiled_pallas):
    """One whole unified engine step at full 8B width, depth cut to two
    layers, with params shaped by ``jax.eval_shape`` of the packed-param
    constructor — the program ``chip_smoke.py`` runs, minus 30 layers."""
    from repro.models.lm import LM, QuantConfig
    from repro.serving.engine import Engine, EngineConfig

    cfg = dataclasses.replace(LLAMA3_8B, num_layers=2)
    quant = QuantConfig()
    shapes = jax.eval_shape(
        lambda k: LM(cfg, quant=quant).init_quantized(k)[0],
        jax.random.PRNGKey(0))
    eng = Engine(cfg, jax.tree.map(lambda x: None, shapes), quant,
                 EngineConfig(num_pages=PAGES, page_size=PAGE_SIZE,
                              max_batch=8))
    s = _specs(one_chip)
    params = jax.tree.map(lambda x: s(x.shape, x.dtype), shapes)
    pool = s(eng.cache.k_pool.shape, jnp.uint8)
    stat = s((HKV, 1, HEAD_DIM), jnp.float32)
    i32 = lambda *shape: s(shape, jnp.int32)
    tb, nb, cb, wb = 8, 8, 1, 256
    lowered = eng._fwd.lower(
        cb, False, "work_queue", params, pool, pool,
        i32(tb), i32(tb), i32(tb), i32(tb), i32(tb), i32(tb), i32(tb),
        i32(nb, 1), i32(nb), i32(nb), i32(nb), i32(wb, 4),
        stat, stat, stat, stat)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 * 7
    mem = compiled.memory_analysis()
    assert mem is not None
