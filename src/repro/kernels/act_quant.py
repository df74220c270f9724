"""On-the-fly activation quantization kernel (the FMPQ runtime step).

Quantizes a float activation tile to packed int4 (biased nibbles, blocked
interleave) or int8, emitting per-(row, 128-block) scales. Fused into a
single pass over the data so the serving path pays one HBM read of the
fp activation and one write of the (4×/2× smaller) quantized payload.

Each grid step owns ``bm`` whole rows, so every block of K — including
a ragged tail past any multiple of a larger tile — is written, and the
scales leave as one full-width ``[bm, K/128]`` tile. Blocks are handled
in pairs: two 128-channel blocks fill one 128-lane packed int4 tile (a
half-width lane rotation moves each block's high half under its low
half). Nibble arithmetic runs in int32; the VPU has no 8-bit shifts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_K = 128
HALF = BLOCK_K // 2
_ROW_BYTES = 1 << 20      # f32 input bytes one grid step may hold

__all__ = ["act_quant_int4", "act_quant_int8", "row_block"]


def row_block(k: int, cap: int = 256) -> int:
    """Rows per grid step: the largest power of two ≤ ``cap`` whose
    [rows, K] f32 input tile stays within ``_ROW_BYTES`` (never below 32,
    the int8/uint8 sublane tile)."""
    rows = cap
    while rows > 32 and rows * k * 4 > _ROW_BYTES:
        rows //= 2
    return rows


def _quant_blocks(x_ref, nblk, qmax, qmin):
    """Yield (block index, int32 codes [bm, 128], scale [bm, 1])."""
    for b in range(nblk):
        xb = x_ref[:, b * BLOCK_K:(b + 1) * BLOCK_K]
        amax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
        scale = jnp.maximum(amax, 1e-8) / qmax
        q = jnp.clip(jnp.round(xb / scale), qmin, qmax).astype(jnp.int32)
        yield b, q, scale


def _write_scales(s_ref, scales):
    col = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    out = jnp.zeros(s_ref.shape, jnp.float32)
    for b, s in enumerate(scales):
        out = jnp.where(col == b, s, out)
    s_ref[...] = out


def _act_quant4_kernel(x_ref, p_ref, s_ref, *, nblk):
    codes, scales = [], []
    for _, q, scale in _quant_blocks(x_ref, nblk, 7.0, -8.0):
        codes.append(q + 8)                           # biased nibbles 0..15
        scales.append(scale)
    lane = jax.lax.broadcasted_iota(jnp.int32, codes[0].shape, 1)
    first = lane < HALF
    for pi in range(0, nblk, 2):
        q0 = codes[pi]
        r0 = pltpu.roll(q0, HALF, 1)                  # half turn
        lo_byte = q0 | (r0 << 4)                      # bytes of block pi
        if pi + 1 < nblk:
            q1 = codes[pi + 1]
            hi_byte = pltpu.roll(q1, HALF, 1) | (q1 << 4)
            tile = jnp.where(first, lo_byte, hi_byte)
            p_ref[:, pi * HALF:(pi + 2) * HALF] = tile.astype(jnp.uint8)
        else:                                         # odd tail block
            p_ref[:, pi * HALF:(pi + 1) * HALF] = (
                lo_byte[:, :HALF].astype(jnp.uint8))
    _write_scales(s_ref, scales)


def _act_quant8_kernel(x_ref, q_ref, s_ref, *, nblk):
    scales = []
    for b, q, scale in _quant_blocks(x_ref, nblk, 127.0, -128.0):
        q_ref[:, b * BLOCK_K:(b + 1) * BLOCK_K] = q.astype(jnp.int8)
        scales.append(scale)
    _write_scales(s_ref, scales)


def _call(kernel, x, payload_cols, payload_dtype, bm, interpret):
    m, k = x.shape
    if k % BLOCK_K:
        raise ValueError(f"K={k} must be a multiple of {BLOCK_K}")
    nblk = k // BLOCK_K
    return pl.pallas_call(
        functools.partial(kernel, nblk=nblk),
        grid=(pl.cdiv(m, bm),),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, payload_cols), lambda i: (i, 0)),
            pl.BlockSpec((bm, nblk), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, payload_cols), payload_dtype),
            jax.ShapeDtypeStruct((m, nblk), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(x)


def act_quant_int4(x: jax.Array, *, bm: int = 256, interpret: bool = False):
    """x: [M, K] float → (packed uint8 [M, K/2], scale f32 [M, K/128])."""
    return _call(_act_quant4_kernel, x, x.shape[1] // 2, jnp.uint8, bm,
                 interpret)


def act_quant_int8(x: jax.Array, *, bm: int = 256, interpret: bool = False):
    """x: [M, K] float → (int8 [M, K], scale f32 [M, K/128])."""
    return _call(_act_quant8_kernel, x, x.shape[1], jnp.int8, bm, interpret)
