"""COMET W4Ax mixed-precision GEMM — Pallas TPU kernels (paper §4).

Three schedules are provided:

``w4a4_matmul``      uniform W4A4: packed int4 activations × packed int4
                     weights, per-(row, K-block) × per-(K-block, col)
                     scales, int32 MXU accumulation, f32 epilogue.
``w4a8_matmul``      uniform W4A8: int8 activations × packed int4 weights
                     with the in-kernel fast INT4→INT8 conversion (§4.3).
``w4ax_matmul_mixed``the paper-faithful single mixed kernel: the grid's
                     K dimension walks INT4 blocks then INT8 blocks and
                     switches precision per step (`lax.cond`) — the TPU
                     analogue of issuing INT4/INT8 mma tiles to SMs
                     (Fig. 5b). Used as the §Perf *baseline*; it keeps
                     one 128-channel block per step, which Mosaic does
                     not accept, so it runs in interpret mode only.
``w4ax_matmul_split``the TPU-native optimized schedule (DESIGN.md §2):
                     FMPQ's channel permutation makes INT8 blocks
                     contiguous at the K tail, so the mixed GEMM is two
                     *uniform* sub-GEMMs with no per-step branching —
                     the static-schedule realization of the paper's tile
                     remapping + decomposition (load balance by
                     construction).

Fast INT4→INT8 conversion (§4.3, TPU adaptation)
------------------------------------------------
Nibbles are stored **biased** (+8 → unsigned [0,15]) in the blocked
"location switch" interleave (`pack_int4_interleaved`), so the in-kernel
unpack is exactly two VPU ops — mask and logical shift — i.e. *zero
extension*, never sign extension. The algebra is restored at the int32
accumulation boundary:

    dot(a'+0, w') = dot(a, w) + 8·Σa + 8·Σw + 64·Kb        (a'=a+8, w'=w+8)

so ``dot(a, w) = dot(a', w') − 8·rowsum(a') − 8·colsum(w') + 8192`` for a
128-channel block. The row/col sums are one cheap VPU reduction per tile,
amortized over the [bm,128]×[128,bn] MXU dot — this is the paper's
"fold the correction into the scaling parameters" made additive.

The naive sign-extension path (``conversion="signext"``, arithmetic
shifts, no correction) is retained for the Fig. 10-style ablation.

The uniform kernels walk two blocks per grid step; see the note above
``_w4a4_kernel`` for how the tiles are laid out for Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_K = 128        # quantization block (channels)
PACKED_BLOCK = 64    # bytes per block row-pair (BLOCK_K / 2)

__all__ = [
    "w4a4_matmul",
    "w4a8_matmul",
    "w4ax_matmul_mixed",
    "w4ax_matmul_split",
]


# ---------------------------------------------------------------------------
# In-kernel unpack primitives
# ---------------------------------------------------------------------------

def _unpack_zeroext_rows(packed):
    """[64, bn] packed uint8 → biased int8-valued [128, bn] (values 0..15).

    Two VPU ops (mask, logical shift); the blocked interleave means the
    two nibble panels concatenate in order with no element shuffle.
    """
    lo = (packed & jnp.uint8(0x0F)).astype(jnp.int8)
    hi = (packed >> jnp.uint8(4)).astype(jnp.int8)
    return jnp.concatenate([lo, hi], axis=0)


def _unpack_zeroext_cols(packed):
    """[bm, 64] packed uint8 → biased [bm, 128] (values 0..15)."""
    lo = (packed & jnp.uint8(0x0F)).astype(jnp.int8)
    hi = (packed >> jnp.uint8(4)).astype(jnp.int8)
    return jnp.concatenate([lo, hi], axis=1)


def _unpack_signext_rows(packed):
    """Naive sign-extension unpack (ablation baseline): 3+ ops, no bias."""
    p = packed.astype(jnp.int8)
    lo = jnp.left_shift(p, 4) >> 4          # arithmetic shifts sign-extend
    hi = p >> 4                              # arithmetic on int8
    # stored biased, so convert: biased-nibble arithmetic-shift path needs
    # the bias removed explicitly (extra op vs zeroext+correction)
    lo = (packed & jnp.uint8(0x0F)).astype(jnp.int8) - jnp.int8(8)
    hi = (packed >> jnp.uint8(4)).astype(jnp.int8) - jnp.int8(8)
    return jnp.concatenate([lo, hi], axis=0)


def _int_dot(a, b):
    """int8 × int8 → int32 MXU dot."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )


# ---------------------------------------------------------------------------
# Uniform kernels: one grid step = one 128-lane packed tile = two K blocks
# ---------------------------------------------------------------------------
#
# Mosaic tiles the minor dimension in 128 lanes, so a grid step walks
# STEP_K = 256 channels (two quantization blocks, 128 packed bytes) and
# handles the two blocks inside the kernel:
#
# * weights: rows [64b, 64b+64) of the [128, bn] packed tile hold block b;
#   its nibble panels concatenate along sublanes into the block's
#   [128, bn] biased int4 values (the blocked interleave, unchanged);
# * int4 activations: lanes [64b, 64b+64) of the [bm, 128] packed tile
#   hold block b. A half-width lane rotation puts the other block's bytes
#   under the same lanes, so block b's [bm, 128] operand is one select
#   between the low nibbles of one tile and the high nibbles of the other;
# * scales arrive as whole rows (activation [bm, KB], weight [KB, bn]) and
#   each block picks its own column / row inside the kernel.
#
# Nibble arithmetic runs in int32 (the VPU has no 8-bit shifts); the MXU
# dot takes int8 operands and accumulates in int32, exactly as before.

STEP_K = 2 * BLOCK_K     # channels per grid step
STEP_PACKED = STEP_K // 2


def _nibbles_i32(packed):
    """uint8 → (low, high) nibble planes as int32 (values 0..15)."""
    p = packed.astype(jnp.int32)
    return p & 0x0F, p >> 4


def _weight_block(w_ref, b):
    """Block b of the [128, bn] packed weight tile → biased [128, bn] int32."""
    lo, hi = _nibbles_i32(w_ref[b * PACKED_BLOCK:(b + 1) * PACKED_BLOCK, :])
    return jnp.concatenate([lo, hi], axis=0)


def _act4_blocks(a_ref):
    """[bm, 128] packed int4 activations → the two blocks' biased
    [bm, 128] int32 operands, channel order matching ``_weight_block``."""
    a = a_ref[...].astype(jnp.int32)
    r = pltpu.roll(a, PACKED_BLOCK, 1)        # half turn: either direction
    first = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1) < PACKED_BLOCK
    return (jnp.where(first, a & 0x0F, r >> 4),
            jnp.where(first, r & 0x0F, a >> 4))


def _block_scale(asc_ref, wsc_ref, idx, w_row):
    """Per-block rescale [bm, bn] = a_scale[:, idx] · w_scale[w_row, :]."""
    asc = asc_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, asc.shape, 1)
    sa = jnp.sum(jnp.where(col == idx, asc, 0.0), axis=1, keepdims=True)
    sw = wsc_ref[pl.ds(w_row, 1), :]
    return sa * sw


def _w4a4_kernel(a_ref, asc_ref, w_ref, wsc_ref, o_ref, acc_ref, *,
                 nsteps, k_off, conversion):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for b, a in enumerate(_act4_blocks(a_ref)):   # biased [bm, 128]
        w = _weight_block(w_ref, b)                 # biased [128, bn]
        if conversion == "zeroext":
            d = _int_dot(a.astype(jnp.int8), w.astype(jnp.int8))
            ra = jnp.sum(a, axis=1, keepdims=True)  # Σa' [bm,1]
            cw = jnp.sum(w, axis=0, keepdims=True)  # Σw' [1,bn]
            d = d - 8 * ra - 8 * cw + (8 * 8 * BLOCK_K)
        else:
            d = _int_dot((a - 8).astype(jnp.int8), (w - 8).astype(jnp.int8))
        idx = 2 * ki + b
        scale = _block_scale(asc_ref, wsc_ref, idx, k_off + idx)
        acc_ref[...] += d.astype(jnp.float32) * scale

    @pl.when(ki == nsteps - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def _w4a8_kernel(a_ref, asc_ref, w_ref, wsc_ref, o_ref, acc_ref, *,
                 nsteps, k_off, conversion):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for b in range(2):
        a = a_ref[:, b * BLOCK_K:(b + 1) * BLOCK_K]   # [bm, 128] int8 (true)
        w = _weight_block(w_ref, b)                     # biased [128, bn]
        if conversion == "zeroext":
            d = _int_dot(a, w.astype(jnp.int8))
            ra = jnp.sum(a.astype(jnp.int32), axis=1, keepdims=True)  # Σa
            d = d - 8 * ra
        else:
            d = _int_dot(a, (w - 8).astype(jnp.int8))
        idx = 2 * ki + b
        scale = _block_scale(asc_ref, wsc_ref, idx, k_off + idx)
        acc_ref[...] += d.astype(jnp.float32) * scale

    @pl.when(ki == nsteps - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def _pick_bn(n: int) -> int:
    for c in (512, 256, 128):
        if n % c == 0:
            return c
    return n if n < 128 else 128


def _whole_steps(a, a_scale, w_packed, w_scale, k_off, a_pad_lanes, a_fill):
    """Bring one uniform part to whole 256-channel steps.

    The fast path reads the part's weight rows in place at ``k_off``
    blocks. An odd block count, or an odd offset, slices the part's
    weights out and pads every operand by one block whose activation
    scale is 0, so the pad contributes exactly 0 to the sum."""
    kb = a_scale.shape[1]
    if kb % 2 == 0 and k_off % 2 == 0:
        return a, a_scale, w_packed, w_scale, k_off
    w_packed = w_packed[k_off * PACKED_BLOCK:(k_off + kb) * PACKED_BLOCK]
    w_scale = w_scale[k_off:k_off + kb]
    if kb % 2:
        a = jnp.pad(a, ((0, 0), (0, a_pad_lanes)), constant_values=a_fill)
        a_scale = jnp.pad(a_scale, ((0, 0), (0, 1)))
        w_packed = jnp.pad(w_packed, ((0, PACKED_BLOCK), (0, 0)),
                           constant_values=0x88)
        w_scale = jnp.pad(w_scale, ((0, 1), (0, 0)))
    return a, a_scale, w_packed, w_scale, 0


def _uniform_call(kernel, a, a_scale, w_packed, w_scale, a_block_k, *,
                  k_off, bm, bn, conversion, interpret):
    m = a.shape[0]
    n = w_packed.shape[1]
    kb = a_scale.shape[1]
    nsteps = kb // 2
    bn = _pick_bn(n) if bn is None else bn
    step0 = k_off // 2
    return pl.pallas_call(
        functools.partial(kernel, nsteps=nsteps, k_off=k_off,
                          conversion=conversion),
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn), nsteps),
        in_specs=[
            pl.BlockSpec((bm, a_block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, kb), lambda i, j, k: (i, 0)),
            pl.BlockSpec((STEP_PACKED, bn), lambda i, j, k: (step0 + k, j)),
            pl.BlockSpec((w_scale.shape[0], bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, a_scale, w_packed, w_scale)


def w4a4_matmul(
    a_packed: jax.Array,   # [M, K/2] uint8 (blocked interleave, biased)
    a_scale: jax.Array,    # [M, K/128] f32
    w_packed: jax.Array,   # [>= (k_off + K/128)·64, N] uint8
    w_scale: jax.Array,    # [>= k_off + K/128, N] f32
    *,
    k_off: int = 0,        # first weight block of this part
    bm: int = 128,
    bn: int | None = None,
    conversion: str = "zeroext",
    interpret: bool = False,
) -> jax.Array:
    assert a_packed.shape[1] == a_scale.shape[1] * PACKED_BLOCK
    a_packed, a_scale, w_packed, w_scale, k_off = _whole_steps(
        a_packed, a_scale, w_packed, w_scale, k_off, PACKED_BLOCK, 0x88)
    return _uniform_call(_w4a4_kernel, a_packed, a_scale, w_packed, w_scale,
                         STEP_PACKED, k_off=k_off, bm=bm, bn=bn,
                         conversion=conversion, interpret=interpret)


def w4a8_matmul(
    a_q: jax.Array,        # [M, K] int8
    a_scale: jax.Array,    # [M, K/128] f32
    w_packed: jax.Array,   # [>= (k_off + K/128)·64, N] uint8
    w_scale: jax.Array,    # [>= k_off + K/128, N] f32
    *,
    k_off: int = 0,
    bm: int = 128,
    bn: int | None = None,
    conversion: str = "zeroext",
    interpret: bool = False,
) -> jax.Array:
    assert a_q.shape[1] == a_scale.shape[1] * BLOCK_K
    a_q, a_scale, w_packed, w_scale, k_off = _whole_steps(
        a_q, a_scale, w_packed, w_scale, k_off, BLOCK_K, 0)
    return _uniform_call(_w4a8_kernel, a_q, a_scale, w_packed, w_scale,
                         STEP_K, k_off=k_off, bm=bm, bn=bn,
                         conversion=conversion, interpret=interpret)


# ---------------------------------------------------------------------------
# Paper-faithful mixed kernel: one grid, per-step precision switch
# ---------------------------------------------------------------------------

def _w4ax_mixed_kernel(
    a4_ref, a4s_ref, a8_ref, a8s_ref, w_ref, wsc_ref, o_ref, acc_ref,
    *, nb4, nsteps,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _unpack_zeroext_rows(w_ref[...])            # [128, bn] biased
    cw = jnp.sum(w.astype(jnp.int32), axis=0, keepdims=True)

    def int4_branch(_):
        a = _unpack_zeroext_cols(a4_ref[...])       # biased
        d = _int_dot(a, w)
        ra = jnp.sum(a.astype(jnp.int32), axis=1, keepdims=True)
        d = d - 8 * ra - 8 * cw + (8 * 8 * BLOCK_K)
        return d.astype(jnp.float32) * a4s_ref[...].astype(jnp.float32)

    def int8_branch(_):
        a = a8_ref[...]                             # int8 true values
        d = _int_dot(a, w)
        ra = jnp.sum(a.astype(jnp.int32), axis=1, keepdims=True)
        d = d - 8 * ra
        return d.astype(jnp.float32) * a8s_ref[...].astype(jnp.float32)

    if nb4 == 0:
        contrib = int8_branch(None)
    elif nb4 == nsteps:
        contrib = int4_branch(None)
    else:
        contrib = jax.lax.cond(ki < nb4, int4_branch, int8_branch, None)
    acc_ref[...] += contrib * wsc_ref[...].astype(jnp.float32)

    @pl.when(ki == nsteps - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def w4ax_matmul_mixed(
    a4_packed: jax.Array,  # [M, K4/2] uint8
    a4_scale: jax.Array,   # [M, K4/128]
    a8_q: jax.Array,       # [M, K8] int8
    a8_scale: jax.Array,   # [M, K8/128]
    w_packed: jax.Array,   # [K/2, N] uint8 (K = K4 + K8)
    w_scale: jax.Array,    # [K/128, N]
    *,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Single-kernel mixed W4Ax GEMM (paper-faithful baseline schedule).

    Both activation operands are passed full-size; the K grid walks all
    blocks and each step reads only the operand matching its precision
    (the other ref's index_map is clamped — Pallas still prefetches that
    block but the branch ignores it; this mirrors the paper's naive mixed
    issue where INT4 tiles stall on INT8 neighbours, and is exactly the
    inefficiency the *split* schedule removes).
    """
    m = a4_packed.shape[0]
    n = w_packed.shape[1]
    nb4 = a4_scale.shape[1] if a4_packed.shape[1] else 0
    nb8 = a8_scale.shape[1] if a8_q.shape[1] else 0
    nsteps = nb4 + nb8
    if nsteps == 0:
        raise ValueError("empty GEMM")
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), nsteps)

    # Degenerate uniform cases fall back to the uniform kernels.
    if nb4 == 0:
        return w4a8_matmul(
            a8_q, a8_scale, w_packed, w_scale, bm=bm, bn=bn, interpret=interpret
        )
    if nb8 == 0:
        return w4a4_matmul(
            a4_packed, a4_scale, w_packed, w_scale, bm=bm, bn=bn, interpret=interpret
        )

    kernel = functools.partial(_w4ax_mixed_kernel, nb4=nb4, nsteps=nsteps)

    def a4_map(i, j, k):
        return (i, jnp.minimum(k, nb4 - 1))

    def a4s_map(i, j, k):
        return (i, jnp.minimum(k, nb4 - 1))

    def a8_map(i, j, k):
        return (i, jnp.clip(k - nb4, 0, nb8 - 1))

    def a8s_map(i, j, k):
        return (i, jnp.clip(k - nb4, 0, nb8 - 1))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, PACKED_BLOCK), a4_map),
            pl.BlockSpec((bm, 1), a4s_map),
            pl.BlockSpec((bm, BLOCK_K), a8_map),
            pl.BlockSpec((bm, 1), a8s_map),
            pl.BlockSpec((PACKED_BLOCK, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a4_packed, a4_scale, a8_q, a8_scale, w_packed, w_scale)


# ---------------------------------------------------------------------------
# Optimized split schedule (TPU-native tile remapping, DESIGN.md §2)
# ---------------------------------------------------------------------------

def w4ax_matmul_split(
    a4_packed: jax.Array,
    a4_scale: jax.Array,
    a8_q: jax.Array,
    a8_scale: jax.Array,
    w_packed: jax.Array,
    w_scale: jax.Array,
    *,
    bm: int = 128,
    bn: int | None = None,
    conversion: str = "zeroext",
    interpret: bool = False,
) -> jax.Array:
    """Two uniform sub-GEMMs over the contiguous K4 / K8 channel ranges.

    Load-balanced by construction: every grid step of each sub-kernel
    does identical work, so no "SM" ever waits on a slower-precision
    neighbour — the static realization of the paper's tile remapping +
    Stream-K decomposition (§4.4). Both parts read the one packed weight
    in place: the W4A8 part starts ``nb4`` blocks in.
    """
    nb4 = a4_scale.shape[1] if a4_packed.shape[1] else 0
    out = None
    if nb4 > 0:
        out = w4a4_matmul(
            a4_packed, a4_scale, w_packed, w_scale,
            bm=bm, bn=bn, conversion=conversion, interpret=interpret,
        )
    if a8_q.shape[1] > 0:
        o8 = w4a8_matmul(
            a8_q, a8_scale, w_packed, w_scale, k_off=nb4,
            bm=bm, bn=bn, conversion=conversion, interpret=interpret,
        )
        out = o8 if out is None else out + o8
    if out is None:
        raise ValueError("empty GEMM")
    return out
