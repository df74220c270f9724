"""KV4 decode attention — flash-decoding over a channel-wise asymmetric
int4 KV cache (COMET §3.2 KV quantization, adapted to TPU).

TPU-native zero-point folding (beyond-paper optimization, recorded in
EXPERIMENTS.md): with *channel-wise asymmetric* int4 KV quantization the
dequantization affine terms fold entirely out of the inner loop:

  scores[g,t] = Σ_d q[g,d]·(n_k[t,d] − z_k[d])·s_k[d]
              = Σ_d (q·s_k)[g,d]·n_k[t,d]  −  Σ_d (q·s_k)[g,d]·z_k[d]
              =      q̃ @ n_kᵀ             −  c[g]          (c: per-head scalar)

  out[g,d]    = Σ_t p[g,t]·(n_v[t,d] − z_v[d])·s_v[d]
              = s_v[d]·(p @ n_v)[g,d] − s_v[d]·z_v[d]       (since Σ_t p = 1)

so the kernel's hot loop touches only the raw nibbles — zero dequant
arithmetic per (t, d) element beyond the nibble unpack (2 VPU ops/byte).
The affine pre/post terms (q̃, c, the s_v/z_v epilogue) are O(D) work done
outside the kernel.

The kernel is a standard online-softmax flash-decode: grid over
(batch·kv_head, T chunks), running max/denominator in VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["kv4_decode_attention"]

NEG_INF = -1e30


def _unpack_nibbles_f32(packed):
    """[bt, D/2] uint8 → [bt, D] f32 nibbles in [0, 15].

    Channel pairs are packed sequentially (2j, 2j+1): unpack with the
    blocked layout along the last axis — lo nibbles are channels [0, D/2),
    hi nibbles [D/2, D) — matching `pack_int4_kv` in ops.py (location
    switch along channels so no element interleave is needed).
    """
    p = packed.astype(jnp.int32)     # Mosaic has no 8-bit shifts/casts
    lo = (p & 0x0F).astype(jnp.float32)
    hi = (p >> 4).astype(jnp.float32)
    return jnp.concatenate([lo, hi], axis=-1)


def _kv4_decode_kernel(
    length_ref,            # scalar prefetch: [B] int32 valid lengths
    qt_ref,                # [1, G, D] f32  — q·s_k/√D (pre-scaled)
    c_ref,                 # [1, G, 1] f32  — zero-point fold Σ q̃·z_k
    kp_ref,                # [1, bt, D/2] uint8
    vp_ref,                # [1, bt, D/2] uint8
    o_ref,                 # [1, G, D] f32 — unnormalized Σ p̃·n_v
    l_ref,                 # [1, G, 1] f32 — softmax denominator
    acc_ref, m_ref, d_ref, # scratch: [G, D], [G, 1], [G, 1]
    *,
    bt: int,
    nt: int,
    hkv: int,
):
    bh = pl.program_id(0)
    ti = pl.program_id(1)
    b = bh // hkv

    @pl.when(ti == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)

    length = length_ref[b]
    chunk_start = ti * bt

    @pl.when(chunk_start < length)
    def _compute():
        qt = qt_ref[0]                                # [G, D]
        c = c_ref[0]                                  # [G, 1]
        nk = _unpack_nibbles_f32(kp_ref[0])           # [bt, D]
        s = jax.lax.dot_general(
            qt, nk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) - c                                          # [G, bt]
        pos = chunk_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[...]                            # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                         # [G, bt]
        nv = _unpack_nibbles_f32(vp_ref[0])            # [bt, D]
        pv = jax.lax.dot_general(
            p, nv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [G, D]
        acc_ref[...] = acc_ref[...] * alpha + pv
        d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(ti == nt - 1)
    def _done():
        o_ref[0] = acc_ref[...]
        l_ref[0] = d_ref[...]


def kv4_decode_attention(
    q: jax.Array,          # [B, Hq, D] — decode-step queries
    k_packed: jax.Array,   # [B, Hkv, T, D/2] uint8 (lo=ch [0,D/2), hi=[D/2,D))
    k_scale: jax.Array,    # [B, Hkv, 1, D] f32
    k_zero: jax.Array,     # [B, Hkv, 1, D] f32
    v_packed: jax.Array,   # [B, Hkv, T, D/2] uint8
    v_scale: jax.Array,    # [B, Hkv, 1, D] f32
    v_zero: jax.Array,     # [B, Hkv, 1, D] f32
    length: jax.Array,     # [B] int32 — valid KV lengths
    *,
    bt: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over the quantized cache. Returns [B, Hq, D] f32."""
    b, hq, d = q.shape
    hkv, t = k_packed.shape[1], k_packed.shape[2]
    g = hq // hkv
    nt = pl.cdiv(t, bt)

    # --- affine pre-fold (outside the kernel, O(B·H·D)) ---
    sm = 1.0 / jnp.sqrt(jnp.float32(d))
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    qt = qg * k_scale * sm                            # [B, Hkv, G, D]
    c = jnp.sum(qt * k_zero, axis=-1, keepdims=True)  # [B, Hkv, G, 1]

    qt2 = qt.reshape(b * hkv, g, d)
    c2 = c.reshape(b * hkv, g, 1)
    kp2 = k_packed.reshape(b * hkv, t, d // 2)
    vp2 = v_packed.reshape(b * hkv, t, d // 2)

    kernel = functools.partial(_kv4_decode_kernel, bt=bt, nt=nt, hkv=hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, nt),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda bh, ti, L: (bh, 0, 0)),
            pl.BlockSpec((1, g, 1), lambda bh, ti, L: (bh, 0, 0)),
            pl.BlockSpec((1, bt, d // 2), lambda bh, ti, L: (bh, ti, 0)),
            pl.BlockSpec((1, bt, d // 2), lambda bh, ti, L: (bh, ti, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, g, d), lambda bh, ti, L: (bh, 0, 0)),
            pl.BlockSpec((1, g, 1), lambda bh, ti, L: (bh, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    acc, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(length.astype(jnp.int32), qt2, c2, kp2, vp2)

    # --- affine post-fold: out = s_v ⊙ (acc / l) − s_v ⊙ z_v ---
    acc = acc.reshape(b, hkv, g, d)
    l = l.reshape(b, hkv, g, 1)
    sv = v_scale                                       # [B, Hkv, 1, D]
    zv = v_zero
    out = sv * (acc / l) - sv * zv
    return out.reshape(b, hq, d)
