"""Paged KV4 attention (COMET §5 serving path) under two grid
schedules: the dense per-sequence page walk and the Stream-K
**work-queue** schedule with a split-KV combine.

All kernels are gather-free: instead of materializing each sequence's
packed KV contiguously before the kernel (a per-token O(context) copy),
they consume the *physical page pools* directly, with scalar-prefetched
indirection resolved in each BlockSpec index_map before the DMA is
issued — the vLLM/QServe dataflow on TPU.

**Dense schedule** (``paged_kv4_decode_attention`` /
``paged_kv4_prefill_attention``): grid ``(B·Hkv, max_npages)`` — one
lane per output row, walked page-by-page with online softmax in VMEM
scratch. Pages past a sequence's length are skipped (``pl.when``), so
the *compute* is O(real pages), but the grid itself is the padded
rectangle: every short row in a ragged batch still steps through
``max_npages`` iterations, and one long-context row serializes its
whole history on a single lane while other lanes idle — exactly the SM
under-utilization COMET §4.4 / Fig. 8 attacks with tile decomposition.

**Work-queue schedule** (``paged_kv4_decode_attention_wq`` /
``paged_kv4_prefill_attention_wq``): the TPU analogue of Fig. 8e's
divisible tile pool (Stream-K one-to-many binding + FlashDecoding
split-KV). The host flattens the batch into a descriptor array
``[W, 4]`` of ``(row, phys_page, count, kind)`` items covering only
*real* pages (``serving.kv_cache.build_work_queue``), and the kernel
grid is ``(W,)`` — grid size ≈ Σ pages, not ``B × max_npages``. Each
grid step processes ONE page (or one in-flight fp chunk) for ONE
``(seq, kv_head)`` row and emits a partial flash triple ``(acc, l, m)``
— a local softmax numerator, denominator, and running max. No
cross-step state: a long row's pages land on *different* grid steps
(they parallelize across cores instead of serializing), and short rows
contribute exactly their real pages (no padding iterations). A
log-sum-exp **split-KV combine** (``combine_work_partials``, a segment
reduce over the descriptor's row ids) then merges partials:

    M_r = max_i m_i,   w_i = exp(m_i − M_r)
    out_r = (Σ_i w_i · acc_i) / (Σ_i w_i · l_i)

which is the dense online-softmax result, reassociated — so the two
schedules are numerically equivalent up to float reassociation.
Work-item padding (to a power of two) carries ``count = 0`` and a
sentinel row: its partial has ``m = NEG_INF``, so its combine weight
underflows to exactly 0 and the scatter drops the sentinel segment.

Quantization algebra is shared by both schedules: channel-wise
asymmetric int4 with the TPU-native zero-point fold — the hot loop
touches only raw nibbles (mask + shift). For decode all affine terms
are O(D) pre/post work outside the kernel; prefill mixes int4 history
with fp chunk values, so the V affine is applied per history item
in-kernel (``p@n_v ⊙ s_v − (Σp)·s_v⊙z_v`` — the matmul still runs on
raw nibbles; the affine is linear in ``p``, so it commutes with the
combine).

Layout: pools are ``[num_pages, Hkv, page_size, D/2]`` uint8, so one
grid step's block — one page of one kv head — is a whole ``[ps, D/2]``
tile (Mosaic needs a block's two minor dimensions to be tile-aligned or
whole); dense block tables are ``[B, max_pages]`` int32 with
unmapped entries clamped to 0 (masked by length in-kernel, never read
semantically); work-queue descriptors address physical pages directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.kv4_attention import NEG_INF, _unpack_nibbles_f32

__all__ = [
    "paged_kv4_decode_attention",
    "paged_kv4_prefill_attention",
    "paged_kv4_decode_attention_wq",
    "paged_kv4_prefill_attention_wq",
    "combine_work_partials",
]


def combine_work_partials(acc: jax.Array, l: jax.Array, m: jax.Array,
                          rows: jax.Array, num_rows: int) -> jax.Array:
    """Split-KV log-sum-exp combine: merge per-work-item flash partials.

    acc ``[W, R, D]`` partial numerators (value space), l/m ``[W, R, 1]``
    partial denominators / local maxima, rows ``[W]`` segment ids (ids
    ≥ ``num_rows`` are padding — the scatter drops them). Returns the
    normalized ``[num_rows, R, D]`` attention output; rows with no items
    come back 0 (finite — callers mask padding rows anyway).
    """
    rows = rows.astype(jnp.int32)
    mmax = jax.ops.segment_max(m, rows, num_segments=num_rows)
    # rows with no work items get segment_max's -inf identity; clamp to
    # the finite NEG_INF so fully-masked partials (m == NEG_INF) weight
    # as exp(0) · dropped instead of exp(+inf)
    mmax = jnp.maximum(mmax, NEG_INF)
    w = jnp.exp(m - mmax[jnp.minimum(rows, num_rows - 1)])
    num = jax.ops.segment_sum(acc * w, rows, num_segments=num_rows)
    den = jax.ops.segment_sum(l * w, rows, num_segments=num_rows)
    return num / jnp.maximum(den, 1e-30)


def _paged_kv4_decode_kernel(
    tbl_ref,               # scalar prefetch: [B, NP] int32 physical page ids
    len_ref,               # scalar prefetch: [B] int32 valid lengths
    qt_ref,                # [1, G, D] f32  — q·s_k/√D (pre-scaled)
    c_ref,                 # [1, G, 1] f32  — zero-point fold Σ q̃·z_k
    kp_ref,                # [1, 1, ps, D/2] uint8 — one K page
    vp_ref,                # [1, 1, ps, D/2] uint8 — one V page
    o_ref,                 # [1, G, D] f32 — unnormalized Σ p̃·n_v
    l_ref,                 # [1, G, 1] f32 — softmax denominator
    acc_ref, m_ref, d_ref, # scratch: [G, D], [G, 1], [G, 1]
    *,
    ps: int,
    npages: int,
    hkv: int,
):
    bh = pl.program_id(0)
    pi = pl.program_id(1)
    b = bh // hkv

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)

    length = len_ref[b]
    chunk_start = pi * ps

    @pl.when(chunk_start < length)
    def _compute():
        qt = qt_ref[0]                                 # [G, D]
        c = c_ref[0]                                   # [G, 1]
        nk = _unpack_nibbles_f32(kp_ref[0, 0])         # [ps, D]
        s = jax.lax.dot_general(
            qt, nk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) - c                                          # [G, ps]
        pos = chunk_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[...]                            # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                         # [G, ps]
        nv = _unpack_nibbles_f32(vp_ref[0, 0])         # [ps, D]
        pv = jax.lax.dot_general(
            p, nv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [G, D]
        acc_ref[...] = acc_ref[...] * alpha + pv
        d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(pi == npages - 1)
    def _done():
        o_ref[0] = acc_ref[...]
        l_ref[0] = d_ref[...]


def paged_kv4_decode_attention(
    q: jax.Array,             # [B, Hq, D] — decode-step queries
    k_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical K pages
    k_scale: jax.Array,       # [Hkv, 1, D] (or [B, Hkv, 1, D]) f32
    k_zero: jax.Array,        # [Hkv, 1, D] f32
    v_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical V pages
    v_scale: jax.Array,       # [Hkv, 1, D] f32
    v_zero: jax.Array,        # [Hkv, 1, D] f32
    block_tables: jax.Array,  # [B, NP] int32 physical page per logical page
    length: jax.Array,        # [B] int32 — valid KV lengths (≤ NP·ps)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Flash-decode straight off the paged pools. Returns [B, Hq, D] f32."""
    b, hq, d = q.shape
    hkv, ps = k_pool.shape[1], k_pool.shape[2]
    g = hq // hkv
    npages = block_tables.shape[1]
    tables = jnp.maximum(block_tables.astype(jnp.int32), 0)

    def bcast(s):
        return jnp.broadcast_to(s, (b, hkv, 1, d))

    # --- affine pre-fold (outside the kernel, O(B·H·D)) ---
    sm = 1.0 / jnp.sqrt(jnp.float32(d))
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    qt = qg * bcast(k_scale) * sm                      # [B, Hkv, G, D]
    c = jnp.sum(qt * bcast(k_zero), axis=-1, keepdims=True)

    qt2 = qt.reshape(b * hkv, g, d)
    c2 = c.reshape(b * hkv, g, 1)

    kernel = functools.partial(
        _paged_kv4_decode_kernel, ps=ps, npages=npages, hkv=hkv)

    def page_map(bh, pi, tbl, lens):
        return (tbl[bh // hkv, pi], bh % hkv, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * hkv, npages),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda bh, pi, tbl, lens: (bh, 0, 0)),
            pl.BlockSpec((1, g, 1), lambda bh, pi, tbl, lens: (bh, 0, 0)),
            pl.BlockSpec((1, 1, ps, d // 2), page_map),
            pl.BlockSpec((1, 1, ps, d // 2), page_map),
        ],
        out_specs=[
            pl.BlockSpec((1, g, d), lambda bh, pi, tbl, lens: (bh, 0, 0)),
            pl.BlockSpec((1, g, 1), lambda bh, pi, tbl, lens: (bh, 0, 0)),
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
    )(tables, length.astype(jnp.int32), qt2, c2, k_pool, v_pool)

    # --- affine post-fold: out = s_v ⊙ (acc / l) − s_v ⊙ z_v ---
    acc = acc.reshape(b, hkv, g, d)
    l = l.reshape(b, hkv, g, 1)
    sv = bcast(v_scale)
    zv = bcast(v_zero)
    out = sv * (acc / l) - sv * zv
    return out.reshape(b, hq, d)


# ---------------------------------------------------------------------------
# Chunked ragged prefill
# ---------------------------------------------------------------------------

def _paged_kv4_prefill_kernel(
    tbl_ref,               # scalar prefetch: [B, NP] int32 physical page ids
    ctx_ref,               # scalar prefetch: [B] int32 paged-history lengths
    qlen_ref,              # scalar prefetch: [B] int32 valid chunk tokens
    qt_ref,                # [1, CG, D] f32 — q·s_k/√D (history pre-fold)
    c_ref,                 # [1, CG, 1] f32 — zero-point fold Σ q̃·z_k
    qs_ref,                # [1, CG, D] f32 — q/√D (raw, for the fp chunk)
    kn_ref,                # [1, C, D] f32 — in-flight fp chunk keys
    vn_ref,                # [1, C, D] f32 — in-flight fp chunk values
    vs_ref,                # [1, 1, D] f32 — v_scale (history V dequant)
    vz_ref,                # [1, 1, D] f32 — v_zero
    kp_ref,                # [1, 1, ps, D/2] uint8 — one K history page
    vp_ref,                # [1, 1, ps, D/2] uint8 — one V history page
    o_ref,                 # [1, CG, D] f32 — unnormalized output
    l_ref,                 # [1, CG, 1] f32 — softmax denominator
    acc_ref, m_ref, d_ref, # scratch: [CG, D], [CG, 1], [CG, 1]
    *,
    ps: int,
    npages: int,
    hkv: int,
    g: int,
):
    bh = pl.program_id(0)
    pi = pl.program_id(1)
    b = bh // hkv

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)

    ctx = ctx_ref[b]
    qlen = qlen_ref[b]

    def online_update(s, pv_fn):
        """Shared online-softmax step; pv_fn(p) → [CG, D] value partial."""
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        pv = pv_fn(p)
        acc_ref[...] = acc_ref[...] * alpha + pv
        d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new

    # --- int4 history pages: all chunk queries see all valid history ---
    @pl.when((pi < npages) & (pi * ps < ctx))
    def _history():
        qt = qt_ref[0]                                 # [CG, D]
        cc = c_ref[0]                                  # [CG, 1]
        nk = _unpack_nibbles_f32(kp_ref[0, 0])         # [ps, D]
        s = jax.lax.dot_general(
            qt, nk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) - cc                                         # [CG, ps]
        pos = pi * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < ctx, s, NEG_INF)

        def vals(p):
            nv = _unpack_nibbles_f32(vp_ref[0, 0])
            pv = jax.lax.dot_general(
                p, nv, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                          # [CG, D]
            sv = vs_ref[0, 0]                          # [D]
            zv = vz_ref[0, 0]
            return pv * sv - jnp.sum(p, axis=1, keepdims=True) * (sv * zv)

        online_update(s, vals)

    # --- in-flight fp chunk: intra-chunk causal mask, then write out ---
    @pl.when(pi == npages)
    def _chunk():
        qs = qs_ref[0]                                 # [CG, D]
        kn = kn_ref[0]                                 # [C, D]
        s = jax.lax.dot_general(
            qs, kn, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [CG, C]
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        kj = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((kj <= qi) & (kj < qlen), s, NEG_INF)
        online_update(s, lambda p: jax.lax.dot_general(
            p, vn_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
        o_ref[0] = acc_ref[...]
        l_ref[0] = d_ref[...]


def paged_kv4_prefill_attention(
    q: jax.Array,             # [B, C, Hq, D] — one prefill chunk's queries
    k_new: jax.Array,         # [B, C, Hkv, D] fp in-flight chunk keys
    v_new: jax.Array,         # [B, C, Hkv, D] fp in-flight chunk values
    k_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical K pages
    k_scale: jax.Array,       # [Hkv, 1, D] f32
    k_zero: jax.Array,        # [Hkv, 1, D] f32
    v_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical V pages
    v_scale: jax.Array,       # [Hkv, 1, D] f32
    v_zero: jax.Array,        # [Hkv, 1, D] f32
    block_tables: jax.Array,  # [B, NP] int32 physical page per logical page
    ctx_lens: jax.Array,      # [B] int32 — tokens already paged (history)
    q_lens: jax.Array,        # [B] int32 — valid chunk tokens (≤ C)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Chunked prefill flash attention off the paged pools.

    Query i of sequence b (absolute position ``ctx_lens[b] + i``) attends
    over the int4 history pages [0, ctx_lens[b]) and the causal prefix of
    the fp chunk. Rows i ≥ q_lens[b] are padding (finite garbage — mask
    outside). Returns [B, C, Hq, D] f32.
    """
    b, c, hq, d = q.shape
    hkv, ps = k_pool.shape[1], k_pool.shape[2]
    g = hq // hkv
    npages = block_tables.shape[1]
    tables = jnp.maximum(block_tables.astype(jnp.int32), 0)
    if npages == 0:                    # pure-chunk call (no history yet)
        tables = jnp.zeros((b, 1), jnp.int32)

    # --- affine pre-fold for the history pages (outside the kernel) ---
    sm = 1.0 / jnp.sqrt(jnp.float32(d))
    qg = jnp.moveaxis(q.reshape(b, c, hkv, g, d).astype(jnp.float32), 1, 2)
    ksb = jnp.broadcast_to(k_scale, (hkv, 1, d)).reshape(1, hkv, 1, 1, d)
    kzb = jnp.broadcast_to(k_zero, (hkv, 1, d)).reshape(1, hkv, 1, 1, d)
    qt = qg * ksb * sm                                 # [B, Hkv, C, G, D]
    cterm = jnp.sum(qt * kzb, axis=-1, keepdims=True)
    qt2 = qt.reshape(b * hkv, c * g, d)
    c2 = cterm.reshape(b * hkv, c * g, 1)
    qs2 = (qg * sm).reshape(b * hkv, c * g, d)
    kn2 = k_new.astype(jnp.float32).swapaxes(1, 2).reshape(b * hkv, c, d)
    vn2 = v_new.astype(jnp.float32).swapaxes(1, 2).reshape(b * hkv, c, d)
    vs2 = jnp.broadcast_to(v_scale, (hkv, 1, d))
    vz2 = jnp.broadcast_to(v_zero, (hkv, 1, d))

    kernel = functools.partial(
        _paged_kv4_prefill_kernel, ps=ps, npages=npages, hkv=hkv, g=g)

    def page_map(bh, pi, tbl, ctx, qlen):
        return (tbl[bh // hkv, jnp.maximum(jnp.minimum(pi, npages - 1), 0)],
                bh % hkv, 0, 0)

    def row_map(bh, pi, tbl, ctx, qlen):
        return (bh, 0, 0)

    def head_map(bh, pi, tbl, ctx, qlen):
        return (bh % hkv, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b * hkv, npages + 1),
        in_specs=[
            pl.BlockSpec((1, c * g, d), row_map),       # qt
            pl.BlockSpec((1, c * g, 1), row_map),       # c
            pl.BlockSpec((1, c * g, d), row_map),       # qs
            pl.BlockSpec((1, c, d), row_map),           # k_new
            pl.BlockSpec((1, c, d), row_map),           # v_new
            pl.BlockSpec((1, 1, d), head_map),          # v_scale
            pl.BlockSpec((1, 1, d), head_map),          # v_zero
            pl.BlockSpec((1, 1, ps, d // 2), page_map), # K page
            pl.BlockSpec((1, 1, ps, d // 2), page_map), # V page
        ],
        out_specs=[
            pl.BlockSpec((1, c * g, d), row_map),
            pl.BlockSpec((1, c * g, 1), row_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((c * g, d), jnp.float32),
            pltpu.VMEM((c * g, 1), jnp.float32),
            pltpu.VMEM((c * g, 1), jnp.float32),
        ],
    )
    acc, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, c * g, d), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, c * g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(tables, ctx_lens.astype(jnp.int32), q_lens.astype(jnp.int32),
      qt2, c2, qs2, kn2, vn2, vs2, vz2, k_pool, v_pool)

    # V affine for history already applied in-kernel; just normalize.
    out = (acc / l).reshape(b, hkv, c, g, d)
    out = jnp.moveaxis(out, 2, 1)                      # [B, C, Hkv, G, D]
    return out.reshape(b, c, hq, d)


# ---------------------------------------------------------------------------
# Work-queue (Stream-K) schedule: flat descriptor walk + split-KV combine
# ---------------------------------------------------------------------------

def _paged_kv4_decode_wq_kernel(
    desc_ref,              # scalar prefetch: [W, 4] (row, page, count, kind)
    qt_ref,                # [1, G, D] f32 — the item's row q·s_k/√D
    c_ref,                 # [1, G, 1] f32 — zero-point fold Σ q̃·z_k
    kp_ref,                # [1, 1, ps, D/2] uint8 — the item's K page
    vp_ref,                # [1, 1, ps, D/2] uint8 — the item's V page
    o_ref,                 # [1, G, D] f32 — partial Σ p·n_v (nibble space)
    l_ref,                 # [1, G, 1] f32 — partial denominator Σ p
    m_ref,                 # [1, G, 1] f32 — the item's local max
):
    wi = pl.program_id(0)
    count = desc_ref[wi, 2]

    qt = qt_ref[0]                                     # [G, D]
    nk = _unpack_nibbles_f32(kp_ref[0, 0])             # [ps, D]
    s = jax.lax.dot_general(
        qt, nk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) - c_ref[0]                                       # [G, ps]
    pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < count, s, NEG_INF)
    # padding items (count == 0) produce m == NEG_INF: their combine
    # weight exp(m − M) underflows to exactly 0, so the garbage p == 1
    # rows below never reach an output
    m = jnp.max(s, axis=1, keepdims=True)              # [G, 1]
    p = jnp.exp(s - m)
    nv = _unpack_nibbles_f32(vp_ref[0, 0])
    o_ref[0] = jax.lax.dot_general(
        p, nv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    l_ref[0] = jnp.sum(p, axis=1, keepdims=True)
    m_ref[0] = m


def paged_kv4_decode_attention_wq(
    q: jax.Array,             # [B, Hq, D] — decode-step queries
    k_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical K pages
    k_scale: jax.Array,       # [Hkv, 1, D] (or [B, Hkv, 1, D]) f32
    k_zero: jax.Array,        # [Hkv, 1, D] f32
    v_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical V pages
    v_scale: jax.Array,       # [Hkv, 1, D] f32
    v_zero: jax.Array,        # [Hkv, 1, D] f32
    work_items: jax.Array,    # [W, 4] int32 (row, phys_page, count, kind)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Work-queue flash-decode: grid = (W,) real-page work items, partial
    (acc, l, m) per item, split-KV combine. Returns [B, Hq, D] f32."""
    b, hq, d = q.shape
    hkv, ps = k_pool.shape[1], k_pool.shape[2]
    g = hq // hkv
    nrows = b * hkv
    w = work_items.shape[0]
    desc = work_items.astype(jnp.int32)

    def bcast(s):
        return jnp.broadcast_to(s, (b, hkv, 1, d))

    # --- affine pre-fold (outside the kernel, O(B·H·D)) ---
    sm = 1.0 / jnp.sqrt(jnp.float32(d))
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    qt = qg * bcast(k_scale) * sm                      # [B, Hkv, G, D]
    c = jnp.sum(qt * bcast(k_zero), axis=-1, keepdims=True)
    qt2 = qt.reshape(nrows, g, d)
    c2 = c.reshape(nrows, g, 1)

    def row_map(wi, desc):
        return (jnp.minimum(desc[wi, 0], nrows - 1), 0, 0)

    def page_map(wi, desc):
        return (desc[wi, 1],
                jnp.minimum(desc[wi, 0], nrows - 1) % hkv, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(w,),
        in_specs=[
            pl.BlockSpec((1, g, d), row_map),
            pl.BlockSpec((1, g, 1), row_map),
            pl.BlockSpec((1, 1, ps, d // 2), page_map),
            pl.BlockSpec((1, 1, ps, d // 2), page_map),
        ],
        out_specs=[
            pl.BlockSpec((1, g, d), lambda wi, desc: (wi, 0, 0)),
            pl.BlockSpec((1, g, 1), lambda wi, desc: (wi, 0, 0)),
            pl.BlockSpec((1, g, 1), lambda wi, desc: (wi, 0, 0)),
        ],
    )
    acc, l, m = pl.pallas_call(
        _paged_kv4_decode_wq_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((w, g, d), jnp.float32),
            jax.ShapeDtypeStruct((w, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((w, g, 1), jnp.float32),
        ],
        # every step writes its own output block — the grid is a
        # divisible pool with no cross-step carry, so the whole axis is
        # parallel (the Stream-K property the dense schedule lacks)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(desc, qt2, c2, k_pool, v_pool)

    comb = combine_work_partials(acc, l, m, desc[:, 0], nrows)
    # --- affine post-fold: out = s_v ⊙ (Σp·n_v / Σp) − s_v ⊙ z_v ---
    sv = bcast(v_scale)
    zv = bcast(v_zero)
    out = sv * comb.reshape(b, hkv, g, d) - sv * zv
    return out.reshape(b, hq, d)


def _paged_kv4_prefill_wq_kernel(
    desc_ref,              # scalar prefetch: [W, 4] (row, page, count, kind)
    qt_ref,                # [1, CG, D] f32 — q·s_k/√D (history pre-fold)
    c_ref,                 # [1, CG, 1] f32 — zero-point fold Σ q̃·z_k
    qs_ref,                # [1, CG, D] f32 — q/√D (raw, for the fp chunk)
    kn_ref,                # [1, C, D] f32 — the row's in-flight fp keys
    vn_ref,                # [1, C, D] f32 — the row's in-flight fp values
    vs_ref,                # [1, 1, D] f32 — v_scale (history V dequant)
    vz_ref,                # [1, 1, D] f32 — v_zero
    kp_ref,                # [1, 1, ps, D/2] uint8 — the item's K page
    vp_ref,                # [1, 1, ps, D/2] uint8 — the item's V page
    o_ref,                 # [1, CG, D] f32 — partial numerator (value space)
    l_ref,                 # [1, CG, 1] f32 — partial denominator
    m_ref,                 # [1, CG, 1] f32 — the item's local max
    *,
    g: int,
):
    wi = pl.program_id(0)
    count = desc_ref[wi, 2]
    kind = desc_ref[wi, 3]

    # --- kind 0: one int4 history page (V affine folded per item) ---
    @pl.when(kind == 0)
    def _history():
        qt = qt_ref[0]                                 # [CG, D]
        nk = _unpack_nibbles_f32(kp_ref[0, 0])         # [ps, D]
        s = jax.lax.dot_general(
            qt, nk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) - c_ref[0]                                   # [CG, ps]
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < count, s, NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        nv = _unpack_nibbles_f32(vp_ref[0, 0])
        pv = jax.lax.dot_general(
            p, nv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [CG, D]
        sv = vs_ref[0, 0]
        zv = vz_ref[0, 0]
        lsum = jnp.sum(p, axis=1, keepdims=True)
        o_ref[0] = pv * sv - lsum * (sv * zv)
        l_ref[0] = lsum
        m_ref[0] = m

    # --- kind 1: the row's in-flight fp chunk, causal over count ---
    @pl.when(kind != 0)
    def _chunk():
        qs = qs_ref[0]                                 # [CG, D]
        kn = kn_ref[0]                                 # [C, D]
        s = jax.lax.dot_general(
            qs, kn, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [CG, C]
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        kj = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((kj <= qi) & (kj < count), s, NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        o_ref[0] = jax.lax.dot_general(
            p, vn_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        l_ref[0] = jnp.sum(p, axis=1, keepdims=True)
        m_ref[0] = m


def paged_kv4_prefill_attention_wq(
    q: jax.Array,             # [B, C, Hq, D] — one prefill chunk's queries
    k_new: jax.Array,         # [B, C, Hkv, D] fp in-flight chunk keys
    v_new: jax.Array,         # [B, C, Hkv, D] fp in-flight chunk values
    k_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical K pages
    k_scale: jax.Array,       # [Hkv, 1, D] f32
    k_zero: jax.Array,        # [Hkv, 1, D] f32
    v_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical V pages
    v_scale: jax.Array,       # [Hkv, 1, D] f32
    v_zero: jax.Array,        # [Hkv, 1, D] f32
    work_items: jax.Array,    # [W, 4] int32 (row, phys_page, count, kind)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Work-queue chunked-prefill flash attention: grid = (W,) descriptor
    items (real history pages + one causal chunk item per row), split-KV
    combined. Same semantics as ``paged_kv4_prefill_attention`` — rows
    past a row's ``q_len`` are padding garbage, mask outside. Returns
    [B, C, Hq, D] f32."""
    b, c, hq, d = q.shape
    hkv, ps = k_pool.shape[1], k_pool.shape[2]
    g = hq // hkv
    nrows = b * hkv
    w = work_items.shape[0]
    desc = work_items.astype(jnp.int32)

    # --- affine pre-fold for the history pages (outside the kernel) ---
    sm = 1.0 / jnp.sqrt(jnp.float32(d))
    qg = jnp.moveaxis(q.reshape(b, c, hkv, g, d).astype(jnp.float32), 1, 2)
    ksb = jnp.broadcast_to(k_scale, (hkv, 1, d)).reshape(1, hkv, 1, 1, d)
    kzb = jnp.broadcast_to(k_zero, (hkv, 1, d)).reshape(1, hkv, 1, 1, d)
    qt = qg * ksb * sm                                 # [B, Hkv, C, G, D]
    cterm = jnp.sum(qt * kzb, axis=-1, keepdims=True)
    qt2 = qt.reshape(nrows, c * g, d)
    c2 = cterm.reshape(nrows, c * g, 1)
    qs2 = (qg * sm).reshape(nrows, c * g, d)
    kn2 = k_new.astype(jnp.float32).swapaxes(1, 2).reshape(nrows, c, d)
    vn2 = v_new.astype(jnp.float32).swapaxes(1, 2).reshape(nrows, c, d)
    vs2 = jnp.broadcast_to(v_scale, (hkv, 1, d))
    vz2 = jnp.broadcast_to(v_zero, (hkv, 1, d))

    kernel = functools.partial(_paged_kv4_prefill_wq_kernel, g=g)

    def row_map(wi, desc):
        return (jnp.minimum(desc[wi, 0], nrows - 1), 0, 0)

    def head_map(wi, desc):
        return (jnp.minimum(desc[wi, 0], nrows - 1) % hkv, 0, 0)

    def page_map(wi, desc):
        return (desc[wi, 1],
                jnp.minimum(desc[wi, 0], nrows - 1) % hkv, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(w,),
        in_specs=[
            pl.BlockSpec((1, c * g, d), row_map),       # qt
            pl.BlockSpec((1, c * g, 1), row_map),       # c
            pl.BlockSpec((1, c * g, d), row_map),       # qs
            pl.BlockSpec((1, c, d), row_map),           # k_new
            pl.BlockSpec((1, c, d), row_map),           # v_new
            pl.BlockSpec((1, 1, d), head_map),          # v_scale
            pl.BlockSpec((1, 1, d), head_map),          # v_zero
            pl.BlockSpec((1, 1, ps, d // 2), page_map), # K page
            pl.BlockSpec((1, 1, ps, d // 2), page_map), # V page
        ],
        out_specs=[
            pl.BlockSpec((1, c * g, d), lambda wi, desc: (wi, 0, 0)),
            pl.BlockSpec((1, c * g, 1), lambda wi, desc: (wi, 0, 0)),
            pl.BlockSpec((1, c * g, 1), lambda wi, desc: (wi, 0, 0)),
        ],
    )
    acc, l, m = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((w, c * g, d), jnp.float32),
            jax.ShapeDtypeStruct((w, c * g, 1), jnp.float32),
            jax.ShapeDtypeStruct((w, c * g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(desc, qt2, c2, qs2, kn2, vn2, vs2, vz2, k_pool, v_pool)

    # partials are already in value space — combine IS the output
    out = combine_work_partials(acc, l, m, desc[:, 0], nrows)
    out = out.reshape(b, hkv, c, g, d)
    out = jnp.moveaxis(out, 2, 1)                      # [B, C, Hkv, G, D]
    return out.reshape(b, c, hq, d)
