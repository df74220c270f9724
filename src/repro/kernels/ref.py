"""Pure-jnp reference oracles for every Pallas kernel in this package.

These are the ground truth for tests (``assert_allclose`` sweeps) and the
portable fallback used on CPU (dry-run lowering) where a TPU Pallas body
would otherwise run through the slow interpreter.

Each oracle consumes the *same packed data structures* as its kernel so
that XLA's cost/memory analysis of the ref path reflects the true packed
byte traffic (this is what the roofline reads).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quantizer as Q
from repro.kernels.kv4_attention import NEG_INF, _unpack_nibbles_f32
from repro.kernels.paged_attention import combine_work_partials

__all__ = [
    "w4a4_matmul_ref",
    "w4a8_matmul_ref",
    "w4ax_matmul_ref",
    "kv4_decode_attention_ref",
    "paged_kv4_decode_attention_ref",
    "paged_kv4_prefill_attention_ref",
    "paged_kv4_decode_attention_wq_ref",
    "paged_kv4_prefill_attention_wq_ref",
    "act_quant_ref",
]


def _unpack_w(w_packed: jax.Array, block_size: int) -> jax.Array:
    """Interleaved packed [K/2, N] uint8 → int8 [K, N] (sign-corrected)."""
    return Q.unpack_int4_interleaved(w_packed, axis=0, block_size=block_size)


def w4a4_matmul_ref(
    a_packed: jax.Array,   # [M, K/2] uint8 — blocked-interleave packed int4 acts
    a_scale: jax.Array,    # [M, K/B] f32 per-(row, block) scales
    w_packed: jax.Array,   # [K/2, N] uint8 — interleaved packed int4 weights
    w_scale: jax.Array,    # [K/B, N] f32 per-(block, col) scales
    block_size: int = 128,
) -> jax.Array:
    """Uniform W4A4 GEMM with per-block dequant at the int32 boundary."""
    a = Q.unpack_int4_interleaved(a_packed, axis=1, block_size=block_size)
    w = _unpack_w(w_packed, block_size)           # [K, N] int8 in [-8, 7]
    m, k = a.shape
    n = w.shape[1]
    nb = k // block_size
    ab = a.reshape(m, nb, block_size).astype(jnp.int32)
    wb = w.reshape(nb, block_size, n).astype(jnp.int32)
    # int32 per-block partial dot: [M, nb, N]
    part = jnp.einsum("mbk,bkn->mbn", ab, wb)
    # HIGHEST: the rescale is exact f32 on every backend (a TPU's
    # default f32 dot takes bf16 passes, ~2^-9 relative error)
    out = jnp.einsum(
        "mbn,mb,bn->mn",
        part.astype(jnp.float32),
        a_scale.astype(jnp.float32),
        w_scale.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return out


def w4a8_matmul_ref(
    a_q: jax.Array,        # [M, K] int8 activations
    a_scale: jax.Array,    # [M, K/B] f32
    w_packed: jax.Array,   # [K/2, N] uint8 packed int4 weights
    w_scale: jax.Array,    # [K/B, N] f32
    block_size: int = 128,
) -> jax.Array:
    """Uniform W4A8 GEMM: int4 weights are converted up to int8 (§4.3)."""
    w = _unpack_w(w_packed, block_size)
    m, k = a_q.shape
    n = w.shape[1]
    nb = k // block_size
    ab = a_q.reshape(m, nb, block_size).astype(jnp.int32)
    wb = w.reshape(nb, block_size, n).astype(jnp.int32)
    part = jnp.einsum("mbk,bkn->mbn", ab, wb)
    return jnp.einsum(
        "mbn,mb,bn->mn",
        part.astype(jnp.float32),
        a_scale.astype(jnp.float32),
        w_scale.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def w4ax_matmul_ref(
    a4_packed: jax.Array,  # [M, K4/2] uint8 — INT4 blocks (leading K4 channels)
    a4_scale: jax.Array,   # [M, K4/B]
    a8_q: jax.Array,       # [M, K8] int8 — INT8 blocks (trailing channels)
    a8_scale: jax.Array,   # [M, K8/B]
    w4_packed: jax.Array,  # [K4/2, N]
    w4_scale: jax.Array,   # [K4/B, N]
    w8_packed: jax.Array,  # [K8/2, N]  (weights stay int4 in both halves)
    w8_scale: jax.Array,   # [K8/B, N]
    block_size: int = 128,
) -> jax.Array:
    """Mixed-precision W4Ax GEMM (paper's kernel): K4 channels in W4A4,
    the remaining K8 in W4A8, accumulated into one output.

    Channel permutation (FMPQ) guarantees the INT8 blocks are the trailing
    channels, so the mixed GEMM is exactly the sum of two uniform GEMMs.
    """
    parts = []
    if a4_packed.shape[1] > 0:
        parts.append(
            w4a4_matmul_ref(a4_packed, a4_scale, w4_packed, w4_scale, block_size)
        )
    if a8_q.shape[1] > 0:
        parts.append(w4a8_matmul_ref(a8_q, a8_scale, w8_packed, w8_scale, block_size))
    if not parts:
        raise ValueError("empty GEMM")
    out = parts[0]
    for p in parts[1:]:
        out = out + p
    return out


def kv4_decode_attention_ref(
    q: jax.Array,          # [B, Hq, D] f32/bf16 — one decode step's queries
    k_packed: jax.Array,   # [B, Hkv, T, D/2] uint8 — int4 KV cache (asym)
    k_scale: jax.Array,    # [B, Hkv, 1, D]
    k_zero: jax.Array,     # [B, Hkv, 1, D]
    v_packed: jax.Array,   # [B, Hkv, T, D/2]
    v_scale: jax.Array,    # [B, Hkv, 1, D]
    v_zero: jax.Array,     # [B, Hkv, 1, D]
    length: jax.Array | None = None,  # [B] valid KV lengths (<= T)
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Decode attention over a channel-wise-asymmetric int4 KV cache.

    GQA: Hq = G * Hkv; query head h attends with KV head h // G.
    Returns [B, Hq, D] in float32.

    ``compute_dtype=bf16`` (serving path, §Perf cell A iteration 3):
    the Pallas kernel keeps the nibble expansion in VMEM; the portable
    path at least halves the materialized convert traffic by keeping the
    dequantized operands bf16 with f32 MXU accumulation. Tests use the
    f32 default as the exact oracle.
    """
    b, hq, d = q.shape
    hkv = k_packed.shape[1]
    g = hq // hkv
    t = k_packed.shape[2]

    k_deq = Q.dequantize_kv_channelwise(
        k_packed, k_scale, k_zero).astype(compute_dtype)
    v_deq = Q.dequantize_kv_channelwise(
        v_packed, v_scale, v_zero).astype(compute_dtype)

    qg = q.reshape(b, hkv, g, d).astype(compute_dtype)
    scores = jnp.einsum("bhgd,bhtd->bhgt", qg, k_deq,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
        float(d))
    if length is not None:
        mask = jnp.arange(t)[None, None, None, :] < length[:, None, None, None]
        scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgt,bhtd->bhgd", p.astype(compute_dtype), v_deq,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, d)


def paged_kv4_decode_attention_ref(
    q: jax.Array,             # [B, Hq, D] — decode-step queries
    k_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical K pages
    k_scale: jax.Array,       # [Hkv, 1, D] (or [B, Hkv, 1, D]) f32
    k_zero: jax.Array,        # [Hkv, 1, D] f32
    v_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical V pages
    v_scale: jax.Array,       # [Hkv, 1, D] f32
    v_zero: jax.Array,        # [Hkv, 1, D] f32
    block_tables: jax.Array,  # [B, NP] int32 (-1/unmapped → clamped to 0)
    length: jax.Array,        # [B] int32 valid lengths
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Oracle for the paged kernel: gather pages in jnp, then run the
    contiguous oracle. The gather is what the Pallas kernel's block-table
    index_map eliminates; here it *defines* the expected semantics."""
    b, hq, d = q.shape
    hkv, ps = k_pool.shape[1], k_pool.shape[2]
    npages = block_tables.shape[1]
    tables = jnp.maximum(block_tables.astype(jnp.int32), 0)

    def gather(pool):
        pages = pool[tables]                     # [B, NP, Hkv, ps, D/2]
        return jnp.moveaxis(pages, 2, 1).reshape(
            b, hkv, npages * ps, d // 2)         # [B, Hkv, NP·ps, D/2]

    def bcast(s):
        return jnp.broadcast_to(s, (b, hkv, 1, d))

    return kv4_decode_attention_ref(
        q, gather(k_pool), bcast(k_scale), bcast(k_zero),
        gather(v_pool), bcast(v_scale), bcast(v_zero), length,
        compute_dtype=compute_dtype,
    )


def paged_kv4_prefill_attention_ref(
    q: jax.Array,             # [B, C, Hq, D] — one prefill chunk's queries
    k_new: jax.Array,         # [B, C, Hkv, D] fp — the chunk's in-flight keys
    v_new: jax.Array,         # [B, C, Hkv, D] fp — the chunk's in-flight values
    k_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical K pages
    k_scale: jax.Array,       # [Hkv, 1, D] f32
    k_zero: jax.Array,        # [Hkv, 1, D] f32
    v_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical V pages
    v_scale: jax.Array,       # [Hkv, 1, D] f32
    v_zero: jax.Array,        # [Hkv, 1, D] f32
    block_tables: jax.Array,  # [B, NP] int32 (-1/unmapped → clamped to 0)
    ctx_lens: jax.Array,      # [B] int32 — tokens already paged (history)
    q_lens: jax.Array,        # [B] int32 — valid chunk tokens (≤ C)
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Oracle for the chunked-prefill kernel.

    Query i of sequence b sits at absolute position ``ctx_lens[b] + i``
    and attends over (a) the int4 paged history [0, ctx_lens[b]) gathered
    and dequantized here, and (b) the causal fp prefix of the in-flight
    chunk ``k_new[b, :i+1]``. Rows i ≥ q_lens[b] are padding: they get
    finite garbage (never NaN) and must be masked by the caller.
    Returns [B, C, Hq, D] f32.
    """
    b, c, hq, d = q.shape
    hkv, ps = k_pool.shape[1], k_pool.shape[2]
    g = hq // hkv
    npages = block_tables.shape[1]
    t_hist = npages * ps
    tables = jnp.maximum(block_tables.astype(jnp.int32), 0)

    def bcast(s):
        return jnp.broadcast_to(s, (b, hkv, 1, d))

    def gather_deq(pool, scale, zero):
        pages = pool[tables]                     # [B, NP, Hkv, ps, D/2]
        flat = jnp.moveaxis(pages, 2, 1).reshape(b, hkv, t_hist, d // 2)
        return Q.dequantize_kv_channelwise(
            flat, bcast(scale), bcast(zero)).astype(compute_dtype)

    kh = gather_deq(k_pool, k_scale, k_zero)     # [B, Hkv, Th, D]
    vh = gather_deq(v_pool, v_scale, v_zero)
    kn = k_new.swapaxes(1, 2).astype(compute_dtype)   # [B, Hkv, C, D]
    vn = v_new.swapaxes(1, 2).astype(compute_dtype)
    keys = jnp.concatenate([kh, kn], axis=2)     # [B, Hkv, Th+C, D]
    vals = jnp.concatenate([vh, vn], axis=2)

    qg = q.reshape(b, c, hkv, g, d).astype(compute_dtype)
    scores = jnp.einsum("bchgd,bhtd->bhgct", qg, keys,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(d))                          # [B, Hkv, G, C, Th+C]

    tpos = jnp.arange(t_hist + c)
    hist_valid = tpos[None, :] < ctx_lens[:, None]          # [B, T]
    j = tpos - t_hist                                        # chunk-local key
    i = jnp.arange(c)
    chunk_valid = ((j[None, None, :] <= i[None, :, None])
                   & (j[None, None, :] < q_lens[:, None, None]))  # [B, C, T]
    valid = jnp.where((tpos < t_hist)[None, None, :],
                      hist_valid[:, None, :], chunk_valid)   # [B, C, T]
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgct,bhtd->bhgcd", p.astype(compute_dtype), vals,
                     preferred_element_type=jnp.float32)
    out = jnp.moveaxis(out, 3, 1)                # [B, C, Hkv, G, D]
    return out.reshape(b, c, hq, d)


def _wq_item_pages(pool, pages, heads):
    """Gather each work item's page for its kv head → [W, ps, D/2]."""
    return jax.vmap(lambda p, h: pool[p, h])(pages, heads)


def paged_kv4_decode_attention_wq_ref(
    q: jax.Array,             # [B, Hq, D] — decode-step queries
    k_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical K pages
    k_scale: jax.Array,       # [Hkv, 1, D] (or [B, Hkv, 1, D]) f32
    k_zero: jax.Array,        # [Hkv, 1, D] f32
    v_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8 physical V pages
    v_scale: jax.Array,       # [Hkv, 1, D] f32
    v_zero: jax.Array,        # [Hkv, 1, D] f32
    work_items: jax.Array,    # [W, 4] int32 (row, phys_page, count, kind)
) -> jax.Array:
    """Oracle for the work-queue decode kernel: compute every item's
    partial flash triple in one vectorized pass, then run the SAME
    split-KV combine the Pallas wrapper uses. The descriptor walk here
    *defines* the schedule's semantics — Σ real pages of work, combined
    by row segment — independent of how the grid binds items to cores."""
    b, hq, d = q.shape
    hkv, ps = k_pool.shape[1], k_pool.shape[2]
    g = hq // hkv
    nrows = b * hkv
    desc = jnp.asarray(work_items, jnp.int32)
    rcl = jnp.minimum(desc[:, 0], nrows - 1)
    heads = rcl % hkv
    counts = desc[:, 2]

    def bcast(s):
        return jnp.broadcast_to(s, (b, hkv, 1, d))

    sm = 1.0 / jnp.sqrt(jnp.float32(d))
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    qt = (qg * bcast(k_scale) * sm).reshape(nrows, g, d)
    c = jnp.sum(qt.reshape(b, hkv, g, d) * bcast(k_zero),
                axis=-1, keepdims=True).reshape(nrows, g, 1)

    nk = _unpack_nibbles_f32(_wq_item_pages(k_pool, desc[:, 1], heads))
    nv = _unpack_nibbles_f32(_wq_item_pages(v_pool, desc[:, 1], heads))
    s = jnp.einsum("wgd,wpd->wgp", qt[rcl], nk,
                   preferred_element_type=jnp.float32) - c[rcl]
    pos = jnp.arange(ps)[None, None, :]
    s = jnp.where(pos < counts[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)             # [W, G, 1]
    p = jnp.exp(s - m)
    acc = jnp.einsum("wgp,wpd->wgd", p, nv,
                     preferred_element_type=jnp.float32)
    l = jnp.sum(p, axis=-1, keepdims=True)

    comb = combine_work_partials(acc, l, m, desc[:, 0], nrows)
    sv = bcast(v_scale)
    zv = bcast(v_zero)
    out = sv * comb.reshape(b, hkv, g, d) - sv * zv
    return out.reshape(b, hq, d)


def paged_kv4_prefill_attention_wq_ref(
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
) -> jax.Array:
    """Oracle for the work-queue prefill kernel: per-item partials for
    both item kinds (int4 history page / causal fp chunk), selected by
    ``kind``, then the shared split-KV combine. Rows past a row's q_len
    are padding garbage — mask outside. Returns [B, C, Hq, D] f32."""
    b, c, hq, d = q.shape
    hkv, ps = k_pool.shape[1], k_pool.shape[2]
    g = hq // hkv
    cg = c * g
    nrows = b * hkv
    desc = jnp.asarray(work_items, jnp.int32)
    rcl = jnp.minimum(desc[:, 0], nrows - 1)
    heads = rcl % hkv
    counts = desc[:, 2]
    kinds = desc[:, 3]

    sm = 1.0 / jnp.sqrt(jnp.float32(d))
    qg = jnp.moveaxis(q.reshape(b, c, hkv, g, d).astype(jnp.float32), 1, 2)
    ksb = jnp.broadcast_to(k_scale, (hkv, 1, d)).reshape(1, hkv, 1, 1, d)
    kzb = jnp.broadcast_to(k_zero, (hkv, 1, d)).reshape(1, hkv, 1, 1, d)
    qt = qg * ksb * sm
    cterm = jnp.sum(qt * kzb, axis=-1, keepdims=True)
    qt2 = qt.reshape(nrows, cg, d)
    c2 = cterm.reshape(nrows, cg, 1)
    qs2 = (qg * sm).reshape(nrows, cg, d)
    kn2 = k_new.astype(jnp.float32).swapaxes(1, 2).reshape(nrows, c, d)
    vn2 = v_new.astype(jnp.float32).swapaxes(1, 2).reshape(nrows, c, d)
    vsb = jnp.broadcast_to(v_scale, (hkv, 1, d))[heads]     # [W, 1, D]
    vzb = jnp.broadcast_to(v_zero, (hkv, 1, d))[heads]

    # --- kind 0: int4 history pages (V affine folded per item) ---
    nk = _unpack_nibbles_f32(_wq_item_pages(k_pool, desc[:, 1], heads))
    nv = _unpack_nibbles_f32(_wq_item_pages(v_pool, desc[:, 1], heads))
    s_h = jnp.einsum("wgd,wpd->wgp", qt2[rcl], nk,
                     preferred_element_type=jnp.float32) - c2[rcl]
    pos = jnp.arange(ps)[None, None, :]
    s_h = jnp.where(pos < counts[:, None, None], s_h, NEG_INF)
    m_h = jnp.max(s_h, axis=-1, keepdims=True)         # [W, CG, 1]
    p_h = jnp.exp(s_h - m_h)
    l_h = jnp.sum(p_h, axis=-1, keepdims=True)
    pv = jnp.einsum("wgp,wpd->wgd", p_h, nv,
                    preferred_element_type=jnp.float32)
    acc_h = pv * vsb - l_h * (vsb * vzb)

    # --- kind 1: the row's in-flight fp chunk, causal over count ---
    s_c = jnp.einsum("wgd,wcd->wgc", qs2[rcl], kn2[rcl],
                     preferred_element_type=jnp.float32)
    qi = (jnp.arange(cg) // g)[None, :, None]
    kj = jnp.arange(c)[None, None, :]
    s_c = jnp.where((kj <= qi) & (kj < counts[:, None, None]), s_c, NEG_INF)
    m_c = jnp.max(s_c, axis=-1, keepdims=True)
    p_c = jnp.exp(s_c - m_c)
    l_c = jnp.sum(p_c, axis=-1, keepdims=True)
    acc_c = jnp.einsum("wgc,wcd->wgd", p_c, vn2[rcl],
                       preferred_element_type=jnp.float32)

    sel = (kinds != 0)[:, None, None]
    acc = jnp.where(sel, acc_c, acc_h)
    l = jnp.where(sel, l_c, l_h)
    m = jnp.where(sel, m_c, m_h)

    out = combine_work_partials(acc, l, m, desc[:, 0], nrows)
    out = out.reshape(b, hkv, c, g, d)
    out = jnp.moveaxis(out, 2, 1)                      # [B, C, Hkv, G, D]
    return out.reshape(b, c, hq, d)


def act_quant_ref(x: jax.Array, block_size: int = 128, bits: int = 4):
    """Oracle for the on-the-fly activation quantization kernel.

    x: [M, K] → (packed-or-int8 payload, scale [M, K/B]).
    bits=4 returns packed uint8 [M, K/2]; bits=8 returns int8 [M, K].
    """
    q, s = Q.quantize_act_groupwise(x, block_size=block_size, bits=bits)
    if bits == 4:
        return Q.pack_int4_interleaved(q, axis=1, block_size=block_size), s
    return q, s
