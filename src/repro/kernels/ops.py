"""jit'd public wrappers around the COMET kernels.

Every op takes ``impl`` ∈ {"auto", "pallas", "ref"}:

* ``auto``   — Pallas on TPU backends, pure-jnp reference elsewhere
               (CPU dry-run lowering, tests of the ref path). The ref
               consumes identical packed buffers, so XLA cost/memory
               analysis of the serving graph reflects true packed bytes.
* ``pallas`` — force the Pallas kernel (``interpret=True`` off-TPU).
* ``ref``    — force the jnp oracle.

Shape policy: wrappers accept [..., K] activations, flatten leading dims
to M, pad M up to the tile multiple, and strip padding on return.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import ref as R
from repro.kernels import w4ax_matmul as WK
from repro.kernels import kv4_attention as AK
from repro.kernels import paged_attention as PK
from repro.kernels import act_quant as QK

BLOCK_K = WK.BLOCK_K

__all__ = [
    "w4ax_matmul",
    "kv4_decode_attention",
    "paged_kv4_decode_attention",
    "paged_kv4_prefill_attention",
    "paged_kv4_decode_attention_wq",
    "paged_kv4_prefill_attention_wq",
    "act_quant",
    "default_impl",
]


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _resolve(impl: str):
    """→ (use_pallas: bool, interpret: bool)."""
    if impl == "auto":
        impl = default_impl()
    if impl == "pallas":
        return True, jax.default_backend() != "tpu"
    if impl == "ref":
        return False, False
    raise ValueError(f"impl must be auto|pallas|ref, got {impl}")


def _pad_rows(x: jax.Array, multiple: int):
    m = x.shape[0]
    pad = (-m) % multiple
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x, m


# ---------------------------------------------------------------------------
# W4Ax GEMM
# ---------------------------------------------------------------------------

def w4ax_matmul(
    a4_packed: jax.Array,   # [..., K4/2] uint8
    a4_scale: jax.Array,    # [..., K4/128] f32
    a8_q: jax.Array,        # [..., K8] int8
    a8_scale: jax.Array,    # [..., K8/128] f32
    w_packed: jax.Array,    # [K/2, N] uint8
    w_scale: jax.Array,     # [K/128, N] f32
    *,
    schedule: str = "split",     # "split" (optimized) | "mixed" (paper baseline)
    impl: str = "auto",
    bm: int = 128,
    bn: int | None = None,
) -> jax.Array:
    """Mixed-precision W4Ax GEMM: out = dequant(a) @ dequant(w). → [..., N] f32."""
    lead = a4_packed.shape[:-1]
    n = w_packed.shape[1]
    m_lead = math.prod(lead) if lead else 1

    a4p = a4_packed.reshape(m_lead, a4_packed.shape[-1])
    a4s = a4_scale.reshape(m_lead, a4_scale.shape[-1])
    a8q = a8_q.reshape(m_lead, a8_q.shape[-1])
    a8s = a8_scale.reshape(m_lead, a8_scale.shape[-1])

    use_pallas, interp = _resolve(impl)
    nb4 = a4s.shape[1] if a4p.shape[1] else 0
    k4p = nb4 * WK.PACKED_BLOCK

    if not use_pallas:
        out = R.w4ax_matmul_ref(
            a4p, a4s, a8q, a8s,
            w_packed[:k4p], w_scale[:nb4],
            w_packed[k4p:], w_scale[nb4:],
        )
        return out.reshape(*lead, n)

    m0 = a4p.shape[0] if a4p.shape[1] else a8q.shape[0]
    eff_bm = min(bm, max(8, 1 << (m0 - 1).bit_length())) if m0 else bm
    a4p, m = _pad_rows(a4p, eff_bm)
    a4s, _ = _pad_rows(a4s, eff_bm)
    a8q, _ = _pad_rows(a8q, eff_bm)
    a8s, _ = _pad_rows(a8s, eff_bm)
    if schedule == "split":
        out = WK.w4ax_matmul_split(
            a4p, a4s, a8q, a8s, w_packed, w_scale,
            bm=eff_bm, bn=bn, interpret=interp,
        )
    elif schedule == "mixed":
        out = WK.w4ax_matmul_mixed(
            a4p, a4s, a8q, a8s, w_packed, w_scale,
            bm=eff_bm, bn=bn or 128, interpret=interp,
        )
    else:
        raise ValueError(f"schedule must be split|mixed, got {schedule}")
    return out[:m].reshape(*lead, n)


# ---------------------------------------------------------------------------
# KV4 decode attention
# ---------------------------------------------------------------------------

def kv4_decode_attention(
    q: jax.Array,
    k_packed: jax.Array,
    k_scale: jax.Array,
    k_zero: jax.Array,
    v_packed: jax.Array,
    v_scale: jax.Array,
    v_zero: jax.Array,
    length: jax.Array | None = None,
    *,
    impl: str = "auto",
    bt: int = 256,
) -> jax.Array:
    use_pallas, interp = _resolve(impl)
    t = k_packed.shape[2]
    if length is None:
        length = jnp.full((q.shape[0],), t, jnp.int32)
    if not use_pallas:
        return R.kv4_decode_attention_ref(
            q, k_packed, k_scale, k_zero, v_packed, v_scale, v_zero, length,
            compute_dtype=jnp.bfloat16,
        )
    bt = min(bt, t)
    return AK.kv4_decode_attention(
        q, k_packed, k_scale, k_zero, v_packed, v_scale, v_zero, length,
        bt=bt, interpret=interp,
    )


# ---------------------------------------------------------------------------
# Paged KV4 decode attention (gather-free serving hot path)
# ---------------------------------------------------------------------------

def paged_kv4_decode_attention(
    q: jax.Array,             # [B, Hq, D]
    k_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8
    k_scale: jax.Array,       # [Hkv, 1, D] or [B, Hkv, 1, D]
    k_zero: jax.Array,
    v_pool: jax.Array,
    v_scale: jax.Array,
    v_zero: jax.Array,
    block_tables: jax.Array,  # [B, NP] int32
    length: jax.Array,        # [B] int32
    *,
    impl: str = "auto",
) -> jax.Array:
    """Decode attention straight off the paged pools — no gather_kv.

    The Pallas path resolves ``(seq, logical page) → physical page``
    inside the kernel via scalar-prefetched block tables; the ref path
    gathers pages in jnp (same semantics, used for CPU serving + tests).
    """
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        return R.paged_kv4_decode_attention_ref(
            q, k_pool, k_scale, k_zero, v_pool, v_scale, v_zero,
            block_tables, length,
        )
    return PK.paged_kv4_decode_attention(
        q, k_pool, k_scale, k_zero, v_pool, v_scale, v_zero,
        block_tables, length, interpret=interp,
    )


# ---------------------------------------------------------------------------
# Paged KV4 chunked prefill attention (ragged prompt hot path)
# ---------------------------------------------------------------------------

def paged_kv4_prefill_attention(
    q: jax.Array,             # [B, C, Hq, D] — one prefill chunk's queries
    k_new: jax.Array,         # [B, C, Hkv, D] fp in-flight chunk keys
    v_new: jax.Array,         # [B, C, Hkv, D] fp in-flight chunk values
    k_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8
    k_scale: jax.Array,       # [Hkv, 1, D]
    k_zero: jax.Array,
    v_pool: jax.Array,
    v_scale: jax.Array,
    v_zero: jax.Array,
    block_tables: jax.Array,  # [B, NP] int32
    ctx_lens: jax.Array,      # [B] int32 — tokens already paged
    q_lens: jax.Array,        # [B] int32 — valid chunk tokens (≤ C)
    *,
    impl: str = "auto",
) -> jax.Array:
    """Chunked prefill attention: fp chunk queries over int4 paged history
    plus the causal in-flight fp chunk — the prompt path never holds more
    than one chunk of fp KV. Returns [B, C, Hq, D] f32 (rows past
    ``q_lens`` are padding garbage; mask outside)."""
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        return R.paged_kv4_prefill_attention_ref(
            q, k_new, v_new, k_pool, k_scale, k_zero,
            v_pool, v_scale, v_zero, block_tables, ctx_lens, q_lens,
        )
    return PK.paged_kv4_prefill_attention(
        q, k_new, v_new, k_pool, k_scale, k_zero,
        v_pool, v_scale, v_zero, block_tables, ctx_lens, q_lens,
        interpret=interp,
    )


# ---------------------------------------------------------------------------
# Work-queue (Stream-K) paged attention: flat descriptors + split-KV combine
# ---------------------------------------------------------------------------

def paged_kv4_decode_attention_wq(
    q: jax.Array,             # [B, Hq, D]
    k_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8
    k_scale: jax.Array,       # [Hkv, 1, D] or [B, Hkv, 1, D]
    k_zero: jax.Array,
    v_pool: jax.Array,
    v_scale: jax.Array,
    v_zero: jax.Array,
    work_items: jax.Array,    # [W, 4] int32 (row, phys_page, count, kind)
    *,
    impl: str = "auto",
) -> jax.Array:
    """Work-queue decode attention: the grid walks flat ``(seq, kv_head,
    page)`` descriptors covering only real pages (Stream-K one-to-many
    binding), each emitting a partial flash state merged by the split-KV
    combine — no ``B × max_npages`` padding rectangle. Descriptors come
    from ``serving.kv_cache.build_work_queue``."""
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        return R.paged_kv4_decode_attention_wq_ref(
            q, k_pool, k_scale, k_zero, v_pool, v_scale, v_zero,
            work_items,
        )
    return PK.paged_kv4_decode_attention_wq(
        q, k_pool, k_scale, k_zero, v_pool, v_scale, v_zero,
        work_items, interpret=interp,
    )


def paged_kv4_prefill_attention_wq(
    q: jax.Array,             # [B, C, Hq, D] — one prefill chunk's queries
    k_new: jax.Array,         # [B, C, Hkv, D] fp in-flight chunk keys
    v_new: jax.Array,         # [B, C, Hkv, D] fp in-flight chunk values
    k_pool: jax.Array,        # [P, Hkv, ps, D/2] uint8
    k_scale: jax.Array,       # [Hkv, 1, D]
    k_zero: jax.Array,
    v_pool: jax.Array,
    v_scale: jax.Array,
    v_zero: jax.Array,
    work_items: jax.Array,    # [W, 4] int32 (row, phys_page, count, kind)
    *,
    impl: str = "auto",
) -> jax.Array:
    """Work-queue chunked-prefill attention: same semantics as
    ``paged_kv4_prefill_attention`` (rows past a row's q_len are padding
    garbage — mask outside) but scheduled over flat work items — history
    pages AND the per-row causal fp chunk are uniform entries in one
    divisible pool, so a ragged batch's grid is Σ real work, not
    ``B × (max_npages + 1)``."""
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        return R.paged_kv4_prefill_attention_wq_ref(
            q, k_new, v_new, k_pool, k_scale, k_zero,
            v_pool, v_scale, v_zero, work_items,
        )
    return PK.paged_kv4_prefill_attention_wq(
        q, k_new, v_new, k_pool, k_scale, k_zero,
        v_pool, v_scale, v_zero, work_items, interpret=interp,
    )


# ---------------------------------------------------------------------------
# Activation quantization
# ---------------------------------------------------------------------------

def act_quant(x: jax.Array, *, bits: int = 4, impl: str = "auto"):
    """[..., K] float → (payload, scales [..., K/128]).

    bits=4 → packed uint8 [..., K/2]; bits=8 → int8 [..., K].
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k).astype(jnp.float32)
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        payload, scale = R.act_quant_ref(x2, block_size=BLOCK_K, bits=bits)
    else:
        bm = QK.row_block(k)
        x2p, m = _pad_rows(x2, min(bm, max(8, x2.shape[0])))
        eff_bm = min(bm, x2p.shape[0])
        if bits == 4:
            payload, scale = QK.act_quant_int4(x2p, bm=eff_bm, interpret=interp)
        else:
            payload, scale = QK.act_quant_int8(x2p, bm=eff_bm, interpret=interp)
        payload, scale = payload[:m], scale[:m]
    pk = payload.shape[-1]
    return payload.reshape(*lead, pk), scale.reshape(*lead, k // BLOCK_K)
