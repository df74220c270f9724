"""Paged KV4 attention (decode + chunked prefill) vs oracle and gather.

Sweeps page sizes, ragged lengths (incl. len < one page and len not a
multiple of page_size), GQA head ratios, and batch > 1 — the contract
the gather-free serving hot path depends on. The prefill sweeps add
ragged chunk lengths, zero-history sequences, and the fp-chunk/int4-
history boundary the chunked prompt path relies on.

The work-queue sweeps re-run every dense case through the flat
Stream-K descriptor schedule (``build_work_queue`` → ``*_wq`` kernels
→ split-KV combine) and require the result to match the DENSE oracle —
the two grid schedules must be numerically interchangeable up to float
reassociation, including the ragged dominant-long-row and qlen-0
pad-row cases the unified engine's bucketed batches produce.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.kernels import ops, ref
from repro.layers.attention import flash_attention
from repro.serving.kv_cache import (PagedKV4Cache, PagedKV4Config,
                                    build_work_queue)


def make_paged(rng, b, hkv, d, ps, lengths, num_pages=None):
    """Random pools + a shuffled (non-identity) block table per seq."""
    npages = max((int(l) + ps - 1) // ps for l in lengths)
    need = sum((int(l) + ps - 1) // ps for l in lengths)
    num_pages = num_pages or need + 3
    k_pool = jnp.asarray(
        rng.integers(0, 256, size=(num_pages, hkv, ps, d // 2)), jnp.uint8)
    v_pool = jnp.asarray(
        rng.integers(0, 256, size=(num_pages, hkv, ps, d // 2)), jnp.uint8)
    tbl = np.full((b, npages), -1, np.int32)
    perm = rng.permutation(num_pages)
    i = 0
    for bi, l in enumerate(lengths):
        n = (int(l) + ps - 1) // ps
        tbl[bi, :n] = perm[i:i + n]
        i += n
    stats = lambda: (
        jnp.asarray(rng.uniform(0.05, 0.2, size=(hkv, 1, d)), jnp.float32),
        jnp.asarray(rng.uniform(6.0, 9.0, size=(hkv, 1, d)), jnp.float32))
    ks, kz = stats()
    vs, vz = stats()
    return (k_pool, ks, kz, v_pool, vs, vz,
            jnp.asarray(tbl), jnp.asarray(lengths, jnp.int32))


CASES = [
    # (b, hq, hkv, d, ps, lengths)
    (1, 4, 1, 64, 32, [7]),              # MQA, len < one page
    (2, 8, 2, 64, 32, [33, 64]),         # GQA 4, ragged + page-aligned
    (2, 8, 8, 128, 64, [100, 17]),       # MHA, len % ps != 0
    (4, 8, 2, 64, 128, [5, 130, 256, 200]),   # batch 4, big pages
    (3, 16, 4, 64, 64, [64, 1, 190]),    # GQA 4, len == 1 edge
    (4, 8, 2, 64, 32, [300, 3, 2, 1]),   # one dominant long-context row
]


@pytest.mark.parametrize("b,hq,hkv,d,ps,lengths", CASES)
@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_paged_matches_oracle(rng, b, hq, hkv, d, ps, lengths, impl):
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    kp, ks, kz, vp, vs, vz, tbl, lens = make_paged(
        rng, b, hkv, d, ps, lengths)
    o_ref = ref.paged_kv4_decode_attention_ref(
        q, kp, ks, kz, vp, vs, vz, tbl, lens)
    o = ops.paged_kv4_decode_attention(
        q, kp, ks, kz, vp, vs, vz, tbl, lens, impl=impl)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("ps", [32, 64, 128])
def test_page_size_sweep(rng, ps):
    b, hq, hkv, d = 3, 8, 2, 64
    lengths = [ps - 1, ps, 2 * ps + 3]   # below / exact / across pages
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    kp, ks, kz, vp, vs, vz, tbl, lens = make_paged(
        rng, b, hkv, d, ps, lengths)
    o_ref = ref.paged_kv4_decode_attention_ref(
        q, kp, ks, kz, vp, vs, vz, tbl, lens)
    o_pal = ops.paged_kv4_decode_attention(
        q, kp, ks, kz, vp, vs, vz, tbl, lens, impl="pallas")
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)


def test_paged_matches_gather_on_cache(rng):
    """Through the real cache: paged kernel on the pools == contiguous
    kernel on gather_kv's materialization (both Pallas, f32)."""
    cfg = get_smoke_config("llama3_8b")
    ps = 4
    cache = PagedKV4Cache(
        cfg, PagedKV4Config(num_pages=32, page_size=ps, max_seqs=4,
                            max_pages_per_seq=16), 1)
    hkv, d = cfg.num_kv_heads, cfg.head_dim
    lengths = [10, 3, 17]
    for sid, t in enumerate(lengths):
        k = jnp.asarray(rng.normal(size=(1, t, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, t, hkv, d)), jnp.float32)
        assert cache.allocate_seq(sid, t)
        cache.write_prompt(0, sid, k, v)
        cache.seq_len[sid] = t
    slots = [0, 1, 2]
    # page-multiple so the contiguous kernel's chunking divides evenly
    max_len = -(-max(lengths) // ps) * ps
    lens = cache.lengths_device(slots)
    tbl = cache.block_tables_device(slots, max_len)
    q = jnp.asarray(rng.normal(size=(3, cfg.num_heads, d)), jnp.float32)

    o_paged = ops.paged_kv4_decode_attention(
        q, cache.k_pool[0], cache.k_scale, cache.k_zero,
        cache.v_pool[0], cache.v_scale, cache.v_zero,
        tbl, lens, impl="pallas")

    kp, vp, _ = cache.gather_kv(0, slots, max_len)
    bcast = lambda s: jnp.broadcast_to(s[None], (3, *s.shape))
    o_gather = ops.kv4_decode_attention(
        q, kp, bcast(cache.k_scale), bcast(cache.k_zero),
        vp, bcast(cache.v_scale), bcast(cache.v_zero),
        lens, impl="pallas", bt=ps)
    np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_gather),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- prefill

PREFILL_CASES = [
    # (b, hq, hkv, d, ps, ctx_lens, q_lens, C)
    (1, 4, 1, 64, 32, [40], [16], 16),            # MQA, ragged history
    (2, 8, 2, 64, 32, [0, 33], [8, 3], 8),        # zero-history + ragged
    (2, 8, 8, 128, 64, [100, 17], [16, 16], 16),  # MHA, len % ps != 0
    (3, 16, 4, 64, 64, [64, 1, 190], [1, 7, 16], 16),  # GQA, len-1 edges
    # the unified engine's union batch: a decode row (qlen 1, long int4
    # history), a first-chunk row, a mid-prefill row, and a zero-qlen
    # bucket-padding row — one kernel call serves all four
    (4, 8, 2, 64, 32, [150, 0, 33, 64], [1, 8, 3, 0], 8),
]


def make_prefill(rng, b, hq, hkv, d, ps, ctx_lens, q_lens, c):
    kp, ks, kz, vp, vs, vz, tbl, _ = make_paged(
        rng, b, hkv, d, ps, [max(l, 1) for l in ctx_lens])
    q = jnp.asarray(rng.normal(size=(b, c, hq, d)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, c, hkv, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, c, hkv, d)), jnp.float32)
    return (q, kn, vn, kp, ks, kz, vp, vs, vz, tbl,
            jnp.asarray(ctx_lens, jnp.int32), jnp.asarray(q_lens, jnp.int32))


@pytest.mark.parametrize("b,hq,hkv,d,ps,ctx_lens,q_lens,c", PREFILL_CASES)
@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_prefill_matches_oracle(rng, b, hq, hkv, d, ps, ctx_lens, q_lens, c,
                                impl):
    args = make_prefill(rng, b, hq, hkv, d, ps, ctx_lens, q_lens, c)
    o_ref = ref.paged_kv4_prefill_attention_ref(*args)
    o = ops.paged_kv4_prefill_attention(*args, impl=impl)
    # rows past q_lens are padding garbage — compare valid rows only
    for bi, ql in enumerate(q_lens):
        np.testing.assert_allclose(
            np.asarray(o)[bi, :ql], np.asarray(o_ref)[bi, :ql],
            rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_prefill_zero_history_is_causal_flash(rng, impl):
    """ctx = 0 → the kernel is plain fp causal attention over the chunk
    (the whole-prompt-in-one-chunk case must match the fp prefill path)."""
    b, hq, hkv, d, ps, c = 2, 8, 2, 64, 32, 16
    q = jnp.asarray(rng.normal(size=(b, c, hq, d)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, c, hkv, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, c, hkv, d)), jnp.float32)
    kp = jnp.zeros((1, hkv, ps, d // 2), jnp.uint8)
    ks = jnp.ones((hkv, 1, d), jnp.float32)
    kz = jnp.zeros((hkv, 1, d), jnp.float32)
    o = ops.paged_kv4_prefill_attention(
        q, kn, vn, kp, ks, kz, kp, ks, kz,
        jnp.zeros((b, 0), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.full((b,), c, jnp.int32), impl=impl)
    o_flash = flash_attention(q, kn, vn, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_flash),
                               rtol=1e-4, atol=1e-4)


def test_prefill_last_row_matches_decode(rng):
    """A single-query chunk over history of length L equals the DECODE
    kernel attending over the same pages with the new token's KV written
    at L — the prefill/decode seam is seamless. The new token's KV is
    placed exactly on the int4 grid so fp-chunk attention (prefill) and
    int4-pool attention (decode) see identical values."""
    b, hq, hkv, d, ps = 2, 8, 2, 64, 32
    lengths = [40, 17]
    kp, ks, kz, vp, vs, vz, tbl, _ = make_paged(
        rng, b, hkv, d, ps, [l + 1 for l in lengths])
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)), jnp.float32)
    # grid-exact new token: nibbles → dequantize → fp chunk values
    nk = rng.integers(0, 16, size=(b, hkv, d)).astype(np.float32)
    nv = rng.integers(0, 16, size=(b, hkv, d)).astype(np.float32)
    kn = ((nk - np.asarray(kz)[None, :, 0]) * np.asarray(ks)[None, :, 0])
    vn = ((nv - np.asarray(vz)[None, :, 0]) * np.asarray(vs)[None, :, 0])
    kn = jnp.asarray(kn[:, None], jnp.float32)     # [B, 1, Hkv, D]
    vn = jnp.asarray(vn[:, None], jnp.float32)
    o_pre = ops.paged_kv4_prefill_attention(
        q, kn, vn, kp, ks, kz, vp, vs, vz, tbl,
        jnp.asarray(lengths, jnp.int32), jnp.ones((b,), jnp.int32),
        impl="pallas")
    # write the same token (packed nibbles) into the pools at position L
    half = d // 2
    pk = (nk[..., :half].astype(np.uint8)
          | (nk[..., half:].astype(np.uint8) << 4))
    pv = (nv[..., :half].astype(np.uint8)
          | (nv[..., half:].astype(np.uint8) << 4))
    kp_np, vp_np = np.asarray(kp).copy(), np.asarray(vp).copy()
    tbl_np = np.asarray(tbl)
    for bi, l in enumerate(lengths):
        page, off = tbl_np[bi, l // ps], l % ps
        kp_np[page, :, off] = pk[bi]
        vp_np[page, :, off] = pv[bi]
    o_dec = ops.paged_kv4_decode_attention(
        q[:, 0], jnp.asarray(kp_np), ks, kz, jnp.asarray(vp_np), vs, vz,
        tbl, jnp.asarray([l + 1 for l in lengths], jnp.int32),
        impl="pallas")
    np.testing.assert_allclose(np.asarray(o_pre)[:, 0], np.asarray(o_dec),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------- work queue

@pytest.mark.parametrize("b,hq,hkv,d,ps,lengths", CASES)
@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_wq_decode_matches_dense_oracle(rng, b, hq, hkv, d, ps, lengths,
                                        impl):
    """The flat work-queue schedule == the dense oracle, and its work
    count covers only real pages (≈ Σ pages, not B·max_npages)."""
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    kp, ks, kz, vp, vs, vz, tbl, lens = make_paged(
        rng, b, hkv, d, ps, lengths)
    o_dense = ref.paged_kv4_decode_attention_ref(
        q, kp, ks, kz, vp, vs, vz, tbl, lens)
    desc = build_work_queue(np.asarray(tbl), lengths, ps, hkv)
    o = ops.paged_kv4_decode_attention_wq(
        q, kp, ks, kz, vp, vs, vz, jnp.asarray(desc), impl=impl)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_dense),
                               rtol=1e-4, atol=1e-4)
    real = int((desc[:, 2] > 0).sum())
    assert real == hkv * sum(-(-int(l) // ps) for l in lengths)
    # pow-2 padded, never the dense rectangle's worth of extra lanes
    assert real <= desc.shape[0] < 2 * max(real, 4) + 8


@pytest.mark.parametrize("b,hq,hkv,d,ps,ctx_lens,q_lens,c", PREFILL_CASES)
@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_wq_prefill_matches_dense_oracle(rng, b, hq, hkv, d, ps, ctx_lens,
                                         q_lens, c, impl):
    """Work-queue prefill (history page items + causal chunk items) ==
    the dense oracle on every valid row — including the union-batch case
    with a decode row, a first-chunk row, and a qlen-0 pad row."""
    args = make_prefill(rng, b, hq, hkv, d, ps, ctx_lens, q_lens, c)
    q, kn, vn, kp, ks, kz, vp, vs, vz, tbl, ctx, qls = args
    o_dense = ref.paged_kv4_prefill_attention_ref(*args)
    desc = build_work_queue(np.asarray(tbl), ctx_lens, ps, hkv, q_lens)
    o = ops.paged_kv4_prefill_attention_wq(
        q, kn, vn, kp, ks, kz, vp, vs, vz, jnp.asarray(desc), impl=impl)
    for bi, ql in enumerate(q_lens):
        np.testing.assert_allclose(
            np.asarray(o)[bi, :ql], np.asarray(o_dense)[bi, :ql],
            rtol=1e-4, atol=1e-4)
    # qlen-0 rows contribute no chunk item; ctx-0 rows no page items
    real = int((desc[:, 2] > 0).sum())
    assert real == hkv * (sum(-(-int(l) // ps) for l in ctx_lens)
                          + sum(1 for l in q_lens if l > 0))


@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_wq_prefill_zero_history_is_causal_flash(rng, impl):
    """ctx = 0 everywhere → only chunk items exist and the work-queue
    kernel reduces to plain fp causal attention over the chunk."""
    b, hq, hkv, d, ps, c = 2, 8, 2, 64, 32, 16
    q = jnp.asarray(rng.normal(size=(b, c, hq, d)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, c, hkv, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, c, hkv, d)), jnp.float32)
    kp = jnp.zeros((1, hkv, ps, d // 2), jnp.uint8)
    ks = jnp.ones((hkv, 1, d), jnp.float32)
    kz = jnp.zeros((hkv, 1, d), jnp.float32)
    desc = build_work_queue(np.zeros((b, 1), np.int32), [0, 0], ps, hkv,
                            [c, c])
    o = ops.paged_kv4_prefill_attention_wq(
        q, kn, vn, kp, ks, kz, kp, ks, kz, jnp.asarray(desc), impl=impl)
    o_flash = flash_attention(q, kn, vn, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_flash),
                               rtol=1e-4, atol=1e-4)


def test_build_work_queue_layout():
    """Descriptor contract: row-major item order, real-page coverage,
    per-page token counts, pow-2 padding with the sentinel row."""
    tbl = np.asarray([[5, 3, 7, -1], [2, -1, -1, -1]])
    desc = build_work_queue(tbl, [70, 9], page_size=32, num_kv_heads=2,
                            q_lens=[4, 0])
    # seq 0: 3 pages (32+32+6) + chunk, per head; seq 1: 1 page (9 tok)
    real = desc[desc[:, 2] > 0]
    assert len(real) == 2 * (3 + 1) + 2 * 1
    np.testing.assert_array_equal(
        real[:4], [[0, 5, 32, 0], [0, 3, 32, 0], [0, 7, 6, 0],
                   [0, 0, 4, 1]])                    # head 0 of seq 0
    np.testing.assert_array_equal(real[8], [2, 2, 9, 0])   # seq 1, head 0
    assert desc.shape[0] == 16                       # pow-2 padded
    assert (desc[len(real):, 0] == 4).all()          # sentinel row B·Hkv
    assert (desc[len(real):, 2] == 0).all()
    # bucketed batches override the sentinel so it clears the padded
    # row count (rows [B, Nb) are live qlen-0 segments in the combine)
    desc8 = build_work_queue(tbl, [70, 9], 32, 2, [4, 0], pad_row=8 * 2)
    assert (desc8[len(real):, 0] == 16).all()
    np.testing.assert_array_equal(desc8[:len(real)], real)
    with pytest.raises(IndexError):
        build_work_queue(tbl, [70, 40], 32, 2)       # unmapped page hit


def test_batched_append_matches_per_seq(rng):
    """append_tokens (one scatter) == per-sequence append_token loop."""
    cfg = get_smoke_config("llama3_8b")
    hkv, d = cfg.num_kv_heads, cfg.head_dim
    pcfg = PagedKV4Config(num_pages=16, page_size=4, max_seqs=4,
                          max_pages_per_seq=8)
    a = PagedKV4Cache(cfg, pcfg, 1)
    b_ = PagedKV4Cache(cfg, pcfg, 1)
    lengths = [3, 4, 9]                  # mid-page / page-boundary cases
    for sid, t in enumerate(lengths):
        for c in (a, b_):
            assert c.allocate_seq(sid, t + 1)
            c.seq_len[sid] = t
    k = jnp.asarray(rng.normal(size=(3, 1, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(3, 1, hkv, d)), jnp.float32)
    a.append_tokens(0, [0, 1, 2], k, v)
    for bi in range(3):
        b_.append_token(0, bi, k[bi:bi + 1], v[bi:bi + 1])
    np.testing.assert_array_equal(np.asarray(a.k_pool), np.asarray(b_.k_pool))
    np.testing.assert_array_equal(np.asarray(a.v_pool), np.asarray(b_.v_pool))
