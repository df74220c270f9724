"""Paged KV4 cache — vLLM-style block tables over packed int4 storage.

The physical pool is ``[num_pages, Hkv, page_size, D/2]`` uint8 per layer
stack (one K pool + one V pool, layers stacked on the leading axis): one
page of one kv head is a contiguous ``[page_size, D/2]`` tile, the block
the paged attention kernels fetch per grid step.
Sequences own pages through a block table ``[max_seqs, max_pages]`` int32
(-1 = unmapped) plus an O(1) per-sequence page count maintained by the
allocator (no row scans on the hot path). Appending a token touches
exactly one page; eviction frees whole pages. Per-channel scales/zeros
are static (calibrated), so pages never need rescaling — the property
that makes int4 paging cheap.

The decode hot path is gather-free: `block_tables_device`/
`lengths_device` hand the physical indirection straight to the
block-table-aware paged attention kernel, which resolves
``(seq, logical page) → physical page`` in its index maps — decode is
O(pages touched). Page destinations for a step's appends are computed
once on the host (`token_dests`) and reused by every layer's
`scatter_tokens` call — one block-table lookup + validation per step,
not per layer.

Prefill is chunk-granular: `grow_to` acquires pages for the next chunk
only (admission never reserves a whole prompt), and `scatter_tokens`
writes a ragged chunk's quantized KV into precomputed (page, offset)
destinations — prompts stream through the pools incrementally, so a
prompt's KV is never resident in fp beyond the in-flight chunk.

**Page ownership is refcounted** (not per-seq): every mapped page
carries a reference count, and full prompt pages can be *published*
into a chained-hash prefix index (`publish_prefix`) once their
sequence's prefill completes. A later request whose prompt shares that
prefix adopts the published pages at admission (`match_prefix` →
`allocate_seq(prefix_pages=...)`): its block table starts with the
shared pages (ref+1 each) and only the un-cached suffix is ever
forwarded or written. Shared pages are written by nobody — a sequence
only writes positions >= its matched prefix, which land in its private
pages; static per-channel scales make the int4 bytes position- and
request-independent, so published pages are bit-exact for every reader.

Freeing is refcount-exact: `free_seq` decrements every mapped page;
pages reaching ref==0 go back to the free list unless they are
published, in which case they move to a *reclaimable* LRU — still
cached (a future `match_prefix` revives them) but counted in
`pages_free` and evicted LRU-first the moment an allocation would
otherwise fail, BEFORE any scheduler preemption fires.

**Work-queue schedule (host side).** `build_work_queue` /
`work_queue_np` flatten a ragged batch's real pages into the Stream-K
descriptor array the work-queue attention kernels walk: one item per
``(seq, kv_head, page)`` covering only mapped history (plus one
in-flight-chunk item per row), padded to a power-of-two count so the
kernel grid is a uniform pool of work instead of the dense
``(B·Hkv, max_npages)`` rectangle that serializes long rows and pads
short ones (see `kernels/paged_attention.py` for the device side).

The reclaimable prefix LRU can be capped by bytes
(``reclaimable_max_bytes``): publishing beyond the cap evicts
oldest-first, `prefix_evicted_pages` counts every eviction (cap or
allocation pressure), and `prefix_reclaimable_bytes` reports the
resident overhang — the observability a long-running server needs to
size the cache.

The legacy gather path (`gather_kv`) that materializes a sequence's
packed KV contiguously (a per-token O(context) copy) is retained only as
the benchmark baseline and for tests.
"""

from __future__ import annotations

import base64
import dataclasses
import hashlib
import json
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.serving.faults import InjectedFault

__all__ = ["PagedKV4Config", "PagedKV4Cache", "build_work_queue",
           "quantize_kv_with", "qdq_kv_with"]


@dataclasses.dataclass(frozen=True)
class PagedKV4Config:
    num_pages: int
    page_size: int = 64
    max_seqs: int = 64
    max_pages_per_seq: int = 128
    reclaimable_max_bytes: Optional[int] = None  # byte cap on the prefix LRU


def build_work_queue(block_tables, ctx_lens, page_size: int,
                     num_kv_heads: int, q_lens=None,
                     min_items: int = 8,
                     pad_row: Optional[int] = None,
                     seq_ids=None) -> np.ndarray:
    """Flatten a ragged batch into Stream-K work descriptors.

    → ``[W, 4]`` int32 rows ``(row, phys_page, count, kind)``:

    * ``row``  — output-row segment id ``seq_idx · Hkv + kv_head`` the
      item's partial belongs to (the split-KV combine's segment key);
    * ``phys_page`` — physical pool page (kind 0) — the kernel's
      BlockSpec index map reads it directly, no block-table walk;
    * ``count`` — valid tokens in the page (kind 0: ``min(ps, ctx −
      page_start)``) or the row's causal chunk length (kind 1);
    * ``kind`` — 0: int4 history page, 1: in-flight fp chunk.

    One item per ``(seq, kv_head, real page)`` — pages past ``ctx_lens``
    simply don't exist, so Σ items ≈ Σ real pages, not ``B × max_pages``
    — plus, when ``q_lens`` is given, one chunk item per row with
    ``q_lens > 0``. Items are ordered row-major so consecutive grid
    steps reuse the row's query block (one-to-many tile binding).

    ``W`` is padded to a power of two (≥ ``min_items``); padding rows
    carry the sentinel ``pad_row`` (default ``B·Hkv`` — pass the
    *bucketed* row count when the consumer pads the batch beyond B, so
    the sentinel stays out of every live segment) and ``count = 0`` —
    the combine's segment scatter drops them.

    ``seq_ids`` (optional, [B]) names the caller's sequences — engine
    cache slots — purely for diagnostics: the unmapped-page error
    reports these instead of positional batch indices, which are
    misleading whenever the batch is a non-contiguous slot subset.
    """
    tables = np.atleast_2d(np.asarray(block_tables))
    ctx = np.atleast_1d(np.asarray(ctx_lens)).astype(np.int64)
    b, ps, hkv = ctx.shape[0], page_size, num_kv_heads
    # fully vectorized (this runs every engine step): flatten each
    # sequence's real pages in seq order, scatter the optional chunk
    # item behind them, then tile the per-seq stream across kv heads
    npg = -(-ctx // ps)                              # real pages per seq
    has_chunk = (np.zeros(b, np.int64) if q_lens is None else
                 (np.atleast_1d(np.asarray(q_lens)) > 0).astype(np.int64))
    qls = (None if q_lens is None
           else np.atleast_1d(np.asarray(q_lens)).astype(np.int64))
    seq_of_pg = np.repeat(np.arange(b), npg)
    pg_off = np.concatenate([[0], np.cumsum(npg)])
    pg_idx = np.arange(pg_off[-1]) - pg_off[seq_of_pg]
    pages_flat = tables[seq_of_pg, pg_idx]
    if (pages_flat < 0).any():
        bad_idx = np.unique(seq_of_pg[pages_flat < 0])
        if seq_ids is not None:
            bad = np.atleast_1d(np.asarray(seq_ids))[bad_idx].tolist()
            what = "seq slot(s)"
        else:
            bad = bad_idx.tolist()
            what = "batch row(s)"
        raise IndexError(
            f"work queue over unmapped page(s) for {what} {bad} — "
            "grow capacity first")
    counts_flat = np.minimum(ps, ctx[seq_of_pg] - ps * pg_idx)
    # per-seq item streams: pages first, then the chunk item (if any)
    n_per_seq = npg + has_chunk
    off = np.concatenate([[0], np.cumsum(n_per_seq)])
    tot = int(off[-1])
    pages_c = np.zeros(tot, np.int64)
    counts_c = np.zeros(tot, np.int64)
    kinds_c = np.zeros(tot, np.int64)
    pg_pos = off[seq_of_pg] + pg_idx
    pages_c[pg_pos] = pages_flat
    counts_c[pg_pos] = counts_flat
    if qls is not None:
        ch = np.nonzero(has_chunk)[0]
        ch_pos = off[ch] + npg[ch]
        counts_c[ch_pos] = qls[ch]
        kinds_c[ch_pos] = 1
    # tile across heads, row-major: block k = i·hkv + h replays seq i's
    # stream, so consecutive grid steps share the row's query block
    reps = np.repeat(n_per_seq, hkv)                 # items per (seq, head)
    bs = np.cumsum(reps) - reps                      # flat block starts
    within = np.arange(int(reps.sum())) - np.repeat(bs, reps)
    src = np.repeat(off[np.repeat(np.arange(b), hkv)], reps) + within
    w = max(len(src), 1)
    wb = max(min_items, 1 << (w - 1).bit_length())
    desc = np.zeros((wb, 4), np.int32)
    desc[:, 0] = b * hkv if pad_row is None else pad_row
    desc[:len(src), 0] = np.repeat(np.arange(b * hkv), reps)
    desc[:len(src), 1] = pages_c[src]
    desc[:len(src), 2] = counts_c[src]
    desc[:len(src), 3] = kinds_c[src]
    return desc


def quantize_kv_with(k, v, k_scale, k_zero, v_scale, v_zero):
    """k/v: [B, T, Hkv, D] float → packed [B, Hkv, T, D/2] uint8.

    Module-level (explicit scales) so the TP-sharded forward can pass
    per-shard scale slices through ``shard_map`` — ``Hkv`` here is
    whatever the scale arrays say (local heads under TP)."""
    def pack(x, scale, zero):
        xt = x.swapaxes(1, 2).astype(jnp.float32)          # [B, Hkv, T, D]
        n = jnp.clip(jnp.round(xt / scale + zero), 0, 15).astype(jnp.uint8)
        half = n.shape[-1] // 2
        return (n[..., :half] | (n[..., half:] << 4)).astype(jnp.uint8)
    return pack(k, k_scale, k_zero), pack(v, v_scale, v_zero)


def qdq_kv_with(k, v, k_scale, k_zero, v_scale, v_zero):
    """Fake-quantize k/v ([B, T, Hkv, D] float) through the int4
    codebook → the exact f32 values a reader dequantizes from the
    pools. Explicit-scale sibling of :meth:`PagedKV4Cache.qdq_kv`."""
    def roundtrip(x, scale, zero):
        xt = x.swapaxes(1, 2).astype(jnp.float32)          # [B, Hkv, T, D]
        n = jnp.clip(jnp.round(xt / scale + zero), 0, 15)
        return ((n - zero) * scale).swapaxes(1, 2)
    return (roundtrip(k, k_scale, k_zero), roundtrip(v, v_scale, v_zero))


class PagedKV4Cache:
    """Host-managed page allocator + device-resident pools.

    Allocation/free run in Python (the engine's scheduler thread);
    device ops (append, gather) are jittable pure functions over the
    pool arrays.
    """

    # rule R1 (snapshot-completeness) allowlist: constructor-derived
    # config/calibration state the restore path rebuilds from the same
    # ctor args (scales/zeros are pure functions of cfg + kv_range), and
    # the engine-injected fault harness, which never crosses a snapshot.
    _SNAPSHOT_EXEMPT = frozenset({
        "cfg", "pcfg", "k_scale", "k_zero", "v_scale", "v_zero",
        "page_bytes", "faults",
    })

    def __init__(self, cfg: ModelConfig, pcfg: PagedKV4Config,
                 num_layer_slots: int,
                 k_stats=None, v_stats=None, kv_range: float = 16.0):
        self.cfg = cfg
        self.pcfg = pcfg
        hkv, d = cfg.num_kv_heads, cfg.head_dim
        shape = (num_layer_slots, pcfg.num_pages, hkv, pcfg.page_size, d // 2)
        self.k_pool = jnp.zeros(shape, jnp.uint8)
        self.v_pool = jnp.zeros(shape, jnp.uint8)

        def default_stats(rng):
            # symmetric range ±rng mapped onto [0, 15] (asym affine)
            scale = jnp.full((hkv, 1, d), rng / 15.0, jnp.float32)
            zero = jnp.full((hkv, 1, d), 7.5, jnp.float32)
            return scale, zero

        self.k_scale, self.k_zero = k_stats or default_stats(kv_range)
        self.v_scale, self.v_zero = v_stats or default_stats(kv_range)

        self.block_table = np.full(
            (pcfg.max_seqs, pcfg.max_pages_per_seq), -1, np.int32)
        self.seq_len = np.zeros((pcfg.max_seqs,), np.int32)
        self.page_count = np.zeros((pcfg.max_seqs,), np.int32)
        self.free_pages = list(range(pcfg.num_pages - 1, -1, -1))
        self.active = set()
        # refcounted ownership + prefix cache: ref[p] = sequences mapping
        # page p; prefix_index: chain-hash key → published physical page;
        # page_key: inverse map for published pages; _reclaimable: LRU of
        # published pages with ref==0 (cached but immediately evictable)
        self.ref = np.zeros((pcfg.num_pages,), np.int32)
        self.prefix_index: dict = {}
        self.page_key: dict = {}
        self._reclaimable: OrderedDict = OrderedDict()
        # prefix-LRU observability: bytes one page pins in the pools
        # (K + V across the layer stack) and lifetime eviction count
        self.page_bytes = 2 * num_layer_slots * pcfg.page_size * hkv * (d // 2)
        self.prefix_evicted_pages = 0
        # optional FaultInjector (serving/faults.py) the engine shares
        # with the cache; consulted at alloc_page / append_kv
        self.faults = None

    # ------------------------------------------------------------- allocator

    @property
    def pages_free(self) -> int:
        """Pages allocatable right now: the free list plus published
        ref==0 pages (evicted LRU-first on demand)."""
        return len(self.free_pages) + len(self._reclaimable)

    @property
    def prefix_reclaimable_bytes(self) -> int:
        """Pool bytes pinned by published ref==0 pages (the LRU)."""
        return len(self._reclaimable) * self.page_bytes

    def _evict_reclaimable(self) -> Optional[int]:
        """Drop the LRU reclaimable page's index entry; → page id."""
        if not self._reclaimable:
            return None
        p, key = self._reclaimable.popitem(last=False)
        del self.prefix_index[key]
        del self.page_key[p]
        self.prefix_evicted_pages += 1
        return p

    def _acquire_page(self) -> Optional[int]:
        """Pop a free page, evicting the LRU reclaimable prefix page
        (and its index entry) if the free list is empty. Eviction runs
        BEFORE any scheduler preemption can fire: allocation only fails
        once both pools are dry."""
        if self.faults is not None and self.faults.check("alloc_page"):
            return None         # injected exhaustion — same shape as dry
        if self.free_pages:
            p = self.free_pages.pop()
        else:
            p = self._evict_reclaimable()
            if p is None:
                return None
        self.ref[p] = 1
        return p

    def _adopt_page(self, p: int):
        """Take a reference on a published page (a prefix-cache hit)."""
        if int(self.ref[p]) == 0:
            self._reclaimable.pop(p, None)
        self.ref[p] += 1

    def _release_page(self, p: int):
        self.ref[p] -= 1
        if self.ref[p] > 0:
            return                      # still shared
        key = self.page_key.get(p)
        if key is not None and self.prefix_index.get(key) == p:
            # published: keep the content cached, evictable LRU-first —
            # unless the byte cap says the LRU is already full, in
            # which case evict oldest-first down to the cap
            self._reclaimable[p] = key
            self._reclaimable.move_to_end(p)
            cap = self.pcfg.reclaimable_max_bytes
            while (cap is not None
                   and self.prefix_reclaimable_bytes > cap):
                self.free_pages.append(self._evict_reclaimable())
        else:
            self.free_pages.append(p)

    @property
    def max_tokens_per_seq(self) -> int:
        return self.pcfg.max_pages_per_seq * self.pcfg.page_size

    def pages_needed(self, tokens: int) -> int:
        ps = self.pcfg.page_size
        return (tokens + ps - 1) // ps

    def pages_available_for(self, prefix_pages) -> int:
        """Acquirable pages for an allocation that will adopt
        ``prefix_pages``: matched pages sitting on the reclaimable LRU
        (ref==0) count in ``pages_free`` but are about to be adopted —
        they cannot double as headroom for the new acquisitions."""
        reserved = sum(1 for p in prefix_pages if int(self.ref[int(p)]) == 0)
        return self.pages_free - reserved

    def allocate_seq(self, seq_id: int, reserve_tokens: int,
                     prefix_pages: tuple = (),
                     prefix_tokens: int = 0) -> bool:
        """Reserve pages for ``reserve_tokens`` (a whole prompt, or just
        its first prefill chunk); False if pool exhausted or the request
        exceeds the per-sequence page cap.

        ``prefix_pages``/``prefix_tokens`` (from :meth:`match_prefix`):
        published pages covering the request's shared prompt prefix —
        adopted (ref+1) instead of allocated, so only the un-cached
        suffix is charged to the pool; ``seq_len`` starts at the end of
        the shared prefix (its KV is already resident)."""
        need = max(self.pages_needed(reserve_tokens), len(prefix_pages))
        if (need - len(prefix_pages) > self.pages_available_for(prefix_pages)
                or seq_id in self.active
                or need > self.pcfg.max_pages_per_seq):
            return False
        for i, p in enumerate(prefix_pages):
            self._adopt_page(int(p))
            self.block_table[seq_id, i] = int(p)
        for i in range(len(prefix_pages), need):
            p = self._acquire_page()
            if p is None:
                # mid-loop exhaustion (the availability check races with
                # nothing here, but prefix adoption above can consume
                # reclaimable pages the estimate counted as free): roll
                # back every reference this call took — adopted prefix
                # refs AND already-acquired pages — so the block table
                # never holds a poisoned slot and the caller sees a
                # clean False, exactly like the up-front failure path
                for j in range(i):
                    self._release_page(int(self.block_table[seq_id, j]))
                self.block_table[seq_id, :i] = -1
                return False
            self.block_table[seq_id, i] = p
        self.seq_len[seq_id] = prefix_tokens
        self.page_count[seq_id] = need
        self.active.add(seq_id)
        return True

    def extend_seq(self, seq_id: int) -> bool:
        """Ensure capacity for one more token; may grab a new page.
        O(1): uses the maintained per-sequence page count, no row scan."""
        ln = int(self.seq_len[seq_id])
        need = self.pages_needed(ln + 1)
        have = int(self.page_count[seq_id])
        if need <= have:
            return True
        if need > self.pcfg.max_pages_per_seq:
            return False
        p = self._acquire_page()
        if p is None:
            return False
        self.block_table[seq_id, have] = p
        self.page_count[seq_id] = have + 1
        return True

    def at_capacity(self, seq_id: int) -> bool:
        """True when the sequence can NEVER grow another token — it has
        hit ``max_pages_per_seq``, or it would need more pages than the
        whole pool owns — so preemption cannot help it. (The pool bound
        also guarantees preempted sequences are always re-admissible:
        their folded prompt is at most the pages they already held.)"""
        return (self.pages_needed(int(self.seq_len[seq_id]) + 1)
                > min(self.pcfg.max_pages_per_seq, self.pcfg.num_pages))

    def grow_to(self, seq_id: int, target_tokens: int) -> int:
        """Acquire pages toward ``target_tokens`` capacity (chunked
        prefill's page-granular admission). Grabs as many pages as the
        pool allows, capped at ``max_pages_per_seq``; returns the token
        capacity actually backed by pages."""
        cap = min(self.pages_needed(target_tokens),
                  self.pcfg.max_pages_per_seq)
        have = int(self.page_count[seq_id])
        while have < cap:
            p = self._acquire_page()
            if p is None:
                break
            self.block_table[seq_id, have] = p
            have += 1
        self.page_count[seq_id] = have
        return have * self.pcfg.page_size

    def truncate_seq(self, seq_id: int, new_len: int) -> int:
        """Set the sequence's resident length to ``new_len`` tokens,
        releasing every page past ``pages_needed(new_len)`` — the
        speculative-decode rollback: a verify chunk scatters int4 KV
        for the whole k+1-token draft, and the unaccepted tail is
        retracted here, pages returning to their pre-draft baseline.

        Refcount/prefix-safe by construction: pages drop through the
        same :meth:`_release_page` path ``free_seq`` uses, so a shared
        (adopted) page survives for its other owners and a *published*
        page reaching ref==0 parks on the reclaimable LRU — still
        matchable — instead of the free list. ``new_len`` may also sit
        PAST ``seq_len`` (up to the page-backed capacity): the spec
        path writes KV beyond ``seq_len`` during verification and then
        lands the accepted length here in one move. Stale int4 bytes
        past ``new_len`` stay in the kept pages — attention masks by
        ``seq_len`` and the next append overwrites them. Returns the
        number of page references dropped."""
        if seq_id not in self.active:
            raise ValueError(f"truncate_seq: seq {seq_id} not active")
        have = int(self.page_count[seq_id])
        if not 0 <= new_len <= have * self.pcfg.page_size:
            raise ValueError(
                f"truncate_seq: new_len={new_len} outside the page-backed "
                f"range [0, {have * self.pcfg.page_size}] of seq {seq_id}")
        keep = self.pages_needed(new_len)
        for i in range(keep, have):
            self._release_page(int(self.block_table[seq_id, i]))
            self.block_table[seq_id, i] = -1
        self.page_count[seq_id] = min(have, keep)
        self.seq_len[seq_id] = new_len
        return max(0, have - keep)

    def free_seq(self, seq_id: int):
        """Drop the sequence's references. Private pages return to the
        free list; shared pages survive for their other owners; published
        pages reaching ref==0 stay cached on the reclaimable LRU."""
        pages = self.block_table[seq_id]
        for p in pages[pages >= 0]:
            self._release_page(int(p))
        self.block_table[seq_id, :] = -1
        self.seq_len[seq_id] = 0
        self.page_count[seq_id] = 0
        self.active.discard(seq_id)

    # ---------------------------------------------------------- prefix cache

    def _page_keys(self, tokens, nfull: int) -> list:
        """Chained page digests: key_i commits to ALL tokens through
        page i, so a single dict hit proves the whole prefix matches.
        SHA-256 (not builtin ``hash``): a page key maps straight to
        another request's KV pages, so keys must be collision-resistant
        even against adversarial prompts — builtin tuple hashing is
        predictable and forgeable."""
        ps = self.pcfg.page_size
        keys, key = [], b""
        for i in range(nfull):
            chunk = np.asarray(tokens[i * ps:(i + 1) * ps], np.int64)
            key = hashlib.sha256(key + chunk.tobytes()).digest()
            keys.append(key)
        return keys

    def match_prefix(self, tokens) -> tuple[list, int]:
        """Longest published prefix of ``tokens`` → (pages, matched).

        Walks full pages through the prefix index and stops at the first
        miss. Matching is capped one token short of the full prompt so
        at least one token always flows through prefill — the forward
        over that suffix is what produces the request's first logits.
        Pure lookup: takes no references (adoption happens inside
        :meth:`allocate_seq`, with no eviction possible in between)."""
        nfull = max(0, (len(tokens) - 1)) // self.pcfg.page_size
        pages = []
        for key in self._page_keys(tokens, nfull):
            p = self.prefix_index.get(key)
            if p is None:
                break
            pages.append(p)
        return pages, len(pages) * self.pcfg.page_size

    def publish_prefix(self, seq_id: int, tokens):
        """Publish the sequence's full prompt pages into the prefix
        index (called once its prefill completes — the pages' int4
        content is final; everything the sequence writes from here on
        lands in later, private pages). First publisher wins: a page
        whose chain key is already indexed is skipped, keeping its
        owner's copy private."""
        nfull = len(tokens) // self.pcfg.page_size
        for i, key in enumerate(self._page_keys(tokens, nfull)):
            if key in self.prefix_index:
                continue
            page = int(self.block_table[seq_id, i])
            if self.page_key.get(page) is not None:
                continue            # already published under another key
            self.prefix_index[key] = page
            self.page_key[page] = key

    # ------------------------------------------------------------- device ops

    def quantize_kv(self, k, v):
        """k/v: [B, T, Hkv, D] float → packed [B, Hkv, T, D/2]."""
        return quantize_kv_with(k, v, self.k_scale, self.k_zero,
                                self.v_scale, self.v_zero)

    def qdq_kv(self, k, v):
        """Fake-quantize K/V ([B, T, Hkv, D] float) through the pool's
        int4 codebook → the exact f32 values a reader dequantizes from
        the pools. The unified forward routes decode rows' in-flight
        chunk through this so their self-attention sees the same
        numerics as the split decode path (which reads the just-written
        int4 page) — greedy argmax then cannot flip on the fp-vs-int4
        difference of one token."""
        return qdq_kv_with(k, v, self.k_scale, self.k_zero,
                           self.v_scale, self.v_zero)

    def write_prompt(self, layer_slot: int, seq_id: int, k, v):
        """Write a prompt's packed KV ([1, T, Hkv, D] float) into pages."""
        kp, vp = self.quantize_kv(k, v)                    # [1, Hkv, T, D/2]
        t = kp.shape[2]
        ps = self.pcfg.page_size
        need = self.pages_needed(t)
        pad = need * ps - t
        kp = jnp.pad(kp, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(vp, ((0, 0), (0, 0), (0, pad), (0, 0)))
        # [Hkv, need, ps, D/2] → per page [Hkv, ps, D/2]
        kp = kp[0].reshape(kp.shape[1], need, ps, -1).swapaxes(0, 1)
        vp = vp[0].reshape(vp.shape[1], need, ps, -1).swapaxes(0, 1)
        pages = self.block_table[seq_id, :need]
        self.k_pool = self.k_pool.at[layer_slot, pages].set(kp)
        self.v_pool = self.v_pool.at[layer_slot, pages].set(vp)
        if layer_slot == 0:
            self.seq_len[seq_id] = t

    def append_token(self, layer_slot: int, seq_id: int, k, v,
                     pos: Optional[int] = None):
        """Write one token's KV ([1, 1, Hkv, D] float) at position ``pos``
        (default: current seq_len). Does NOT advance seq_len — call
        :meth:`advance` once after all layers have written."""
        kp, vp = self.quantize_kv(k, v)                     # [1, Hkv, 1, D/2]
        ln = int(self.seq_len[seq_id]) if pos is None else int(pos)
        ps = self.pcfg.page_size
        page = int(self.block_table[seq_id, ln // ps])
        off = ln % ps
        self.k_pool = self.k_pool.at[layer_slot, page, :, off].set(
            kp[0, :, 0, :])
        self.v_pool = self.v_pool.at[layer_slot, page, :, off].set(
            vp[0, :, 0, :])

    def token_dests_np(self, seq_ids, positions):
        """Host-side :meth:`token_dests`: validated numpy (pages, offs).

        The unified engine pads these up to its shape bucket (padding
        tokens get an out-of-range page id whose scatter update is
        dropped) before shipping them to the device once per step."""
        if self.faults is not None and self.faults.check("append_kv"):
            raise InjectedFault("append_kv: injected destination failure")
        seq_ids = np.atleast_1d(np.asarray(seq_ids))
        pos = np.atleast_1d(np.asarray(positions))
        ps = self.pcfg.page_size
        pages_np = self.block_table[seq_ids, pos // ps]
        if (pages_np < 0).any():
            raise IndexError(
                f"write into unmapped page(s) for seqs "
                f"{seq_ids[pages_np < 0].tolist()} — grow capacity first")
        return pages_np.astype(np.int32), (pos % ps).astype(np.int32)

    def token_dests(self, seq_ids, positions):
        """Resolve per-token (physical page, in-page offset) destinations
        on the host — ONCE per step — so every layer's scatter reuses the
        same validated device arrays instead of re-reading the block
        table ``num_layers`` times. → (pages [N] jnp, offs [N] jnp)."""
        pages_np, offs_np = self.token_dests_np(seq_ids, positions)
        return jnp.asarray(pages_np), jnp.asarray(offs_np)

    def scatter_tokens(self, layer_slot: int, pages, offs, k, v):
        """Quantize + scatter N tokens' KV into precomputed destinations.
        k/v is float ``[B, T, Hkv, D]`` with B·T == N tokens in
        (seq-major) order matching ``pages``/``offs`` — covers both the
        decode batch ([B, 1, ...]) and a ragged prefill chunk
        ([1, T, ...]); the chunk is the only fp KV ever materialized for
        a prompt."""
        kq, vq = self.quantize_kv(k, v)               # [B, Hkv, T, D/2]
        hkv, half = kq.shape[1], kq.shape[-1]
        kq = jnp.moveaxis(kq, 1, 2).reshape(-1, hkv, half)   # [N, Hkv, D/2]
        vq = jnp.moveaxis(vq, 1, 2).reshape(-1, hkv, half)
        self.k_pool = self.k_pool.at[layer_slot, pages, :, offs].set(kq)
        self.v_pool = self.v_pool.at[layer_slot, pages, :, offs].set(vq)

    def append_tokens(self, layer_slot: int, seq_ids, k, v, positions=None):
        """Batched one-token append: k/v ``[B, 1, Hkv, D]`` float, one
        scatter into the pools for the whole decode batch. Positions
        default to each sequence's current length; does NOT advance.
        (Hot path: compute :meth:`token_dests` once per step and call
        :meth:`scatter_tokens` per layer instead.)"""
        seq_ids = np.atleast_1d(np.asarray(seq_ids))
        pos = (self.seq_len[seq_ids] if positions is None
               else np.atleast_1d(np.asarray(positions)))
        pages, offs = self.token_dests(seq_ids, pos)
        self.scatter_tokens(layer_slot, pages, offs, k, v)

    def advance(self, seq_ids):
        for s in np.atleast_1d(seq_ids):
            self.seq_len[s] += 1

    # ---------------------------------------------- full-state snapshot

    def snapshot_state(self) -> str:
        """Serialize the ENTIRE cache — device pools included — for
        journaled crash recovery (``serving/recovery.py``).

        The legacy engine restore path re-prefills demoted requests, and
        a re-prefill runs the in-flight chunk in fp — numerics that can
        differ from the int4-history decode path by enough to flip a
        greedy argmax. Bitwise-identical continuation therefore needs
        the pools' int4 bytes verbatim, plus every piece of host
        allocator state *in iteration order* (free-list order and
        reclaimable-LRU order both steer future page assignment)."""
        pools = {
            "k": base64.b64encode(np.asarray(self.k_pool).tobytes()).decode(),
            "v": base64.b64encode(np.asarray(self.v_pool).tobytes()).decode(),
        }
        return json.dumps({
            "pool_shape": list(self.k_pool.shape),
            "pools": pools,
            "block_table": self.block_table.tolist(),
            "seq_len": self.seq_len.tolist(),
            "page_count": self.page_count.tolist(),
            "free_pages": list(self.free_pages),
            "ref": self.ref.tolist(),
            "active": sorted(self.active),
            "prefix_index": {k.hex(): int(v)
                             for k, v in self.prefix_index.items()},
            "page_key": {int(p): k.hex() for p, k in self.page_key.items()},
            "reclaimable": [[int(p), k.hex()]
                            for p, k in self._reclaimable.items()],
            "prefix_evicted_pages": self.prefix_evicted_pages,
        })

    def restore_state(self, blob: str):
        """Load a :meth:`snapshot_state` blob into THIS cache (built with
        the same configs — pool shape is validated). After this, decode
        resumes with the exact pool bytes and allocator order the
        snapshotted engine had."""
        state = json.loads(blob)
        shape = tuple(state["pool_shape"])
        if shape != tuple(self.k_pool.shape):
            raise ValueError(
                f"snapshot pool shape {shape} != cache pool shape "
                f"{tuple(self.k_pool.shape)} — restore needs an "
                "identically-configured cache")
        k = np.frombuffer(base64.b64decode(state["pools"]["k"]),
                          np.uint8).reshape(shape)
        v = np.frombuffer(base64.b64decode(state["pools"]["v"]),
                          np.uint8).reshape(shape)
        self.k_pool = jnp.asarray(k)
        self.v_pool = jnp.asarray(v)
        self.block_table = np.asarray(state["block_table"], np.int32)
        self.seq_len = np.asarray(state["seq_len"], np.int32)
        self.page_count = np.asarray(state["page_count"], np.int32)
        self.free_pages = list(state["free_pages"])
        self.ref = np.asarray(state["ref"], np.int32)
        self.active = set(state["active"])
        self.prefix_index = {bytes.fromhex(k): int(v)
                             for k, v in state["prefix_index"].items()}
        self.page_key = {int(p): bytes.fromhex(k)
                         for p, k in state["page_key"].items()}
        self._reclaimable = OrderedDict(
            (int(p), bytes.fromhex(k)) for p, k in state["reclaimable"])
        self.prefix_evicted_pages = state.get("prefix_evicted_pages", 0)

    # -------------------------------------------------- block-table views

    def work_queue_np(self, seq_ids, ctx_lens, q_lens=None,
                      min_items: int = 8,
                      pad_row: Optional[int] = None,
                      num_kv_heads: Optional[int] = None) -> np.ndarray:
        """Stream-K work descriptors for these sequences' *real* pages
        (see :func:`build_work_queue`): ``[W, 4]`` int32, W padded to a
        power of two. ``ctx_lens`` is the paged history per row;
        ``q_lens`` (optional) adds one in-flight-chunk item per row;
        ``pad_row`` overrides the padding sentinel for bucketed
        batches; ``num_kv_heads`` overrides the head count the stream
        is tiled over (the TP-sharded engine builds ONE descriptor with
        the per-shard local head count — each sequence's page stream is
        identical for every head, so the same local-head descriptor is
        valid on every model shard). Unmapped-page errors report the
        caller's ``seq_ids``, not positional batch indices."""
        return build_work_queue(
            self.block_table[np.asarray(seq_ids)], ctx_lens,
            self.pcfg.page_size,
            self.cfg.num_kv_heads if num_kv_heads is None else num_kv_heads,
            q_lens, min_items, pad_row, seq_ids=seq_ids)

    def block_tables_np(self, seq_ids, npages: int) -> np.ndarray:
        """[B, npages] int32 host table with unmapped slots (-1) clamped
        to 0 (masked by length in-kernel, never read semantically)."""
        tables = self.block_table[np.asarray(seq_ids), :npages]
        return np.maximum(tables, 0).astype(np.int32)

    def block_tables_device(self, seq_ids, max_len: int) -> jax.Array:
        """[B, NP] int32 physical-page table for the paged-attention
        kernel, sliced to the pages covering ``max_len``."""
        return jnp.asarray(
            self.block_tables_np(seq_ids, self.pages_needed(max_len)))

    def lengths_device(self, seq_ids) -> jax.Array:
        return jnp.asarray(self.seq_len[np.asarray(seq_ids)], jnp.int32)

    def gather_kv(self, layer_slot: int, seq_ids, max_len: int):
        """[Benchmark baseline] Materialize packed KV for a decode batch.

        → (k_packed, v_packed) [B, Hkv, max_len, D/2] plus lengths [B].
        Unmapped pages read page 0 but are masked by length in attention.
        """
        ps = self.pcfg.page_size
        npages = (max_len + ps - 1) // ps
        tables = jnp.asarray(
            np.where(self.block_table[seq_ids, :npages] < 0, 0,
                     self.block_table[seq_ids, :npages]))
        kp = self.k_pool[layer_slot][tables]    # [B, npages, Hkv, ps, D/2]
        vp = self.v_pool[layer_slot][tables]
        b, hkv = kp.shape[0], kp.shape[2]
        kp = jnp.moveaxis(kp, 2, 1).reshape(b, hkv, npages * ps, -1)
        vp = jnp.moveaxis(vp, 2, 1).reshape(b, hkv, npages * ps, -1)
        lengths = jnp.asarray(self.seq_len[seq_ids])
        return kp[:, :, :max_len], vp[:, :, :max_len], lengths
