"""COMET serving engine: request-lifecycle API over continuous batching
on the paged KV4 cache.

The engine is the paper's §5 system layer: W4Ax projections + int4 paged
KV + vLLM-style scheduling. Unlike the scanned `LM.decode` (used for the
compile-time dry-run), the engine walks layers in a Python loop so each
layer's attention reads/writes the *paged* pool directly — the realistic
serving dataflow.

**Public surface (the request lifecycle — see serving/api.py).**
``submit(prompt, params) -> RequestHandle`` enqueues a request with
per-request :class:`SamplingParams`; ``step()`` advances every in-flight
request one scheduling quantum and emits :class:`RequestOutput` events
(one per sampled token, plus a terminal event); ``events()`` drains the
engine-wide event queue, ``stream(handle)`` yields one request's events
as they happen (driving ``step()`` internally), and ``submit(...,
on_event=...)`` delivers push-style callbacks. ``abort(handle)`` cancels
at any state — QUEUED, PREFILLING, or DECODING — releasing pages
refcount-exactly. The legacy batch API (``add_request`` + ``run``) is a
thin compatibility wrapper over this lifecycle.

**Prefix caching.** Full prompt pages are published into the cache's
chained-hash prefix index when a request's prefill completes; admission
matches each waiting prompt against the index (`PagedKV4Cache.
match_prefix`), adopts the shared pages refcounted, charges only the
un-cached suffix against the pool, and starts ``prefill_pos`` at the end
of the matched prefix — N requests sharing a system prompt forward its
KV once. Pages with refcount 0 stay cached on a reclaimable LRU and are
evicted before any preemption fires. Counters: ``prefix_hit_tokens``
(prompt tokens served from cache) and ``prefill_tokens`` (prompt tokens
actually forwarded). Enabled by ``EngineConfig.prefix_cache`` (chunked
prefill only; the whole-prompt baseline always recomputes).

**Unified step (the default).** Each step issues exactly ONE forward per
layer: decode tokens (a chunk of 1 with paged int4 history) and prompt
chunks from all partially-prefilled requests are packed into a single
ragged batch — one embed, one W4Ax projection pass per layer, one KV
scatter, one ``paged_kv4_prefill_attention`` call, one MLP, one head
call over the union of finished-prefill rows and decode rows, and one
vectorized sampling call. This is the QServe/Atom dataflow the paper's
throughput rests on: the accelerator sees one dense mixed-precision
stream instead of alternating prefill and decode passes (half the kernel
launches and weight traffic per step).

The unified forward is jitted over **bucketed shapes**: the packed
layout ``(nseq, cmax, ttot)`` plus the attention-schedule dimension
(work-item count under ``attention_schedule="work_queue"``, ``npages``
under ``"dense"``) is rounded up to powers of two, so steady-state
ragged traffic hits the jit cache instead of retracing every
``(nseq, cmax, ttot)`` combination (the dominant cost of the CPU
smoke engine). Padding tokens carry out-of-range scatter destinations
(dropped writes) and zero-length rows (masked in attention), so padding
is semantically inert. ``Engine.trace_count`` counts distinct compiled
forward variants — it plateaus after warmup; ``forward_calls`` proves
the one-forward-per-step invariant.

**Attention schedule** (COMET §4.4's SM-balance pillar, on TPU). The
default ``attention_schedule="work_queue"`` runs paged attention over a
flat Stream-K work pool: the host flattens the batch's REAL pages into
``(row, page, count, kind)`` descriptors (``PagedKV4Cache.
work_queue_np``), the kernel grid walks them uniformly (grid ≈ Σ pages
— a long row's history parallelizes across lanes, short rows pay only
their own pages), and a split-KV log-sum-exp combine merges the partial
flash states. ``"dense"`` keeps the ``(B·Hkv, max_npages)`` rectangle
as the measured baseline. Counters ``attn_work_items`` (real work,
schedule-invariant), ``attn_grid_items`` (launched grid) and
``attn_dense_grid_items`` (the rectangle the dense schedule pays) make
the padding waste measurable — fig10's ragged ablation asserts them.

**Tensor parallelism** (``Engine(..., mesh=..., param_axes=...)`` with a
``(data, model)`` mesh whose model axis > 1). The one-forward-per-step
seam is the ONLY device boundary: ``shard_map`` wraps the unified body,
sharding projection weights column-wise (wq/wk/wv/w_up/w_gate) and
row-wise (wo/w_down) and the int4 KV pools over kv heads, while the
scheduler, prefix index, and page allocator stay host-global — page ids
mean the same thing on every shard, so block tables and Stream-K
work-queue descriptors replicate untouched (each shard walks the same
page stream with its local head slice; per-shard real work is exactly
``attn_work_items / tp``, tracked in ``attn_work_items_per_shard``).
Exactly two all-reduces per layer, at the attention-output and
MLP-down projections (f32 partial sums, rounded to bf16 once after the
psum — greedy decode stays token-identical to single-device). The
embed table and lm head replicate (global token/vocab ids inside the
shard). Everything host-side — admission, preemption, prefix caching,
snapshot/restore — is unchanged and unaware of the mesh.

Prefill is chunked and ragged: the scheduler plans up to
``prefill_chunk_tokens`` prompt tokens per step (budget shared with the
step's decode rows, start round-robined so one long prompt cannot
starve the rest), writes each chunk's quantized KV into the pools, and
attends fp-queries-over-int4-history — a prompt's KV is never resident
in fp beyond one chunk. Admission only needs pages for the next chunk
and preemption can fire mid-prefill.

**Speculative decode** (unified path; ``SamplingParams.speculation=k``).
Decode amortizes the W4Ax weight pass over ONE token per request per
forward — the bottleneck speculation attacks. Each step, a host-side
:class:`~repro.serving.speculation.DraftSource` (default: deterministic
n-gram prompt lookup over the request's prompt + generated history;
pluggable seam for a draft model sharing the page pools) proposes up to
k tokens per speculating decode row. The row then rides the SAME ragged
forward as a qlen-(k+1) chunk — last sampled token + k drafts, int4
paged history, in-flight KV fake-quantized like every decode token —
through the same bucketed jit cache; no new kernel, no second forward.
The head gathers logits at every chunk position of speculating rows
(spec-off steps keep the historical one-logit-per-row layout
bit-for-bit, so their jit cache entries are untouched), and
verification walks them position-by-position: greedy rows accept on
exact argmax match (emitted text is bitwise identical to
speculation-off, just in fewer forwards — the fake-quantize contract
makes a token's in-flight chunk KV equal the int4 page readback its
non-speculative step would see); stochastic rows accept by exact
rejection sampling against the deterministic point-mass proposal (the
output distribution is unchanged). The first rejected position commits
the corrected token; full acceptance commits a bonus token from the
final logits — 1..k+1 tokens per step, emitted in order through the
normal event stream. Unaccepted drafts roll back via the refcount/
prefix-safe ``PagedKV4Cache.truncate_seq`` (pages return to their
pre-draft baseline; the ``sanitize=True`` kv-length-consistency
invariant pins the landing spot every step). Draft tokens debit the
step's ``prefill_chunk_tokens`` budget so spec rows compete fairly with
prefill chunks. Counters: ``spec_draft_tokens`` / ``spec_accepted_tokens``
/ ``spec_rollback_tokens`` (acceptance rate in the serve CLI),
``spec_noop_count`` (drafting suppressed with ≤1 token remaining),
``draft_errors`` (a raising/garbage draft source degrades to plain
decode — drafting is best-effort, never fatal). Fault points ``draft``
and ``verify`` cover the new path; TP sharding is oblivious to it (a
spec row is just another chunk).

**Benchmark baselines** (Fig. 11): ``unified_step=False`` splits the
step back into a ragged prefill forward plus a separate decode forward
(the PR-2 dataflow); ``prefill_mode="whole"`` runs one O(T²) fp forward
per prompt (TTFT baseline); ``decode_attention="gather"`` materializes
each sequence's packed KV per step (the seed's dataflow). All three
imply the split step.

Sequences that hit ``max_pages_per_seq`` finish with
``stop_reason="length_cap"`` (preemption cannot help them — retrying
would livelock); prompts that can never fit the cap fail admission with
``stop_reason="prompt_too_long"``.

Supported families here: dense, moe (the paper's evaluation set —
LLaMA/Qwen/Mistral class + MoE). Hybrid/ssm decode serve through
``LM.decode`` (their state is O(1) — paging buys nothing).

**Fault tolerance.** ``step()`` never propagates a per-request failure:
an exception in the forward (or an ``InjectedFault`` from the
``serving/faults.py`` harness — armed via ``EngineConfig.inject_faults``
or an explicit ``faults=`` injector) quarantines every request in that
step's batch to ``FAILED`` with refcount-exact page release, a sampler
exception or a non-finite logits row quarantines exactly the rows being
sampled, and requests outside the failed batch keep decoding. A
throwing ``on_event`` callback is detached (``callback_errors``), never
fatal. Per-request deadlines (``SamplingParams.deadline_ms`` /
``ttft_ms``) are enforced at every step boundary BEFORE admission —
expired requests land in ``TIMED_OUT`` with partial output retained.
Under pressure the engine degrades instead of stalling: the allocator
drains the reclaimable prefix LRU before any preemption, a bounded
waiting queue (``EngineConfig.max_waiting``) rejects at submit
(``FAILED("queue_full")``, the handle returns already terminal), and a
preemption victim that cannot re-queue is shed (``FAILED("shed")``).
Counters: ``failed_count``, ``timeout_count``, ``shed_count``,
``rejected_count``, ``internal_errors``, ``callback_errors``. An
unexpected exception anywhere else in the step is swallowed into
``internal_errors``/``last_error`` — the serving loop survives
everything. These guards cover the unified step (the default); the
split/whole/gather fig11 baselines rely on the outer backstop only.

**Step-phase spans.** With ``trace_spans`` set, each unified step
marks its phases (serving/tracing.py) as ``jax.profiler`` spans on the
profiler's clock, so a device trace shows what the host was doing while
the device idled. Admission stamps ``Request.admitted_at`` once, beside
``arrived_at`` and ``first_token_at``.

Crash recovery: ``snapshot()`` captures scheduler state; ``Engine.
restore`` rebuilds mid-flight work after a crash (prompts re-prefill
from ``prefill_pos=0`` — partial prefill is device KV, lost with the
node). Sampling is keyed by (request_id, position), but regenerated text
is not bit-identical in general: re-prefill attends in fp while decode
attends over the int4 pages, so greedy argmax can flip on near-ties.
``snapshot(full=True)`` instead captures EVERYTHING — int4 pool bytes,
allocator free-list/LRU order, the exact waiting/running split and
cursors — so ``restore`` of a full blob resumes the very next step
bit-identically (nothing re-prefills); ``serving/recovery.py`` pairs it
with a per-token event journal for exactly-once redelivery and a
bitwise replay check. Availability above one engine lives in
``serving/replication.py``: a :class:`ReplicaGroup` runs N engines on
the data axis, health-checks each step, ships the RecoveryLog
artifacts after every healthy step, and fails over (standby promotion
or exactly-once request migration) from the shipped view.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import qlinear as QL
from repro.core.qlinear import BLOCK_K
from repro.kernels import ops
from repro.layers import attention as ATT
from repro.layers import common as C
from repro.layers import mlp as MLP
from repro.models.lm import LM, QuantConfig
from repro.parallel import sharding as SH
from repro.serving import kv_cache as KVC
from repro.serving.api import (RequestHandle, RequestOutput, RequestState,
                               SamplingParams)
from repro.serving.faults import FaultInjector, InjectedFault
from repro.serving.jit_args import argnums_of
from repro.serving.kv_cache import PagedKV4Cache, PagedKV4Config
from repro.serving.sanitize import check_engine
from repro.serving.scheduler import Request, Scheduler
from repro.serving.speculation import DraftSource, PromptLookupDraft
from repro.serving.tracing import Phases

__all__ = ["Engine", "EngineConfig", "SamplingParams", "RequestState",
           "RequestOutput", "RequestHandle"]


def _bucket(n: int, lo: int = 1) -> int:
    """Round ``n`` up to a power of two (≥ lo) — the jit-cache shape key."""
    return max(lo, 1 << max(int(n) - 1, 0).bit_length())


def _spec_probs(row: np.ndarray, temp: float, top_k: int) -> np.ndarray:
    """Top-k/temperature sampling distribution for one logits row
    (float64 host softmax — the speculative verifier's reference
    measure)."""
    lg = np.asarray(row, np.float64) / max(temp, 1e-8)
    if top_k < lg.shape[0]:
        kth = np.partition(lg, -top_k)[-top_k]
        lg = np.where(lg >= kth, lg, -np.inf)
    lg = lg - lg.max()
    p = np.exp(lg)
    return p / p.sum()


def _reject_sample(row: np.ndarray, temp: float, top_k: int,
                   drafted: Optional[int], rid: int, pos: int):
    """Exact rejection sampling against a DETERMINISTIC draft proposal.

    The prompt-lookup draft is a point mass q = δ(drafted), so the
    textbook accept probability min(1, p/q) collapses to p(drafted) and
    the residual distribution to p restricted to x ≠ drafted,
    renormalized — together they reproduce p exactly, which is the
    speculative-sampling guarantee. ``drafted=None`` (the bonus
    position after full acceptance) is a plain draw from p. Seeded by
    (request_id, position) like the batched sampler, so reruns replay.
    Returns (token, accepted)."""
    p = _spec_probs(row, temp, top_k)
    rng = np.random.default_rng((int(rid) & 0x7FFFFFFF, int(pos), 0x5BEC))
    if drafted is not None:
        if rng.random() < p[drafted]:
            return int(drafted), True
        residual = p.copy()
        residual[drafted] = 0.0
        mass = residual.sum()
        if mass <= 0.0:
            # p WAS the point mass at the draft — the residual is empty
            # and the only exact outcome is the drafted token
            return int(drafted), True
        p = residual / mass
    return int(rng.choice(p.shape[0], p=p)), False


def _pad_to(a: np.ndarray, n: int, fill=0) -> np.ndarray:
    out = np.full((n,), fill, np.int32)
    out[: len(a)] = a
    return out


def _layout_shapes(tb: int, nb: int, npb: int, lb: int, wb: int) -> tuple:
    """Shapes of the unified forward's 12 int32 layout arrays, in the
    order :meth:`Engine._unified_forward` takes them: tokens, positions,
    pages, offs, tseq, toff and the dq mask ``[Tb]``; the block tables
    ``[Nb, NPb]``; ctx and qlens ``[Nb]``; the logit slots ``[Lb]``; the
    work-queue descriptors ``[Wb, 4]``."""
    return ((tb,),) * 7 + ((nb, npb), (nb,), (nb,), (lb,), (wb, 4))


def _pack_layout(arrays, shapes, fills) -> np.ndarray:
    """Lay ``arrays`` back to back into one 1-D int32 buffer, so the whole
    layout crosses to the device in one transfer: each array fills the
    front of its segment (sized by ``shapes``) and its ``fills`` entry the
    rest. An array longer than its segment raises."""
    buf = np.empty(sum(math.prod(s) for s in shapes), np.int32)
    o = 0
    for a, shape, fill in zip(arrays, shapes, fills):
        n = math.prod(shape)
        seg, a = buf[o:o + n], np.ravel(a)
        seg[:a.size] = a
        seg[a.size:] = fill
        o += n
    return buf


def _unpack_layout(buf, shapes) -> tuple:
    """Slice a :func:`_pack_layout` buffer back into its arrays, at offsets
    fixed by ``shapes`` (the engine jits this with ``shapes`` static)."""
    out, o = [], 0
    for shape in shapes:
        n = math.prod(shape)
        out.append(buf[o:o + n].reshape(shape))
        o += n
    return tuple(out)


def _row_linear(p, x, tp_axis):
    """Row-parallel (K-sharded) projection seam: each shard holds a
    K-slice of the weight, so its output is a partial sum that must
    all-reduce over ``tp_axis``. With ``tp_axis=None`` this is exactly
    ``C.linear`` (the single-device path stays bit-identical).

    Numerics under TP: the act-quant must see the input in the SAME
    dtype as the single-device path (``absmax_scale`` divides in the
    input dtype before its f32 cast, so a bf16-valued f32 input still
    shifts the scale's last bit and flips int4 codes on rounding ties).
    So the handler gets the bf16 input unchanged and only the GEMM
    *output* is kept f32 (``out_dtype``) for the psum — rounding to
    bf16 once, after the cross-shard sum. psum over bf16-rounded
    partials would instead inject ~0.4% logit noise and flip greedy
    argmax on near-ties."""
    if tp_axis is None:
        return C.linear(p, x)
    xb = x.astype(jnp.bfloat16)
    pl = {k: v for k, v in p.items() if k != "b"}
    if "w_packed" in pl:
        y = QL._dispatch_qlinear(pl, xb, out_dtype=jnp.float32)
    else:
        y = C.linear(pl, xb.astype(jnp.float32),
                     compute_dtype=jnp.float32)
    y = jax.lax.psum(y, tp_axis)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(jnp.bfloat16)


def _mlp_row(p, x, act: str, tp_axis):
    """Dense MLP with the down-projection as the TP all-reduce seam:
    up/gate are column-sharded (bit-identical per-channel math), the
    silu·up product stays local, and only w_down's K-sharded partial
    sums cross shards. ``tp_axis=None`` delegates to ``MLP.mlp_apply``
    unchanged."""
    if tp_axis is None:
        return MLP.mlp_apply(p, x, act)
    up = C.linear(p["w_up"], x)
    if act == "swiglu":
        h = jax.nn.silu(C.linear(p["w_gate"], x)) * up
    elif act == "gelu":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(act)
    return _row_linear(p["w_down"], h, tp_axis)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 32
    num_pages: int = 512
    page_size: int = 64
    max_pages_per_seq: int = 64
    temperature: float = 0.0        # 0 → greedy
    top_k: int = 40
    decode_attention: str = "paged"  # "paged" (gather-free) | "gather"
    prefill_mode: str = "chunked"    # "chunked" (ragged) | "whole" (baseline)
    prefill_chunk_tokens: int = 64   # ragged-prefill token budget per step
    kv_range: float = 16.0           # calibrated |k|,|v| range → int4 scales
    unified_step: bool = True        # ONE forward/step (decode ∪ prefill);
    #                                  False → split-step fig11 baseline
    prefix_cache: bool = True        # publish/reuse shared prompt pages
    #                                  (refcounted; chunked prefill only)
    attention_schedule: str = "work_queue"  # "work_queue" (Stream-K flat
    #                                  descriptors + split-KV combine) |
    #                                  "dense" ((B·Hkv, max_npages) grid —
    #                                  the measured fig10 baseline)
    prefix_cache_max_bytes: Optional[int] = None  # byte cap on the
    #                                  reclaimable prefix-page LRU
    max_waiting: Optional[int] = None  # bound on the waiting queue —
    #                                  submits past it are rejected
    #                                  (FAILED "queue_full") and preempt
    #                                  victims are shed, not re-queued
    inject_faults: Optional[str] = None  # fault schedule spec
    #                                  (serving/faults.py grammar), e.g.
    #                                  "forward:step=3,action=nan"
    sanitize: bool = False          # re-derive the core invariants
    #                                  (page-refcount conservation,
    #                                  exactly-one-terminal, no-token-
    #                                  after-terminal) after EVERY step
    #                                  and raise SanitizerError on the
    #                                  first violation — the runtime
    #                                  half of repro.analysis.cometlint
    #                                  (serving/sanitize.py)

    def __post_init__(self):
        if self.max_waiting is not None and self.max_waiting < 1:
            raise ValueError("max_waiting must be >= 1 (None = unbounded)")
        if self.decode_attention not in ("paged", "gather"):
            raise ValueError(
                f"decode_attention must be 'paged' or 'gather', got "
                f"{self.decode_attention!r}")
        if self.prefill_mode not in ("chunked", "whole"):
            raise ValueError(
                f"prefill_mode must be 'chunked' or 'whole', got "
                f"{self.prefill_mode!r}")
        if self.prefill_chunk_tokens < 1:
            raise ValueError("prefill_chunk_tokens must be >= 1")
        if self.attention_schedule not in ("work_queue", "dense"):
            raise ValueError(
                f"attention_schedule must be 'work_queue' or 'dense', "
                f"got {self.attention_schedule!r}")

    @property
    def unified(self) -> bool:
        """Unified step needs the chunked ragged path and the paged
        kernel; the whole-prompt / gather baselines imply a split step."""
        return (self.unified_step and self.prefill_mode == "chunked"
                and self.decode_attention == "paged")

    @property
    def prefix_caching(self) -> bool:
        """Prefix reuse rides on ``prefill_pos`` chunk streaming — the
        whole-prompt baseline always forwards the full prompt."""
        return self.prefix_cache and self.prefill_mode == "chunked"


class Engine:
    # declared jit intent (rule R2): which _unified_forward parameters
    # are static (shape-bucket keys traced per value) and which are
    # donated (pool buffers updated in place). Indices are derived from
    # these NAMES at construction via jit_args.argnums_of, so adding or
    # reordering a forward parameter re-resolves correctly and renaming
    # one fails loudly instead of staticizing/donating the wrong arg.
    _FWD_STATIC_ARGS = ("cmax", "no_history", "schedule")
    _FWD_DONATE_ARGS = ("k_pool", "v_pool")

    # rule R1 (snapshot-completeness) allowlist: __init__ attrs that are
    # deliberately NOT in the full-snapshot blob — rebuilt by the
    # constructor (model/params/jit caches/sharding layouts) or
    # process-lifetime observability counters a restored incarnation
    # starts from zero (the serve CLI reports them per process).
    _SNAPSHOT_EXEMPT = frozenset({
        # rebuilt by __init__ / only meaningful in-process
        "lm", "params", "donate_pools", "_fwd", "_fwd_shapes",
        "_unpack", "_layout_sharding",
        "_sample_fns", "_gather_bcast", "_param_pspecs", "_scale_pspec",
        "_pool_pspec", "tp_size",
        "_events", "draft_source", "trace_spans", "_phases",
        "last_forward_rows",
        # per-process observability counters
        "peak_prefill_fp_tokens", "interleaved_steps", "forward_calls",
        "host_uploads", "trace_count", "prefix_hit_tokens", "prefill_tokens",
        "aborted_count", "failed_count", "timeout_count", "shed_count",
        "rejected_count", "callback_errors", "internal_errors",
        "last_error", "sanitize_checks", "attn_work_items",
        "attn_grid_items", "attn_dense_grid_items", "attn_forwards",
        "attn_work_items_per_shard", "spec_draft_tokens",
        "spec_accepted_tokens", "spec_rollback_tokens", "spec_noop_count",
        "draft_errors",
    })

    def __init__(self, cfg: ModelConfig, qparams, quant: QuantConfig,
                 ecfg: EngineConfig = EngineConfig(), *,
                 mesh=None, param_axes=None, faults=None, clock=time.time,
                 draft_source: Optional[DraftSource] = None):
        """``mesh``/``param_axes`` (both optional) turn on tensor-parallel
        sharded serving: a ``(data, model)`` mesh whose "model" axis > 1
        shards projection weights and the int4 KV pools over kv heads
        (``shard_map`` around the unified forward; see
        :meth:`_unified_forward`). ``param_axes`` is the logical-axes
        tree ``LM.quantize`` returns alongside ``qparams`` — required
        whenever the model axis is sharded. A mesh with model == 1 (or
        ``mesh=None``) is the single-device engine, unchanged.

        ``faults``: a :class:`FaultInjector` to ride along (chaos tests
        hand one in directly; ``ecfg.inject_faults`` builds one from the
        CLI spec grammar). ``clock``: the wall-clock source for arrival
        stamps and deadline enforcement — injectable so deadline tests
        are deterministic. ``draft_source``: the speculative-decode
        proposal oracle (serving/speculation.py) — defaults to n-gram
        :class:`PromptLookupDraft`; only consulted for requests with
        ``SamplingParams.speculation > 0``."""
        if cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"paged engine supports dense/moe; {cfg.family} serves via "
                "LM.decode")
        self.cfg = cfg
        self.quant = quant
        self.lm = LM(cfg, quant=quant)
        self.params = qparams
        self.ecfg = ecfg
        self.mesh = mesh
        self.tp_size = (int(mesh.shape["model"])
                        if mesh is not None and "model" in mesh.axis_names
                        else 1)
        self.cache = PagedKV4Cache(
            cfg,
            PagedKV4Config(
                num_pages=ecfg.num_pages, page_size=ecfg.page_size,
                max_seqs=ecfg.max_batch * 2,
                max_pages_per_seq=ecfg.max_pages_per_seq,
                reclaimable_max_bytes=ecfg.prefix_cache_max_bytes),
            num_layer_slots=cfg.num_layers,
            kv_range=ecfg.kv_range)
        self.sched = Scheduler(ecfg.max_batch, ecfg.max_batch * 2,
                               max_waiting=ecfg.max_waiting)
        self.clock = clock
        # fault-injection harness (serving/faults.py): shared with the
        # cache so alloc_page/append_kv fire at their real call sites
        if faults is None:
            faults = (FaultInjector.from_spec(ecfg.inject_faults)
                      if ecfg.inject_faults else FaultInjector())
        self.faults = faults
        self.cache.faults = faults
        self.steps = 0
        self.tokens_generated = 0
        # observability: largest fp-token prefill forward issued (bounded
        # by prefill_chunk_tokens in chunked mode), steps that ran
        # prefill and decode work back-to-back (interleave evidence for
        # fig11), model forwards issued (unified: exactly one per step
        # with work), host→device transfers those forwards issued
        # (unified: one packed layout each, so equal to forward_calls;
        # the split baseline's eager uploads are not counted), and
        # distinct compiled forward variants (unified: real jit traces,
        # counted inside the traced body; split: one per new
        # packed-shape signature — the eager dispatch cache key)
        self.peak_prefill_fp_tokens = 0
        self.interleaved_steps = 0
        self.forward_calls = 0
        self.host_uploads = 0
        self.trace_count = 0
        # prefix-cache + lifecycle counters: prompt tokens served from
        # published pages vs actually forwarded, and aborted requests
        self.prefix_hit_tokens = 0
        self.prefill_tokens = 0
        self.aborted_count = 0
        # robustness counters: step-level quarantines, deadline/TTFT
        # expiries, load-shed preemption victims, bounded-queue submit
        # rejections, throwing on_event callbacks (detached, not fatal),
        # and the last-resort backstop for unexpected step exceptions
        self.failed_count = 0
        self.timeout_count = 0
        self.shed_count = 0
        self.rejected_count = 0
        self.callback_errors = 0
        self.internal_errors = 0
        self.last_error: Optional[str] = None
        # step boundaries that passed the runtime sanitizer (ecfg.sanitize)
        self.sanitize_checks = 0
        # speculative decode: the pluggable host-side draft oracle and
        # its acceptance accounting — drafted/accepted give the
        # acceptance rate, rollback counts the int4 KV retracted by
        # truncate_seq, spec_noop_count the drafts suppressed because
        # ≤1 token remained, draft_errors the raising/garbage draft
        # calls degraded to plain decode
        self.draft_source = (draft_source if draft_source is not None
                             else PromptLookupDraft())
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_rollback_tokens = 0
        self.spec_noop_count = 0
        self.draft_errors = 0
        # attention-schedule counters (fig10 measured ablation): real
        # work items (Σ real pages + chunk items, per kv head — equal
        # under both schedules), grid items actually launched (dense:
        # the padded (B·Hkv)·(npages+1) rectangle; work_queue: the
        # pow-2-bucketed flat count), the dense-equivalent grid for the
        # same forwards, and how many attention forwards contributed
        self.attn_work_items = 0
        self.attn_grid_items = 0
        self.attn_dense_grid_items = 0
        self.attn_forwards = 0
        # per-shard real work: each model shard attends its local kv
        # heads over the SAME per-sequence page stream, so the split is
        # exact — equal entries here are the load-balance evidence the
        # fig11 sharded part asserts (single device: one entry, equal
        # to attn_work_items)
        self.attn_work_items_per_shard = [0] * self.tp_size
        # step-phase profiler spans (serving/tracing.py), off by default:
        # while off, each phase boundary costs one check of trace_spans;
        # while on, last_forward_rows keeps the last forward's
        # [(ctx, qlen)] rows
        self.trace_spans = False
        self._phases = Phases()
        self.last_forward_rows: list = []
        # forward shapes this engine has run (unified: the jit bucket
        # key; split: the eager dispatch signature)
        self._fwd_shapes: set = set()
        self._gather_bcast: dict = {}      # bsz → broadcast scales/zeros
        # donate the pool buffers so the traced KV scatter updates them
        # in place instead of copying ~num_pages of int4 every step; CPU
        # has no buffer donation (XLA warns and copies), so gate it to
        # the accelerator backends where it is honored
        self.donate_pools = jax.default_backend() in ("tpu", "gpu")
        self._param_pspecs = None
        self._pool_pspec = None
        self._scale_pspec = None
        if self.tp_size > 1:
            self._init_sharding(param_axes)
        elif mesh is not None:
            # a one-device mesh (one replica's slice): commit params and
            # pools to that device, so replicas never share device 0
            self.params = jax.device_put(self.params, self._pool_sharding())
            self._place_pools()
        self._fwd = jax.jit(
            self._unified_forward,
            static_argnums=argnums_of(self._unified_forward,
                                      *self._FWD_STATIC_ARGS),
            donate_argnums=(argnums_of(self._unified_forward,
                                       *self._FWD_DONATE_ARGS)
                            if self.donate_pools else ()))
        # the forward's layout crosses to the device as ONE packed int32
        # buffer (each transfer has a fixed cost that dwarfs its few KB),
        # put straight onto the mesh replicated — the sharding every
        # layout array enters the forward with — and sliced back into the
        # forward's arguments by a jitted prologue, one dispatch per step
        self._layout_sharding = (NamedSharding(mesh, P())
                                 if mesh is not None else None)
        self._unpack = jax.jit(_unpack_layout, static_argnames="shapes")
        self._sample_fns: dict = {}        # kmax → jitted batched sampler
        self._by_id: dict[int, Request] = {}
        self._next_id = 0
        # monotonically increasing submission counter: request_ids are
        # REUSABLE after release(), so anything that must key per-request
        # state durably (the recovery journal, replica migration) keys by
        # Request.uid — the incarnation-qualified id — instead
        self._submit_seq = 0
        self._events: list[RequestOutput] = []

    # --------------------------------------------------- tensor parallelism

    def _init_sharding(self, param_axes):
        """Lay params + pools out over the mesh for TP-sharded serving.

        Weights shard by SERVE_RULES (column-parallel wq/wk/wv/w_up/
        w_gate over their N dims, row-parallel wo/w_down over their K
        dims); the embed table and lm head are overridden to REPLICATED
        — inside ``shard_map`` the token gather and the vocab matmul use
        global ids, so a vocab-sharded table would read garbage. The
        int4 KV pools shard over kv heads via ``cache_pspecs`` (pages
        stay a host-global namespace). Divisibility is validated up
        front rather than silently falling back to replication, because
        a PARTIALLY sharded projection (w_packed sharded, w_scale
        replicated) is shape-inconsistent inside the W4Ax matmul."""
        cfg, m, mesh = self.cfg, self.tp_size, self.mesh
        if param_axes is None:
            raise ValueError(
                "TP-sharded serving needs param_axes — the axes tree "
                "LM.quantize returns alongside qparams")
        if not self.ecfg.unified:
            raise ValueError(
                "TP-sharded serving runs through the unified one-forward "
                "step; the split/whole/gather baselines are single-device")
        if cfg.family != "dense":
            raise NotImplementedError(
                "TP-sharded serving covers dense models; MoE needs expert-"
                "parallel dispatch at this seam")
        if cfg.num_heads % m or cfg.num_kv_heads % m:
            raise ValueError(
                f"num_heads={cfg.num_heads}, num_kv_heads="
                f"{cfg.num_kv_heads} must both divide by the model axis "
                f"size {m}")
        if cfg.q_dim % (BLOCK_K * m) or cfg.d_ff % (BLOCK_K * m):
            raise ValueError(
                f"row-parallel W4Ax shards must hold whole {BLOCK_K}-"
                f"channel quant blocks: q_dim={cfg.q_dim} and d_ff="
                f"{cfg.d_ff} must divide by {BLOCK_K}*model={BLOCK_K * m}")
        specs = SH.tree_pspecs(param_axes, self.params, mesh,
                               SH.SERVE_RULES)
        for name in ("embed", "lm_head"):
            if name in specs:
                specs[name] = jax.tree.map(
                    lambda s, p: P(*([None] * p.ndim)),
                    specs[name], self.params[name],
                    is_leaf=lambda x: isinstance(x, P))
        self._param_pspecs = specs

        def put(a, s):
            return jax.device_put(a, NamedSharding(mesh, s))

        self.params = jax.tree.map(put, self.params, specs,
                                   is_leaf=lambda x: isinstance(x, P))
        cache = self.cache
        cspecs = SH.cache_pspecs(
            {"k_pool": cache.k_pool, "v_pool": cache.v_pool,
             "k_scale": cache.k_scale, "k_zero": cache.k_zero,
             "v_scale": cache.v_scale, "v_zero": cache.v_zero}, mesh)
        self._pool_pspec = cspecs["k_pool"]
        self._scale_pspec = cspecs["k_scale"]
        self._place_pools()
        cache.k_scale = put(cache.k_scale, cspecs["k_scale"])
        cache.k_zero = put(cache.k_zero, cspecs["k_zero"])
        cache.v_scale = put(cache.v_scale, cspecs["v_scale"])
        cache.v_zero = put(cache.v_zero, cspecs["v_zero"])

    def _pool_sharding(self):
        if self.tp_size > 1:
            return NamedSharding(self.mesh, self._pool_pspec)
        return jax.sharding.SingleDeviceSharding(self.mesh.devices.flat[0])

    def _place_pools(self):
        """Lay the KV pools out on the engine's mesh (no-op without one):
        kv-head sharded under TP, else on the mesh's single device."""
        if self.mesh is None:
            return
        sharding = self._pool_sharding()
        self.cache.k_pool = jax.device_put(self.cache.k_pool, sharding)
        self.cache.v_pool = jax.device_put(self.cache.v_pool, sharding)

    # ----------------------------------------------------- lifecycle API

    def submit(self, prompt: list[int],
               params: Optional[SamplingParams] = None,
               request_id: Optional[int] = None,
               on_event=None) -> RequestHandle:
        """Enqueue a request (state QUEUED) and return its handle.

        ``params`` defaults to the engine-wide sampling configuration;
        ``on_event`` is an optional push callback invoked with every
        :class:`RequestOutput` the request emits.

        Backpressure: with ``EngineConfig.max_waiting`` set and the
        waiting queue full, the request is rejected — the returned
        handle resolves to a request already terminal in ``FAILED``
        with ``stop_reason="queue_full"`` (terminal event emitted, no
        pages or slots ever held)."""
        if params is None:
            params = SamplingParams(temperature=self.ecfg.temperature,
                                    top_k=self.ecfg.top_k)
        if params.speculation + 1 > self.ecfg.prefill_chunk_tokens:
            # the verify chunk is k drafts + the last sampled token; a k
            # that cannot fit the per-step token budget could never ride
            # one forward — reject at submit, not silently mid-step
            raise ValueError(
                f"speculation={params.speculation} exceeds the per-step "
                f"token budget: the k+1-token verify chunk must fit "
                f"prefill_chunk_tokens={self.ecfg.prefill_chunk_tokens}")
        if params.speculation > 0 and params.max_new_tokens == 1:
            # a single-token request never decodes (its one token comes
            # off the prefill's logits), so speculation can never engage
            # — a silent no-op worth counting, not an error
            self.spec_noop_count += 1
        if request_id is None:
            while self._next_id in self._by_id:
                self._next_id += 1
            request_id = self._next_id
        old = self._by_id.get(request_id)
        if old is not None and not old.state.terminal:
            raise ValueError(f"request_id {request_id} already in flight")
        req = Request(
            request_id=request_id, prompt=list(prompt),
            max_new_tokens=params.max_new_tokens, arrived_at=self.clock(),
            params=params, on_event=on_event, uid=self._submit_seq)
        self._submit_seq += 1
        self._by_id[request_id] = req
        if self.sched.waiting_full:
            self.sched.reject(req)
            self.rejected_count += 1
            self._emit(req)
        else:
            self.sched.submit(req)
        return RequestHandle(request_id=request_id, prompt_len=len(prompt))

    def _resolve(self, handle) -> Optional[Request]:
        rid = handle.request_id if isinstance(handle, RequestHandle) \
            else int(handle)
        return self._by_id.get(rid)

    def abort(self, handle) -> bool:
        """Cancel a request at ANY lifecycle state. Pages are released
        refcount-exactly (``cache.pages_free`` returns to its
        pre-submit baseline; shared prefix pages stay cached for their
        other owners). Emits a terminal ABORTED event. Returns False if
        the request is unknown or already terminal."""
        req = self._resolve(handle)
        if req is None or not self.sched.abort(req, self.cache):
            return False
        self.aborted_count += 1
        self._emit(req)
        return True

    def events(self) -> list[RequestOutput]:
        """Drain the engine-wide event queue fed by ``step()``: one
        event per sampled token (in order) plus a terminal event per
        finished/aborted request. A long-running server must drain this
        (or consume via ``stream``/``on_event`` and ignore it) — the
        queue is unbounded by design so the batch ``run()`` wrapper
        loses nothing. Terminal request state itself is retained for
        the engine's lifetime (same policy as ``sched.finished``)."""
        evs, self._events = self._events, []
        return evs

    def stream(self, handle):
        """Yield one request's :class:`RequestOutput` events as they
        happen, driving ``step()`` while the request is in flight (other
        requests keep batching through the same steps). Terminates after
        the request's terminal event."""
        req = self._resolve(handle)
        if req is None:
            return
        cursor = 0
        while True:
            while cursor < len(req.events):
                yield req.events[cursor]
                cursor += 1
            if req.state.terminal or not self.sched.has_work:
                return
            self.step()

    def result(self, handle) -> Optional[Request]:
        """The request's current state (its final state once terminal)."""
        return self._resolve(handle)

    def release(self, handle) -> bool:
        """Drop a TERMINAL request's retained state — its entry in the
        id map, its slot in ``sched.finished``, and its event log — so
        a long-running server's memory scales with in-flight work, not
        lifetime traffic. Call after consuming ``result``/``stream``;
        the request_id becomes immediately reusable. Returns False for
        unknown or still-in-flight requests (aborting first is the way
        to drop those)."""
        req = self._resolve(handle)
        if req is None or not req.state.terminal:
            return False
        self.sched.release(req)
        self._by_id.pop(req.request_id, None)
        req.events.clear()
        req.on_event = None
        return True

    # ----------------------------------------------------- batch-compat API

    def add_request(self, request_id: int, prompt: list[int],
                    max_new_tokens: int):
        """[Compat] the pre-lifecycle batch API: submit with engine-wide
        sampling defaults and an explicit id."""
        self.submit(prompt,
                    SamplingParams(max_new_tokens=max_new_tokens,
                                   temperature=self.ecfg.temperature,
                                   top_k=self.ecfg.top_k),
                    request_id=request_id)

    def run(self, max_steps: int = 10_000) -> list[Request]:
        """[Compat] drive ``step()`` until all work drains; the offline
        batch wrapper over the streaming lifecycle."""
        while self.sched.has_work and self.steps < max_steps:
            self.step()
        return self.sched.finished

    def snapshot(self, full: bool = False) -> str:
        """Serialize engine state for crash recovery.

        Legacy mode (default): scheduler-only — running work demotes to
        waiting and re-prefills on restore (device KV lost with the
        node); continuation is plausible but not bit-identical.

        ``full=True``: the journaled-recovery blob — the exact
        scheduler split/cursors (``Scheduler.snapshot(full=True)``) plus
        the entire cache (``PagedKV4Cache.snapshot_state``: int4 pool
        bytes, block tables, free-list and prefix-LRU order). A restore
        resumes the very next step bit-identically."""
        if full:
            return json.dumps({
                "format": "engine_full",
                "sched": self.sched.snapshot(full=True),
                "cache": self.cache.snapshot_state(),
                "steps": self.steps,
                "tokens_generated": self.tokens_generated,
                "next_id": self._next_id,
                "submit_seq": self._submit_seq,
            })
        return self.sched.snapshot()

    @classmethod
    def restore(cls, blob: str, cfg, qparams, quant,
                ecfg: EngineConfig = EngineConfig(), *,
                mesh=None, param_axes=None, faults=None,
                clock=time.time) -> "Engine":
        eng = cls(cfg, qparams, quant, ecfg, mesh=mesh,
                  param_axes=param_axes, faults=faults, clock=clock)
        state = json.loads(blob)
        if isinstance(state, dict) and state.get("format") == "engine_full":
            eng.sched = Scheduler.restore(
                state["sched"], ecfg.max_batch, ecfg.max_batch * 2,
                max_waiting=ecfg.max_waiting)
            eng.cache.restore_state(state["cache"])
            # restore_state loads host pools; re-lay them out on the mesh
            eng._place_pools()
            eng.steps = state["steps"]
            eng.tokens_generated = state["tokens_generated"]
            eng._next_id = state["next_id"]
            eng._by_id = {r.request_id: r for r in
                          list(eng.sched.waiting) + eng.sched.running
                          + eng.sched.finished}
            eng._restore_uids(state.get("submit_seq"))
            return eng
        eng.sched = Scheduler.restore(blob, ecfg.max_batch,
                                      ecfg.max_batch * 2,
                                      max_waiting=ecfg.max_waiting)
        eng._by_id = {r.request_id: r for r in
                      list(eng.sched.waiting) + eng.sched.finished}
        eng._restore_uids(None)
        return eng

    def _restore_uids(self, submit_seq):
        """Re-establish the incarnation counter after a restore: blobs
        from before uid tracking (or the legacy scheduler snapshot) carry
        requests with ``uid == -1`` — assign them fresh uids so the
        recovery journal's ``(uid, ord)`` keys stay collision-free."""
        reqs = (list(self.sched.waiting) + self.sched.running
                + self.sched.finished)
        top = max((r.uid for r in reqs), default=-1) + 1
        self._submit_seq = max(top, submit_seq or 0)
        for r in reqs:
            if r.uid < 0:
                r.uid = self._submit_seq
                self._submit_seq += 1

    # ----------------------------------------------------------- events

    def _emit(self, req: Request, token: Optional[int] = None):
        """Single event choke point. A terminal event (``token is
        None``) is emitted at most once per request — the exactly-one-
        terminal contract holds even when several failure paths race to
        finish the same request in one step. ``on_event`` delivery is
        guarded: a throwing callback (or an injected ``emit_event``
        fault) is detached and counted, never fatal, and the event log
        keeps the event either way."""
        if token is None:
            if req.terminal_emitted:
                return
            req.terminal_emitted = True
            if not req.finished_at:     # TPOT window end (serve CLI SLOs)
                req.finished_at = self.clock()
        out = RequestOutput(
            request_id=req.request_id, state=req.state, token=token,
            num_generated=len(req.generated), stop_reason=req.stop_reason,
            finished=req.state.terminal)
        self._events.append(out)
        req.events.append(out)
        if req.on_event is not None:
            try:
                if self.faults.check("emit_event"):
                    raise InjectedFault(
                        "emit_event: injected callback failure")
                req.on_event(out)
            except Exception:  # noqa: BLE001 — user-callback boundary:
                # client code may raise anything; detach + count it so
                # one bad callback can't poison the serving loop
                self.callback_errors += 1
                req.on_event = None

    def _record_token(self, req: Request, tok: int):
        """Single choke point for a sampled token: append, stamp TTFT,
        flip PREFILLING→DECODING, and emit the streaming event.
        ``emitted`` is the request's LIFETIME token-event count (unlike
        ``len(generated)``, it survives the preemption fold) — the
        journal's per-request delivery cursor."""
        if req.state.terminal:
            # reentrant abort: an on_event callback cancelled this
            # request earlier in the same step's sampling loop — its
            # terminal event must stay last, so drop the token
            return
        req.generated.append(int(tok))
        req.emitted += 1
        if not req.first_token_at:      # preserve TTFT across preemptions
            req.first_token_at = self.clock()
        if req.state == RequestState.PREFILLING:
            req.state = RequestState.DECODING
        self.tokens_generated += 1
        self._emit(req, token=int(tok))

    def _complete(self, req: Request):
        self.sched.complete(req, self.cache)
        self._emit(req)

    def _fail(self, req: Request, reason: str):
        """Quarantine one request after a step-level failure: pages
        released refcount-exactly, terminal FAILED event, counted."""
        if self.sched.fail(req, self.cache, reason):
            self.failed_count += 1
            self._emit(req)

    def _preempt_one(self) -> Optional[Request]:
        """Preempt the youngest runnable sequence; when the bounded
        waiting queue is full the scheduler sheds the victim instead of
        re-queueing it — count it and emit its terminal event here."""
        victim = self.sched.preempt_one(self.cache)
        if victim is not None and victim.state.terminal:
            self.shed_count += 1
            self._emit(victim)
        return victim

    # ----------------------------------------------------------------- step

    def step(self):
        """Advance every in-flight request one scheduling quantum.

        NEVER raises: per-request failures are quarantined inside
        (``_forward_step``'s guards), and anything unexpected that still
        escapes is swallowed into ``internal_errors``/``last_error`` —
        one poisoned step must not take down the serving loop.

        Exception: ``ecfg.sanitize`` runs the step-boundary invariant
        checks (serving/sanitize.py) OUTSIDE the backstop — a
        ``SanitizerError`` means engine state is already corrupt, and
        the whole point is to stop before serving wrong answers."""
        self.steps += 1
        self.faults.begin_step(self.steps)
        try:
            self._step_inner()
        except Exception as e:  # noqa: BLE001 — the serving-loop backstop
            self.internal_errors += 1
            self.last_error = repr(e)
        if self.trace_spans:
            self._phases.end()
        if self.ecfg.sanitize:
            check_engine(self)
            self.sanitize_checks += 1

    def _step_inner(self):
        if self.trace_spans and self.ecfg.unified:
            self._phases.enter("engine.schedule")
        # deadline/TTFT expiry runs BEFORE admission: a dead-on-arrival
        # request must never acquire pages just to be torn down
        for req in self.sched.expire_deadlines(self.cache, self.clock()):
            self.timeout_count += 1
            self._emit(req)
        chunked = self.ecfg.prefill_mode == "chunked"
        nfin = len(self.sched.finished)
        admitted = self.sched.admit(
            self.cache,
            first_chunk_tokens=(self.ecfg.prefill_chunk_tokens if chunked
                                else None),
            prefix_cache=self.ecfg.prefix_caching)
        for r in admitted:
            if not r.admitted_at:   # a preempted request keeps its stamp
                r.admitted_at = self.clock()
        # admission-time rejections (prompt_too_long) reach finished
        # without passing through _complete — they still owe their
        # terminal event
        for r in self.sched.finished[nfin:]:
            self._emit(r)
        self.prefix_hit_tokens += sum(r.cached_tokens for r in admitted)
        # chunk rows and decode rows share one token budget: the decode
        # batch debits the prefill plan so the forward stays bounded by
        # ~prefill_chunk_tokens (min 1 keeps long prompts progressing)
        n_decode_est = sum(1 for r in self.sched.running
                           if r.prefilled and not r.done)
        budget = max(1, self.ecfg.prefill_chunk_tokens - n_decode_est)
        if self.ecfg.unified:
            self._step_unified(budget)
        else:
            self._step_split(admitted, chunked, budget)
        for req in list(self.sched.running):
            if req.done:
                self._complete(req)

    def _step_unified(self, budget: int):
        """ONE forward for the union of decode rows and prompt chunks.

        Decode slots are reserved *before* the prefill plan: reservation
        can preempt a mid-prefill victim, which would invalidate a plan
        built earlier. Speculative drafts are planned between the two —
        they need the reserved slots to size their verify chunks, and
        their token count debits the prefill budget so spec rows compete
        fairly with prompt chunks for the step's forward."""
        decode = self._reserve_decode_slots(
            [r for r in self.sched.running if r.prefilled and not r.done])
        drafts = self._plan_speculation(decode, budget)
        budget = max(1, budget - sum(len(d) for d in drafts))
        plan = self.sched.plan_prefill(self.cache, budget)
        if not plan and not decode:
            # no forward possible: if prompts are stuck with nothing
            # decodable, free pages so the next step can move
            stuck = [r for r in self.sched.running if not r.prefilled]
            if stuck and not any(r.prefilled for r in self.sched.running):
                self._preempt_one()
            return
        if plan and decode:
            self.interleaved_steps += 1
        self._forward_step(plan, list(zip(decode, drafts)))

    def _plan_speculation(self, decode: list[Request],
                          budget: int) -> list[list[int]]:
        """Plan one draft per decode row (aligned list; ``[]`` = plain
        one-token decode). Pure host work: consult the draft source,
        clamp k to the tokens the request can still commit and to the
        step budget (one token is held back for prefill progress while
        any prompt is mid-stream), validate the proposal, and grow the
        row's page capacity to cover its k+1-token verify chunk —
        trimming the draft instead of preempting anyone when the pool
        is short (drafts are opportunistic; they must never evict real
        work). A raising draft source — or an injected ``draft`` fault
        — degrades to no-draft and counts ``draft_errors``."""
        drafts: list[list[int]] = [[] for _ in decode]
        if not any(r.params is not None and r.params.speculation > 0
                   for r in decode):
            return drafts
        avail = budget - 1 if any(not r.prefilled
                                  for r in self.sched.running) else budget
        for i, r in enumerate(decode):
            k = r.params.speculation if r.params is not None else 0
            if k <= 0:
                continue
            remaining = r.max_new_tokens - len(r.generated)
            if remaining <= 1:
                # at most one token left to commit: a draft would be
                # guaranteed rollback, so speculation no-ops
                self.spec_noop_count += 1
                continue
            k = min(k, remaining - 1, avail)
            if k <= 0:
                continue
            try:
                fault = self.faults.check("draft")
                if fault is not None and fault.action == "raise":
                    raise InjectedFault("draft: injected draft failure")
                d = ([] if fault is not None
                     else list(self.draft_source.draft(
                         r.prompt, r.generated, k))[:k])
                if any(not 0 <= int(t) < self.cfg.vocab_size for t in d):
                    raise ValueError(f"draft token out of vocab: {d}")
            except Exception as e:  # noqa: BLE001 — draft oracles are
                # untrusted; degradation to plain decode, never fatal
                self.draft_errors += 1
                self.last_error = f"draft: {e!r}"
                d = []
            if not d:
                continue
            # page capacity for the verify chunk (ctx + last token + k
            # drafts); the pool decides how much speculation it backs
            ctx = int(self.cache.seq_len[r.seq_slot])
            cap = self.cache.grow_to(r.seq_slot, ctx + 1 + len(d))
            d = [int(t) for t in d[:max(0, cap - ctx - 1)]]
            if d:
                drafts[i] = d
                avail -= len(d)
                self.spec_draft_tokens += len(d)
        return drafts

    def _step_split(self, admitted: list[Request], chunked: bool,
                    budget: int):
        """[Benchmark baseline] the PR-2 two-forward step: ragged prefill
        chunk, then a separate decode forward."""
        if chunked:
            plan = self.sched.plan_prefill(self.cache, budget)
            if plan:
                self._prefill_forward(plan)
            else:
                stuck = [r for r in self.sched.running if not r.prefilled]
                if stuck and not any(r.prefilled
                                     for r in self.sched.running):
                    self._preempt_one()
            prefill_ran = bool(plan)
        else:
            for req in admitted:
                self._prefill(req)
            prefill_ran = bool(admitted)
        runnable = self._reserve_decode_slots(
            [r for r in self.sched.running if r.prefilled and not r.done])
        if runnable:
            self._decode_batch(runnable)
            if prefill_ran:
                self.interleaved_steps += 1

    def _reserve_decode_slots(self, runnable: list[Request]) -> list[Request]:
        """Page headroom for one decode token per runnable sequence.

        Preempts (youngest-first) until every remaining sequence can
        extend. A sequence already at ``max_pages_per_seq`` can never
        extend no matter how many pages are freed — it finishes with
        ``stop_reason="length_cap"`` instead of spinning the loop
        forever (the seed's infinite-loop bug)."""
        pending = list(runnable)
        ready: list[Request] = []
        while pending:
            r = pending.pop(0)
            if r.seq_slot < 0 or r.state.terminal:
                # a length_cap _complete below emits an event whose
                # on_event callback may reentrantly abort() a request
                # still sitting in these local lists — its slot is gone,
                # so it must not reach extend_seq or the forward
                continue
            if self.cache.extend_seq(r.seq_slot):
                ready.append(r)
                continue
            if self.cache.at_capacity(r.seq_slot):
                # complete NOW (not at end of step): the capped request
                # must leave sched.running before any later preempt_one
                # in this loop could victimize it and destroy its output,
                # and freeing its pages helps the still-pending sequences
                r.stop_reason = "length_cap"
                self._complete(r)
                continue
            victim = self._preempt_one()
            if victim is None:
                continue            # nothing to evict — stall r this step
            if victim in pending:
                pending.remove(victim)
            elif victim in ready:
                ready.remove(victim)
            if victim is not r:
                pending.insert(0, r)    # retry r with the freed pages
        # drop rows a reentrant abort invalidated after they were ready
        return [r for r in ready if r.seq_slot >= 0 and not r.state.terminal]

    # ------------------------------------------------------------- sampling

    def _make_sample_fn(self, kmax: int):
        """Batched per-request sampler: one call serves rows mixing
        greedy and stochastic requests with different temperature/top_k.
        ``kmax`` (the bucketed max top_k this batch) is the only static
        shape — per-row k is a mask over the top-kmax candidates."""

        def sample(logits, rids, positions, temps, topks):
            key0 = jax.random.PRNGKey(0)
            keys = jax.vmap(lambda r, p: jax.random.fold_in(
                jax.random.fold_in(key0, r), p))(rids, positions)
            topv, topi = jax.lax.top_k(logits, kmax)
            safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
            masked = jnp.where(jnp.arange(kmax)[None, :] < topks[:, None],
                               topv / safe_t, -jnp.inf)
            idx = jax.vmap(jax.random.categorical)(keys, masked)
            samp = jnp.take_along_axis(topi, idx[:, None], axis=1)[:, 0]
            greedy = jnp.argmax(logits, axis=-1).astype(samp.dtype)
            return jnp.where(temps > 0, samp, greedy)

        return jax.jit(sample)

    def _sample_batch(self, logits: np.ndarray, reqs: list[Request],
                      positions: list[int]) -> list[int]:
        """ONE vectorized sampling call for all rows needing a token
        this step, honoring each request's own SamplingParams. Rows are
        padded up to a power-of-two bucket so steady-state steps reuse
        the compiled sampler; all-greedy batches (the common serving
        default) take a pure-numpy argmax fast path."""
        n = logits.shape[0]
        dflt = self.ecfg
        temps = np.asarray(
            [r.params.temperature if r.params else dflt.temperature
             for r in reqs], np.float32)
        if (temps <= 0.0).all():
            return [int(t) for t in np.argmax(logits, axis=-1)]
        topks = np.asarray(
            [min(r.params.top_k if r.params else dflt.top_k,
                 logits.shape[1]) for r in reqs], np.int32)
        kmax = min(_bucket(int(topks.max())), logits.shape[1])
        fn = self._sample_fns.get(kmax)
        if fn is None:
            fn = self._sample_fns[kmax] = self._make_sample_fn(kmax)
        nb = _bucket(n)
        lg = np.zeros((nb, logits.shape[1]), np.float32)
        lg[:n] = logits
        tp = np.zeros((nb,), np.float32)       # pad rows sample greedily
        tp[:n] = temps
        rids = np.asarray([r.request_id for r in reqs], np.int32)
        toks = fn(
            jnp.asarray(lg),
            jnp.asarray(_pad_to(rids, nb)),
            jnp.asarray(_pad_to(np.asarray(positions, np.int32), nb)),
            jnp.asarray(tp),
            jnp.asarray(_pad_to(topks, nb, fill=1)))
        return [int(t) for t in np.asarray(toks)[:n]]

    def _sample(self, logits: np.ndarray, req: Request,
                position: int) -> int:
        return self._sample_batch(logits[None], [req], [position])[0]

    def _block_params(self, li: int):
        return jax.tree.map(lambda a: a[li], self.params["blocks"])

    # --------------------------------------------------- unified one-forward

    def _forward_step(self, plan: list[tuple[Request, int, int]],
                      decode: list[tuple[Request, list]]):
        """Pack prompt-chunk rows and decode rows into ONE ragged forward.

        A decode row is a chunk of 1 (its last sampled token) whose paged
        history is the whole sequence so far — the same
        fp-queries-over-int4-history contract the prefill kernel already
        serves, so the union needs no second attention dataflow. A
        SPECULATING decode row (``decode`` pairs each request with its
        planned draft, possibly empty) is the same thing with qlen
        1+k: last sampled token + k drafted tokens, verified from the
        chunk's per-position logits after the forward. The packed layout
        is bucketed (powers of two) so repeated steps hit the jit cache;
        padding tokens scatter to out-of-range pages (dropped) and pad
        rows have qlen 0 (masked).

        Failure isolation: everything from destination resolution
        through the forward runs under a guard — an exception there
        (including injected ``append_kv``/``forward`` faults)
        quarantines every request in THIS batch to FAILED and returns;
        requests outside the batch are untouched. Page accounting stays
        exact because all host state (prefill_pos, seq_len, advance)
        only moves AFTER the forward succeeds, so ``free_seq`` on a
        quarantined row returns the pools to baseline. After the
        forward, a per-row non-finite guard fails exactly the rows
        whose logits are NaN/Inf (a spec row checks its whole verify
        chunk), and the sampler/verifier run under their own guards
        (rows mid-prefill are never touched by either)."""
        if self.trace_spans:
            self._phases.enter("engine.pack")
        rows = list(plan) + [
            (r, int(self.cache.seq_len[r.seq_slot]), 1 + len(d))
            for r, d in decode]
        starts = np.asarray([s for _, s, _ in rows])
        takes = np.asarray([t for _, _, t in rows])
        slots = np.asarray([r.seq_slot for r, _, _ in rows])
        nseq, cmax, ttot = len(rows), int(takes.max()), int(takes.sum())
        cum = np.concatenate([[0], np.cumsum(takes)])

        # ragged layout: packed index → (row, in-chunk offset)
        tok_seq = np.repeat(np.arange(nseq), takes)
        tok_off = np.concatenate([np.arange(t) for t in takes])
        tok_pos = starts[tok_seq] + tok_off            # absolute positions
        tokens = np.concatenate(
            [np.asarray(r.prompt[s:s + t]) for r, s, t in plan]
            + [[r.generated[-1]] + d for r, d in decode]).astype(np.int64)
        # logit slots: by default one per row (its LAST packed token —
        # exactly the historical cum[1:]-1 layout, so spec-off steps
        # reuse their jit cache entries bit-for-bit); a speculating row
        # contributes every chunk position, since verification needs
        # logits at each drafted token
        nplan = len(plan)
        slot0: list[int] = []
        logit_idx: list[int] = []
        for si in range(nseq):
            slot0.append(len(logit_idx))
            if si >= nplan and takes[si] > 1:
                logit_idx.extend(range(int(cum[si]), int(cum[si + 1])))
            else:
                logit_idx.append(int(cum[si + 1]) - 1)
        try:
            logits, nan_fault = self._guarded_forward(
                plan, rows, starts, takes, slots, nseq, cmax, ttot, cum,
                tok_seq, tok_off, tok_pos, tokens,
                np.asarray(logit_idx))
        except Exception as e:  # noqa: BLE001 — batch-granular quarantine
            # drafts die with the batch: counted as rollbacks so
            # drafted == accepted + rollback stays conserved under faults
            self.spec_rollback_tokens += sum(len(d) for _, d in decode)
            for r, _, _ in rows:
                self._fail(r, f"forward: {e!r}")
            return
        if self.trace_spans:
            self._phases.enter("engine.sample")
            self.last_forward_rows = list(zip(starts.tolist(),
                                              takes.tolist()))

        # host state: prompt progress + decode appends; a completed
        # prompt publishes its full pages into the prefix index.
        # Speculating rows do NOT advance here — their resident length
        # is decided by verification (accepted prefix) via truncate_seq
        for r, s, t in plan:
            r.prefill_pos = s + t
            self.cache.seq_len[r.seq_slot] = r.prefill_pos
            if self.ecfg.prefix_caching and r.prefill_pos == len(r.prompt):
                self.cache.publish_prefix(r.seq_slot, r.prompt)
        self.cache.advance([r.seq_slot for r, d in decode if not d])

        # one vectorized sample over finished-prefill rows ∪ plain
        # decode rows; speculating rows verify per-position afterwards
        need = [(slot0[si], r, len(r.prompt))
                for si, (r, s, t) in enumerate(plan)
                if s + t == len(r.prompt)]
        need += [(slot0[nplan + j], r, r.total_len)
                 for j, (r, d) in enumerate(decode) if not d]
        spec = [(slot0[nplan + j], r, int(starts[nplan + j]), d)
                for j, (r, d) in enumerate(decode) if d]
        if not need and not spec:
            return
        if nan_fault is not None:
            # injected NaN lands on a row actually being consumed (row
            # clamped into the sampled/verified slots), so the schedule
            # reliably exercises the guards below
            sampled = ([si for si, _, _ in need]
                       + [s0 for s0, _, _, _ in spec])
            logits[sampled[min(nan_fault.row, len(sampled) - 1)], :] = \
                np.nan
        # per-row non-finite guard: a NaN/Inf logits row — injected or
        # real — quarantines exactly that request; finite rows sample on
        if need:
            finite = np.isfinite(
                logits[[si for si, _, _ in need]]).all(axis=-1)
            if not finite.all():
                for (_, r, _), ok in zip(need, finite):
                    if not ok:
                        self._fail(r, "non_finite_logits")
                need = [t for t, ok in zip(need, finite) if ok]
        if need:
            self._sample_rows(logits, need)
        for s0, r, ctx, d in spec:
            if np.isfinite(logits[s0:s0 + len(d) + 1]).all():
                self._verify_row(logits, s0, r, ctx, d)
            else:
                self.spec_rollback_tokens += len(d)
                self._fail(r, "non_finite_logits")

    def _sample_rows(self, logits: np.ndarray, need: list):
        """Guarded batched sampling: a sampler exception (or injected
        ``sample`` fault) fails exactly the rows being sampled — rows
        mid-prefill never reach here."""
        try:
            if self.faults.check("sample"):
                raise InjectedFault("sample: injected sampler failure")
            toks = self._sample_batch(
                logits[[si for si, _, _ in need]],
                [r for _, r, _ in need],
                [p for _, _, p in need])
        except Exception as e:  # noqa: BLE001 — row-granular quarantine
            for _, r, _ in need:
                self._fail(r, f"sample: {e!r}")
            return
        for (_, r, _), tok in zip(need, toks):
            self._record_token(r, tok)

    def _verify_row(self, logits: np.ndarray, s0: int, r: Request,
                    ctx: int, draft: list):
        """Commit one speculating row's verified prefix.

        The forward already wrote KV for the WHOLE 1+k chunk (last
        sampled token + k drafts) at positions [ctx, ctx+k]; the walk
        over the chunk's per-position logits decides how much of it was
        real. ``truncate_seq`` lands the row's resident length at
        ctx + len(committed) FIRST — retracting rejected drafts' KV
        (refcount/prefix-safe) and advancing over accepted ones in one
        move — and only then do the committed tokens emit, so a
        reentrant ``abort()`` from an ``on_event`` callback mid-loop
        finds page accounting already consistent. A verification
        failure (or an injected ``verify`` fault) quarantines exactly
        this request; the rest of the batch keeps its step."""
        if r.seq_slot < 0 or r.state.terminal:
            # reentrant abort earlier in this step's loop — the draft
            # died with the request's pages; count it rolled back
            self.spec_rollback_tokens += len(draft)
            return
        try:
            if self.faults.check("verify"):
                raise InjectedFault("verify: injected verifier failure")
            committed, accepted = self._verify_tokens(logits, s0, r, draft)
            self.cache.truncate_seq(r.seq_slot, ctx + len(committed))
        except Exception as e:  # noqa: BLE001 — row-granular quarantine
            self.spec_rollback_tokens += len(draft)
            self._fail(r, f"verify: {e!r}")
            return
        self.spec_accepted_tokens += accepted
        self.spec_rollback_tokens += len(draft) - accepted
        for tok in committed:
            self._record_token(r, tok)

    def _verify_tokens(self, logits: np.ndarray, s0: int, r: Request,
                       draft: list):
        """Walk the verify chunk's logits; return (committed, accepted).

        Position i of the chunk is conditioned on the last sampled
        token plus drafts 0..i-1, so its logits row is EXACTLY what a
        plain decode step would have produced after committing those
        drafts. Greedy (the serving default): argmax each row; a match
        accepts the draft and moves on, the first mismatch commits the
        corrected token and stops — bitwise the tokens speculation-off
        greedy would emit, just several per forward. Stochastic:
        point-mass rejection sampling per position (accept draft w.p.
        p(draft), else draw the renormalized residual) — the committed
        tokens are distributed exactly as i.i.d. draws from each
        position's sampling distribution. Either way the row after the
        last accepted draft yields one bonus token, so a verified step
        always commits ≥ 1 token. ``accepted`` counts draft tokens
        kept (the acceptance-rate numerator)."""
        p = r.params
        temp = p.temperature if p is not None else self.ecfg.temperature
        top_k = min(p.top_k if p is not None else self.ecfg.top_k,
                    logits.shape[1])
        remaining = r.max_new_tokens - len(r.generated)
        committed: list[int] = []
        accepted = 0
        i = 0
        while i <= len(draft) and len(committed) < remaining:
            row = logits[s0 + i]
            drafted = int(draft[i]) if i < len(draft) else None
            if temp <= 0.0:
                tok, ok = int(np.argmax(row)), False
                ok = drafted is not None and tok == drafted
            else:
                tok, ok = _reject_sample(row, temp, top_k, drafted,
                                         r.request_id, r.total_len + i)
            committed.append(tok)
            if not ok:
                break
            accepted += 1
            i += 1
        return committed, accepted

    def _guarded_forward(self, plan, rows, starts, takes, slots, nseq,
                         cmax, ttot, cum, tok_seq, tok_off, tok_pos,
                         tokens, logit_idx):
        """The fault-guarded section of :meth:`_forward_step`:
        destination resolution (the ``append_kv`` fault point), shape
        bucketing, and the ONE forward (the ``forward`` fault point —
        ``raise`` aborts here; ``nan`` returns the armed fault so the
        caller corrupts a sampled row). ``logit_idx`` lists the packed
        token indices whose logits the caller consumes (one per row
        unless a row speculates, then all its chunk positions); its own
        bucket ``lb`` joins the jit-cache key, and collapses to the
        historical ``nb`` whenever no row speculates. The 12 layout
        arrays are written into one int32 buffer (:func:`_pack_layout`)
        and cross to the device in ONE transfer (``host_uploads``); the
        jitted :func:`_unpack_layout` slices them back out for
        :meth:`_unified_forward`, whose arguments are unchanged.
        Returns (writable logits ndarray [lb, V], nan_fault). No host
        scheduler/cache bookkeeping moves in here — an exception leaves
        page accounting untouched, so the caller's quarantine frees back
        to baseline."""
        pages_np, offs_np = self.cache.token_dests_np(slots[tok_seq],
                                                      tok_pos)
        # shape buckets — the jit cache key
        tb = _bucket(ttot, lo=8)
        nb = _bucket(nseq)
        cb = _bucket(cmax)
        npb = min(_bucket(self.cache.pages_needed(max(int(starts.max()), 1))),
                  self.cache.pcfg.max_pages_per_seq)

        pf_tokens = int(sum(t for _, _, t in plan))
        self.peak_prefill_fp_tokens = max(self.peak_prefill_fp_tokens,
                                          pf_tokens)
        self.prefill_tokens += pf_tokens
        self.forward_calls += 1
        # all rows history-free (a pure first-chunk step, so no decode
        # rows either) → the causal fp flash path, exactly like the
        # split baseline's fast path (its own static trace variant)
        no_history = int(starts.max()) == 0
        schedule = self.ecfg.attention_schedule
        hkv = self.cfg.num_kv_heads
        hkv_loc = hkv // self.tp_size
        wq = schedule == "work_queue" and not no_history
        if wq:
            # flat Stream-K descriptors over the rows' REAL pages (+ one
            # chunk item per row), pow-2 padded — the work count replaces
            # npages as the attention dimension of the jit-cache key, so
            # the dense block tables collapse to a constant-shape dummy.
            # The padding sentinel must clear the BUCKETED row count:
            # rows [nseq, nb) are live (qlen-0) segments in the combine.
            # Under TP the descriptor is built for the LOCAL head count:
            # every shard attends the same page stream with its own head
            # slice, so one replicated descriptor drives all shards
            desc_np = self.cache.work_queue_np(slots, starts, takes,
                                               pad_row=nb * hkv_loc,
                                               num_kv_heads=hkv_loc)
            tables = np.zeros((nb, 1), np.int32)
        else:
            desc_np = np.zeros((8, 4), np.int32)
            tables = np.zeros((nb, npb), np.int32)
            tables[:nseq] = self.cache.block_tables_np(slots, npb)
        if not no_history:
            # fig10 measured-ablation counters: the real work is the
            # same under both schedules; the launched grid is not
            self.attn_forwards += 1
            items = int(
                hkv * (np.sum((starts + self.ecfg.page_size - 1)
                              // self.ecfg.page_size) + nseq))
            self.attn_work_items += items
            # exact split: work per shard is hkv_loc · (pages + rows)
            per = items // self.tp_size
            for i in range(self.tp_size):
                self.attn_work_items_per_shard[i] += per
            self.attn_dense_grid_items += nb * hkv * (npb + 1)
            self.attn_grid_items += (desc_np.shape[0] * self.tp_size if wq
                                     else nb * hkv * (npb + 1))
        lb = _bucket(len(logit_idx))
        nan_fault = None
        fwd_fault = self.faults.check("forward")
        if fwd_fault is not None:
            if fwd_fault.action == "raise":
                raise InjectedFault("forward: injected forward failure")
            nan_fault = fwd_fault
        if self.trace_spans:
            self._phases.enter("engine.upload")
        shapes = _layout_shapes(tb, nb, tables.shape[1], lb,
                                desc_np.shape[0])
        packed = _pack_layout(
            (tokens, tok_pos, pages_np, offs_np, tok_seq, tok_off,
             # decode tokens (the packed tail) fake-quantize their
             # in-flight KV so self-attention matches the int4 the split
             # decode path reads back
             np.arange(ttot) >= cum[len(plan)],
             tables,
             starts, takes,                            # ctx, qlens per row
             # consumed logit slots, own bucket (== nb when nothing
             # speculates — the historical last-token-per-row layout)
             logit_idx,
             desc_np),                                 # wq work items
            shapes,
            # padding destinations: page == num_pages is out of range →
            # the scatter update is dropped; row == nb likewise drops in
            # the packed→padded scatter; everything else pads with 0
            fills=(0, 0, self.cache.pcfg.num_pages, 0, nb) + (0,) * 7)
        layout = jax.device_put(packed, self._layout_sharding)
        self.host_uploads += 1
        # the jit-cache key: the statics and every padded size
        bucket = (cb, no_history, schedule, tb, nb, tables.shape[1],
                  desc_np.shape[0], lb)
        new_bucket = bucket not in self._fwd_shapes
        if new_bucket:
            self._fwd_shapes.add(bucket)
        if self.trace_spans:
            self._phases.enter("engine.dispatch.new_bucket" if new_bucket
                               else "engine.dispatch")
        logits, k_pool, v_pool = self._fwd(
            cb, no_history, schedule, self.params, self.cache.k_pool,
            self.cache.v_pool, *self._unpack(layout, shapes=shapes),
            self.cache.k_scale, self.cache.k_zero,
            self.cache.v_scale, self.cache.v_zero)
        self.cache.k_pool, self.cache.v_pool = k_pool, v_pool
        if self.trace_spans:
            self._phases.enter("engine.readback")
        # np.array (copy): the device buffer view is read-only and the
        # caller mutates rows in place (nan injection)
        return np.array(logits), nan_fault

    def _unified_forward(self, cmax: int, no_history: bool, schedule: str,
                         params, k_pool, v_pool, tokens, positions, pages,
                         offs, tseq, toff, dq_mask, block_tables, ctx,
                         qlens, last_idx, work_items,
                         k_scale, k_zero, v_scale, v_zero):
        """The jitted unified forward (one trace per shape bucket).

        tokens/positions/pages/offs/tseq/toff/dq_mask: [Tb] int32 packed
        layout; block_tables: [Nb, NPb]; ctx/qlens: [Nb]; last_idx:
        [Lb] consumed logit slots (== [Nb] last-token-per-row when no
        row speculates, else every spec chunk position too);
        work_items: [Wb, 4] flat Stream-K descriptors (the attention
        shape key under ``schedule="work_queue"`` — block_tables is a
        [Nb, 1] dummy there; under "dense" the roles swap);
        k_scale/k_zero/v_scale/v_zero: the cache's static per-channel
        int4 scales [Hkv, 1, D] (explicit args so ``shard_map`` can hand
        each shard its head slice). Returns (logits [Lb, V] f32, k_pool,
        v_pool) — pools updated with the step's quantized KV.

        Single device: runs :meth:`_unified_body` directly. TP: wraps
        the same body in ``shard_map`` over the engine mesh — params and
        pools enter pre-sharded (placed by ``_init_sharding``), every
        int32 layout array is replicated (page ids are host-global), and
        each shard computes its kv-head slice end to end with psums only
        at the wo / w_down seams (inside ``_row_linear``).

        The 12 layout arrays are slices of the step's one packed upload
        (``_guarded_forward``), already on the device — replicated over
        the mesh when the engine has one."""
        self.trace_count += 1          # traced body: fires once per compile
        args = (params, k_pool, v_pool, tokens, positions, pages, offs,
                tseq, toff, dq_mask, block_tables, ctx, qlens, last_idx,
                work_items, k_scale, k_zero, v_scale, v_zero)
        if self.tp_size == 1:
            # single device: hand the body the CLOSURE scales (trace-time
            # constants, the historical graph) rather than the traced
            # copies — embedding them keeps the compiled HLO bit-identical
            # to the pre-TP engine, so pinned greedy parity workloads
            # cannot flip on recompilation noise. The traced scale args
            # are dead here (DCE'd); only shard_map needs them live, to
            # hand each shard its head slice
            return self._unified_body(cmax, no_history, schedule, None,
                                      *args[:15], self.cache.k_scale,
                                      self.cache.k_zero, self.cache.v_scale,
                                      self.cache.v_zero)
        body = functools.partial(self._unified_body, cmax, no_history,
                                 schedule, "model")
        pool, scale = self._pool_pspec, self._scale_pspec
        return jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(self._param_pspecs, pool, pool) + (P(),) * 12
            + (scale,) * 4,
            out_specs=(P(), pool, pool),
            check_vma=False)(*args)

    def _unified_body(self, cmax: int, no_history: bool, schedule: str,
                      tp_axis, params, k_pool, v_pool, tokens, positions,
                      pages, offs, tseq, toff, dq_mask, block_tables, ctx,
                      qlens, last_idx, work_items,
                      k_scale, k_zero, v_scale, v_zero):
        """Per-shard unified forward. With ``tp_axis=None`` this IS the
        single-device forward (bit-identical math); under ``shard_map``
        every array is the local shard and ``tp_axis`` names the mesh
        axis the two all-reduce seams psum over. Head counts are derived
        from the local weight shapes via ``_project_qkv`` overrides; the
        attention kernels are already shape-agnostic (they read head
        counts off q/pool shapes), so the same work-queue descriptors
        drive every shard's local heads."""
        cfg = self.cfg
        tp = self.tp_size if tp_axis is not None else 1
        hq_loc = cfg.num_heads // tp
        hkv_loc = cfg.num_kv_heads // tp
        nseq = block_tables.shape[0]
        with self.lm._ctx():
            x = self.lm._embed(params, tokens[None, :])
            pos2 = positions[None, :]
            for li in range(cfg.num_layers):
                bp = jax.tree.map(lambda a: a[li], params["blocks"])
                h = C.apply_norm(bp["attn_norm"], x, cfg.norm, cfg.norm_eps)
                q, k, v = ATT._project_qkv(
                    bp["attn"], cfg, h, h, pos2, pos2,
                    num_heads=hq_loc, num_kv_heads=hkv_loc)
                # quantize + page the union's KV (padding rides on OOB
                # destinations), then attend: fp queries over the int4
                # history pages + each row's causal in-flight fp chunk
                kq, vq = KVC.quantize_kv_with(
                    k, v, k_scale, k_zero, v_scale, v_zero)
                hkv, half = kq.shape[1], kq.shape[-1]  # [1, Hloc, Tb, D/2]
                kq = jnp.moveaxis(kq, 1, 2).reshape(-1, hkv, half)
                vq = jnp.moveaxis(vq, 1, 2).reshape(-1, hkv, half)
                k_pool = k_pool.at[li, pages, :, offs].set(kq, mode="drop")
                v_pool = v_pool.at[li, pages, :, offs].set(vq, mode="drop")
                # decode rows' self-attention reads the fake-quantized
                # chunk — the same values their int4 page dequantizes to
                kdq, vdq = KVC.qdq_kv_with(
                    k, v, k_scale, k_zero, v_scale, v_zero)
                m = (dq_mask != 0)[None, :, None, None]
                k_att = jnp.where(m, kdq, k.astype(jnp.float32))
                v_att = jnp.where(m, vdq, v.astype(jnp.float32))

                def pad(a):    # packed [1, Tb, Hx, D] → [Nb, Cb, Hx, D]
                    z = jnp.zeros((nseq, cmax) + a.shape[2:], a.dtype)
                    return z.at[tseq, toff].set(a[0], mode="drop")

                if no_history:
                    # first chunk for every packed prompt: padding keys
                    # are causally masked, so plain fp flash is exact
                    out = ATT.flash_attention(pad(q), pad(k_att),
                                              pad(v_att), causal=True)
                elif schedule == "work_queue":
                    out = ops.paged_kv4_prefill_attention_wq(
                        pad(q), pad(k_att), pad(v_att),
                        k_pool[li], k_scale, k_zero,
                        v_pool[li], v_scale, v_zero,
                        work_items, impl=self.quant.impl)
                else:
                    out = ops.paged_kv4_prefill_attention(
                        pad(q), pad(k_att), pad(v_att),
                        k_pool[li], k_scale, k_zero,
                        v_pool[li], v_scale, v_zero,
                        block_tables, ctx, qlens, impl=self.quant.impl)
                a = out[tseq, toff][None]          # repack [1, Tb, ...]
                a = a.astype(x.dtype).reshape(1, -1, hq_loc * cfg.head_dim)
                x = x + _row_linear(bp["attn"]["wo"], a, tp_axis)
                h = C.apply_norm(bp["mlp_norm"], x, cfg.norm, cfg.norm_eps)
                if "moe" in bp:
                    y, _ = MLP.moe_apply(bp["moe"], h, cfg)
                else:
                    y = _mlp_row(bp["mlp"], h, cfg.mlp_act, tp_axis)
                x = x + y
            hN = C.apply_norm(params["final_norm"], x[:, last_idx],
                              cfg.norm, cfg.norm_eps)
            logits = self.lm._head(params, hN)
        return logits[0], k_pool, v_pool

    # -------------------------------------------- split-step fig11 baseline

    def _count_trace(self, sig):
        """Split-path proxy for ``trace_count``: eager dispatch caches
        per packed shape, so each new signature is a compile."""
        if sig not in self._fwd_shapes:
            self._fwd_shapes.add(sig)
            self.trace_count += 1

    def _prefill(self, req: Request):
        """[Benchmark baseline] whole-prompt prefill: one O(T²) fp flash
        forward per request; the full prompt's fp KV is live at once."""
        cfg = self.cfg
        self.peak_prefill_fp_tokens = max(self.peak_prefill_fp_tokens,
                                          len(req.prompt))
        self.prefill_tokens += len(req.prompt)
        self.forward_calls += 1
        self._count_trace(("whole", len(req.prompt)))
        with self.lm._ctx():
            tokens = jnp.asarray(req.prompt, jnp.int32)[None, :]
            x = self.lm._embed(self.params, tokens)
            positions = jnp.arange(len(req.prompt))[None, :]
            for li in range(cfg.num_layers):
                bp = self._block_params(li)
                h = C.apply_norm(bp["attn_norm"], x, cfg.norm, cfg.norm_eps)
                q, k, v = ATT._project_qkv(
                    bp["attn"], cfg, h, h, positions, positions)
                a = ATT.flash_attention(q, k, v, causal=cfg.causal)
                self.cache.write_prompt(li, req.seq_slot, k, v)
                a = a.astype(x.dtype).reshape(1, -1, cfg.q_dim)
                x = x + C.linear(bp["attn"]["wo"], a)
                h = C.apply_norm(bp["mlp_norm"], x, cfg.norm, cfg.norm_eps)
                if "moe" in bp:
                    y, _ = MLP.moe_apply(bp["moe"], h, cfg)
                else:
                    y = MLP.mlp_apply(bp["mlp"], h, cfg.mlp_act)
                x = x + y
            hN = C.apply_norm(self.params["final_norm"], x[:, -1:],
                              cfg.norm, cfg.norm_eps)
            logits = self.lm._head(self.params, hN)
        tok = self._sample(np.asarray(logits[0, -1]), req,
                           len(req.prompt))
        self.cache.extend_seq(req.seq_slot)
        req.prefill_pos = len(req.prompt)
        self._record_token(req, tok)

    def _prefill_forward(self, plan: list[tuple[Request, int, int]]):
        """[Split baseline] ONE ragged forward over the planned chunk
        slices (no decode rows — those run in a second forward).

        Tokens from all planned requests are packed into a single
        [1, T_total] sequence (cu_seqlens-style offsets) for the
        position-wise work (norms, W4Ax projections, MLP); attention
        unpacks to a padded [nseq, C_max] view for the paged prefill
        kernel, then repacks. Each layer writes the chunk's quantized KV
        into the pools via destinations precomputed once for the step."""
        cfg = self.cfg
        starts = np.asarray([s for _, s, _ in plan])
        takes = np.asarray([t for _, _, t in plan])
        slots = np.asarray([r.seq_slot for r, _, _ in plan])
        nseq, cmax, ttot = len(plan), int(takes.max()), int(takes.sum())
        cum = np.concatenate([[0], np.cumsum(takes)])

        # ragged layout: packed index → (sequence, in-chunk offset)
        tok_seq = np.repeat(np.arange(nseq), takes)
        tok_off = np.concatenate([np.arange(t) for t in takes])
        tok_pos = starts[tok_seq] + tok_off            # absolute positions
        tokens = np.concatenate(
            [r.prompt[s:s + t] for r, s, t in plan]).astype(np.int64)

        # page destinations: ONE host lookup for the step, all layers
        pages, offs = self.cache.token_dests(slots[tok_seq], tok_pos)
        block_tables = self.cache.block_tables_device(
            slots, max(int(starts.max()), 1))
        ctx = jnp.asarray(starts, jnp.int32)
        qlens = jnp.asarray(takes, jnp.int32)
        tseq = jnp.asarray(tok_seq)
        toff = jnp.asarray(tok_off)
        # packed↔padded fast paths: equal takes means the seq-major packed
        # layout IS the padded layout (reshape, no scatter/gather); chunks
        # with no paged history anywhere are pure fp causal attention
        uniform = bool((takes == takes[0]).all())
        no_history = int(starts.max()) == 0

        self.peak_prefill_fp_tokens = max(self.peak_prefill_fp_tokens, ttot)
        self.prefill_tokens += ttot
        self.forward_calls += 1
        self._count_trace(("prefill", nseq, cmax, ttot, no_history))
        with self.lm._ctx():
            x = self.lm._embed(self.params,
                               jnp.asarray(tokens, jnp.int32)[None, :])
            positions = jnp.asarray(tok_pos)[None, :]
            for li in range(cfg.num_layers):
                bp = self._block_params(li)
                h = C.apply_norm(bp["attn_norm"], x, cfg.norm, cfg.norm_eps)
                q, k, v = ATT._project_qkv(
                    bp["attn"], cfg, h, h, positions, positions)
                # quantize + page the chunk's KV, then attend: fp queries
                # over int4 history pages + the causal in-flight fp chunk
                self.cache.scatter_tokens(li, pages, offs, k, v)

                def pad(a):       # [1, Ttot, Hx, D] → [nseq, Cmax, Hx, D]
                    if uniform:
                        return a[0].reshape(nseq, cmax, *a.shape[2:])
                    z = jnp.zeros((nseq, cmax) + a.shape[2:], a.dtype)
                    return z.at[tseq, toff].set(a[0])

                if no_history:
                    # first chunk for every packed prompt: padding keys
                    # are causally masked, so plain fp flash is exact
                    out = ATT.flash_attention(pad(q), pad(k), pad(v),
                                              causal=True)
                else:
                    out = ops.paged_kv4_prefill_attention(
                        pad(q), pad(k), pad(v),
                        self.cache.k_pool[li], self.cache.k_scale,
                        self.cache.k_zero,
                        self.cache.v_pool[li], self.cache.v_scale,
                        self.cache.v_zero,
                        block_tables, ctx, qlens, impl=self.quant.impl)
                if uniform:
                    a = out.reshape(1, ttot, *out.shape[2:])
                else:
                    a = out[tseq, toff][None]          # repack [1, Ttot, ...]
                a = a.astype(x.dtype).reshape(1, ttot, cfg.q_dim)
                x = x + C.linear(bp["attn"]["wo"], a)
                h = C.apply_norm(bp["mlp_norm"], x, cfg.norm, cfg.norm_eps)
                if "moe" in bp:
                    y, _ = MLP.moe_apply(bp["moe"], h, cfg)
                else:
                    y = MLP.mlp_apply(bp["mlp"], h, cfg.mlp_act)
                x = x + y
            finished = [(si, r) for si, (r, s, t) in enumerate(plan)
                        if s + t == len(r.prompt)]
            if finished:
                last = [int(cum[si] + takes[si] - 1) for si, _ in finished]
                hN = C.apply_norm(self.params["final_norm"], x[:, last],
                                  cfg.norm, cfg.norm_eps)
                logits = np.asarray(self.lm._head(self.params, hN))

        for r, s, t in plan:
            r.prefill_pos = s + t
            self.cache.seq_len[r.seq_slot] = r.prefill_pos
            if self.ecfg.prefix_caching and r.prefill_pos == len(r.prompt):
                self.cache.publish_prefix(r.seq_slot, r.prompt)
        if finished:
            toks = self._sample_batch(
                logits[0], [r for _, r in finished],
                [len(r.prompt) for _, r in finished])
            for (_, r), tok in zip(finished, toks):
                self._record_token(r, tok)

    def _attend_paged(self, li: int, q, block_tables, lengths,
                      work_items=None):
        """One kernel call for the whole decode batch — block tables in,
        no per-sequence materialization. With ``work_items`` set (the
        work-queue schedule) the flat descriptors replace the dense
        block-table walk."""
        cache = self.cache
        if work_items is not None:
            return ops.paged_kv4_decode_attention_wq(
                q[:, 0], cache.k_pool[li], cache.k_scale, cache.k_zero,
                cache.v_pool[li], cache.v_scale, cache.v_zero,
                work_items, impl=self.quant.impl)
        return ops.paged_kv4_decode_attention(
            q[:, 0], cache.k_pool[li], cache.k_scale, cache.k_zero,
            cache.v_pool[li], cache.v_scale, cache.v_zero,
            block_tables, lengths, impl=self.quant.impl)

    def _attend_gather(self, li: int, q, slots, max_len, lengths):
        """[Benchmark baseline] per-token O(context) gather, then the
        contiguous KV4 kernel. The batch-broadcast scale/zero tensors are
        cached per batch size — they are step-invariant, and rebuilding
        them allocated four arrays per layer per step."""
        cache = self.cache
        bsz = q.shape[0]
        kp, vp, _ = cache.gather_kv(li, slots, max_len)
        if bsz not in self._gather_bcast:
            bcast = lambda s: jnp.broadcast_to(s[None], (bsz, *s.shape))
            self._gather_bcast[bsz] = (
                bcast(cache.k_scale), bcast(cache.k_zero),
                bcast(cache.v_scale), bcast(cache.v_zero))
        ks, kz, vs, vz = self._gather_bcast[bsz]
        return ops.kv4_decode_attention(
            q[:, 0], kp, ks, kz, vp, vs, vz, lengths,
            impl=self.quant.impl)

    def _decode_batch(self, reqs: list[Request]):
        """[Split baseline] the separate decode forward."""
        cfg = self.cfg
        slots = [r.seq_slot for r in reqs]
        bsz = len(reqs)
        last = jnp.asarray([[r.generated[-1]] for r in reqs], jnp.int32)

        lengths_np = self.cache.seq_len[slots].copy()
        max_len = int(lengths_np.max()) + 1
        paged = self.ecfg.decode_attention == "paged"
        # block tables are fixed for the step (extend_seq already ran);
        # lengths include the token being appended this step. Page
        # destinations for the appends are resolved on the host ONCE and
        # reused by every layer's scatter (was: one block-table lookup +
        # validation per layer — num_layers host syncs per step).
        lengths = jnp.asarray(lengths_np + 1, jnp.int32)
        pages, offs = self.cache.token_dests(slots, lengths_np)
        self.forward_calls += 1
        hkv = self.cfg.num_kv_heads
        npages = self.cache.pages_needed(max_len)
        work_items = None
        block_tables = None
        if paged and self.ecfg.attention_schedule == "work_queue":
            # the decode batch attends over ctx + the token written this
            # step — descriptors cover exactly those real pages, and the
            # dense block tables never ship to the device
            desc_np = self.cache.work_queue_np(slots, lengths_np + 1)
            work_items = jnp.asarray(desc_np)
            self.attn_grid_items += desc_np.shape[0]
            self._count_trace(("decode", bsz, desc_np.shape[0]))
        else:
            if paged:
                block_tables = self.cache.block_tables_device(
                    slots, max_len)
                self.attn_grid_items += bsz * hkv * npages
            self._count_trace(("decode", bsz, npages))
        if paged:
            self.attn_forwards += 1
            items = int(hkv * np.sum(
                (lengths_np + self.ecfg.page_size) // self.ecfg.page_size))
            self.attn_work_items += items
            self.attn_work_items_per_shard[0] += items  # split: one device
            self.attn_dense_grid_items += bsz * hkv * npages
        with self.lm._ctx():
            x = self.lm._embed(self.params, last)
            positions = jnp.asarray(lengths_np)[:, None]
            for li in range(cfg.num_layers):
                bp = self._block_params(li)
                h = C.apply_norm(bp["attn_norm"], x, cfg.norm, cfg.norm_eps)
                q, k, v = ATT._project_qkv(
                    bp["attn"], cfg, h, h, positions, positions)
                # write the batch's new KV (one scatter), then attend over
                # the pools via block tables — one kernel call per layer
                self.cache.scatter_tokens(li, pages, offs, k, v)
                if paged:
                    out = self._attend_paged(li, q, block_tables, lengths,
                                             work_items)
                else:
                    out = self._attend_gather(li, q, slots, max_len, lengths)
                out = out.reshape(bsz, 1, cfg.q_dim).astype(x.dtype)
                x = x + C.linear(bp["attn"]["wo"], out)
                h = C.apply_norm(bp["mlp_norm"], x, cfg.norm, cfg.norm_eps)
                if "moe" in bp:
                    y, _ = MLP.moe_apply(bp["moe"], h, cfg)
                else:
                    y = MLP.mlp_apply(bp["mlp"], h, cfg.mlp_act)
                x = x + y
            hN = C.apply_norm(self.params["final_norm"], x,
                              cfg.norm, cfg.norm_eps)
            logits = np.asarray(self.lm._head(self.params, hN))
        self.cache.advance(slots)
        toks = self._sample_batch(
            logits[:, -1], reqs, [r.total_len for r in reqs])
        for r, tok in zip(reqs, toks):
            self._record_token(r, tok)
