"""Serving launcher: quantize a model offline (FMPQ W4AxKV4) and run the
request-lifecycle engine over a synthetic request trace.

Requests go through ``Engine.submit`` with per-request
:class:`SamplingParams`; ``--stream`` prints tokens as ``step()`` emits
them (the ``engine.events()`` queue); ``--prefix-cache`` toggles
refcounted shared-prompt page reuse (``--shared-prefix`` controls how
many prompt tokens the synthetic trace shares, ``--prefix-cache-max-
bytes`` caps the reclaimable LRU); ``--attention-schedule`` picks the
paged-attention grid schedule (Stream-K work queue vs dense baseline);
``--abort-every N`` cancels every Nth request mid-flight to exercise
the abort path; ``--mesh DxM`` (model > 1) turns on tensor-parallel
sharded serving — heads and int4 KV pools shard over the model axis
with the scheduler and page allocator staying host-global.

Robustness knobs (the fault-tolerant serving core): ``--deadline-ms`` /
``--ttft-ms`` set per-request deadlines (expired requests end
``TIMED_OUT``), ``--max-waiting`` bounds the waiting queue (submits past
it are rejected ``FAILED("queue_full")`` and preemption victims are shed
instead of re-queued), ``--inject-faults SPEC`` arms a deterministic
fault schedule (``serving/faults.py`` grammar, e.g.
``"forward:step=3,action=nan;alloc_page:nth=20"``) to chaos-test the
step-level isolation, and ``--snapshot-every N`` rides a journaled
:class:`~repro.serving.recovery.RecoveryLog` along with the run (full
engine snapshot every N steps + per-token event journal).

``--speculation K`` turns on speculative multi-token decode on the
unified path: every request carries ``SamplingParams.speculation=K``,
the engine drafts K tokens per decode row from the prompt-lookup
source and verifies them in one forward (greedy output stays bitwise
identical to K=0). The summary's ``[sched] speculation:`` line reports
drafted/accepted (acceptance rate), rollbacks, and the no-op/error
counters; the ``[slo]`` line reports TTFT and TPOT mean + p95 over the
finished requests.

Replicated serving (``serving/replication.py``): ``--replicas N`` runs
N engine replicas behind a :class:`ReplicaGroup` — least-loaded
routing, per-step health checks, RecoveryLog artifact shipping —
with ``--failover standby|migrate`` picking the death policy and
``--kill-replica-at STEP`` (``--kill-replica IDX``) arming the
deterministic ``crash`` fault for failover smokes; the ``[group]``
summary line reports failovers/migrations/health. The
end-of-run summary reports throughput, prefix-cache hit rate + eviction
counters, schedule work/grid counters (per shard under TP), lifecycle
counts (aborted/failed/timed-out/shed/rejected), and the fired faults.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3_8b --smoke \
      --requests 16 --max-new 32 --stream --prefix-cache on
  PYTHONPATH=src python -m repro.launch.serve --arch llama3_8b --smoke \
      --requests 12 --deadline-ms 2000 --max-waiting 4 \
      --inject-faults "forward:step=5,action=nan;sample:nth=3"
  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
      PYTHONPATH=src python -m repro.launch.serve --arch llama3_8b \
      --smoke --mesh 1x2 --head-dim 64 --int4-fraction 1.0
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs.base import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, parse_mesh_arg
from repro.models.lm import LM, QuantConfig
from repro.serving.engine import Engine, EngineConfig, SamplingParams


def _ms_stats(xs: list) -> str:
    """mean + p95 of a latency sample, formatted in ms (or 'n/a')."""
    if not xs:
        return "n/a"
    arr = np.asarray(xs) * 1000.0
    return f"mean {arr.mean():.1f}ms p95 {np.percentile(arr, 95):.1f}ms"


def _group_ecfg(args) -> EngineConfig:
    """Per-replica engine config for the ReplicaGroup path. Fault specs
    are armed through explicit per-replica injectors (so
    ``--kill-replica-at`` targets one replica), never via
    ``inject_faults`` — the group hands each engine its injector."""
    return EngineConfig(
        max_batch=args.max_batch, num_pages=args.pages,
        page_size=args.page_size, temperature=args.temperature,
        prefill_mode=args.prefill_mode,
        prefill_chunk_tokens=args.prefill_chunk,
        kv_range=args.kv_range,
        unified_step=(args.step_mode == "unified"),
        prefix_cache=(args.prefix_cache == "on"),
        attention_schedule=args.attention_schedule,
        prefix_cache_max_bytes=(args.prefix_cache_max_bytes or None),
        max_waiting=(args.max_waiting or None),
        sanitize=args.sanitize)


def _run_group(args, cfg, qparams, qaxes, quant, model: int):
    """Drive a ReplicaGroup over the synthetic trace (--replicas N)."""
    from repro.launch.mesh import make_replica_meshes
    from repro.serving.faults import Fault, FaultInjector
    from repro.serving.replication import ReplicaGroup

    # every replica gets its own device slice, model=1 included —
    # otherwise all replicas' params and pools land on device 0
    meshes = make_replica_meshes(args.replicas, model)
    print(f"[mesh] {args.replicas} replica(s) x (data=1, "
          f"model={model}) over {jax.device_count()} "
          f"{jax.default_backend()} device(s)", flush=True)
    faults = []
    for i in range(args.replicas):
        inj = (FaultInjector.from_spec(args.inject_faults)
               if args.inject_faults else FaultInjector())
        if args.kill_replica_at and i == args.kill_replica:
            inj.faults.append(Fault("crash", step=args.kill_replica_at))
        faults.append(inj)
    group = ReplicaGroup(
        cfg, qparams, quant, _group_ecfg(args),
        replicas=args.replicas, failover=args.failover,
        snapshot_every=(args.snapshot_every or 4), faults=faults,
        meshes=meshes, param_axes=qaxes)

    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, cfg.vocab_size,
                          size=args.shared_prefix).tolist()
    sp = SamplingParams(max_new_tokens=args.max_new,
                        temperature=args.temperature, top_k=args.top_k,
                        speculation=args.speculation,
                        deadline_ms=(args.deadline_ms or None),
                        ttft_ms=(args.ttft_ms or None))
    prompts = []
    for _ in range(args.requests):
        plen = int(rng.integers(args.prompt_len // 2, args.prompt_len + 1))
        prompts.append(shared
                       + rng.integers(0, cfg.vocab_size, size=plen).tolist())
    pending = [(i * args.arrival_every, p) for i, p in enumerate(prompts)]

    def stream_cb(ev):
        # lifetime ordinal from the GROUP record (a migrated request's
        # engine-local num_generated restarts after the fold; the group
        # count is the client-visible stream position)
        if ev.token is not None:
            n = len(group.delivered.get(ev.request_id, []))
            print(f"  [stream] req {ev.request_id} +tok {ev.token} "
                  f"(#{n})", flush=True)
        elif ev.finished:
            print(f"  [stream] req {ev.request_id} {ev.state.value}"
                  + (f" ({ev.stop_reason})" if ev.stop_reason else ""),
                  flush=True)

    t0 = time.time()
    gsteps = 0
    while (pending or group.has_work) and gsteps < 10_000:
        while pending and pending[0][0] <= gsteps:
            _, prompt = pending.pop(0)
            group.submit(prompt, sp,
                         on_event=stream_cb if args.stream else None)
        group.step()
        gsteps += 1
    dt = time.time() - t0

    total_tokens = sum(len(v) for v in group.delivered.values())
    print(f"[done] {len(group.terminals)} requests, {total_tokens} "
          f"tokens in {dt:.1f}s → {total_tokens/max(dt, 1e-9):.1f} tok/s "
          f"(group_steps={gsteps}, replica_steps={group.replica_steps})",
          flush=True)
    c = group.counters()
    health = " ".join(f"r{i}={h}" for i, h in sorted(c["health"].items()))
    print(f"[group] replicas={args.replicas} failover={args.failover} "
          f"failovers={c['failovers']} "
          f"migrated={c['migrated_requests']} "
          f"replica_steps={c['replica_steps']} "
          f"dup_suppressed={c['duplicates_suppressed']} "
          f"internal_errors={c['internal_errors']} {health}", flush=True)
    live = [r for r in group.replicas if r.alive]
    print(f"[robust] failed="
          f"{sum(r.engine.failed_count for r in live)} timed_out="
          f"{sum(r.engine.timeout_count for r in live)} shed="
          f"{sum(r.engine.shed_count for r in live)} rejected="
          f"{sum(r.engine.rejected_count for r in live)} "
          f"internal_errors={c['internal_errors']} sanitize_checks="
          f"{sum(r.engine.sanitize_checks for r in live)}", flush=True)
    for rep in group.replicas:
        if rep.engine.faults.fired:
            fired = [f"{p}:{a}@step{s}"
                     for p, a, s in rep.engine.faults.fired]
            print(f"[faults] replica {rep.idx}: fired {', '.join(fired)}",
                  flush=True)
    for idx, why, step in group.deaths:
        print(f"[death] replica {idx} at engine step {step} ({why})",
              flush=True)
    if not args.inject_faults and not args.kill_replica_at:
        _exit_on_failures(c["internal_errors"],
                          sum(r.engine.failed_count for r in live),
                          [r.engine.last_error for r in live])


def _exit_on_failures(internal_errors: int, failed: int, last_errors):
    """With no faults armed, a quarantined or failed request means the
    forward itself broke: exit non-zero instead of reporting success."""
    if internal_errors or failed:
        errs = "; ".join(e for e in last_errors if e) or "n/a"
        raise SystemExit(
            f"[error] failures with no faults armed: failed={failed} "
            f"internal_errors={internal_errors} last_error={errs}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--pages", type=int, default=256)
    ap.add_argument("--page-size", type=int, default=32)
    ap.add_argument("--int4-fraction", type=float, default=0.875)
    ap.add_argument("--schedule", default="split", choices=["split", "mixed"])
    ap.add_argument("--impl", default="auto", choices=["auto", "pallas", "ref"],
                    help="kernel implementation: auto = compiled Pallas on "
                         "TPU, the jnp reference elsewhere")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request SamplingParams.temperature (0=greedy)")
    ap.add_argument("--top-k", type=int, default=40,
                    help="per-request SamplingParams.top_k")
    ap.add_argument("--speculation", type=int, default=0,
                    help="per-request SamplingParams.speculation: draft "
                         "K tokens per decode row from the prompt-lookup "
                         "source and verify them in one forward (0 = "
                         "off; greedy output is bitwise identical either "
                         "way — K only changes how many forwards it "
                         "takes)")
    ap.add_argument("--prefill-mode", default="chunked",
                    choices=["chunked", "whole"])
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="ragged-prefill token budget per step")
    ap.add_argument("--kv-range", type=float, default=16.0,
                    help="calibrated |k|,|v| range for the int4 KV "
                         "scales; tighter ranges reduce quantization "
                         "error (decode reads quantized KV, prefill "
                         "attends to same-chunk KV in full precision, "
                         "so fold/migration parity tightens with it)")
    ap.add_argument("--step-mode", default="unified",
                    choices=["unified", "split"],
                    help="unified: ONE forward/step over decode rows + "
                         "prompt chunks (bucketed shapes); split: "
                         "separate prefill + decode forwards (baseline)")
    ap.add_argument("--attention-schedule", default="work_queue",
                    choices=["work_queue", "dense"],
                    help="paged-attention grid schedule: flat Stream-K "
                         "work queue with split-KV combine (default) or "
                         "the dense (B·Hkv, max_npages) baseline")
    ap.add_argument("--prefix-cache", default="on", choices=["on", "off"],
                    help="refcounted shared-prompt page reuse")
    ap.add_argument("--prefix-cache-max-bytes", type=int, default=0,
                    help="byte cap on the reclaimable prefix-page LRU "
                         "(0 = unlimited); evictions show in the summary")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prompt tokens shared by every request (a "
                         "synthetic system prompt — the prefix-cache "
                         "workload)")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are emitted")
    ap.add_argument("--abort-every", type=int, default=0,
                    help="abort every Nth request after its first token "
                         "(0 = never) — exercises the abort path")
    ap.add_argument("--deadline-ms", type=float, default=0,
                    help="per-request wall-clock deadline (0 = none): "
                         "expired requests end TIMED_OUT with partial "
                         "output retained")
    ap.add_argument("--ttft-ms", type=float, default=0,
                    help="per-request first-token budget (0 = none)")
    ap.add_argument("--max-waiting", type=int, default=0,
                    help="bound on the waiting queue (0 = unbounded): "
                         "submits past it are rejected (queue_full) and "
                         "preemption victims are shed, not re-queued")
    ap.add_argument("--inject-faults", default="",
                    help="deterministic fault schedule (serving/faults.py "
                         "grammar), e.g. 'forward:step=3,action=nan;"
                         "alloc_page:nth=20' — chaos-tests step isolation")
    ap.add_argument("--sanitize", action="store_true",
                    help="run the step-boundary runtime sanitizers "
                         "(serving/sanitize.py): page-refcount "
                         "conservation + event-contract checks after "
                         "every step; SanitizerError aborts the run "
                         "the moment an invariant breaks")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="journaled crash recovery: full engine snapshot "
                         "every N steps + per-token event journal "
                         "(0 = off)")
    ap.add_argument("--arrival-every", type=int, default=0,
                    help="submit one request every N engine steps "
                         "(0 = all up front). Staggered arrivals let "
                         "later requests hit the prefix published by "
                         "earlier ones")
    ap.add_argument("--mesh", default="1x1", metavar="DxM",
                    help="(data, model) mesh for tensor-parallel sharded "
                         "serving, e.g. 1x4 shards heads + KV pools over "
                         "4 devices (CPU smoke: set XLA_FLAGS=--xla_force_"
                         "host_platform_device_count=N first). 1x1 = "
                         "single-device (default). Asking for more "
                         "devices than exist is an error (no silent "
                         "clamping)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="run N engine replicas behind a ReplicaGroup "
                         "(params replicated, page pools + scheduler "
                         "per-replica; least-loaded routing, per-step "
                         "health checks, failover). Each replica owns its "
                         "own device, or with --mesh 1xM its own M-device "
                         "slice; N·M devices must exist")
    ap.add_argument("--failover", default="migrate",
                    choices=["standby", "migrate"],
                    help="replica-death policy: promote a standby engine "
                         "resumed from the shipped RecoveryLog artifacts "
                         "into the dead slot, or migrate the dead "
                         "replica's in-flight requests to the survivors")
    ap.add_argument("--kill-replica-at", type=int, default=0,
                    help="deterministically kill one replica before its "
                         "Nth engine step (the 'crash' fault point; "
                         "0 = never) — the failover smoke")
    ap.add_argument("--kill-replica", type=int, default=0,
                    help="which replica index --kill-replica-at kills")
    ap.add_argument("--head-dim", type=int, default=0,
                    help="override cfg.head_dim (0 = keep). The smoke "
                         "configs use head_dim=32 → q_dim=128, too small "
                         "for row-parallel TP (shards must hold whole "
                         "128-channel quant blocks) — pass 64 with "
                         "--smoke --mesh 1x2")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.head_dim:
        cfg = dataclasses.replace(cfg, head_dim=args.head_dim)
    quant = QuantConfig(int4_fraction=args.int4_fraction,
                        schedule=args.schedule, impl=args.impl)
    lm_q = LM(cfg, quant=quant)

    print(f"[init+quantize] {cfg.name} "
          f"(~{cfg.param_count()/1e6:.1f}M params)", flush=True)
    qparams, qaxes = lm_q.init_quantized(jax.random.PRNGKey(args.seed))

    data, model = parse_mesh_arg(args.mesh)
    if args.replicas > 1:
        _run_group(args, cfg, qparams, qaxes, quant, model)
        return
    mesh = None
    if model > 1:
        # strict: make_local_mesh raises when the requested topology
        # doesn't fit the devices — no silently different mesh
        mesh = make_local_mesh(data, model)
        print(f"[mesh] (data={mesh.shape['data']}, "
              f"model={int(mesh.shape['model'])}) over "
              f"{jax.device_count()} {jax.default_backend()} device(s)",
              flush=True)

    eng = Engine(cfg, qparams, quant, EngineConfig(
        max_batch=args.max_batch, num_pages=args.pages,
        page_size=args.page_size, temperature=args.temperature,
        prefill_mode=args.prefill_mode,
        prefill_chunk_tokens=args.prefill_chunk,
        kv_range=args.kv_range,
        unified_step=(args.step_mode == "unified"),
        prefix_cache=(args.prefix_cache == "on"),
        attention_schedule=args.attention_schedule,
        prefix_cache_max_bytes=(args.prefix_cache_max_bytes or None),
        max_waiting=(args.max_waiting or None),
        inject_faults=(args.inject_faults or None),
        sanitize=args.sanitize),
        mesh=mesh, param_axes=qaxes)
    log = None
    if args.snapshot_every:
        from repro.serving.recovery import RecoveryLog
        log = RecoveryLog(eng, snapshot_every=args.snapshot_every)

    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, cfg.vocab_size,
                          size=args.shared_prefix).tolist()
    if 0 < args.shared_prefix < args.page_size:
        print(f"[warn] --shared-prefix {args.shared_prefix} < --page-size "
              f"{args.page_size}: prefix matching is full-page-granular, "
              "so the shared prefix can never hit — shrink --page-size or "
              "grow the prefix", flush=True)
    sp = SamplingParams(max_new_tokens=args.max_new,
                        temperature=args.temperature, top_k=args.top_k,
                        speculation=args.speculation,
                        deadline_ms=(args.deadline_ms or None),
                        ttft_ms=(args.ttft_ms or None))
    prompts = []
    for _ in range(args.requests):
        plen = int(rng.integers(args.prompt_len // 2, args.prompt_len + 1))
        prompts.append(shared
                       + rng.integers(0, cfg.vocab_size, size=plen).tolist())
    # arrival trace: request i is submitted at step i*arrival_every
    pending = [(i * args.arrival_every, p) for i, p in enumerate(prompts)]
    abort_ids: set = set()
    submitted = 0

    t0 = time.time()
    while (pending or eng.sched.has_work) and eng.steps < 10_000:
        while pending and pending[0][0] <= eng.steps:
            _, prompt = pending.pop(0)
            h = eng.submit(prompt, sp)
            submitted += 1
            if args.abort_every and submitted % args.abort_every == 0:
                abort_ids.add(h.request_id)
        if log is not None:
            evs = log.step()
        else:
            eng.step()
            evs = eng.events()
        for ev in evs:
            if ev.token is not None and ev.request_id in abort_ids:
                eng.abort(ev.request_id)       # cancel after first token
                abort_ids.discard(ev.request_id)
            if args.stream:
                if ev.token is not None:
                    print(f"  [stream] req {ev.request_id} "
                          f"+tok {ev.token} (#{ev.num_generated})",
                          flush=True)
                elif ev.finished:
                    print(f"  [stream] req {ev.request_id} "
                          f"{ev.state.value}"
                          + (f" ({ev.stop_reason})" if ev.stop_reason
                             else ""), flush=True)
    dt = time.time() - t0

    finished = eng.sched.finished
    total_tokens = sum(len(r.generated) for r in finished)
    prompt_tokens = eng.prefill_tokens + eng.prefix_hit_tokens
    hit_rate = eng.prefix_hit_tokens / prompt_tokens if prompt_tokens else 0.0
    print(f"[done] {len(finished)} requests, {total_tokens} tokens in "
          f"{dt:.1f}s → {total_tokens/dt:.1f} tok/s "
          f"(steps={eng.steps}, forwards={eng.forward_calls}, "
          f"traces={eng.trace_count}, preemptions={eng.sched.preemptions})",
          flush=True)
    print(f"[cache] prefix hit rate {hit_rate:.0%} "
          f"({eng.prefix_hit_tokens}/{prompt_tokens} prompt tokens served "
          f"from published pages); evicted={eng.cache.prefix_evicted_pages} "
          f"pages; reclaimable={eng.cache.prefix_reclaimable_bytes}B; "
          f"aborted={eng.aborted_count}", flush=True)
    print(f"[robust] failed={eng.failed_count} timed_out={eng.timeout_count} "
          f"shed={eng.shed_count} rejected={eng.rejected_count} "
          f"callback_errors={eng.callback_errors} "
          f"internal_errors={eng.internal_errors} "
          f"sanitize_checks={eng.sanitize_checks} "
          f"released={eng.sched.released_count}", flush=True)
    # latency SLOs measured from the lifecycle stamps: TTFT from
    # arrival to first token, TPOT over the decode window
    ttft = [r.first_token_at - r.arrived_at
            for r in finished if r.first_token_at]
    tpot = [(r.finished_at - r.first_token_at) / (len(r.generated) - 1)
            for r in finished
            if r.finished_at and r.first_token_at and len(r.generated) > 1]
    print(f"[slo] ttft {_ms_stats(ttft)} | tpot {_ms_stats(tpot)} "
          f"(over {len(ttft)} first tokens / {len(tpot)} decode windows)",
          flush=True)
    if eng.faults.faults:
        fired = [f"{p}:{a}@step{s}" for p, a, s in eng.faults.fired]
        print(f"[faults] armed: {eng.faults.describe()}; "
              f"fired: {', '.join(fired) or '(none)'}; "
              f"pending: {len(eng.faults.pending)}", flush=True)
    if log is not None:
        print(f"[recovery] journal={len(log.journal)} events, "
              f"snapshot@step{log._snapshot_step} "
              f"(every {log.snapshot_every}), replayed={log.replayed}",
              flush=True)
    if eng.attn_forwards:
        waste = eng.attn_grid_items - eng.attn_work_items
        dense_waste = eng.attn_dense_grid_items - eng.attn_work_items
        print(f"[sched] {args.attention_schedule}: "
              f"{eng.attn_work_items} attention work items over "
              f"{eng.attn_forwards} forwards; grid={eng.attn_grid_items} "
              f"(waste {waste}; dense rectangle would waste "
              f"{dense_waste})", flush=True)
        if eng.tp_size > 1:
            print(f"[sched] per-shard work items "
                  f"{eng.attn_work_items_per_shard} (balanced split of "
                  f"{eng.attn_work_items} over model={eng.tp_size})",
                  flush=True)
    if args.speculation or eng.spec_draft_tokens:
        acc = eng.spec_accepted_tokens / max(1, eng.spec_draft_tokens)
        print(f"[sched] speculation: drafted={eng.spec_draft_tokens} "
              f"accepted={eng.spec_accepted_tokens} (acceptance {acc:.0%}) "
              f"rollback={eng.spec_rollback_tokens} "
              f"noop={eng.spec_noop_count} "
              f"draft_errors={eng.draft_errors} "
              f"[{eng.draft_source.describe()}]", flush=True)
    for r in finished[:4]:
        print(f"  req {r.request_id}: {r.state.value:9s} "
              f"{r.generated[:12]}…", flush=True)
    if not eng.faults.faults:
        _exit_on_failures(eng.internal_errors, eng.failed_count,
                          [eng.last_error])


if __name__ == "__main__":
    main()
