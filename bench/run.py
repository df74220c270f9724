#!/usr/bin/env python3
"""Serving benchmark: one cell of ``BENCHMARK.json`` on the chips of this
machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

In one process: load the cell's configuration and traffic mix by name;
build the packed model on the device from the seed; warm up by replaying
the run's own traffic on a virtual clock until a whole replay traces no
new forward shape; serve the traffic in real time through
``Engine.submit``/``step`` (a pre-roll of ``preroll_s`` fills the batch, then the window of
``--seconds`` is measured); drain; read the peak device memory; free the
engine; decide ``correct`` against the plain reference (``bench/check.py``)
and print the result as the last line of standard output, with the
compared numbers and their limits under ``compared``, last (they are also
the last lines of standard error).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the window with the JAX profiler and reports the per-layer metrics, the
device's busy time and the breakdown. Exits non-zero, with no result,
when JAX finds no TPU or fewer chips than the cell needs.

The persistent compilation cache lives at ``<checkout>/.jax_cache`` and
the engine's compiled forward programs at ``<checkout>/.bench_programs``
(:mod:`bench.programs`).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
PROGRAMS_DIR = ROOT / ".bench_programs"
TRACE_DIR = ROOT / ".bench_trace"
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import registry as REG  # noqa: E402
from bench.programs import Programs  # noqa: E402

DRAIN_S = 60.0          # wait past the window for the due requests' first
#                         tokens and the compared requests' last, at most
MAX_REPLAYS = 3         # warm-up replays, at most: a run that needs more
#                         would not end within its time limit (PERF.md)


def use_checkout_cache():
    """Point JAX's persistent compilation cache at the fixed directory
    inside this checkout, with no size or compile-time floor, and keep
    the TPU runtime's logs off ``/tmp``. Call before JAX is first
    imported."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"   # no eviction
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19]) / ticks
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return max(0.0, uptime - start)
    except (OSError, ValueError, IndexError):
        return 0.0


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


def _shifted(reqs, shift: int, vocab: int):
    """The same sizes and due times with other token ids (no prefix is
    shared with the measured run's prompts)."""
    from bench.traffic import Request
    return [Request(r.rid, r.due_s, tuple((t + shift) % vocab
                                          for t in r.prompt), r.max_new)
            for r in reqs]


def _sampling(req):
    from repro.serving.api import SamplingParams
    return SamplingParams(max_new_tokens=req.max_new, temperature=0.0)


def _reset(eng):
    """Abort whatever a replay left in flight and drop its records."""
    for r in list(eng.sched.running) + list(eng.sched.waiting):
        eng.abort(r.request_id)
    for ev in eng.events():
        if ev.finished:
            eng.release(ev.request_id)
    for r in list(eng.sched.finished):
        eng.release(r.request_id)


def replay_scale(i: int) -> float:
    """Speed of warm-up replay ``i``: 1, then alternately 8 % faster and
    slower in widening steps (0.92, 1.08, 0.84, ...), so the replays
    walk the batch shapes that step-time jitter can reach."""
    return 1.0 + 0.08 * ((i + 1) // 2) * (-1) ** i


def warm_up(eng, reqs, mix, concurrency, horizon_s, vocab,
            max_replays: int = MAX_REPLAYS) -> list:
    """Replay the run's traffic (other token ids) on a virtual clock
    until a whole replay traces no new forward shape (``trace_count``
    stops rising), at most ``max_replays`` times. → the shapes each
    replay traced."""
    from bench.client import Client, VirtualClock
    traced = []
    for i in range(max_replays):
        before, t0 = eng.trace_count, time.perf_counter()
        cl = Client(eng, _shifted(reqs, i + 1, vocab), mix.loop, concurrency,
                    _sampling, VirtualClock(scale=replay_scale(i)))
        cl.run_until(horizon_s)
        _reset(eng)
        traced.append(eng.trace_count - before)
        _log(f"setup: replay {i} x{replay_scale(i):.2f}: {traced[-1]} new "
             f"forward shapes, {time.perf_counter() - t0:.1f} s")
        if traced[-1] == 0:
            break
    return traced


def build(cell: dict, seed: int, bench_dir: Path = REG.HERE):
    """The cell's configuration, mix and a built engine (not yet warm)
    whose forward runs the checkout's programs (:mod:`bench.programs`)."""
    import jax
    from bench import model as M
    from bench import traffic as TR

    cfg = M.load_config(cell["config"], bench_dir / "configs")
    mix = TR.load_mix(cell["traffic"], bench_dir / "traffic")
    vocab = cfg["vocab_size"]
    concurrency = (cfg["engine"]["max_batch"]
                   if mix.concurrency == "max_batch" else
                   int(mix.concurrency or 0))
    params = M.build_params(cfg, seed)
    jax.block_until_ready(params)
    eng = M.build_engine(cfg, params, time.perf_counter)
    programs = Programs(eng, PROGRAMS_DIR / cell["name"])
    return types.SimpleNamespace(cfg=cfg, mix=mix, vocab=vocab,
                                 concurrency=concurrency, params=params,
                                 eng=eng, programs=programs)


def measure(sess, reqs, seed: int, seconds: float, trace: bool):
    """Serve ``reqs`` in real time on the built, warm engine: the
    pre-roll, the measured window (traced with ``trace``), then a drain
    that waits for the due requests' first tokens and the compared
    requests' last. → the run's records."""
    import jax
    from bench import check as CK
    from bench.client import Client, RealClock

    eng, mix = sess.eng, sess.mix
    preroll = float(mix.preroll_s)
    wanted = CK.choose(reqs, preroll, preroll + seconds, seed)
    clock = RealClock()
    client = Client(eng, reqs, mix.loop, sess.concurrency, _sampling, clock,
                    spans=trace)
    recorder = CK.LogitsRecorder(eng, client, wanted)
    client.t0 = clock.now()
    client.run_until(client.t0 + preroll)
    w0 = clock.now()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        w0 = clock.now()
    n_steps0 = len(client.steps)
    client.run_until(w0 + seconds)
    w1 = clock.now()
    if trace:
        jax.profiler.stop_trace()
    if mix.loop == "open":
        client.send_due(w1)     # due in the window, behind the last step
    records = list(client.records.values())
    due = [r for r in records if w0 <= r.due < w1]
    compared = [r for r in records if r.rid in wanted]

    def waiting():
        return (any(not r.token_times for r in due)
                or len(compared) < len(wanted)
                or any(r.end_state is None for r in compared))
    client.drain(w1 + DRAIN_S, waiting)
    recorder.remove()
    window_steps = client.steps[n_steps0:]
    _log(f"window: {len(window_steps)} steps, "
         f"{sum(s.traces for s in window_steps)} forward shapes traced")
    compared = [r for r in client.records.values() if r.rid in wanted]
    return types.SimpleNamespace(
        records=records, due=due, w0=w0, w1=w1, t_end=clock.now(),
        window_steps=window_steps, compared=compared, reqs=reqs,
        logits=recorder.rows, prompts={r.rid: r.prompt for r in reqs})


def serve(cell: dict, seed: int, seconds: float, trace: bool,
          bench_dir: Path = REG.HERE, engine_hook=None,
          max_replays: int = MAX_REPLAYS):
    """Build, warm up and serve one run of a cell; → (session, run).
    ``engine_hook(engine)``, when given, runs on the built engine before
    the warm-up (the tests break the timed path through it);
    ``max_replays`` bounds the warm-up (0: none, for readings that do
    not time the window)."""
    from bench import traffic as TR

    t0 = time.perf_counter()
    sess = build(cell, seed, bench_dir)
    _log(f"setup: model and engine built, {len(sess.programs.exes)} "
         f"forward programs loaded, {time.perf_counter() - t0:.1f} s")
    if engine_hook is not None:
        engine_hook(sess.eng)
    preroll = float(sess.mix.preroll_s)
    reqs = TR.generate(sess.mix, seed, TR.request_count(
        sess.mix, preroll + seconds, sess.concurrency), sess.vocab)
    warm_up(sess.eng, reqs, sess.mix, sess.concurrency, preroll + seconds,
            sess.vocab, max_replays)
    return sess, measure(sess, reqs, seed, seconds, trace)


def device_info(cell: dict) -> dict:
    import jax
    devs = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell["chips"]])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def free(sess):
    """Drop the program's state (params, pools, engine) before the
    reference runs on the device."""
    sess.eng = sess.params = sess.programs = None
    gc.collect()


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, t_proc: float, bench_dir: Path = REG.HERE,
             engine_hook=None) -> dict:
    """Everything after the device check; returns the result dict."""
    from bench import check as CK
    from bench import e2e as E

    sess, run = serve(cell, seed, seconds, trace, bench_dir, engine_hook)
    setup_s = t_proc + (run.w0 - T_IMPORT)
    device = device_info(cell)
    failed = sum(1 for r in run.due if not r.token_times
                 or r.end_state in ("FAILED", "TIMED_OUT", "ABORTED"))
    result = {"correct": False, "attempted": len(run.due), "failed": failed,
              "metrics": {}, "device": device}
    ctx = types.SimpleNamespace(
        records=run.records, window_steps=run.window_steps, w0=run.w0,
        w1=run.w1, t_end=run.t_end, loop=sess.mix.loop, cfg=sess.cfg,
        trace=None, ops=[], busy_s=0.0, window_s=run.w1 - run.w0,
        peaks=REG.peaks(device["kind"], bench_dir),
        cost=lambda k: REG.cost(k, bench_dir))
    ctx.roofline = lambda k: REG.roofline(ctx, k, bench_dir)
    if trace:
        from bench import trace as TRC
        red = TRC.reduce(TRC.find_xplane(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ops = [o for _, o in sorted(red["devices"].items())][:cell["chips"]]
        ctx.trace = red
        ctx.ops = ops[0] if ops else []
        ctx.busy_s = (sum(TRC.busy_ns(o) for o in ops) / max(len(ops), 1)
                      * 1e-9)
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        top = sorted(TRC.op_totals(ctx.ops).items(), key=lambda kv: -kv[1])
        result["breakdown"] = {
            "device_ops": [[n, t * 1e-9] for n, t in top[:10]],
            "idle_gaps": [[n, s] for n, s in
                          TRC.idle_gaps(ctx.ops, red["spans"])],
        }
        for m in bench["per_layer"]:
            v = REG.reader(m["name"], bench_dir)(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = E.end_to_end(run.records, run.w0, run.w1, run.t_end)
        e2e["setup_s"] = setup_s
        for m in bench["end_to_end"]:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    del ctx
    free(sess)
    t0 = time.perf_counter()
    verdict = CK.check(sess.cfg, seed, run.compared, run.prompts,
                       run.logits)
    _log(f"check: reference pass {time.perf_counter() - t0:.1f} s")
    result["correct"] = verdict["correct"]
    for name, v in verdict["compared"].items():
        _log(f"compare {name}={v['value']} limit={v['limit']}")
    result["compared"] = verdict["compared"]
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        bench = REG.load_benchmark(ROOT)
        cell = REG.cell(bench, args.workload)
    except (OSError, KeyError, ValueError) as e:
        _log(f"error: {e}")
        return 2
    use_checkout_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        _log(f"error: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
             f"JAX found {len(devs)} {devs[0].platform} device(s)")
        return 3
    result = run_cell(bench, cell, args.seed, args.seconds,
                      bool(args.trace), T_PROC)
    print(json.dumps(result), flush=True)
    return 0


T_IMPORT = time.perf_counter()
T_PROC = process_age_s()

if __name__ == "__main__":
    sys.exit(main())
