#!/usr/bin/env python3
"""Serve full-width LLaMA-3-8B on one TPU through compiled Pallas kernels.

Run from the repository root:

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the four-chip paths only

One chip, in one process:

1. kernel parity — every hot-path kernel (act-quant int4/int8, the W4Ax
   split GEMM, both work-queue attention kernels with the split-KV
   combine) at LLaMA-3-8B widths, compiled Pallas against the jnp
   oracles of ``repro.kernels.ref``, each within a stated tolerance;
2. the packed model — ``llama3_8b`` at its published shape (32 layers,
   d_model 4096, 32/8 heads, head_dim 128, d_ff 14336, vocab 128256)
   with random weights from ``--seed``, built layer by layer so no f32
   copy of the model ever exists; prints ``peak_bytes_in_use``;
3. serving — four requests (prompts of 97..128 tokens, 16 new tokens
   each) through ``Engine.submit``/``step``, once cold (compiles
   included) and once warm (fresh prompts, same shape buckets);
4. end to end — the first step's last-position logits of the compiled
   Pallas engine against an ``impl="ref"`` engine on the same params.

``--chips 4`` runs only what needs four chips, at full width with the
depth cut to ``FOUR_CHIP_LAYERS`` layers: tensor-parallel serving on a
1x4 mesh at ``int4_fraction=1.0`` against the one-chip engine on the
same prompts, and four one-chip replicas behind a ``ReplicaGroup``, each
with its params and pools on its own device.

Exits non-zero, and prints no result line, when JAX finds no TPU or any
check fails. On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# --- tolerances, with the reason each is what it is ----------------------
# act-quant: kernel and oracle run the same f32 ops per 128-channel block;
# the only licence is a division that Mosaic and XLA may round differently,
# which can move a value sitting on a rounding tie by one code.
ACT_CODE_MISMATCH_SHARE = 1e-3   # share of codes allowed to differ ...
ACT_CODE_MAX_DIFF = 1            # ... and then by one code at most
ACT_SCALE_RTOL = 1e-6            # scales: one f32 division, same inputs
# W4Ax GEMM: the per-block int32 dots are exact on both sides; only the
# order of the <=112 f32 block rescale-and-adds differs.
GEMM_REL_ERR = 1e-4              # max|pallas - ref| / max|ref|
# attention: f32 flash partials with exp; the kernel's MXU dots may take
# bf16 passes for f32 operands (relative error ~2^-8 on scores).
ATTN_REL_ERR = 2e-2              # max|pallas - ref| / max|ref|
# end to end: Pallas and ref differ in f32 summation order. A difference
# that moves an activation across a bf16 or int4 rounding boundary flips
# a code, and with random weights the flips compound with depth: on a
# reduced-width 32-layer model (CPU), relative noise of 1e-7 / 1e-3 on
# every GEMM output moved the first-step logits by up to 0.38 / 0.49 per
# row. So the logits agree statistically, not to the ulp. A wiring fault
# gives zero or uncorrelated logits: relative L2 error 1.0 to ~1.4.
LOGITS_REL_L2 = 0.6              # ||pallas - ref|| / ||ref||, per row

SEED = 0
PROMPTS, PROMPT_LEN, MAX_NEW = 4, 128, 16
FOUR_CHIP_LAYERS = 2


def _log(msg: str):
    print(msg, flush=True)


class CheckFailed(Exception):
    pass


def _check(ok: bool, what: str):
    _log(f"  [{'ok' if ok else 'FAIL'}] {what}")
    if not ok:
        raise CheckFailed(what)


def _rel(a, b):
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# ------------------------------------------------------------ kernel parity

def kernel_parity():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.llama3_8b import CONFIG as cfg
    from repro.core import quantizer as Q
    from repro.kernels import ops
    from repro.serving.kv_cache import build_work_queue

    _log("[parity] compiled Pallas vs kernels/ref.py at LLaMA-3-8B widths")
    rng = np.random.default_rng(SEED)
    d, ff, hq, hkv, hd = (cfg.d_model, cfg.d_ff, cfg.num_heads,
                          cfg.num_kv_heads, cfg.head_dim)

    def split(k):
        nb = k // 128
        nb4 = int(round(0.875 * nb))
        return nb4 * 128, (nb - nb4) * 128

    def pallas_and_ref(fn, *args):
        out = jax.jit(lambda *a: fn(*a, "pallas"))(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: fn(*a, "ref"))(*args)
        return out, want

    m = 64
    for k in (d, ff):
        for bits, kk in zip((4, 8), split(k)):
            x = jnp.asarray(rng.normal(size=(m, kk)) * 3, jnp.bfloat16)
            (p, s), (p_ref, s_ref) = pallas_and_ref(
                lambda x, impl: ops.act_quant(x, bits=bits, impl=impl), x)
            if bits == 4:
                codes = lambda a: np.asarray(Q.unpack_int4_interleaved(
                    a, axis=1, block_size=128), np.int32)
            else:
                codes = lambda a: np.asarray(a, np.int32)
            diff = np.abs(codes(p) - codes(p_ref))
            share = float(np.mean(diff > 0))
            srel = float(np.max(np.abs(np.asarray(s) - np.asarray(s_ref))
                                / np.abs(np.asarray(s_ref))))
            _check(share <= ACT_CODE_MISMATCH_SHARE
                   and int(diff.max()) <= ACT_CODE_MAX_DIFF
                   and srel <= ACT_SCALE_RTOL,
                   f"act_quant int{bits} [{m}x{kk}]: code mismatch share "
                   f"{share:.2e} (max diff {int(diff.max())}), scale rel "
                   f"{srel:.2e}")

    for k, n in ((d, d), (d, hkv * hd), (d, ff), (ff, d)):
        k4, k8 = split(k)
        x = rng.normal(size=(m, k)).astype(np.float32)
        w = (rng.normal(size=(k, n)) * 0.02).astype(np.float32)
        q4, s4 = Q.quantize_act_groupwise(jnp.asarray(x[:, :k4]), 128, bits=4)
        a4 = Q.pack_int4_interleaved(q4, axis=1, block_size=128)
        a8, s8 = Q.quantize_act_groupwise(jnp.asarray(x[:, k4:]), 128, bits=8)
        wq = Q.quantize_weight_int4(jnp.asarray(w), group_size=128)
        out, want = pallas_and_ref(
            lambda *a: ops.w4ax_matmul(*a[:-1], impl=a[-1]),
            a4, s4, a8, s8, wq.data, wq.scale)
        rel = _rel(out, want)
        _check(bool(np.isfinite(np.asarray(out)).all())
               and rel <= GEMM_REL_ERR,
               f"w4ax split GEMM [{m}x{k}]x[{k}x{n}] (K4={k4}, K8={k8}): "
               f"max rel err {rel:.2e} <= {GEMM_REL_ERR:g}")

    pages, ps = 64, 64
    pool = lambda: jnp.asarray(
        rng.integers(0, 256, size=(pages, hkv, ps, hd // 2)), jnp.uint8)
    stat = lambda lo, hi: jnp.asarray(
        rng.uniform(lo, hi, size=(hkv, 1, hd)), jnp.float32)
    kp, vp = pool(), pool()
    ks, kz, vs, vz = stat(0.05, 0.2), stat(6, 9), stat(0.05, 0.2), stat(6, 9)
    for b, c in ((8, 1), (4, 64)):
        ctx = rng.integers(1, 8 * ps, size=b)
        perm = rng.permutation(pages)
        npg = [(int(x) + ps - 1) // ps for x in ctx]
        tbl = np.full((b, max(npg)), -1, np.int32)
        off = 0
        for i, n_ in enumerate(npg):
            tbl[i, :n_] = perm[off:off + n_] % pages
            off += n_
        desc = jnp.asarray(build_work_queue(tbl, ctx, ps, hkv,
                                            np.full(b, c)))
        q = jnp.asarray(rng.normal(size=(b, c, hq, hd)), jnp.float32)
        kn = jnp.asarray(rng.normal(size=(b, c, hkv, hd)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(b, c, hkv, hd)), jnp.float32)
        out, want = pallas_and_ref(
            lambda *a: ops.paged_kv4_prefill_attention_wq(*a[:-1],
                                                          impl=a[-1]),
            q, kn, vn, kp, ks, kz, vp, vs, vz, desc)
        rel = _rel(out, want)
        _check(bool(np.isfinite(np.asarray(out)).all())
               and rel <= ATTN_REL_ERR,
               f"work-queue prefill attention B={b} C={c} W={desc.shape[0]}"
               f": max rel err {rel:.2e} <= {ATTN_REL_ERR:g}")
    ctx = rng.integers(1, 8 * ps, size=8)
    npg = [(int(x) + ps - 1) // ps for x in ctx]
    tbl = np.full((8, max(npg)), -1, np.int32)
    perm, off = rng.permutation(pages), 0
    for i, n_ in enumerate(npg):
        tbl[i, :n_] = perm[off:off + n_] % pages
        off += n_
    desc = jnp.asarray(build_work_queue(tbl, ctx, ps, hkv))
    q = jnp.asarray(rng.normal(size=(8, hq, hd)), jnp.float32)
    out, want = pallas_and_ref(
        lambda *a: ops.paged_kv4_decode_attention_wq(*a[:-1], impl=a[-1]),
        q, kp, ks, kz, vp, vs, vz, desc)
    rel = _rel(out, want)
    _check(bool(np.isfinite(np.asarray(out)).all()) and rel <= ATTN_REL_ERR,
           f"work-queue decode attention B=8 W={desc.shape[0]}: max rel "
           f"err {rel:.2e} <= {ATTN_REL_ERR:g}")


# ------------------------------------------------------------------ serving

def _prompts(n, vocab, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    lo = PROMPT_LEN * 3 // 4 + 1
    return [rng.integers(0, vocab, int(rng.integers(lo, PROMPT_LEN + 1)))
            .tolist() for _ in range(n)]


def _spy_logits(eng):
    """Record the logits of every forward the engine issues."""
    import numpy as np
    seen = []
    fwd = eng._fwd

    def spy(*a):
        out = fwd(*a)
        seen.append(np.asarray(out[0]))
        return out

    eng._fwd = spy
    return seen


def _serve(eng, prompts):
    """Drive prompts through submit/step; return (requests, steps, s)."""
    from repro.serving.api import SamplingParams
    handles = [eng.submit(p, SamplingParams(max_new_tokens=MAX_NEW))
               for p in prompts]
    steps0 = eng.steps
    t0 = time.perf_counter()
    while eng.sched.has_work and eng.steps - steps0 < 500:
        eng.step()
    dt = time.perf_counter() - t0
    return [eng.result(h) for h in handles], eng.steps - steps0, dt


def _engine_ok(eng, reqs, what):
    from repro.serving.api import RequestState
    fin = sum(r.state == RequestState.FINISHED for r in reqs)
    ntok = sum(len(r.generated) for r in reqs)
    _check(fin == len(reqs) and ntok == len(reqs) * MAX_NEW
           and eng.internal_errors == 0 and eng.failed_count == 0,
           f"{what}: {fin}/{len(reqs)} FINISHED, {ntok} tokens, "
           f"internal_errors={eng.internal_errors} failed_count="
           f"{eng.failed_count} last_error={eng.last_error}")


def _row_rel_l2(a, b):
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return [float(np.linalg.norm(x - y) / np.linalg.norm(y))
            for x, y in zip(a, b)]


def _ecfg(**kw):
    from repro.serving.engine import EngineConfig
    # every prompt's first chunk fits one step: one prefill bucket and
    # one decode bucket, so a cold run compiles two forwards
    base = dict(max_batch=PROMPTS, num_pages=64, page_size=64,
                max_pages_per_seq=8, prefill_chunk_tokens=PROMPTS * PROMPT_LEN)
    base.update(kw)
    return EngineConfig(**base)


def one_chip():
    import jax
    import numpy as np

    from repro.configs.llama3_8b import CONFIG as cfg
    from repro.models.lm import LM, QuantConfig
    from repro.serving.engine import Engine

    kernel_parity()

    dev = jax.devices()[0]
    _log(f"[model] {cfg.name}: {cfg.num_layers} layers, d_model "
         f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, "
         f"head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}"
         f", random weights (seed {SEED})")
    quant = QuantConfig()
    t0 = time.perf_counter()
    qparams, _ = LM(cfg, quant=quant).init_quantized(jax.random.PRNGKey(SEED))
    jax.block_until_ready(qparams)
    nbytes = sum(a.nbytes for a in jax.tree.leaves(qparams))
    peak = dev.memory_stats()["peak_bytes_in_use"]
    _log(f"[model] packed params {nbytes / 2**30:.3f} GiB built in "
         f"{time.perf_counter() - t0:.1f}s; peak_bytes_in_use "
         f"{peak / 2**30:.3f} GiB")
    _check(peak < 16e9, f"peak device memory {peak} B < 16 GB")

    eng = Engine(cfg, qparams, quant, _ecfg())
    seen = _spy_logits(eng)
    prompts = _prompts(PROMPTS, cfg.vocab_size, SEED)
    reqs, steps, cold = _serve(eng, prompts)
    traces = eng.trace_count
    _log(f"[serve] cold: {len(reqs)} requests, {steps} steps, "
         f"{sum(len(r.generated) for r in reqs)} tokens, {traces} "
         f"compiles, {cold:.1f}s wall")
    _engine_ok(eng, reqs, "cold run")
    reqs, steps, warm = _serve(eng, _prompts(PROMPTS, cfg.vocab_size,
                                             SEED + 1))
    _log(f"[serve] warm: {len(reqs)} requests, {steps} steps, "
         f"{sum(len(r.generated) for r in reqs)} tokens, "
         f"{eng.trace_count - traces} new compiles, {warm:.1f}s wall")
    _engine_ok(eng, reqs, "warm run")
    _check(eng.trace_count == traces, "warm run compiled nothing new")

    ref = Engine(cfg, qparams, QuantConfig(impl="ref"), _ecfg())
    ref_seen = _spy_logits(ref)
    from repro.serving.api import SamplingParams
    for p in prompts:
        ref.submit(p, SamplingParams(max_new_tokens=MAX_NEW))
    ref.step()
    _check(ref.internal_errors == 0 and ref.failed_count == 0,
           f"ref engine first step ran (last_error={ref.last_error})")
    got, want = seen[0][:PROMPTS], ref_seen[0][:PROMPTS]
    rel = _row_rel_l2(got, want)
    top1 = float(np.mean(np.argmax(got, -1) == np.argmax(want, -1)))
    _check(bool(np.isfinite(got).all()) and max(rel) <= LOGITS_REL_L2,
           f"first-step logits, Pallas vs ref: per-row relative L2 "
           f"{', '.join(f'{r:.3e}' for r in rel)} <= {LOGITS_REL_L2:g}; "
           f"top-1 agreement {top1:.2f}")
    peak = dev.memory_stats()["peak_bytes_in_use"]
    _log(f"[memory] peak_bytes_in_use after serving {peak / 2**30:.3f} GiB")
    _check(peak < 16e9, f"peak device memory {peak} B < 16 GB")


# --------------------------------------------------------------- four chips

def four_chips():
    import jax
    import numpy as np

    from repro.configs.llama3_8b import CONFIG
    from repro.launch.mesh import make_local_mesh, make_replica_meshes
    from repro.models.lm import LM, QuantConfig
    from repro.serving.api import SamplingParams
    from repro.serving.engine import Engine
    from repro.serving.replication import ReplicaGroup

    if len(jax.devices()) < 4:
        raise CheckFailed(f"--chips 4 needs 4 devices, found "
                          f"{len(jax.devices())}")
    cfg = dataclasses.replace(CONFIG, num_layers=FOUR_CHIP_LAYERS)
    _log(f"[model] {cfg.name} at full width, depth cut to "
         f"{cfg.num_layers} layers, random weights (seed {SEED})")

    quant = QuantConfig(int4_fraction=1.0)
    qparams, qaxes = LM(cfg, quant=quant).init_quantized(
        jax.random.PRNGKey(SEED))
    prompts = _prompts(PROMPTS, cfg.vocab_size, SEED)
    runs = {}
    for name, mesh in (("one-chip", None), ("tp 1x4", make_local_mesh(1, 4))):
        eng = Engine(cfg, qparams, quant, _ecfg(), mesh=mesh,
                     param_axes=qaxes if mesh is not None else None)
        seen = _spy_logits(eng)
        reqs, steps, dt = _serve(eng, prompts)
        _log(f"[tp] {name}: tp_size={eng.tp_size}, {steps} steps, "
             f"{eng.trace_count} compiles, {dt:.1f}s wall")
        _engine_ok(eng, reqs, f"{name} run")
        runs[name] = (seen[0][:PROMPTS], [r.generated for r in reqs])
    (l1, t1), (l4, t4) = runs["one-chip"], runs["tp 1x4"]
    rel = _row_rel_l2(l4, l1)
    agree = float(np.mean([a == b for x, y in zip(t1, t4)
                           for a, b in zip(x, y)]))
    _check(max(rel) <= LOGITS_REL_L2,
           f"TP 1x4 vs one chip, first-step logits per-row relative L2 "
           f"{', '.join(f'{r:.3e}' for r in rel)} <= {LOGITS_REL_L2:g}; "
           f"greedy token agreement {agree:.3f}")
    del qparams

    quant = QuantConfig()
    qparams, qaxes = LM(cfg, quant=quant).init_quantized(
        jax.random.PRNGKey(SEED))
    group = ReplicaGroup(cfg, qparams, quant, _ecfg(num_pages=16),
                         replicas=4, meshes=make_replica_meshes(4, 1),
                         param_axes=qaxes)
    devs = []
    for rep in group.replicas:
        e = rep.engine
        here = {dv for a in jax.tree.leaves(e.params) for dv in a.devices()}
        here |= e.cache.k_pool.devices() | e.cache.v_pool.devices()
        devs.append(sorted(str(dv) for dv in here))
    _log(f"[replicas] devices per replica: {devs}")
    _check(all(len(x) == 1 for x in devs) and len({x[0] for x in devs}) == 4,
           "each replica's params and pools sit on its own device")
    t0 = time.perf_counter()
    for p in _prompts(8, cfg.vocab_size, SEED + 2):
        group.submit(p, SamplingParams(max_new_tokens=MAX_NEW))
    group.run()
    dt = time.perf_counter() - t0
    ntok = sum(len(v) for v in group.delivered.values())
    fwd = [r.engine.forward_calls for r in group.replicas]
    _log(f"[replicas] 8 requests, {ntok} tokens, forwards per replica "
         f"{fwd}, {dt:.1f}s wall")
    _check(ntok == 8 * MAX_NEW and group.internal_errors == 0
           and all(r.engine.failed_count == 0 for r in group.replicas)
           and min(fwd) > 0,
           "replica group served every request on all four replicas")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    if jax.default_backend() != "tpu":
        print(f"no TPU: JAX backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    devs = jax.devices()
    dev = devs[0]
    _log(f"[device] {dev.platform} {dev.device_kind} x{len(devs)}; "
         f"jax {jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips()
        else:
            one_chip()
    except CheckFailed:
        return 1
    _log(f"[done] {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
