"""Tensor-parallel sharded engine: greedy parity with single-device.

These tests need >= 2 JAX devices; CI runs them in a dedicated job with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (on a stock
single-device CPU host they skip). Parity is asserted bitwise on greedy
tokens: ``int4_fraction=1.0`` keeps every act-quant block shard-local
(q_dim and d_ff split on 128-channel boundaries) and the wo / w_down
all-reduce seams keep f32 partials until one final bf16 rounding, so
the sharded forward reproduces the single-device forward exactly.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.models.lm import LM, QuantConfig
from repro.serving.engine import Engine, EngineConfig

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")

# smoke configs keep q_dim=128 — one act-quant block, unsplittable;
# head_dim=64 gives q_dim=256 and d_ff=256: two blocks, shardable by 2
CFG = dataclasses.replace(get_smoke_config("llama3_8b"), head_dim=64)
QC = QuantConfig(int4_fraction=1.0, impl="ref")
TP = 2


@pytest.fixture(scope="module")
def model():
    lm = LM(CFG)
    params, axes = lm.init(jax.random.PRNGKey(0))
    qparams, qaxes = LM(CFG, quant=QC).quantize(params, axes)
    return qparams, qaxes


def _engine(model, mesh, **ecfg):
    qparams, qaxes = model
    cfg = EngineConfig(max_batch=4, num_pages=64, page_size=8,
                       kv_range=4.0, **ecfg)
    return Engine(CFG, qparams, QC, cfg, mesh=mesh,
                  param_axes=qaxes if mesh is not None else None)


def _run_pair(model, prompts, max_new, **ecfg):
    out = []
    for mesh in (None, make_local_mesh(1, TP)):
        eng = _engine(model, mesh, **ecfg)
        for i, p in enumerate(prompts):
            eng.add_request(i, p, max_new)
        done = eng.run(max_steps=300)
        toks = {r.request_id: list(r.generated) for r in done}
        out.append((eng, toks))
    return out


def _prompts(lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG.vocab_size, n).tolist() for n in lens]


def test_sharded_parity_mixed(model):
    """Mixed ragged prefill + decode: tokens bitwise-identical, the
    one-forward-per-step seam survives shard_map, traces don't grow,
    and the per-shard work counters tile the total evenly."""
    (e1, t1), (e2, t2) = _run_pair(model, _prompts((11, 19, 7, 26)), 8)
    assert e2.tp_size == TP
    assert t2 == t1
    assert e2.forward_calls == e2.steps
    assert e2.trace_count <= e1.trace_count
    assert len(e2.attn_work_items_per_shard) == TP
    assert sum(e2.attn_work_items_per_shard) == e2.attn_work_items
    assert max(e2.attn_work_items_per_shard) == min(
        e2.attn_work_items_per_shard)
    # head-sharding changes per-shard work, not the global accounting
    assert e2.attn_work_items == e1.attn_work_items


def test_sharded_parity_decode_only(model):
    """Near-trivial prefill, long decode: the paged int4 read path (the
    work-queue kernel over sharded pools) dominates every step."""
    (e1, t1), (e2, t2) = _run_pair(model, _prompts((1, 2, 1, 3)), 12)
    assert t2 == t1
    assert e2.forward_calls == e2.steps
    assert sum(e2.attn_work_items_per_shard) == e2.attn_work_items


def test_sharded_parity_prefix_cache(model):
    """Published prefix pages are a host-global namespace: cache hits
    skip the same prefill tokens under TP and decode from pages written
    by a DIFFERENT request's sharded forward."""
    rng = np.random.default_rng(7)
    prefix = rng.integers(1, CFG.vocab_size, 16).tolist()
    sfx = [rng.integers(1, CFG.vocab_size, n).tolist() for n in (5, 9)]
    out = []
    for mesh in (None, make_local_mesh(1, TP)):
        eng = _engine(model, mesh, prefix_cache=True,
                      max_pages_per_seq=16)
        eng.add_request(0, prefix + sfx[0], 6)
        eng.run(max_steps=200)            # publisher completes
        eng.add_request(1, prefix + sfx[1], 6)
        eng.run(max_steps=200)
        toks = {r.request_id: list(r.generated)
                for r in eng.sched.finished}
        out.append((eng, toks))
    (e1, t1), (e2, t2) = out
    assert t2 == t1
    assert e2.prefix_hit_tokens == e1.prefix_hit_tokens
    assert e2.prefix_hit_tokens > 0
    assert e2.forward_calls == e2.steps


def test_sharded_dense_schedule_parity(model):
    """The fig10-ablated dense grid (no work queue) shards the same
    way — parity must not depend on the descriptor path."""
    (e1, t1), (e2, t2) = _run_pair(
        model, _prompts((9, 14)), 6, attention_schedule="dense")
    assert t2 == t1
    assert e2.forward_calls == e2.steps


def test_sharded_speculation_parity(model):
    """Speculative decode under TP: the verify chunk (qlen 1+k decode
    row) rides the sharded forward, and greedy output stays bitwise
    identical to single-device spec-off — drafts change the step
    count, never the tokens."""
    from repro.serving.engine import SamplingParams
    # greedy decode on THIS model (head_dim=64 reshapes the random
    # weights) takes a few tokens to fall into its absorbing cycles,
    # so give it cycle-prone prompts and enough output length
    prompts = [[188] * 12, [49] * 8, [188] * 10]
    out = []
    for mesh, k in ((None, 0), (make_local_mesh(1, TP), 0),
                    (make_local_mesh(1, TP), 4)):
        eng = _engine(model, mesh, sanitize=True)
        for i, p in enumerate(prompts):
            eng.submit(p, SamplingParams(max_new_tokens=20,
                                         temperature=0.0, speculation=k),
                       request_id=i)
        done = eng.run(max_steps=300)
        toks = {r.request_id: list(r.generated) for r in done}
        out.append((eng, toks))
    (e1, t1), (e2, t2), (e3, t3) = out
    assert e3.tp_size == TP
    assert t2 == t1 and t3 == t1
    assert e3.spec_draft_tokens > 0 and e3.spec_accepted_tokens > 0
    assert e3.spec_draft_tokens == \
        e3.spec_accepted_tokens + e3.spec_rollback_tokens
    assert e3.steps < e2.steps          # drafts actually shrank the run
    for eng in (e1, e2, e3):
        assert eng.internal_errors == 0


def test_sharded_one_upload_per_forward(model, monkeypatch):
    """Under TP each forward's layout crosses in ONE transfer, put
    straight onto the mesh replicated (the sharding the layout enters
    ``shard_map`` with)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_local_mesh(1, TP)
    eng = _engine(model, mesh)
    puts = []
    real = jax.device_put

    def spy(x, *a, **k):
        puts.append(a[0] if a else k.get("device"))
        return real(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", spy)
    for i, p in enumerate(_prompts((11, 19, 7))):
        eng.add_request(i, p, 6)
    eng.run(max_steps=300)
    assert eng.internal_errors == 0
    assert eng.host_uploads == eng.forward_calls == eng.steps == len(puts)
    assert all(s == NamedSharding(mesh, P()) for s in puts)


def test_sharded_requires_param_axes(model):
    """mesh without param_axes cannot place weights — loud error, not
    a silently replicated (wrong-counter) engine."""
    qparams, _ = model
    with pytest.raises(ValueError, match="param_axes"):
        Engine(CFG, qparams, QC,
               EngineConfig(max_batch=2, num_pages=32, page_size=8),
               mesh=make_local_mesh(1, TP), param_axes=None)


def test_sharded_rejects_indivisible_heads():
    """num_kv_heads % tp != 0 must fail fast at construction."""
    bad = dataclasses.replace(CFG, num_heads=3, num_kv_heads=3)
    lm = LM(bad)
    params, axes = lm.init(jax.random.PRNGKey(1))
    qp, qa = LM(bad, quant=QC).quantize(params, axes)
    with pytest.raises(ValueError, match="num_kv_heads|num_heads"):
        Engine(bad, qp, QC,
               EngineConfig(max_batch=2, num_pages=32, page_size=8),
               mesh=make_local_mesh(1, TP), param_axes=qa)


def test_sharded_fault_isolation_and_invariants(model):
    """Chaos under TP: a NaN-logits fault quarantines one request while
    the survivors keep decoding bitwise-identically to the single-device
    engine under the SAME schedule, pages return to baseline, and
    step() never raises on either side."""
    from repro.serving.api import RequestState
    from repro.serving.faults import Fault, FaultInjector
    qparams, qaxes = model
    prompts = _prompts((9, 12, 7), seed=13)
    out = []
    for mesh in (None, make_local_mesh(1, TP)):
        eng = Engine(CFG, qparams, QC,
                     EngineConfig(max_batch=4, num_pages=64, page_size=8,
                                  kv_range=4.0),
                     mesh=mesh, param_axes=qaxes if mesh else None,
                     faults=FaultInjector([Fault("forward", step=3,
                                                 action="nan", row=0)]))
        for i, p in enumerate(prompts):
            eng.add_request(i, p, 6)
        eng.run(max_steps=300)
        out.append(eng)
    e1, e2 = out
    assert e2.tp_size == TP
    for eng in out:
        assert eng.internal_errors == 0
        assert eng.failed_count == 1
        assert eng.cache.pages_free == 64
        assert (eng.cache.ref == 0).all()
    by_state = lambda e, s: sorted(
        r.request_id for r in e.sched.finished if r.state == s)
    assert by_state(e2, RequestState.FAILED) == \
        by_state(e1, RequestState.FAILED)
    # survivors' tokens stay bitwise equal to single-device
    assert {r.request_id: list(r.generated)
            for r in e2.sched.finished
            if r.state == RequestState.FINISHED} == \
        {r.request_id: list(r.generated)
         for r in e1.sched.finished if r.state == RequestState.FINISHED}


def test_sharded_full_snapshot_resumes_bitwise(model):
    """snapshot(full=True)/restore under TP: restore re-lays the int4
    pools over the mesh, and the continuation equals the uninterrupted
    sharded run token-for-token."""
    qparams, qaxes = model
    mesh = make_local_mesh(1, TP)
    ecfg = EngineConfig(max_batch=4, num_pages=64, page_size=8,
                        kv_range=4.0)
    prompts = _prompts((10, 15), seed=17)

    ref_eng = Engine(CFG, qparams, QC, ecfg, mesh=mesh, param_axes=qaxes)
    for i, p in enumerate(prompts):
        ref_eng.add_request(i, p, 8)
    ref = {r.request_id: list(r.generated)
           for r in ref_eng.run(max_steps=300)}

    eng = Engine(CFG, qparams, QC, ecfg, mesh=mesh, param_axes=qaxes)
    for i, p in enumerate(prompts):
        eng.add_request(i, p, 8)
    for _ in range(4):
        eng.step()                         # mid-decode "crash"
    blob = eng.snapshot(full=True)
    eng2 = Engine.restore(blob, CFG, qparams, QC, ecfg, mesh=mesh,
                          param_axes=qaxes)
    eng2.run(max_steps=300)
    assert {r.request_id: list(r.generated)
            for r in eng2.sched.finished} == ref
    assert eng2.cache.pages_free == 64
