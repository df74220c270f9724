"""Replica groups: health-checked failover with exactly-once migration.

The acceptance contract for ``serving/replication.py``: for every kill
point (mid-prefill, mid-decode, mid-snapshot-gap) and both failover
policies, every client stream is greedy-token-identical to the
no-failure group run, each request's terminal event is delivered
exactly once, the surviving replicas' pools drain back to baseline, and
no step ever escapes into ``internal_errors``. Plus the control plane:
least-loaded routing, bounded-queue backpressure under halved capacity,
heartbeat-deadline deaths, and standby promotion health states.
"""
import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.models.lm import LM, QuantConfig
from repro.serving.api import SamplingParams
from repro.serving.engine import EngineConfig
from repro.serving.faults import Fault, FaultInjector
from repro.serving.replication import ReplicaGroup

# small chunk so prefill spans several steps — a step-2 kill lands
# genuinely mid-prefill
ECFG = dict(max_batch=4, num_pages=64, page_size=8, max_pages_per_seq=16,
            prefill_chunk_tokens=8, kv_range=4.0,
            # every replica engine (and every failover-resumed one — the
            # ecfg is shared) runs the step-boundary runtime sanitizers
            sanitize=True)
SNAP = 4                        # checkpoint cadence: gap kills at 6/7
MAX_NEW = 6


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama3_8b")
    qc = QuantConfig(weight_only=True, kv4=True, impl="ref")
    lm = LM(cfg)
    params, axes = lm.init(jax.random.PRNGKey(0))
    qparams, _ = LM(cfg, quant=qc).quantize(params, axes)
    return cfg, qc, qparams


def _prompts(n=3, seed=41):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 100, int(rng.integers(12, 18))).tolist()
            for _ in range(n)]


def make_group(setup, **kw):
    cfg, qc, qparams = setup
    ecfg = EngineConfig(**dict(ECFG, **kw.pop("ecfg", {})))
    kw.setdefault("replicas", 2)
    kw.setdefault("snapshot_every", SNAP)
    return ReplicaGroup(cfg, qparams, qc, ecfg, **kw)


def _drive(group, prompts, max_new=MAX_NEW):
    rids = [group.submit(p, SamplingParams(max_new_tokens=max_new))
            for p in prompts]
    group.run()
    return rids


@pytest.fixture(scope="module")
def reference(setup):
    """The no-failure group run every chaos case is compared against."""
    group = make_group(setup)
    rids = _drive(group, _prompts())
    assert group.failovers == 0 and group.internal_errors == 0
    return {rid: (group.tokens_for(rid), group.terminal_for(rid))
            for rid in rids}


# ------------------------------------------------------------- chaos sweep


@pytest.mark.parametrize("failover", ["standby", "migrate"])
@pytest.mark.parametrize("kill_step,phase",
                         [(2, "mid_prefill"), (6, "mid_decode"),
                          (7, "mid_snapshot_gap")])
def test_kill_sweep_streams_identical(setup, reference, failover,
                                      kill_step, phase):
    """Kill replica 0 deterministically at each phase: the group's
    delivered streams equal the no-failure run bitwise, one terminal
    each, survivors drain to pool baseline, internal_errors == 0."""
    faults = [FaultInjector([Fault("crash", step=kill_step)]),
              FaultInjector()]
    group = make_group(setup, failover=failover, faults=faults)
    rids = _drive(group, _prompts())

    assert group.failovers == 1
    assert group.deaths and group.deaths[0][1] == "crash"
    assert group.internal_errors == 0
    for rid in rids:
        toks, term = reference[rid]
        assert group.tokens_for(rid) == toks, phase
        got = group.terminal_for(rid)
        assert got is not None and got.state == term.state
    # exactly-once is structural (terminals is a dict) — also prove no
    # duplicate slipped through the suppression counter unnoticed:
    # every suppressed duplicate is counted, never delivered
    assert len(group.terminals) == len(rids)
    for rep in group.replicas:
        if rep.alive:
            assert rep.engine.cache.pages_free == ECFG["num_pages"]
            assert rep.engine.internal_errors == 0
    if failover == "standby":
        assert group.health[0] == "promoted"
        assert all(r.alive for r in group.replicas)
    else:
        assert group.health[0] == "dead:crash"
        assert group.migrated_requests >= 0


def test_migrate_moves_in_flight_requests(setup, reference):
    """A mid-decode kill in migrate mode actually moves work: the dead
    replica owned requests, they complete on the survivor, and the
    owner map points at the survivor afterwards."""
    faults = [FaultInjector([Fault("crash", step=6)]), FaultInjector()]
    group = make_group(setup, failover="migrate", faults=faults)
    rids = _drive(group, _prompts())
    assert group.migrated_requests > 0
    assert all(group.owner[rid] == 1 for rid in rids)
    for rid in rids:
        assert group.tokens_for(rid) == reference[rid][0]


# ------------------------------------------------------- health + routing


def test_heartbeat_deadline_kills_slow_replica(setup, reference):
    """A replica whose step overruns the heartbeat deadline is marked
    dead and its slow step's events are discarded — the survivor
    regenerates them, so streams still match the no-failure run."""
    t = {"now": 0.0}
    group = make_group(setup, failover="migrate", heartbeat_s=1.0,
                       clock=lambda: t["now"])
    rep = group.replicas[0]
    orig = rep.log.step

    def slow_step():
        out = orig()
        if rep.engine.steps >= 3:
            t["now"] += 5.0              # blows the 1s deadline
        return out

    rep.log.step = slow_step
    rids = _drive(group, _prompts())
    assert group.health[0] == "dead:heartbeat"
    assert group.failovers == 1
    assert group.internal_errors == 0
    for rid in rids:
        assert group.tokens_for(rid) == reference[rid][0]
        assert group.terminal_for(rid) is not None


def test_least_loaded_routing_spreads_requests(setup):
    """Submits spread over the replicas by in-flight load — with equal
    loads the tie breaks by index, so alternating submits alternate."""
    group = make_group(setup)
    rids = [group.submit(p, SamplingParams(max_new_tokens=2))
            for p in _prompts(n=4, seed=43)]
    owners = [group.owner[rid] for rid in rids]
    assert owners == [0, 1, 0, 1]
    group.run()
    assert len(group.terminals) == 4


def test_backpressure_rejects_when_all_replicas_full(setup):
    """Per-replica admission backpressure: with bounded waiting queues
    saturated everywhere, extra submits land on the least-loaded
    replica and its engine rejects them (FAILED queue_full) — explicit,
    counted outcomes instead of unbounded queues."""
    group = make_group(setup, ecfg=dict(max_batch=1, max_waiting=1))
    rids = [group.submit(p, SamplingParams(max_new_tokens=2))
            for p in _prompts(n=8, seed=47)]
    group.run()
    assert len(group.terminals) == 8             # one terminal each
    rejected = [rid for rid in rids
                if group.terminal_for(rid).stop_reason == "queue_full"]
    served = [rid for rid in rids
              if group.terminal_for(rid).state.value == "finished"]
    assert rejected and served
    assert sum(r.engine.rejected_count for r in group.replicas) \
        == len(rejected)


def test_shed_on_halved_capacity(setup):
    """When a kill halves capacity, migrated load beyond the survivor's
    bounded queue degrades through the existing reject/shed path — every
    request still gets exactly one terminal."""
    faults = [FaultInjector([Fault("crash", step=6)]), FaultInjector()]
    group = make_group(setup, failover="migrate", faults=faults,
                       ecfg=dict(max_batch=2, max_waiting=2))
    rids = [group.submit(p, SamplingParams(max_new_tokens=4))
            for p in _prompts(n=8, seed=53)]
    group.run()
    assert group.failovers == 1
    assert len(group.terminals) == len(rids)     # exactly-once, all of them
    reasons = {group.terminal_for(rid).stop_reason for rid in rids}
    # at least some requests were degraded explicitly (rejected at
    # submit or shed by preemption) rather than silently queued forever
    survivor = group.replicas[1].engine
    assert survivor.rejected_count + survivor.shed_count > 0 \
        or "queue_full" in reasons or "shed" in reasons
    assert survivor.cache.pages_free == ECFG["num_pages"]


def test_replica_lost_without_survivors_fails_terminally(setup):
    """Total loss (single replica, migrate, no survivors): every
    in-flight request gets exactly one synthesized FAILED
    replica_lost terminal — streams end, they don't hang."""
    faults = [FaultInjector([Fault("crash", step=3)])]
    group = make_group(setup, replicas=1, failover="migrate",
                       faults=faults)
    rids = [group.submit(p, SamplingParams(max_new_tokens=MAX_NEW))
            for p in _prompts(n=2, seed=59)]
    group.run()
    assert not group.has_work
    for rid in rids:
        term = group.terminal_for(rid)
        assert term is not None
        assert term.stop_reason == "replica_lost"
        assert term.state.value == "failed"


def test_group_validates_arguments(setup):
    cfg, qc, qparams = setup
    ecfg = EngineConfig(**ECFG)
    with pytest.raises(ValueError, match="replicas"):
        ReplicaGroup(cfg, qparams, qc, ecfg, replicas=0)
    with pytest.raises(ValueError, match="failover"):
        ReplicaGroup(cfg, qparams, qc, ecfg, failover="bogus")
    with pytest.raises(ValueError, match="one injector per replica"):
        ReplicaGroup(cfg, qparams, qc, ecfg, replicas=2,
                     faults=[FaultInjector()])


def test_replicas_each_own_a_device_subprocess():
    """model=1 replicas on a 4-device host: every replica's params and
    KV pools sit on its own device (none doubled up on device 0), and
    requests routed to each replica still finish. Runs on 4 forced CPU
    devices in a child process (the device count is fixed at start-up)."""
    import os
    import subprocess
    import sys
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import warnings; warnings.filterwarnings("ignore")
import jax, numpy as np
from repro.configs.base import get_smoke_config
from repro.launch.mesh import make_replica_meshes
from repro.models.lm import LM, QuantConfig
from repro.serving.api import SamplingParams
from repro.serving.engine import EngineConfig
from repro.serving.replication import ReplicaGroup

cfg = get_smoke_config("llama3_8b")
qc = QuantConfig(impl="ref")
qparams, qaxes = LM(cfg, quant=qc).init_quantized(jax.random.PRNGKey(0))
group = ReplicaGroup(cfg, qparams, qc,
                     EngineConfig(max_batch=4, num_pages=32, page_size=8),
                     replicas=4, meshes=make_replica_meshes(4, 1),
                     param_axes=qaxes)
devs = []
for rep in group.replicas:
    eng = rep.engine
    leaf_devs = {d for a in jax.tree.leaves(eng.params) for d in a.devices()}
    pool_devs = eng.cache.k_pool.devices() | eng.cache.v_pool.devices()
    assert len(leaf_devs) == 1 and leaf_devs == pool_devs, (leaf_devs, pool_devs)
    devs.append(leaf_devs.pop())
assert len(set(devs)) == 4, devs
rng = np.random.default_rng(0)
for _ in range(4):
    group.submit(rng.integers(1, 100, 12).tolist(),
                 SamplingParams(max_new_tokens=3))
group.run()
assert group.internal_errors == 0
assert all(r.engine.failed_count == 0 for r in group.replicas)
assert sum(len(v) for v in group.delivered.values()) == 12
assert all(r.engine.forward_calls > 0 for r in group.replicas)
print("OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert "OK" in out.stdout, out.stderr[-2000:]
