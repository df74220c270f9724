"""Replica-group failover walkthrough: kill a replica mid-decode and
watch the group keep every client stream intact.

Runs the same workload twice on a two-replica group — once untouched
(the reference), once with a deterministic ``crash`` fault killing
replica 0 at its step 6 (mid-decode) — and proves the failure is
invisible at the layer clients read:

* every delivered token stream is greedy-identical to the reference,
* every request gets exactly ONE terminal event (duplicates from the
  recovery replay are verified bitwise and suppressed),
* the survivors' page pools drain back to baseline,
* the whole episode is counters, not exceptions
  (``internal_errors == 0``).

Both failover policies run: ``migrate`` folds the dead replica's
in-flight requests (prompt + delivered tokens) onto the survivor under
their original request ids; ``standby`` resumes the dead replica whole
from its shipped RecoveryLog artifacts and promotes it in place.

    PYTHONPATH=src python examples/failover_walkthrough.py

The serve CLI drives the same seam, one device per replica (on a CPU,
force two host devices):

    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
    PYTHONPATH=src python -m repro.launch.serve --arch llama3_8b \
        --smoke --requests 6 --max-new 8 --replicas 2 \
        --failover migrate --kill-replica-at 6 --stream
"""
import jax
import numpy as np

from repro.configs.base import get_smoke_config
from repro.models.lm import LM, QuantConfig
from repro.serving.engine import EngineConfig, SamplingParams
from repro.serving.faults import Fault, FaultInjector
from repro.serving.replication import ReplicaGroup

cfg = get_smoke_config("llama3_8b")
quant = QuantConfig(weight_only=True, kv4=True, impl="ref")
params, axes = LM(cfg).init(jax.random.PRNGKey(0))
qparams, _ = LM(cfg, quant=quant).quantize(params, axes)

ECFG = EngineConfig(max_batch=4, num_pages=64, page_size=8,
                    max_pages_per_seq=16, prefill_chunk_tokens=8,
                    kv_range=4.0)
rng = np.random.default_rng(7)
PROMPTS = [rng.integers(1, 100, int(n)).tolist()
           for n in rng.integers(12, 18, 3)]


def run_group(failover, kill_step=None):
    faults = None
    if kill_step is not None:
        faults = [FaultInjector([Fault("crash", step=kill_step)]),
                  FaultInjector()]
    group = ReplicaGroup(cfg, qparams, quant, ECFG, replicas=2,
                         failover=failover, snapshot_every=4,
                         faults=faults)
    rids = [group.submit(p, SamplingParams(max_new_tokens=6))
            for p in PROMPTS]
    group.run()
    return group, rids


# the no-failure run every failover case is compared against
ref, rids = run_group("migrate")
print("reference (no failure):")
for rid in rids:
    print(f"  req {rid}: {ref.tokens_for(rid)} "
          f"[{ref.terminal_for(rid).state.value}]")
assert ref.failovers == 0

for failover in ("migrate", "standby"):
    group, rids = run_group(failover, kill_step=6)
    idx, why, at = group.deaths[0]
    c = group.counters()
    print(f"\n--- {failover}: replica {idx} killed ({why}) at engine "
          f"step {at} ---")
    print(f"  failovers={c['failovers']} "
          f"migrated={c['migrated_requests']} "
          f"dup_suppressed={c['duplicates_suppressed']} "
          f"internal_errors={c['internal_errors']} "
          f"health={c['health']}")
    for rid in rids:
        toks = group.tokens_for(rid)
        same = "identical" if toks == ref.tokens_for(rid) else "DIFFERS"
        print(f"  req {rid} (owner → replica {group.owner[rid]}): "
              f"{toks} [{group.terminal_for(rid).state.value}] {same}")
        assert toks == ref.tokens_for(rid)
        assert group.terminal_for(rid) is not None
    assert len(group.terminals) == len(rids)    # exactly one terminal each
    assert group.internal_errors == 0
    for rep in group.replicas:
        if rep.alive:                           # pools drain to baseline
            assert rep.engine.cache.pages_free == ECFG.num_pages

print("\nevery stream identical across both failover policies — the "
      "kill cost throughput, never correctness")
