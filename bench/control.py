#!/usr/bin/env python3
"""The control of ``correct``, the planted faults, and the readings the
limits come from.

    python3 bench/control.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] [--faults <name> ...] [--fault-seeds <k>]

For each seed, in one process: serve one run of the cell as
``bench/run.py`` does (no trace; only the first seed warms up, since the
readings do not time the window and later seeds find the first one's
compiled programs); then, on the same warm engine, one
short run of the same traffic (other token ids) with each named fault of
``bench/faults.py`` planted; free the engine; then read the compared
numbers for the program, for the control — the reference itself at
``int4_fraction = 1.0`` (the configuration's int8 activation blocks taken
to int4, the step below the stated precision) put in the program's place
at the same positions — and for each fault run. Prints one JSON line
per reading, as soon as it is read, with the process's peak host memory.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import resource
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as RUN  # noqa: E402

CONTROL_FRACTION = 1.0
FAULT_SECONDS = 10.0    # a fault run's window: long enough for the
#                         compared sample (it is drawn from the window)


def _peak_rss_gib() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def readings(cell: dict, seed: int, seconds: float, faults=(),
             bench_dir: Path = RUN.REG.HERE, emit=None,
             max_replays: int = RUN.MAX_REPLAYS) -> dict:
    """→ {"seed", "program", "control", <fault>..., "correct": {...}};
    ``emit(name, values, correct)``, when given, gets each reading as
    soon as it is read."""
    from bench import check as CK
    from bench import faults as F
    sess, run = RUN.serve(cell, seed, seconds, False, bench_dir,
                          max_replays=max_replays)
    fault_runs = {}
    for i, name in enumerate(faults):
        RUN._reset(sess.eng)
        undo = F.FAULTS[name](sess.eng)
        fault_runs[name] = RUN.measure(
            sess, RUN._shifted(run.reqs, 1000 + i, sess.vocab), seed,
            FAULT_SECONDS, False)
        undo()
    cfg = sess.cfg
    RUN.free(sess)
    out = {"seed": seed, "correct": {}}
    todo = [("program", run, None), ("control", run, CONTROL_FRACTION)]
    todo += [(name, fr, None) for name, fr in fault_runs.items()]
    for name, r, frac in todo:
        v = CK.check(cfg, seed, r.compared, r.prompts, r.logits,
                     int4_fraction=frac)
        out[name] = v["values"]
        out["correct"][name] = v["correct"]
        if emit is not None:
            emit(name, out[name], v["correct"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, default=1,
                    help="plant the faults in the runs of the first k seeds")
    args = ap.parse_args(argv)
    bench = RUN.REG.load_benchmark(RUN.ROOT)
    cell = RUN.REG.cell(bench, args.workload)
    RUN.use_checkout_cache()
    for i, seed in enumerate(args.seeds):
        def emit(name, values, ok, seed=seed):
            print(json.dumps({"seed": seed, "reading": name, "correct": ok,
                              **values, "peak_rss_gib": _peak_rss_gib()}),
                  flush=True)
        readings(cell, seed, args.seconds,
                 args.faults if i < args.fault_seeds else (), emit=emit,
                 max_replays=RUN.MAX_REPLAYS if i == 0 else 0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
