"""Discovery by name: everything that belongs to one configuration, one
traffic mix, one per-layer metric or one kernel sits in a file of its own
under ``bench/``, found from the name ``BENCHMARK.json`` gives it.

* configuration ``<c>``  → ``configs/<c>.json``
* traffic mix ``<t>``    → ``traffic/<t>.json``
* per-layer metric ``<m>`` → ``metrics/<m>.py`` (``read(ctx)``, returns a
  number or None when the run holds nothing to read)
* kernel ``<k>``         → ``costs/<k>.py`` (``calls(cfg, rows)`` gives
  [(operations, bytes)] of one step, ``PEAK`` names the peak that bounds
  the operations, ``MATCH`` the device-op names that are its calls); a
  metric ``<k>_roofline`` reads it through :func:`roofline`
* peaks                  → ``peaks.json``, keyed by ``device_kind``
"""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent

__all__ = ["load_benchmark", "cell", "reader", "cost", "peaks", "roofline"]


def load_benchmark(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


@functools.lru_cache(maxsize=None)
def _module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + Path(path).stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, bench_dir: Path = HERE):
    return _module(str(Path(bench_dir) / "metrics" / f"{name}.py")).read


def cost(kernel: str, bench_dir: Path = HERE):
    return _module(str(Path(bench_dir) / "costs" / f"{kernel}.py"))


def peaks(device_kind: str, bench_dir: Path = HERE) -> dict:
    table = json.loads((Path(bench_dir) / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


def roofline(ctx, kernel: str, bench_dir: Path = HERE):
    """Percent of its roofline a kernel reached over the traced window:
    the least time of its calls (per call, the larger of operations over
    the bounding peak and bytes over HBM bandwidth) over its device time.
    None when the trace holds none of its calls."""
    if ctx.trace is None:
        return None
    from bench import trace as TRC
    mod = cost(kernel, bench_dir)
    busy = TRC.kernel_ns(ctx.ops, mod.MATCH) * 1e-9
    if busy <= 0:
        return None
    pk = ctx.peaks
    least = sum(max(o / pk[mod.PEAK], b / pk["hbm_bytes_s"])
                for s in ctx.window_steps for o, b in mod.calls(ctx.cfg, s.rows))
    if least <= 0:
        return None
    return 100.0 * least / busy
