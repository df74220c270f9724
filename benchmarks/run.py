"""Benchmark harness entry point — one benchmark per paper table/figure.

Each module prints its human-readable table followed by a machine line
``name,us_per_call,derived``. This runner executes them all and also
emits the roofline summary if dry-run records exist.

  PYTHONPATH=src python -m benchmarks.run
"""

from __future__ import annotations

import os
import sys
import traceback


def main() -> None:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (conversion_ablation, fig9_kernel_bench,
                            fig10_schedule_ablation, fig11_e2e_throughput,
                            fig12_same_batch, fmpq_ratio,
                            table1_quant_error)

    benches = [
        ("table1_quant_error", table1_quant_error.main),
        ("fmpq_ratio", fmpq_ratio.main),
        ("fig9_kernel_bench", fig9_kernel_bench.main),
        ("fig10_schedule_ablation", fig10_schedule_ablation.main),
        ("fig11_e2e_throughput", fig11_e2e_throughput.main),
        ("fig12_same_batch", fig12_same_batch.main),
        ("conversion_ablation", conversion_ablation.main),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches:
        try:
            fn()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},0,FAILED")
            traceback.print_exc()

    # roofline summary from dry-run records, if present
    dr = os.path.join(os.path.dirname(__file__), "..",
                      "experiments", "dryrun")
    if os.path.isdir(dr) and any(f.endswith(".json") for f in os.listdir(dr)):
        from benchmarks import roofline
        print("\n== §Roofline summary (single-pod 16x16, split schedule) ==")
        rows = [roofline.analyze_record(r)
                for r in roofline.load_records(dr, "16x16")]
        rows.sort(key=lambda r: (r["arch"], r["shape"]))
        roofline.print_table(rows)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
