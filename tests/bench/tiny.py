"""The test-only cell: a small dense decoder under a small chat mix, both
in ``tests/bench/data`` — found by the harness's own loaders by name."""

from pathlib import Path

DATA = Path(__file__).parent / "data"
CELL = {"name": "tiny_dense.tiny_chat", "config": "tiny_dense",
        "traffic": "tiny_chat", "chips": 1}
BENCH = {
    "end_to_end": [{"name": n, "unit": u} for n, u in (
        ("ttft_p90_ms", "ms"), ("itl_p50_ms", "ms"), ("itl_p95_ms", "ms"),
        ("output_tok_s", "tokens/s"), ("setup_s", "s"))],
    "per_layer": [],
    "workloads": [CELL],
}
SECONDS = 2.0
