"""End-to-end metrics from the client's own timestamps.

Every number is taken over all requests and all gaps of the measured
window ``[w0, w1)``, on the client's clock (seconds):

* ``ttft_p90_ms`` — time from when a request was *due* to the client
  seeing its first token, 90th percentile over every request due in the
  window. A request that never shows a token (failed, or still waiting
  when the post-window drain ends) counts as a miss at the drain's end.
* ``itl_p50_ms`` / ``itl_p95_ms`` — median and 95th percentile of every
  gap between consecutive tokens of one request, both tokens seen inside
  the window.
* ``output_tok_s`` — every output token seen inside the window, divided
  by the window's length.
"""

from __future__ import annotations

import numpy as np

__all__ = ["ttft_s", "gaps_s", "end_to_end", "percentile"]


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_s(records, w0: float, w1: float, t_end: float) -> list:
    out = []
    for r in records:
        if not (w0 <= r.due < w1):
            continue
        first = r.token_times[0] if r.token_times else t_end
        out.append(max(first, r.due) - r.due)
    return out


def gaps_s(records, w0: float, w1: float) -> list:
    out = []
    for r in records:
        t = [x for x in r.token_times if w0 <= x < w1]
        out.extend(np.diff(t).tolist())
    return out


def tokens_in(records, w0: float, w1: float) -> int:
    return sum(1 for r in records for x in r.token_times if w0 <= x < w1)


def end_to_end(records, w0: float, w1: float, t_end: float) -> dict:
    """The window's end-to-end numbers (no ``setup_s``). Raises
    ValueError when the window holds no request or no gap: such a run
    measured nothing."""
    ttft = ttft_s(records, w0, w1, t_end)
    gaps = gaps_s(records, w0, w1)
    if not ttft or not gaps:
        raise ValueError(f"window holds {len(ttft)} requests and "
                         f"{len(gaps)} token gaps")
    return {
        "ttft_p90_ms": 1e3 * percentile(ttft, 90),
        "itl_p50_ms": 1e3 * percentile(gaps, 50),
        "itl_p95_ms": 1e3 * percentile(gaps, 95),
        "output_tok_s": tokens_in(records, w0, w1) / (w1 - w0),
    }
