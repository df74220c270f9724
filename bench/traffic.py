"""One general load generator, driven by a traffic mix's data file.

A mix is ``bench/traffic/<name>.json``::

    {"loop": "open", "rate_rps": 0.8,
     "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                "min": 32, "max": 3072},
     "output": {"dist": "uniform", "min": 128, "max": 512},
     "size_seed": 1}

``preroll_s`` of traffic runs before the measured window, so the window
starts on a loaded server. ``loop`` is ``open`` (Poisson arrivals at ``rate_rps``, sent on schedule
whatever the server does) or ``closed`` (``concurrency`` clients, each
sending its next request when the previous one finishes; ``"max_batch"``
takes the engine's batch). The sizes, their order and the inter-arrival
gaps are drawn from the mix's fixed ``size_seed``, so every run seed
offers the same work on the same schedule (and so meets the same forward
shapes, which later runs in a checkout find compiled); the run seed draws
every token id.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

__all__ = ["Mix", "Request", "load_mix", "generate"]


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    due_s: float            # open loop: offset from the window's start
    prompt: tuple           # token ids
    max_new: int


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    loop: str
    rate_rps: float
    concurrency: object
    prompt: dict
    output: dict
    size_seed: int
    preroll_s: float


def load_mix(name: str, root: Path = HERE / "traffic") -> Mix:
    spec = json.loads((root / f"{name}.json").read_text())
    loop = spec["loop"]
    if loop not in ("open", "closed"):
        raise ValueError(f"mix {name}: loop must be open or closed")
    return Mix(name=name, loop=loop, rate_rps=float(spec.get("rate_rps", 0)),
               concurrency=spec.get("concurrency"), prompt=spec["prompt"],
               output=spec["output"], size_seed=int(spec["size_seed"]),
               preroll_s=float(spec.get("preroll_s", 0.0)))


def _lengths(rng: np.random.Generator, d: dict, n: int) -> np.ndarray:
    if d["dist"] == "lognormal":
        x = d["median"] * np.exp(d["sigma"] * rng.standard_normal(n))
    elif d["dist"] == "uniform":
        x = rng.uniform(d["min"], d["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return np.clip(np.floor(x), d["min"], d["max"]).astype(np.int64)


def request_count(mix: Mix, seconds: float, concurrency: int) -> int:
    """Requests one run can offer: the window plus a minute of slack at
    the offered rate (open), or enough for every client to turn over
    many times (closed)."""
    if mix.loop == "open":
        return int(np.ceil(mix.rate_rps * (seconds + 60))) + 8
    return max(4 * concurrency, int(seconds) * 4)


def generate(mix: Mix, seed: int, n: int, vocab: int) -> list[Request]:
    """``n`` requests for run ``seed``: sizes, order and gaps from the
    mix's own seed, token ids from ``seed``."""
    base = np.random.default_rng(mix.size_seed)
    plens = _lengths(base, mix.prompt, n)
    olens = _lengths(base, mix.output, n)
    gaps = (base.exponential(1.0 / mix.rate_rps, n) if mix.loop == "open"
            else np.zeros(n))
    due = np.cumsum(gaps) - gaps[0]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0x7AFF1C])
    return [Request(rid=i, due_s=float(due[i]),
                    prompt=tuple(int(t) for t in
                                 rng.integers(0, vocab, int(plens[i]))),
                    max_new=int(olens[i]))
            for i in range(n)]
