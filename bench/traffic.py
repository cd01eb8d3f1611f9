"""The one traffic generator: a request schedule from a mix's parameters.

A mix is a data file, ``bench/traffic/<name>.json``:

    {"loop": "open", "arrivals": "poisson", "rate_per_s": 0.7,
     "prompt": {"median": 512, "sigma": 0.8, "min": 32, "max": 1536},
     "output": {"median": 128, "sigma": 0.8, "min": 8, "max": 384},
     "block": 32, "preroll_s": 4.0}

Lengths are lognormal (``median``, ``sigma`` of the log), rounded
and clipped to ``[min, max]``; open-loop gaps are exponential with mean
``1 / rate_per_s``. Token ids are uniform over the vocabulary.

The schedule comes in stretches: the pre-roll, then the window, each
with exactly its share of the rate, then blocks of ``block``. A stretch of ``k`` requests
holds the stratified quantiles ``(j + 0.5) / k`` of each distribution,
gaps scaled to fill it, in an order drawn from the mix's own
``order_seed``. So the schedule is a fixed trace of the mix, as a
recorded trace would be, and ``--seed`` draws the token ids (and the
harness the weights). With the order drawn from ``--seed`` the window's
90th percentiles swung by a quarter from seed to seed at the same
work, while two runs of one seed agreed to about 1%.
"""

from __future__ import annotations

import functools
import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass(frozen=True)
class Request:
    index: int
    offset_s: float     # due time after the schedule's start
    prompt_len: int
    output_len: int


def load(path) -> dict:
    mix = json.loads(Path(path).read_text())
    validate(mix)
    return mix


def validate(mix: dict) -> None:
    if mix["loop"] != "open":
        raise ValueError(f"loop must be open: {mix['loop']!r}")
    if mix.get("arrivals") != "poisson" or not mix["rate_per_s"] > 0:
        raise ValueError(f"open loop needs poisson arrivals at a rate: {mix}")
    for key in ("prompt", "output"):
        d = mix[key]
        if not 1 <= d["min"] <= d["median"] <= d["max"]:
            raise ValueError(f"{key}: need 1 <= min <= median <= max: {d}")
    if int(mix["block"]) < 1:
        raise ValueError("block must be >= 1")


def lognormal_quantiles(d: dict, n: int) -> np.ndarray:
    """``n`` stratified lognormal lengths, rounded and clipped."""
    z = _normal_quantiles(n)
    x = np.exp(math.log(d["median"]) + d["sigma"] * z)
    return np.clip(np.rint(x), d["min"], d["max"]).astype(np.int64)


@functools.lru_cache(maxsize=None)
def _normal_quantiles(n: int) -> np.ndarray:
    return np.array([NormalDist().inv_cdf((j + 0.5) / n) for j in range(n)])


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` stratified exponential gaps scaled to mean exactly 1/rate."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g / g.mean() / rate


def _rng(seed: int, *words: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, *words])


def _segments(mix: dict, preroll_s: float, seconds: float):
    """``(requests, seconds)`` of each stretch of the schedule: the
    pre-roll and the window get exactly their share of the rate, then
    blocks of ``block`` follow."""
    b = int(mix["block"])
    rate = float(mix["rate_per_s"])
    for dur in (preroll_s, seconds):
        if dur > 0:
            yield max(1, round(rate * dur)), dur
    while True:
        yield b, b / rate


def schedule(mix: dict, n: int, preroll_s: float = 0.0,
             seconds: float = 0.0) -> List[Request]:
    """The first ``n`` requests of the mix's schedule. Each stretch
    (``_segments``) holds the stratified quantiles of its own size, in an
    order drawn from ``order_seed``, with its gaps scaled to fill it."""
    out: List[Request] = []
    t0 = 0.0
    for blk, (k, dur) in enumerate(_segments(mix, preroll_s, seconds)):
        if len(out) >= n:
            break
        rng = _rng(mix.get("order_seed", 0), 1, blk)
        p = rng.permutation(lognormal_quantiles(mix["prompt"], k))
        o = rng.permutation(lognormal_quantiles(mix["output"], k))
        g = rng.permutation(exponential_gaps(k / dur, k))
        starts = t0 + np.concatenate([[0.0], np.cumsum(g)[:-1]])
        for j in range(k):
            if len(out) < n:
                out.append(Request(len(out), float(starts[j]), int(p[j]),
                                   int(o[j])))
        t0 += dur
    return out


def prompt_tokens(seed: int, index: int, length: int, vocab: int
                  ) -> List[int]:
    """Request ``index``'s prompt: ``length`` ids uniform over the vocab."""
    return _rng(seed, 2, index).integers(0, vocab, length).tolist()


def max_requests(mix: dict, span_s: float) -> int:
    """Enough schedule for ``span_s`` seconds of offered load, and more."""
    return int(span_s * float(mix["rate_per_s"]) * 2) + 2 * int(mix["block"])
