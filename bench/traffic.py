"""Traffic generation: one general generator for every mix file.

A mix file (``bench/traffic/<mix>.json``) gives the engine shape and the
traffic parameters; this module turns it and a seed into requests.

Lengths are lognormal (median, sigma) clipped to [min, max], and
arrivals (open-loop mixes) are Poisson at ``rate_per_s``.  So that a
seed changes the order of the work and not its amount, both are
stratified in blocks: every block of ``block`` consecutive requests
holds the same ``block`` quantiles of the length distributions, and of
the exponential inter-arrival distribution, in an order drawn from the
seed.  Any whole number of blocks therefore carries the same total work
and spans the same time under every seed.  Token ids are uniform over
the vocabulary from the seed.
"""

from __future__ import annotations

import itertools
import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Iterator, List, Optional, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclass(frozen=True)
class Item:
    """One generated request: prompt tokens, answer length, due time (s
    after the window opens; 0 for a saturated backlog)."""
    prompt: Tuple[int, ...]
    max_new_tokens: int
    due_s: float


def load_mix(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        mix = json.load(f)
    if mix["kind"] not in ("saturated", "poisson"):
        raise ValueError(f"mix {name}: unknown kind {mix['kind']!r}")
    return mix


def length_quantiles(spec: dict, n: int) -> List[int]:
    """The ``n`` mid-quantiles of a clipped lognormal length."""
    z = NormalDist()
    out = []
    for i in range(n):
        v = spec["median"] * math.exp(spec["sigma"] * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(v), spec["min"]), spec["max"])))
    return out


def gap_quantiles(rate: float, n: int) -> List[float]:
    """The ``n`` mid-quantiles of an exponential inter-arrival gap."""
    return [-math.log1p(-(i + 0.5) / n) / rate for i in range(n)]


def rng_of(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def stream(mix: dict, seed: int, vocab: int,
           block: Optional[int] = None) -> Iterator[Item]:
    """The requests of ``mix`` under ``seed``, without end."""
    block = int(block or mix.get("block", 64))
    plens = length_quantiles(mix["prompt"], block)
    alens = length_quantiles(mix["answer"], block)
    gaps = (gap_quantiles(float(mix["rate_per_s"]), block)
            if mix["kind"] == "poisson" else [0.0] * block)
    order = rng_of(seed, 1)
    tokens = rng_of(seed, 2)
    due = 0.0
    while True:
        pi, ai, gi = (order.permutation(block) for _ in range(3))
        for k in range(block):
            plen = plens[pi[k]]
            yield Item(prompt=tuple(int(t) for t in
                                    tokens.integers(0, vocab, plen)),
                       max_new_tokens=alens[ai[k]], due_s=due)
            due += gaps[gi[k]]


def generate(mix: dict, seed: int, vocab: int, n: int) -> List[Item]:
    """The first ``n`` requests of ``mix`` under ``seed``.  An open-loop
    mix without a ``block`` stratifies all ``n`` as one block, so the
    whole window carries the same work under every seed."""
    block = mix.get("block", n if mix["kind"] == "poisson" else None)
    return list(itertools.islice(stream(mix, seed, vocab, block), n))


def n_for_window(mix: dict, seconds: float) -> int:
    """Requests an open-loop mix makes due inside a window of
    ``seconds``: ``n`` stratified gaps sum to just under ``n / rate``."""
    return max(1, int(round(float(mix["rate_per_s"]) * seconds)))
