"""The one traffic generator: turns a mix file (``traffic/<mix>.json``)
and a seed into requests.

Every seed gets the same work in another order.  The mix is dealt as
decks of ``deck`` requests: each deck holds the same multiset of (prompt
length, output length) pairs, and for Poisson arrivals the same multiset
of gaps, and the seed only shuffles each deck and draws the prompt
tokens.  So two seeds differ in order and token ids, not in how much
there is to do, and a run's throughput does not swing with the draw.
The gaps are shuffled with the rest, so bursts and lulls fall
differently for each seed; a window several decks long holds about
``rate_rps`` x its length arrivals whatever the seed.

Mix keys:

- ``arrival``: ``"backlog"`` (a queue kept ``queue_per_lane`` x lanes
  deep) or ``"poisson"`` (open loop at ``rate_rps`` requests per second).
- ``prompt_len``: ``{"values": [...], "weights": [...]}``, a fixed grid,
  since every prompt length is a compiled program of its own.
- ``output_len``: ``{"dist": "loguniform" | "uniform", "min", "max"}``.
- ``deck``: requests per deck.
- ``temperature``: 0 for greedy decoding (outputs are then exactly
  ``output_len`` long, as no stop token is set).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Iterator, List

import numpy as np


@dataclass
class Spec:
    uid: int
    prompt: List[int]
    max_new: int
    gap_s: float          # Poisson: time from this arrival to the next


def _deal(values, weights, n: int) -> List[int]:
    """``n`` items with each value as often as its weight allots
    (largest remainder)."""
    w = np.asarray(weights, float) / float(np.sum(weights))
    raw = w * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return [int(v) for v, c in zip(values, counts) for _ in range(c)]


def _quantiles(dist: dict, n: int) -> List[int]:
    """``n`` output lengths at the mid-quantiles of the distribution."""
    lo, hi = dist["min"], dist["max"]
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "loguniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown output_len dist {dist['dist']!r}")
    return [int(round(v)) for v in x]


def deck(mix: dict) -> List[tuple]:
    """The deck every seed shares: (prompt_len, max_new, gap_s) triples.
    Prompt and output lengths are paired by a fixed shuffle, and the
    Poisson gaps are exponential mid-quantiles scaled so that a deck
    spans exactly ``deck / rate_rps`` seconds."""
    n = mix["deck"]
    plens = _deal(mix["prompt_len"]["values"], mix["prompt_len"]["weights"], n)
    outs = _quantiles(mix["output_len"], n)
    np.random.default_rng(0).shuffle(plens)
    if mix["arrival"] == "poisson":
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps = gaps * (n / mix["rate_rps"]) / gaps.sum()
    elif mix["arrival"] == "backlog":
        gaps = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    gaps = np.random.default_rng(1).permutation(gaps)
    return [(p, o, float(g)) for p, o, g in zip(plens, outs, gaps)]


def requests(mix: dict, vocab: int, seed: int) -> Iterator[Spec]:
    """Endless stream of requests for ``seed``: deck after deck, each in
    its own seeded order, prompts of uniform random token ids."""
    base = deck(mix)
    rng = np.random.default_rng(seed)
    uid = 0
    while True:
        for i in rng.permutation(len(base)):
            plen, out, gap = base[i]
            prompt = rng.integers(0, vocab, plen).tolist()
            yield Spec(uid, prompt, out, gap)
            uid += 1
