"""The one traffic generator: reads a mix file from ``bench/traffic/``.

A mix fixes the multiset of request sizes and, in an open loop, the
arrival times. The run's seed only reorders the sizes and draws the
token ids, so every seed offers the same work:

- Sizes come in decks of ``deck`` requests. A deck holds the
  ``(i + 0.5) / deck`` quantiles of the prompt and output length
  distributions; each seed shuffles the prompt and the output lengths of
  every deck independently.
- Open loop: arrival times come from ``arrivals.arrival_seed``, the same
  for every seed. ``mmpp`` is a two-state Markov-modulated Poisson
  process (calm and burst phases with exponential dwell times), whose
  ``rate`` is the mean over both phases.
- Tokens: a request picks one topic by weight; its tokens are drawn i.i.d.
  from a Zipf law of exponent ``zipf_alpha`` over the topic's own subset
  of ``vocab_frac`` of the vocabulary (alpha 0 is uniform). A topic with
  ``redraw_every`` draws a new subset every that many requests.

Request ``i`` depends only on (seed, i), never on timing.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


# -------------------------------------------------------------- arrivals
# poisson_arrivals and bursty_arrivals follow repro.workloads.arrivals.

def poisson_arrivals(rate: float, horizon: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Homogeneous Poisson process on [0, horizon): exponential gaps."""
    if rate <= 0 or horizon <= 0:
        return np.empty((0,))
    n = max(int(rate * horizon * 2), 16)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    while t[-1] < horizon:
        more = np.cumsum(rng.exponential(1.0 / rate, size=n)) + t[-1]
        t = np.concatenate([t, more])
    return t[t < horizon]


def bursty_arrivals(rate_low: float, rate_high: float, horizon: float,
                    rng: np.random.Generator, *, mean_dwell_low: float,
                    mean_dwell_high: float) -> np.ndarray:
    """Two-state MMPP: alternating calm and burst phases of exponential
    length, Poisson at the phase's rate inside each; starts calm."""
    times = []
    t, high = 0.0, False
    while t < horizon:
        end = min(t + rng.exponential(mean_dwell_high if high
                                      else mean_dwell_low), horizon)
        seg = poisson_arrivals(rate_high if high else rate_low, end - t, rng)
        times.append(seg + t)
        t, high = end, not high
    return np.sort(np.concatenate(times)) if times else np.empty((0,))


def arrival_times(spec: dict, horizon: float,
                  rate: Optional[float] = None) -> np.ndarray:
    """Due times in [0, horizon) of an open-loop mix (``rate`` overrides
    the mix's mean rate, for a sweep)."""
    rate = spec["rate"] if rate is None else rate
    rng = np.random.default_rng(spec["arrival_seed"])
    if spec["kind"] == "poisson":
        return poisson_arrivals(rate, horizon, rng)
    if spec["kind"] != "mmpp":
        raise ValueError(f"unknown arrival kind {spec['kind']!r}")
    lo_s, hi_s, k = spec["dwell_low_s"], spec["dwell_high_s"], \
        spec["high_over_low"]
    # mean rate = (lo_s * r + hi_s * k * r) / (lo_s + hi_s)
    r_low = rate * (lo_s + hi_s) / (lo_s + k * hi_s)
    return bursty_arrivals(r_low, k * r_low, horizon, rng,
                           mean_dwell_low=lo_s, mean_dwell_high=hi_s)


# --------------------------------------------------------------- lengths

def quantiles(dist: dict, n: int) -> np.ndarray:
    """The (i + 0.5) / n quantiles of a clipped length distribution."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = dist["lo"], dist["hi"]
    if dist["dist"] == "uniform":
        v = lo + np.floor(q * (hi - lo + 1))
    elif dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        v = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    else:
        raise ValueError(f"unknown length dist {dist['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def _zipf(alpha: float, n: int) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** (-alpha)
    return p / p.sum()


@dataclass
class Request:
    """One generated request: its prompt and output budget."""
    index: int
    prompt: np.ndarray            # (P,) int32
    max_new: int


class Traffic:
    """Request ``i`` of one mix under one seed."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.seed = int(seed)
        self.deck = int(mix.get("deck", 64))
        self._p_deck = quantiles(mix["prompt"], self.deck)
        self._o_deck = quantiles(mix["output"], self.deck)
        topics = mix["tokens"]["topics"]
        w = np.array([t["weight"] for t in topics], np.float64)
        self._topic_w = w / w.sum()
        self._topics = topics
        self._subsets = {}
        limit = mix["slots"]["prefill_len"]
        if self._p_deck.max() > limit:
            raise ValueError(f"prompts up to {self._p_deck.max()} exceed "
                             f"prefill_len {limit}")

    def _deck_order(self, d: int):
        rng = np.random.default_rng([self.seed, 1, d])
        return rng.permutation(self.deck), rng.permutation(self.deck)

    def lengths(self, i: int):
        d, j = divmod(i, self.deck)
        po, oo = self._deck_order(d)
        return int(self._p_deck[po[j]]), int(self._o_deck[oo[j]])

    def _subset(self, k: int, i: int) -> np.ndarray:
        t = self._topics[k]
        epoch = i // t["redraw_every"] if t.get("redraw_every") else 0
        key = (k, epoch)
        if key not in self._subsets:
            n = max(int(self.vocab * t["vocab_frac"]), 1)
            rng = np.random.default_rng([self.seed, 2, k, epoch])
            self._subsets[key] = rng.permutation(self.vocab)[:n]
        return self._subsets[key]

    def request(self, i: int) -> Request:
        plen, olen = self.lengths(i)
        rng = np.random.default_rng([self.seed, 3, i])
        k = int(rng.choice(len(self._topics), p=self._topic_w))
        ids = self._subset(k, i)
        alpha = self._topics[k]["zipf_alpha"]
        if alpha == 0:
            toks = ids[rng.integers(0, len(ids), size=plen)]
        else:
            toks = ids[rng.choice(len(ids), size=plen,
                                  p=_zipf(alpha, len(ids)))]
        return Request(i, toks.astype(np.int32), olen)

    def first(self, n: int) -> List[Request]:
        return [self.request(i) for i in range(n)]
