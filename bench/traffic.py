"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) gives the arrivals and the length
distributions; nothing here knows a mix by name.  Every seed gets the
same multiset of prompt lengths, output lengths and inter-arrival gaps:
each is the set of evenly spaced quantiles of its distribution
(stratified sampling).  The run's seed draws the token ids.  So two seeds
give the same work, and the spread between seeds is the spread of the
system, not of the draw.

Arrivals:

* ``{"kind": "poisson", "rate_per_s": r}``: an open loop.  Gaps are the
  quantiles of an exponential of mean 1/r, so a window of ``s`` seconds
  holds about ``r * s`` arrivals, in an order the run's seed permutes.
* ``{"kind": "backlog", "queued": q}``: a saturated engine.  The harness
  keeps ``q`` requests queued at every moment, in an order the run's
  seed permutes, ``BLOCK`` requests at a time.  The first ``in_flight``
  requests (the engine's slot count) stand for requests already
  decoding when the run starts: their outputs are drawn from the
  residual of the output distribution (``P(R > r)`` proportional to
  ``P(L > r)``), so slots free up from the start as they would in steady
  state.

Lengths: ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
"max": b}`` or ``{"dist": "uniform", "min": a, "max": b}``, in tokens.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Iterator, List, Mapping

import numpy as np

BLOCK = 256     # backlog requests whose lengths are one stratified draw


@dataclass
class Request:
    uid: int
    due_s: float            # seconds after the start of the load
    prompt: np.ndarray      # int32 token ids
    max_new: int


def _u(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Mapping[str, Any], n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a length distribution, as ints."""
    lo, hi = int(spec["min"]), int(spec["max"])
    u = _u(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = np.round(float(spec["median"]) * np.exp(float(spec["sigma"]) * z))
    elif spec["dist"] == "uniform":
        x = np.floor(lo + u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def residual_lengths(spec: Mapping[str, Any], n: int) -> np.ndarray:
    """``n`` quantiles of the tokens still to come of a request caught at
    a random moment of its decoding (the equilibrium residual life)."""
    pool = lengths(spec, 4096)
    r = np.arange(1, int(spec["max"]) + 1)
    surv = np.array([np.mean(pool >= x) for x in r])
    cdf = np.cumsum(surv) / np.sum(surv)
    return r[np.minimum(np.searchsorted(cdf, _u(n)), len(r) - 1)]


def max_context(mix: Mapping[str, Any]) -> int:
    """Longest prompt plus longest output the mix can send."""
    return int(mix["prompt"]["max"]) + int(mix["output"]["max"])


class Traffic:
    """The requests of one run of a mix, from ``seed``."""

    def __init__(self, mix: Mapping[str, Any], *, seed: int, vocab: int,
                 seconds: float, n_slots: int):
        self.mix = mix
        self.kind = mix["arrival"]["kind"]
        self.vocab = vocab
        self.preroll_s = float(mix.get("preroll_s", 0.0))
        ss = np.random.SeedSequence([seed, 0x7A11])
        self._perm_rng, self._tok_rng = (np.random.default_rng(s)
                                         for s in ss.spawn(2))
        if self.kind == "poisson":
            rate = float(mix["arrival"]["rate_per_s"])
            self.n = int(math.ceil(rate * (self.preroll_s + seconds))) + 1
            gaps = -np.log1p(-_u(self.n)) / rate
            perm = self._perm_rng.permutation
            self._due = np.cumsum(perm(gaps))
            self._plen = perm(lengths(mix["prompt"], self.n))
            self._olen = perm(lengths(mix["output"], self.n))
        elif self.kind == "backlog":
            self.queued = int(mix["arrival"]["queued"])
            self.in_flight = n_slots
        else:
            raise ValueError(f"unknown arrival kind {self.kind!r}")

    def _tokens(self, n: int) -> np.ndarray:
        return self._tok_rng.integers(0, self.vocab, size=int(n),
                                      dtype=np.int32)

    def scheduled(self) -> List[Request]:
        """Open loop: every request with its due time."""
        assert self.kind == "poisson"
        return [Request(i, float(self._due[i]), self._tokens(self._plen[i]),
                        int(self._olen[i])) for i in range(self.n)]

    def stream(self) -> Iterator[Request]:
        """Backlog: an endless stream, all due at the start."""
        assert self.kind == "backlog"
        uid = 0
        first = residual_lengths(self.mix["output"], self.in_flight)
        plen = self._perm_rng.permutation(lengths(self.mix["prompt"],
                                                  self.in_flight))
        for p, o in zip(plen, self._perm_rng.permutation(first)):
            yield Request(uid, 0.0, self._tokens(p), int(o))
            uid += 1
        while True:
            plen = self._perm_rng.permutation(
                lengths(self.mix["prompt"], BLOCK))
            olen = self._perm_rng.permutation(
                lengths(self.mix["output"], BLOCK))
            for p, o in zip(plen, olen):
                yield Request(uid, 0.0, self._tokens(p), int(o))
                uid += 1
