"""Request sizes from a traffic mix file: one fixed set per mix, in an
order and with token ids drawn from the run's seed.

Every run of a mix serves the same (prompt length, output length) pairs,
and an open loop the same arrival schedule; ``--seed`` draws the token
ids (and, in a closed loop, which caller runs which fixed sequence). So
two seeds do the same work, and the spread between runs measures the
system, not the draw: a window holds some tens of chat requests, and an
order drawn per seed would make their 95th percentile the worst of a
different coincidence in every run.

Distributions are read from the mix file as
``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``,
``{"dist": "uniform", "min": a, "max": b}`` or ``{"dist": "fixed",
"value": v}``; a fixed set of n values is the distribution's quantiles at
(i + 0.5) / n, rounded and clipped to [min, max].
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

# the fixed pairing of lengths inside one mix (not the run's seed)
_PAIRING_SEED = 20240917


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n values of ``dist`` at its (i + 0.5) / n quantiles, ascending."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(v) for v in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def bounds(dist: dict) -> tuple:
    if dist["dist"] == "fixed":
        return int(dist["value"]), int(dist["value"])
    return int(dist["min"]), int(dist["max"])


def arrival_gaps(rate: float, n: int) -> np.ndarray:
    """n exponential inter-arrival gaps of mean 1/rate, at quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def zipf_ranks(count: int, s: float, n: int) -> np.ndarray:
    """n picks among ``count`` items of Zipf popularity (exponent s), as
    the fixed counts its quantiles give."""
    p = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(p / p.sum())
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u), count - 1)


class Space:
    """The request sizes of one traffic mix for one vocabulary."""

    def __init__(self, mix: dict, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.unique = mix["prompt"]["unique"]
        self.shared = mix["prompt"].get("shared_prefixes")
        self.output = mix["output"]

    def bounds(self) -> dict:
        """Least and greatest prompt, output and whole-request lengths,
        and whether prompts can share leading tokens."""
        u_lo, u_hi = bounds(self.unique)
        s_lo = s_hi = 0
        if self.shared:
            s_lo, s_hi = bounds(self.shared["length"])
        o_lo, o_hi = bounds(self.output)
        return {"prompt_min": u_lo + s_lo, "prompt_max": u_hi + s_hi,
                "output_min": o_lo, "output_max": o_hi,
                "total_max": u_hi + s_hi + o_hi,
                "shared": bool(self.shared)}

    def sizes(self, n: int) -> list:
        """The fixed set of n (shared prefix index or -1, unique length,
        output length) triples, in a fixed shuffled order."""
        pair = np.random.default_rng(_PAIRING_SEED)
        uniq = quantiles(self.unique, n)
        outs = quantiles(self.output, n)[pair.permutation(n)]
        if self.shared:
            ranks = zipf_ranks(self.shared["count"],
                               self.shared["zipf_s"], n)
            pref = ranks[pair.permutation(n)]
        else:
            pref = np.full(n, -1)
        triples = [(int(p), int(u), int(o))
                   for p, u, o in zip(pref, uniq, outs)]
        return [triples[i] for i in pair.permutation(n)]

    def prefixes(self, rng: np.random.Generator) -> list:
        """The shared prefixes' token ids, drawn once per seed; their
        lengths are fixed, assigned to popularity ranks in a fixed order."""
        if not self.shared:
            return []
        count = self.shared["count"]
        lengths = quantiles(self.shared["length"], count)
        lengths = lengths[np.random.default_rng(_PAIRING_SEED + 1)
                          .permutation(count)]
        return [rng.integers(0, self.vocab, int(n)).astype(np.int32)
                for n in lengths]

    def prompt(self, rng, prefixes, triple) -> np.ndarray:
        pref, n_unique, _ = triple
        tail = rng.integers(0, self.vocab, n_unique).astype(np.int32)
        return tail if pref < 0 else np.concatenate([prefixes[pref], tail])


def poisson_dues(rate: float, seconds: float) -> np.ndarray:
    """Due times of round(rate * seconds) requests in [0, seconds): a
    fixed set of exponential gaps in a fixed shuffled order, scaled so
    that the mean rate is exactly ``rate``."""
    n = max(1, int(round(rate * seconds)))
    order = np.random.default_rng(_PAIRING_SEED + 2).permutation(n)
    gaps = arrival_gaps(rate, n)[order]
    dues = np.cumsum(gaps)
    return dues * (seconds * (1.0 - 0.5 / n) / dues[-1])
