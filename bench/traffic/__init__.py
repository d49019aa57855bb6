"""The one traffic generator.  A mix is a data file beside this one
(``<traffic>.json``); the generator reads its parameters and a seed.

Every seed gets the same multiset of sizes and inter-arrival gaps, in an
order of its own: lengths are the quantiles ``(i + 0.5) / n`` of their
clipped lognormal and gaps the same quantiles of the exponential, then each
list is shuffled by the seed.  So a seed changes which request comes when
and how they overlap, never how much work a run offers, and the spread
between seeds is the system's own.
"""
from __future__ import annotations

import statistics
from typing import Dict, List

import numpy as np

__all__ = ["stratified_lognormal", "stratified_gaps", "serving_requests"]

_NORMAL = statistics.NormalDist()


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified_lognormal(n: int, median: float, sigma: float, lo: int,
                         hi: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths at the quantiles of a lognormal with the given
    median and log-sigma, clipped to [lo, hi], in the order ``rng`` gives."""
    z = np.asarray([_NORMAL.inv_cdf(q) for q in _quantiles(n)])
    lengths = np.clip(np.rint(median * np.exp(sigma * z)), lo, hi)
    return rng.permutation(lengths.astype(np.int64))


def stratified_gaps(n: int, rate: float, rng: np.random.Generator
                    ) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles of an exponential of mean
    ``1 / rate``, in the order ``rng`` gives."""
    return rng.permutation(-np.log1p(-_quantiles(n)) / rate)


def serving_requests(mix: Dict, seconds: float, seed: int, vocab: int
                     ) -> List[Dict]:
    """The open-loop request list of one run: ``rate_per_s * seconds``
    requests, each ``{"arrival": s, "prompt": int32 ids, "max_new": n}``,
    arriving from 0 on with the stratified gaps (the first at 0)."""
    rng = np.random.default_rng(seed)
    n = max(int(round(mix["rate_per_s"] * seconds)), 1)
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    prompts = stratified_lognormal(n, p["median"], p["sigma"], p["min"],
                                   p["max"], rng)
    outputs = stratified_lognormal(n, o["median"], o["sigma"], o["min"],
                                   o["max"], rng)
    gaps = stratified_gaps(n, mix["rate_per_s"], rng)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return [{"arrival": float(a),
             "prompt": rng.integers(0, vocab, size=int(pl), dtype=np.int32),
             "max_new": int(ol)}
            for a, pl, ol in zip(arrivals, prompts, outputs)]
