"""Arrivals of an `open_loop` mix (`bench/traffic/<mix>.json` with
`"kind": "open_loop"`): independent clients sending at `rate_per_s` on
average (Poisson arrivals), spread over `tenants` tenants with Zipf(`zipf_s`)
shares, each request one system of a size drawn log-uniformly from
[`n_min`, `n_max`] out of a pool of `pool`.

Every seed gets the same multiset of sizes, gaps and tenants, in its own
order: stratified quantiles of each distribution, shuffled by the seed.  So
two seeds offer the same work, and differ in its order and in the data.
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, purpose); any non-negative seed."""
    return np.random.default_rng([int(seed), int(stream)])


def _quantiles(m: int) -> np.ndarray:
    return (np.arange(m) + 0.5) / m


def pool_sizes(traffic: dict) -> np.ndarray:
    """Sizes of the pool's systems: log-uniform quantiles."""
    lo, hi, m = traffic["n_min"], traffic["n_max"], traffic["pool"]
    logn = np.log(lo) + _quantiles(m) * (np.log(hi) - np.log(lo))
    return np.clip(np.rint(np.exp(logn)).astype(int), lo, hi)


def zipf_counts(m: int, tenants: int, s: float) -> np.ndarray:
    """Requests per tenant: Zipf(s) shares of m, rounded by largest remainder."""
    w = 1.0 / np.arange(1, tenants + 1) ** s
    share = w / w.sum() * m
    counts = np.floor(share).astype(int)
    rest = m - counts.sum()
    counts[np.argsort(-(share - counts))[:rest]] += 1
    return counts


def schedule(traffic: dict, seed: int, seconds: float) -> dict:
    """Arrival schedule over `seconds`.

    Returns arrays `t_due` (seconds from the window's start, increasing),
    `tenant` (index) and `item` (index into the pool), one entry per request.
    """
    rate = float(traffic["rate_per_s"])
    m = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-_quantiles(m)) / rate
    t_due = np.cumsum(rng(seed, 1).permutation(gaps))
    t_due = np.minimum(t_due * (seconds / t_due[-1]), seconds)
    counts = zipf_counts(m, int(traffic["tenants"]), float(traffic["zipf_s"]))
    tenant = rng(seed, 2).permutation(np.repeat(np.arange(len(counts)), counts))
    item = rng(seed, 3).permutation(np.arange(m) % int(traffic["pool"]))
    return {"t_due": t_due, "tenant": tenant, "item": item}
