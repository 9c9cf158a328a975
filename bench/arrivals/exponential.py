"""Open loop: independent users at a fixed ``rate_qps``, each query sent on
schedule whatever the server does.

Mix parameters: ``rate_qps``; ``block`` (optional), the number of arrivals
over which the rate holds exactly.

The gaps between arrivals are the ``n = rate_qps * seconds`` midpoint
quantiles of the exponential at that rate, the same set for every seed,
so the offered work does not change with the seed. Sorted, they fall into
``block`` strata of consecutive quantiles; each run of ``block`` arrivals
takes one gap from every stratum, the seed choosing which and their order.
Every block then spans about the same time, and the seed moves only the
bursts inside a block: the 95th percentile of latency no longer follows
where one seed happens to pile its short gaps. Without ``block`` the whole
set is one block, a plain shuffle.
"""
from __future__ import annotations

import numpy as np

from bench import traffic


def gaps(rate_qps: float, seconds: float) -> np.ndarray:
    """The midpoint quantiles of the exponential gap, ascending."""
    n = max(1, int(round(rate_qps * seconds)))
    return -np.log1p(-(np.arange(n) + 0.5) / n) / rate_qps


def order(n: int, block: int, rng) -> np.ndarray:
    """A permutation of ``range(n)`` (the sorted gaps' indices) in which
    every run of ``block`` holds one index of each stratum. Where ``n`` is
    not a multiple of ``block``, the stratum of the shortest gaps is the
    short one, and the blocks that lack one of its gaps come last."""
    block = min(max(1, block), n)
    m = -(-n // block)                  # blocks
    slots = np.full((m, block), -1, np.int64)
    for k in range(block):
        hi = n - (block - 1 - k) * m    # strata counted from the top
        members = np.arange(max(0, hi - m), hi)
        slots[:members.size, k] = rng.permutation(members)
    slots = rng.permuted(rng.permutation(slots, axis=0), axis=1)
    slots = slots[np.argsort((slots < 0).any(axis=1), kind="stable")]
    flat = slots.ravel()
    return flat[flat >= 0]


def offsets(rate_qps: float, seconds: float, rng,
            block: int = 0) -> np.ndarray:
    """Due offsets (s) from the window's start. Their total, the last due
    offset, is the same for every seed, and just under ``seconds``."""
    g = gaps(rate_qps, seconds)
    return np.cumsum(g[order(g.size, block or g.size, rng)])


def prepare(mix: dict, seconds: float, rng, pick, query_of):
    off = offsets(float(mix["rate_qps"]), seconds, rng,
                  int(mix.get("block", 0)))
    docs = pick(off.size)
    return off, docs, [query_of(d) for d in docs]


def drive(prepared, submit, t0: float, t_end: float, batch_of):
    off, docs, queries = prepared
    return traffic.drive_open(submit, queries, docs, off, t0, batch_of)
