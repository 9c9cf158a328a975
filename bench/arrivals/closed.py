"""Closed loop: ``clients`` callers, each sending its next query when its
last one returns, until the window closes.

Mix parameters: ``clients``. The queries are drawn from the seed before
the window opens; each caller makes the next one when it is due.
"""
from __future__ import annotations

from bench import traffic

POOL = 1 << 16              # more queries than a window's callers send


def prepare(mix: dict, seconds: float, rng, pick, query_of):
    return int(mix["clients"]), pick(POOL), query_of


def drive(prepared, submit, t0: float, t_end: float, batch_of):
    clients, pool, query_of = prepared

    def make(i):
        d = int(pool[i % pool.size])
        return d, query_of(d)
    return traffic.drive_closed(submit, make, clients, t_end, batch_of)
