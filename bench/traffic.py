"""The one traffic generator every mix file drives.

A mix (``traffic/<mix>.json``) is data. It names its arrival law,
``arrivals/<name>.py``, and its query-selection law, ``selectors/<name>.py``,
each read with the mix's own parameters, and ``queries``, the query maker
the configuration's generator provides (``more_like_this``: a stored
document's own bag). A new law is a new file, a new mix a new data
file.

An arrival law provides ``prepare(mix, seconds, rng, pick, query_of)``,
which draws everything from the seed before the window opens, and
``drive(prepared, submit, t0, t_end, batch_of)``, which sends the queries
and returns one ``Request`` each. Open-loop laws send on schedule through
``drive_open``; closed-loop laws through ``drive_closed``.

Open loop: every query is timed from its *due* time, so a stalled server's
later queries carry the stall; how late the generator itself sent them,
and how long each ``submit`` call took, are recorded apart.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    doc: int                        # the document the query was made from
    due: float                      # perf_counter instant it was due
    sent: float = 0.0               # instant ``submit`` was called
    submit_s: float = 0.0           # how long the ``submit`` call took
    done: Optional[float] = None    # perf_counter instant it was answered
    batch: int = -1                 # the coalesced batch that answered it
    doc_ids: Optional[np.ndarray] = None
    scores: Optional[np.ndarray] = None
    error: Optional[BaseException] = None

    @property
    def latency_ms(self) -> float:
        return (self.done - self.due) * 1e3


def _finish(req: Request, fut, batch_of: Callable[[], int]):
    def done(f):
        req.done = time.perf_counter()
        req.batch = batch_of()
        try:
            res = f.result()
            req.doc_ids = np.asarray(res.doc_ids)
            req.scores = np.asarray(res.scores)
        except BaseException as e:      # recorded; counted as failed
            req.error = e
    fut.add_done_callback(done)


def _send(submit: Callable, q, req: Request, batch_of):
    """``submit(q)`` for ``req``; its Future, or None where it refused."""
    req.sent = time.perf_counter()
    try:
        fut = submit(q)
    except Exception as e:              # a refusal at the door
        req.error = e
        req.done = req.sent
        return None
    finally:
        req.submit_s = time.perf_counter() - req.sent
    _finish(req, fut, batch_of)
    return fut


def drive_open(submit: Callable, queries: List, docs: np.ndarray,
               offsets: np.ndarray, t0: float,
               batch_of: Callable[[], int] = lambda: -1) -> List[Request]:
    """Send ``queries[i]`` at ``t0 + offsets[i]`` through ``submit``
    (returns a Future) from this thread; never waits for an answer."""
    reqs = []
    for i, (q, off) in enumerate(zip(queries, offsets)):
        req = Request(i, int(docs[i]), t0 + float(off))
        wait = req.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        _send(submit, q, req, batch_of)
        reqs.append(req)
    return reqs


def drive_closed(submit: Callable, make_query: Callable[[int], tuple],
                 clients: int, t_end: float,
                 batch_of: Callable[[], int] = lambda: -1,
                 timeout_s: float = 120.0) -> List[Request]:
    """``clients`` threads, each sending its next query when the last
    returns, until ``t_end``. ``make_query(i)`` -> (doc, query) for the
    i-th query of the run, so the seed fixes which queries are sent."""
    reqs: List[Request] = []
    lock = threading.Lock()
    counter = iter(range(1 << 62))

    def client():
        while time.perf_counter() < t_end:
            with lock:
                i = next(counter)
            doc, q = make_query(i)
            req = Request(i, doc, time.perf_counter())
            with lock:
                reqs.append(req)
            fut = _send(submit, q, req, batch_of)
            if fut is None:
                continue
            try:
                fut.result(timeout=timeout_s)
            except BaseException:       # recorded by the callback
                if not fut.done():
                    return              # never answered: stop this client

    threads = [threading.Thread(target=client, name=f"bench-client-{c}",
                                daemon=True) for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout_s + max(0.0, t_end - time.perf_counter()))
    return sorted(reqs, key=lambda r: r.index)
