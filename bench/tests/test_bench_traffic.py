"""The open-loop schedule: seeded, the same offered work for every seed,
the rate held over every block, and latency timed from the due time, so a
stall carries over."""
import threading
import time
from concurrent.futures import Future

import numpy as np

from bench import spec, traffic

exponential = spec.part("arrivals", "exponential")


def _gaps(x):
    return np.diff(np.concatenate([[0.0], x]))


def test_same_gaps_for_every_seed(block=0):
    a = exponential.offsets(20.0, 45.0, np.random.default_rng(1), block)
    b = exponential.offsets(20.0, 45.0, np.random.default_rng(2**40 + 1),
                            block)
    assert a.size == b.size == 900
    assert np.allclose(np.sort(_gaps(a)), np.sort(_gaps(b)))
    assert not np.allclose(a, b)
    assert a[0] > 0 and np.isclose(a[-1], b[-1]) and 44.0 < a[-1] < 45.0
    assert np.all(np.diff(a) > 0)


def test_same_gaps_for_every_seed_in_blocks():
    test_same_gaps_for_every_seed(block=8)


def test_block_holds_one_gap_of_every_stratum():
    n, block = 225, 8
    order = exponential.order(n, block, np.random.default_rng(7))
    assert np.array_equal(np.sort(order), np.arange(n))
    m = -(-n // block)
    full = n - (block - 1) * m          # blocks holding every stratum
    # stratum of sorted gap i, counted from the top as ``order`` counts
    stratum = block - 1 - (n - 1 - order) // m
    for lo in range(0, full * block, block):
        assert np.array_equal(np.sort(stratum[lo:lo + block]),
                              np.arange(block))
    assert np.all(stratum[full * block:] > 0)


def test_blocks_span_the_same_time_for_every_seed():
    """Each run of ``block`` arrivals spans about ``block / rate``; an
    unstratified shuffle lets one seed pile its short gaps together."""
    def spans(block, seed):
        off = exponential.offsets(4.5, 50.0, np.random.default_rng(seed),
                                  block)
        return np.diff(off[7::8])
    stratified = np.concatenate([spans(8, s) for s in range(20)])
    shuffled = np.concatenate([spans(0, s) for s in range(20)])
    assert abs(np.mean(stratified) - 8 / 4.5) < 0.1
    assert stratified.std() < 0.5 * shuffled.std()


class _Stalling:
    """One worker answering in order; the first answer takes ``stall``."""

    def __init__(self, stall: float):
        self.stall = stall
        self.jobs = []
        self.cv = threading.Condition()
        self.t = threading.Thread(target=self._run, daemon=True)
        self.t.start()

    def submit(self, q):
        f = Future()
        with self.cv:
            self.jobs.append(f)
            self.cv.notify()
        return f

    def _run(self):
        first = True
        while True:
            with self.cv:
                while not self.jobs:
                    self.cv.wait()
                f = self.jobs.pop(0)
            if first:
                time.sleep(self.stall)
                first = False
            f.set_result(_Result())


class _Result:
    doc_ids = np.zeros(1, np.int64)
    scores = np.zeros(1, np.float32)


def test_latency_from_due_time_carries_a_stall():
    server = _Stalling(0.4)
    offsets = np.arange(8) * 0.05
    t0 = time.perf_counter() + 0.01
    reqs = traffic.drive_open(server.submit, list(range(8)), np.arange(8),
                              offsets, t0)
    deadline = time.perf_counter() + 5
    while any(r.done is None for r in reqs) and time.perf_counter() < deadline:
        time.sleep(0.01)
    stall_end = t0 + 0.4
    for r in reqs:
        assert r.done is not None
        # every query due inside the stall waited for its end
        assert r.done >= stall_end - 1e-3
        assert abs(r.latency_ms - (r.done - r.due) * 1e3) < 1e-9
    # a query due late in the stall still carries what is left of it
    assert reqs[7].latency_ms >= (stall_end - reqs[7].due) * 1e3 - 1
    # the generator itself was on time: lateness is recorded apart, and
    # so is the time spent inside each submit call
    assert max(r.sent - r.due for r in reqs) < 0.05
    assert all(0 <= r.submit_s < 0.05 for r in reqs)


def test_closed_loop_keeps_its_callers_busy():
    """Each caller sends its next query when the last returns, until the
    window closes; every query is answered."""
    closed = spec.part("arrivals", "closed")
    server = _Stalling(0.0)
    prepared = closed.prepare({"clients": 3}, 0.3, None,
                              lambda n: np.arange(n) % 50, lambda d: d)
    t0 = time.perf_counter()
    reqs = closed.drive(prepared, server.submit, t0, t0 + 0.3, lambda: -1)
    assert len(reqs) > 3
    assert [r.index for r in reqs] == list(range(len(reqs)))
    assert all(r.done is not None and r.error is None for r in reqs)
