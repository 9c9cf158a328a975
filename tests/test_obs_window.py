"""Rolling-window instruments (DESIGN.md §8.4): lazy ring rotation
under an injectable clock, merged-window percentiles sharing the
lifetime interpolation, registry-attached twins on every existing
handle, the 16-thread observe+rotate hammer, and the Obs.disabled()
zero-clock-read floor in the plan executor."""
import threading

import numpy as np
import pytest

from repro.configs.paper_search import smoke
from repro.core import corpus as corpus_lib
from repro.obs import Obs, MetricsRegistry
from repro.obs.metrics import percentile_from_state
from repro.obs.window import WindowedCounter, WindowedHistogram
from repro.storage import FlashSearchSession, FlashStore

CFG = smoke()


class FakeClock:
    """Deterministic, thread-safe monotonic clock for rotation tests."""

    def __init__(self, t: float = 0.0):
        self._t = t
        self._lock = threading.Lock()

    def __call__(self) -> float:
        with self._lock:
            return self._t

    def advance(self, dt: float) -> None:
        with self._lock:
            self._t += dt


# -- rotation mechanics ------------------------------------------------

def test_counter_expires_after_window():
    clk = FakeClock()
    c = WindowedCounter(window_s=10.0, slices=5, clock=clk)
    c.inc(3)
    assert c.total() == 3
    clk.advance(4.0)              # 2 slices later: still inside window
    c.inc(2)
    assert c.total() == 5
    clk.advance(7.0)              # first obs now > window_s old
    assert c.total() == 2
    clk.advance(10.0)             # everything aged out
    assert c.total() == 0
    assert c.rate_per_s() == 0.0


def test_histogram_rotation_is_incremental():
    clk = FakeClock()
    h = WindowedHistogram(window_s=6.0, slices=3, clock=clk)
    for t, v in ((0.0, 1.0), (2.0, 10.0), (4.0, 100.0)):
        while clk() < t:
            clk.advance(2.0)
        h.observe(v)
    assert h.count == 3
    clk.advance(2.0)              # t=6: the t=0 slice expires
    assert h.count == 2
    clk.advance(2.0)              # t=8: the t=2 slice expires
    assert h.count == 1
    st = h.state()
    assert st.lo == st.hi == 100.0
    clk.advance(100.0)            # idle gap >> window: all clear
    assert h.count == 0
    assert h.p99 == 0.0           # empty window: percentile is 0, not NaN


def test_spike_ages_out_of_extremes():
    # per-slice min/max: a latency spike must stop pinning the window
    # max after it rotates out (the reason lifetime hists can't drive
    # admission control)
    clk = FakeClock()
    h = WindowedHistogram(window_s=4.0, slices=4, clock=clk)
    h.observe(5000.0)             # the spike
    clk.advance(1.0)
    for _ in range(20):
        h.observe(1.0)
    assert h.state().hi == 5000.0
    clk.advance(3.5)              # spike slice expired, steady slice live
    assert h.state().hi == 1.0
    assert h.p99 <= 1.0 + 1e-9


def test_window_percentiles_match_lifetime_interpolation():
    # same data inside one live window -> merged-window quantiles equal
    # the lifetime histogram's (shared percentile_from_state)
    from repro.obs.metrics import Histogram
    clk = FakeClock()
    w = WindowedHistogram(window_s=60.0, slices=6, clock=clk)
    life = Histogram()
    rng = np.random.default_rng(3)
    for v in rng.gamma(2.0, 20.0, size=500):
        w.observe(float(v))
        life.observe(float(v))
    for q in (0.5, 0.95, 0.99):
        assert w.percentile(q) == pytest.approx(life.percentile(q))
    assert w.state().counts == life.state().counts


def test_fraction_le_empty_window_is_one():
    clk = FakeClock()
    w = WindowedHistogram(window_s=5.0, slices=5, clock=clk)
    assert w.fraction_le(100.0) == 1.0     # no traffic violates nothing
    w.observe(10.0)
    w.observe(1000.0)
    assert 0.0 < w.fraction_le(100.0) < 1.0
    clk.advance(50.0)
    assert w.fraction_le(100.0) == 1.0


def test_bad_window_params_raise():
    with pytest.raises(ValueError):
        WindowedCounter(window_s=0.0)
    with pytest.raises(ValueError):
        WindowedHistogram(slices=0)


# -- registry integration ----------------------------------------------

def test_registry_attaches_twins_to_every_handle():
    reg = MetricsRegistry(window_s=30.0)
    h = reg.histogram("stage_ms", stage="score")
    c = reg.counter("queries_total", surface="store")
    g = reg.gauge("some_gauge")
    h.observe(5.0)
    c.inc(4)
    g.set(1.0)
    w = reg.windowed("stage_ms", stage="score")
    assert w is not None and w.count == 1 and w.window_s == 30.0
    assert reg.windowed("queries_total", surface="store").total() == 4
    assert reg.windowed("some_gauge") is None          # gauges: no twin
    assert reg.windowed("never_created", x="y") is None  # never creates


def test_registry_windows_can_be_disabled():
    reg = MetricsRegistry(windows=False)
    reg.histogram("stage_ms", stage="score").observe(1.0)
    assert reg.windowed("stage_ms", stage="score") is None


def test_prometheus_window_gauges_render():
    clk = FakeClock()
    reg = MetricsRegistry(window_s=60.0, clock=clk)
    reg.histogram("query_ms", surface="store").observe(12.0)
    reg.counter("queries_total", surface="store").inc()
    text = reg.to_prometheus(include_windows=True)
    assert "# TYPE repro_query_ms_window gauge" in text
    assert ('repro_query_ms_window{stat="p99",surface="store",'
            'window="60s"}') in text
    assert ('repro_queries_total_window{stat="total",surface="store",'
            'window="60s"} 1') in text
    # default rendering is unchanged (file exporters, older tests)
    assert "_window" not in reg.to_prometheus()


# -- concurrency -------------------------------------------------------

def test_hammer_16_threads_no_lost_observations():
    # no rotation (huge window): concurrent observes must all land
    h = WindowedHistogram(window_s=3600.0, slices=6)
    c = WindowedCounter(window_s=3600.0, slices=6)
    n_threads, per_thread = 16, 500

    def work(tid):
        for i in range(per_thread):
            h.observe(float(i % 100))
            c.inc()

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    st = h.state()
    assert st.total == n_threads * per_thread
    assert sum(st.counts) == st.total
    assert c.total() == n_threads * per_thread


def test_hammer_concurrent_observe_and_rotate_equals_serial():
    # the same observe/advance schedule driven concurrently (16 threads
    # per phase, rotation forced between phases) and serially must end
    # in the identical merged state — rotation loses nothing the window
    # still covers and keeps nothing it shouldn't
    schedule = [(0.0, 200), (2.0, 150), (4.0, 250), (9.0, 100)]
    window_s, slices, n_threads = 10.0, 5, 16

    def run_concurrent():
        clk = FakeClock()
        h = WindowedHistogram(window_s=window_s, slices=slices, clock=clk)
        for t_at, n_obs in schedule:
            while clk() < t_at:
                clk.advance(window_s / slices)
            barrier = threading.Barrier(n_threads)

            def work(tid):
                barrier.wait()     # all threads race observe + rotate
                for i in range(n_obs):
                    h.observe(float((tid * n_obs + i) % 50))

            threads = [threading.Thread(target=work, args=(t,))
                       for t in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        return h.state()

    def run_serial():
        clk = FakeClock()
        h = WindowedHistogram(window_s=window_s, slices=slices, clock=clk)
        for t_at, n_obs in schedule:
            while clk() < t_at:
                clk.advance(window_s / slices)
            for tid in range(n_threads):
                for i in range(n_obs):
                    h.observe(float((tid * n_obs + i) % 50))
        return h.state()

    a, b = run_concurrent(), run_serial()
    assert a.counts == b.counts
    # the t=0 phase rotated out (clock parked at t=10, window 10 s with
    # 2 s slices -> live slices cover (2, 10]); the rest survived
    assert a.total == b.total == n_threads * (150 + 250 + 100)
    assert a.lo == b.lo and a.hi == b.hi
    assert percentile_from_state(tuple(range(50)), a, 0.99) == \
        percentile_from_state(tuple(range(50)), b, 0.99)


# -- the Obs.disabled() instrumentation floor --------------------------

class _CountingTime:
    """time-module proxy that counts perf_counter reads."""

    def __init__(self, real_time):
        self._real = real_time
        self.reads = 0

    def perf_counter(self):
        self.reads += 1
        return self._real.perf_counter()

    def __getattr__(self, name):
        return getattr(self._real, name)


def _count_clock_reads(monkeypatch, *mods) -> _CountingTime:
    """Route every perf_counter read of ``mods`` and of the stage helper
    (``repro.obs.trace``) through one counting proxy."""
    import time as real_time

    from repro.obs import trace as trace_mod

    proxy = _CountingTime(real_time)
    for mod in (trace_mod,) + mods:
        if hasattr(mod, "time"):
            monkeypatch.setattr(mod, "time", proxy)
    return proxy


def _small_store(tmp_path):
    corpus = corpus_lib.synthesize(120, CFG.vocab_size,
                                   CFG.avg_nnz_per_doc, CFG.nnz_pad, seed=5)
    root = str(tmp_path / "store")
    store = FlashStore.create(root, vocab_size=CFG.vocab_size,
                              docs_per_segment=40)
    store.append_corpus(corpus)
    return corpus, root


def test_disabled_obs_does_zero_clock_reads(tmp_path, monkeypatch):
    from repro.storage import plan as plan_mod
    from repro.storage import prefetch as prefetch_mod
    from repro.storage import session as session_mod

    corpus, root = _small_store(tmp_path)
    proxy = _count_clock_reads(monkeypatch, plan_mod, prefetch_mod,
                               session_mod)

    qi, qv = corpus_lib.make_query(corpus, 3, CFG.max_query_nnz)
    off = FlashSearchSession(FlashStore.open(root), CFG, obs=Obs.disabled())
    r_off = off.search(qi[None], qv[None])
    off.search(qi[None], qv[None])
    assert proxy.reads == 0, (
        f"Obs.disabled() path read the clock {proxy.reads} times")
    off.close()

    # sanity: the proxy does count when observability is on, and the
    # results are bit-identical either way (the §8 differential)
    on = FlashSearchSession(FlashStore.open(root), CFG, obs=Obs())
    r_on = on.search(qi[None], qv[None])
    assert proxy.reads > 0
    np.testing.assert_array_equal(r_on.doc_ids, r_off.doc_ids)
    np.testing.assert_array_equal(r_on.scores, r_off.scores)
    on.close()


def test_disabled_obs_service_does_zero_clock_reads(tmp_path, monkeypatch):
    """The floor holds through the engine's per-pass stages and the
    service's batch annotation too: a coalesced batch served under
    Obs.disabled() reads no clock in any module on the path."""
    from repro.core import engine as engine_mod
    from repro.serve import search_service as service_mod
    from repro.serve.api import Query
    from repro.storage import plan as plan_mod
    from repro.storage import prefetch as prefetch_mod
    from repro.storage import session as session_mod

    corpus, root = _small_store(tmp_path)
    proxy = _count_clock_reads(monkeypatch, plan_mod, prefetch_mod,
                               session_mod, engine_mod, service_mod)
    queries = [Query(*corpus_lib.make_query(corpus, d, CFG.max_query_nnz))
               for d in (3, 50, 97)]

    def serve(obs):
        sess = FlashSearchSession(FlashStore.open(root), CFG, obs=obs)
        with service_mod.SearchService(sess, max_batch=4) as svc:
            rows = [f.result() for f in [svc.submit(q) for q in queries]]
        sess.close()
        return rows

    off = serve(Obs.disabled())
    assert proxy.reads == 0, (
        f"Obs.disabled() service path read the clock {proxy.reads} times")
    on = serve(Obs())
    assert proxy.reads > 0
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a.doc_ids, b.doc_ids)
        np.testing.assert_array_equal(a.scores, b.scores)
