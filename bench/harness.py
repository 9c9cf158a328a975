"""One run of one cell: set-up, the measured window, the check against the
plain reference, and the result line.

The served path is the one a user calls, with every program setting at
its default. A configuration names its ``surface`` (``store`` where it
names none):

- ``store``: ``FlashSearchSession.submit`` -> the ``SearchService``
  coalescer -> ``Planner`` / ``execute_plan`` (``SlabCache``,
  ``Prefetcher``, decode and upload) -> ``PatternSearchEngine`` ->
  the scoring program -> the top-k merge, over one store written through
  the program's own ``FlashStore.create`` / ``append_docs``;
- ``cluster``: ``FlashClusterSession.submit`` -> the same coalescer ->
  ``ShardRouter`` (scatter to one ``FlashSearchSession`` a shard replica,
  each on its router-assigned device, over the cluster-shared slab
  cache; gather and merge), over ``n_shards`` x ``replicas`` stores
  written by the program's own ``build_sharded_store`` under ``policy``.

Order of a run: generate the corpus from the seed; build the store; open
the session; warm every L bucket (1, 2, 4, 8) and run warm-up queries
until the slab cache reads the same on two batches in a row (set-up ends
here); drive the mix for ``seconds``; wait for every answer (a minute past
the close at most); read the device's peak memory; close the session;
compare a sample of the answers with the plain reference; with a trace,
reduce it.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from bench import spec, trace_reduce
from bench.peaks import peaks_for

DRAIN_S = 60.0          # how long past the close an answer is waited for
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def say(msg: str):
    print(msg, file=sys.stderr, flush=True)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise NoDevice(f"no TPU: JAX's devices are {d0.platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def chip_memory(chips: int, key: str) -> List[Optional[int]]:
    """``memory_stats()[key]`` of each of the first ``chips`` devices
    (None where the backend keeps no such count)."""
    import jax
    return [(d.memory_stats() or {}).get(key)
            for d in jax.devices()[:chips]]


def compile_cache_env(root: str) -> str:
    """Give the program its persistent compilation cache: a fixed directory
    inside the checkout, every program cached. Call before JAX is imported;
    JAX reads these variables then, and the program's own
    ``repro.compile_cache.enable_compile_cache`` leaves a set directory to
    JAX. A directory the machine names elsewhere would be shared by every
    checkout on it, so it is replaced."""
    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    return path


class GcPauses:
    """The interpreter's garbage collections during a window: how many
    full (generation 2) collections ran and the longest pause of any."""

    def __init__(self):
        import gc
        self._gc = gc
        self.full, self.longest, self._t0 = 0, 0.0, 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        self.longest = max(self.longest, time.perf_counter() - self._t0)
        self.full += info["generation"] == 2

    def close(self):
        self._gc.callbacks.remove(self._on)


class CompileCounter:
    """Programs JAX built in this process, compiled or loaded from the
    persistent cache (and their seconds), and how many of them it loaded,
    through its monitoring events."""

    def __init__(self):
        self.n = self.loaded = 0
        self.seconds = 0.0
        self._lock = threading.Lock()
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def close(self):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration_secs, **kw):
        with self._lock:
            if event == COMPILE_EVENT:
                self.n += 1
                self.seconds += duration_secs
            elif event == CACHE_LOAD_EVENT:
                self.loaded += 1


class HostWatch:
    """What held the host back during a window: a heartbeat thread that
    sleeps ``TICK_S`` at a time and records every wake-up later than
    ``STALL_S`` (a stall of the whole interpreter, not of one thread),
    the process's involuntary context switches, and where the kernel
    exposes them, the cgroup's CPU throttling and the CPU pressure
    stall time."""

    TICK_S, STALL_S = 0.005, 0.05

    def __init__(self, t0: float):
        self.t0, self.stalls = t0, []
        self._before = self._read()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-host-watch")
        self._thread.start()

    def _run(self):
        while not self._stop.is_set():
            t = time.perf_counter()
            time.sleep(self.TICK_S)
            late = time.perf_counter() - t - self.TICK_S
            if late > self.STALL_S:
                self.stalls.append((round(t - self.t0, 2),
                                    round(late * 1e3, 1)))

    @staticmethod
    def _read() -> Dict[str, float]:
        import resource
        ru = resource.getrusage(resource.RUSAGE_SELF)
        out = {"involuntary_switches": float(ru.ru_nivcsw),
               "cpu_s": ru.ru_utime + ru.ru_stime}
        try:
            with open("/sys/fs/cgroup/cpu.stat") as f:
                for line in f:
                    k, v = line.split()
                    if k in ("nr_throttled", "throttled_usec"):
                        out["cgroup_" + k] = float(v)
        except OSError:
            pass
        try:
            with open("/proc/pressure/cpu") as f:
                some = f.readline().split()
            out["cpu_pressure_some_us"] = float(some[-1].split("=")[1])
        except (OSError, IndexError, ValueError):
            pass
        return out

    def close(self) -> str:
        self._stop.set()
        self._thread.join()
        after = self._read()
        moved = {k: round(after[k] - v, 3) for k, v in self._before.items()
                 if k in after}
        worst = sorted(self.stalls, key=lambda s: -s[1])[:5]
        return (f"host stalls over {self.STALL_S * 1e3:.0f} ms: "
                f"{len(self.stalls)} (worst (s into the window, ms): "
                f"{worst}) | {moved}")


def _key(name: str, labels: dict) -> str:
    return name + "{" + ",".join(f"{k}={v}" for k, v in
                                 sorted(labels.items())) + "}"


def snapshot(registry) -> Dict[str, tuple]:
    """Counters as (value,), histograms as (count, sum)."""
    out = {}
    for name, labels, kind, m in registry.items():
        if kind == "counter":
            out[_key(name, labels)] = (m.value,)
        elif kind == "histogram":
            st = m.state()
            out[_key(name, labels)] = (st.total, st.sum)
    return out


def delta(before: Dict[str, tuple], after: Dict[str, tuple]):
    return {k: tuple(a - b for a, b in zip(v, before.get(k, (0,) * len(v))))
            for k, v in after.items()}


def _stack(bags):
    """[(ids, vals), ...] -> [L, Qn] rows, -1 / 0 padded."""
    qn = max(max(b[0].size for b in bags), 1)
    ids = np.full((len(bags), qn), -1, np.int32)
    vals = np.zeros((len(bags), qn), np.float32)
    for l, (i, v) in enumerate(bags):
        ids[l, :i.size] = i
        vals[l, :v.size] = v
    return ids, vals


def build_store(path: str, corpus, config: dict):
    from repro.storage import FlashStore
    shutil.rmtree(path, ignore_errors=True)
    store = FlashStore.create(path, vocab_size=int(config["vocab_size"]))
    per = store.manifest["docs_per_segment"]
    for lo in range(0, corpus.n_docs, per):
        store.append_docs(corpus.docs(lo, min(corpus.n_docs, lo + per)))
    return store


def _written_at(path: str) -> float:
    """The newest modification time of the files under ``path``."""
    return max((os.path.getmtime(os.path.join(d, f))
                for d, _, files in os.walk(path) for f in files),
               default=0.0)


def open_store(path: str, corpus, config: dict):
    """Surface ``store``: one store, one ``FlashSearchSession``. Returns
    the session, the segments one scan reads and a note for the log."""
    from repro.storage import FlashSearchSession
    store = build_store(path, corpus, config)
    return (FlashSearchSession(store, search_config(config)), store.entries,
            "")


def open_cluster(path: str, corpus, config: dict):
    """Surface ``cluster``: ``n_shards`` x ``replicas`` stores written by
    ``build_sharded_store`` under ``policy``, served by one
    ``FlashClusterSession``. A scan reads one replica of every shard, so
    the segments are those of replica 0 of each. The note gives how long
    the document list took, each replica's write (shard-major, the order
    the program writes them; the first also holds the partitioning), read
    from the files' modification times, and each replica's device."""
    from repro.cluster import FlashClusterSession, build_sharded_store
    shutil.rmtree(path, ignore_errors=True)
    t0 = time.perf_counter()
    docs = corpus.docs(0, corpus.n_docs)
    t1, at = time.perf_counter(), time.time()
    store = build_sharded_store(
        path, docs=docs, n_shards=int(config["n_shards"]),
        replicas=int(config["replicas"]), policy=config["policy"],
        vocab_size=int(config["vocab_size"]),
        docs_per_segment=int(config["docs_per_segment"]))
    del docs
    writes = []
    for s, shard in enumerate(store.manifest["shards"]):
        for r, rel in enumerate(shard["replicas"]):
            done = _written_at(os.path.join(path, rel))
            writes.append((f"{s}.{r}", round(done - at, 3)))
            at = done
    entries = [e for s in range(store.n_shards)
               for e in store.store(s).entries]
    session = FlashClusterSession(store, search_config(config))
    placement = [(f"{s}.{r}", str(session.router.device_of(s, r)))
                 for s in range(store.n_shards)
                 for r in range(store.replicas)]
    return session, entries, (
        f"document list {t1 - t0:.3f}s | replica writes (shard.replica, s): "
        f"{writes} | placement: {placement}")


SURFACES = {"store": open_store, "cluster": open_cluster}


def search_config(config: dict):
    from repro.configs.paper_search import SearchConfig
    return SearchConfig(name=config["name"],
                        vocab_size=int(config["vocab_size"]),
                        nnz_pad=int(config["nnz_pad"]),
                        max_query_nnz=int(config["max_query_nnz"]),
                        top_k=int(config["top_k"]))


def slab_lookups(counts: Dict[str, tuple], surface: str) -> tuple:
    """The slab cache's hits and misses in ``counts`` (a snapshot or a
    delta) as the session of ``surface`` publishes them: a store session
    under ``surface=store``; a cluster's router, summed over the shards,
    under ``surface=cluster`` (its shard sessions publish none)."""
    return tuple(counts.get(f"cache_{k}_total{{surface={surface}}}",
                            (0,))[0] for k in ("hits", "misses"))


def warm_up(session, make, corpus, rng, lookups, max_passes: int = 8
            ) -> List[tuple]:
    """Compile every L bucket the coalescer can flush (1, 2, 4, 8), then
    run batches until the slab cache's hits and misses per batch repeat
    (``lookups()`` reads them); last, two bursts through ``submit`` warm
    the coalescer itself."""
    from repro.serve import Query
    seen = []

    def batch(L):
        docs = rng.integers(0, corpus.n_docs, L)
        before = lookups()
        session.search_typed(Query(*_stack([make(corpus, int(d))
                                            for d in docs])))
        seen.append((L,) + tuple(a - b for a, b in zip(lookups(), before)))

    for L in (1, 2, 4, 8):
        batch(L)
    for _ in range(max_passes):
        batch(8)
        if seen[-1][1:] == seen[-2][1:]:
            break
    for L in (8, 3):
        docs = rng.integers(0, corpus.n_docs, L)
        futs = [session.submit(Query(*make(corpus, int(d)))) for d in docs]
        for f in futs:
            f.result(timeout=600)
    return seen


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


class Setup:
    """Everything a window runs against: the corpus, the store and the open
    session, warmed to steady state. Built once per process."""

    def __init__(self, cell: spec.Cell, seed: int, *, root: str = spec.ROOT,
                 require_tpu: bool = True, compile_cache: bool = True):
        self.info = device_info(cell.chips, require_tpu)
        self.cache_dir = None
        if compile_cache:
            from repro.compile_cache import enable_compile_cache
            self.cache_dir = enable_compile_cache()
        self.compiles = CompileCounter()
        from repro.obs import default_obs
        self.registry = default_obs().registry
        self.at_start = snapshot(self.registry)
        self.cell, self.seed = cell, seed
        config = cell.config
        self.surface = config.get("surface", "store")
        self.gen = spec.part("generators", config["generator"])
        # the query maker the mix names, from the configuration's generator
        self.make = getattr(self.gen, cell.traffic["queries"])
        self.ref = spec.part("references", config["reference"])
        self.peaks = peaks_for(self.info["kind"]) if require_tpu else None
        self.idle_bytes = chip_memory(cell.chips, "bytes_in_use")
        work = os.path.join(root, ".bench_work")
        os.makedirs(work, exist_ok=True)
        self.store_dir = os.path.join(work, "store")
        self.trace_dir = os.path.join(work, "trace")
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        t0 = time.perf_counter()
        self.corpus = self.gen.generate(config, seed)
        t1 = time.perf_counter()
        self.session, entries, note = SURFACES[self.surface](
            self.store_dir, self.corpus, config)
        t2 = time.perf_counter()
        self.seg_bytes = 4.0 * sum(e.n_items for e in entries) / len(entries)
        seen = warm_up(self.session, self.make, self.corpus,
                       np.random.default_rng([seed, 1]),
                       lambda: slab_lookups(snapshot(self.registry),
                                            self.surface))
        t3 = time.perf_counter()
        self.service = self.session.service()
        c = self.compiles
        say(f"[setup] {self.corpus.n_docs} docs, surface {self.surface}, "
            f"{len(entries)} segments a scan, "
            f"{self.seg_bytes:.1f} stream bytes per segment | generate "
            f"{t1 - t0:.3f}s store {t2 - t1:.3f}s warm-up {t3 - t2:.3f}s | "
            f"{note + ' | ' if note else ''}"
            f"warm-up batches (L, cache hits, misses): {seen} | programs "
            f"built {c.n} in {c.seconds:.3f}s, {c.loaded} of them loaded "
            f"from the persistent cache ({self.cache_dir})")

    def query(self, doc: int):
        from repro.serve import Query
        return Query(*self.make(self.corpus, int(doc)))

    def window(self, mix: dict, seconds: float, rng, *, trace: bool = False,
               t_start: Optional[float] = None) -> dict:
        """Drive ``mix`` for ``seconds`` and wait for every answer (at most
        ``DRAIN_S`` past the close). Returns the requests and the window's
        counters; ``setup_s`` is read from ``t_start`` as the window opens."""
        import jax
        session, service = self.session, self.service
        batch_of = lambda: service.stats.n_batches  # noqa: E731
        arrivals = spec.part("arrivals", mix["arrivals"])
        selector = spec.part("selectors", mix["selector"])
        n_docs = self.corpus.n_docs
        prepared = arrivals.prepare(
            mix, seconds, rng, lambda n: selector.pick(n_docs, n, rng, mix),
            self.query)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=opts)
        n_batches0 = service.stats.n_batches
        n_req0 = service.stats.n_requests
        before = snapshot(self.registry)
        compiles0 = self.compiles.n
        traces0 = session.compile_stats["n_traces"]
        gcs = GcPauses()
        win = jax.profiler.TraceAnnotation("bench.window")
        setup_s = (time.perf_counter() - t_start
                   if t_start is not None else None)
        w0 = time.perf_counter()
        t_end = w0 + seconds
        watch = HostWatch(w0)
        win.__enter__()
        reqs = arrivals.drive(prepared, session.submit, w0, t_end,
                              batch_of)
        time.sleep(max(0.0, t_end - time.perf_counter()))
        w1 = time.perf_counter()
        pending = service.pending_count
        win.__exit__(None, None, None)
        host = watch.close()
        gcs.close()
        after = snapshot(self.registry)
        n_batches = service.stats.n_batches - n_batches0
        n_req = service.stats.n_requests - n_req0
        compiled = self.compiles.n - compiles0
        retraced = session.compile_stats["n_traces"] - traces0
        if trace:
            jax.profiler.stop_trace()
        deadline = time.perf_counter() + DRAIN_S
        while (any(r.done is None for r in reqs)
               and time.perf_counter() < deadline):
            time.sleep(0.05)
        ok = [r for r in reqs if r.done is not None and r.error is None]
        late = np.array([(r.sent - r.due) * 1e3 for r in reqs] or [0.0])
        worst = [(round(float(late[i]), 1), round(reqs[i].due - w0, 2))
                 for i in np.argsort(-late)[:3]] if reqs else []
        sub = np.array([r.submit_s * 1e3 for r in reqs] or [0.0])
        in_window = [r for r in ok if r.done <= t_end]
        d = delta(before, after)
        occ = n_req / n_batches if n_batches else 0.0
        hits, misses = slab_lookups(d, self.surface)
        say(f"[window] {seconds:.3f}s measured ({w1 - w0:.3f}s), "
            f"{len(reqs)} attempted, {len(ok)} answered, {len(in_window)} "
            f"by the close, {pending} queued at the close | generator late "
            f"p50 {percentile(late, 50):.3f} ms p99 "
            f"{percentile(late, 99):.3f} ms max {float(late.max()):.3f} ms "
            f"(worst (ms late, s into the window): {worst}) | submit calls "
            f"p99 {percentile(sub, 99):.3f} ms max {float(sub.max()):.3f} ms "
            f"| {host} | full garbage "
            f"collections {gcs.full}, longest collection "
            f"{gcs.longest * 1e3:.1f} ms | "
            f"{n_batches} batches, mean occupancy {occ:.3f} | slab cache "
            f"hits {hits} misses {misses} | programs built in the window "
            f"{compiled}, scoring-program traces {retraced}")
        lat = [r.latency_ms for r in ok]
        if lat:
            say(f"[latency] p50 {percentile(lat, 50):.3f} ms p95 "
                f"{percentile(lat, 95):.3f} ms p99 {percentile(lat, 99):.3f}"
                f" ms max {max(lat):.3f} ms over {len(lat)} answered")
        return {"reqs": reqs, "ok": ok, "in_window": in_window,
                "t_end": t_end, "seconds": seconds, "setup_s": setup_s,
                "pending": pending, "batches": n_batches, "requests": n_req,
                "delta": d, "compiled": compiled}

    def close(self) -> Optional[int]:
        """Read the peak device memory of each chip the cell asks for,
        then free the session and the store. Returns the fullest chip's
        peak."""
        peaks = chip_memory(self.cell.chips, "peak_bytes_in_use")
        say(f"[memory] peak bytes a chip {peaks}, in use before set-up "
            f"{self.idle_bytes}")
        self.run_delta = delta(self.at_start, snapshot(self.registry))
        self.compiles.close()
        self.session.close()
        self.session = self.service = None
        shutil.rmtree(self.store_dir, ignore_errors=True)
        return max((p for p in peaks if p is not None), default=None)

    def check(self, w: dict) -> dict:
        """Compare a sample of the window's answers, drawn from the seed,
        with the plain reference: the longest query, one query of the
        largest batch, and the rest at random. Each number with its
        limit."""
        mix, config = self.cell.traffic, self.cell.config
        make, ref, corpus = self.make, self.ref, self.corpus
        ok, reqs = w["ok"], w["reqs"]
        t0 = time.perf_counter()
        crng = np.random.default_rng([self.seed, 3])
        n_check = min(int(mix["check_sample"]), len(ok))
        pick, sizes = set(), {}
        if ok:
            pick.add(max(range(len(ok)), key=lambda i: make(
                corpus, ok[i].doc)[0].size))
            for r in ok:
                sizes[r.batch] = sizes.get(r.batch, 0) + 1
            big = max(sizes, key=sizes.get)
            pick.add(next(i for i, r in enumerate(ok) if r.batch == big))
            for i in crng.permutation(len(ok)):
                if len(pick) >= n_check:
                    break
                pick.add(int(i))
        sample = [ok[i] for i in sorted(pick)]
        parts = []
        plain = ref.Reference(corpus.ids, corpus.vals,
                              int(config["vocab_size"])) if sample else None
        for lo in range(0, len(sample), 8):
            blk = sample[lo:lo + 8]
            cos = plain.cos(*_stack([make(corpus, r.doc) for r in blk]))
            parts.append(ref.judge(np.stack([r.doc_ids for r in blk]),
                                   np.stack([r.scores for r in blk]), cos,
                                   int(config["top_k"]),
                                   [r.doc for r in blk]))
        checks = {k: {"value": v, "limit": ref.LIMITS[k]}
                  for k, v in ref.merge_judgements(parts).items()}
        checks["unanswered"] = {"value": len(reqs) - len(ok), "limit": 0}
        checks["pairs_truncated"] = {
            "value": self.run_delta.get(
                f"pairs_truncated_total{{surface={self.surface}}}", (0,))[0],
            "limit": 0}
        say(f"[check] {len(sample)} answers against the reference in "
            f"{time.perf_counter() - t0:.3f}s, from batches of sizes "
            f"{sorted({sizes[r.batch] for r in sample})}")
        return checks


def end_to_end(cell: spec.Cell, w: dict) -> dict:
    """The cell's end-to-end metrics, each read by ``e2e/<name>.py`` from
    the window's record; a reader with nothing to read leaves its metric
    out."""
    rec = {"latencies_ms": [r.latency_ms for r in w["ok"]],
           "answered_by_close": len(w["in_window"]),
           "seconds": w["seconds"], "setup_s": w["setup_s"]}
    out = {}
    for m in cell.end_to_end:
        v = spec.part("e2e", m["name"]).read(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: str = spec.ROOT,
             require_tpu: bool = True, compile_cache: bool = True) -> dict:
    """One run; returns the result object (the caller prints it).
    ``require_tpu=False`` and ``compile_cache=False`` are for the CPU
    tests, which drive a run at a small size."""
    s = Setup(cell, seed, root=root, require_tpu=require_tpu,
              compile_cache=compile_cache)
    w = s.window(cell.traffic, seconds, np.random.default_rng([seed, 2]),
                 trace=trace, t_start=t_start)
    peak = s.close()
    checks = s.check(w)
    correct = bool(w["ok"]) and all(c["value"] <= c["limit"]
                                    for c in checks.values())
    device = dict(s.info, memory_peak_bytes=peak)
    out = {"correct": correct, "attempted": len(w["reqs"]),
           "failed": len(w["reqs"]) - len(w["ok"])}
    if not trace:
        metrics = end_to_end(cell, w)
    else:
        path = trace_reduce.find_xplane(s.trace_dir)
        red = trace_reduce.reduce_trace(path) if path else {}
        shutil.rmtree(s.trace_dir, ignore_errors=True)
        rec = {"batches": w["batches"], "requests": w["requests"],
               "delta": w["delta"], "surface": s.surface, "trace": red,
               "peaks": s.peaks, "segment_stream_bytes": s.seg_bytes,
               "seconds": seconds}
        metrics = {}
        for m in cell.per_layer:
            v = spec.part("metrics", m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red.get("busy_s") is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            out["breakdown"] = {"device_ops": red["device_ops"],
                                "idle_gaps": red["idle_gaps"]}
            say(f"[trace] programs {json.dumps(red['programs'])}")
    out["metrics"] = metrics
    out["device"] = device
    for k, c in checks.items():
        say(f"check {k} {c['value']!r} limit {c['limit']!r}")
    out["checks"] = checks
    return out
