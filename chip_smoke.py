#!/usr/bin/env python3
"""Chip smoke test: the served search path, end to end, on a TPU.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # the sharded cluster tier, 4 chips

One chip. Builds a FlashStore of ``--docs`` synthetic documents at the
paper's widths (``configs.paper_search.baseline``: vocab 141,000, ~60
terms per doc, Zipf 1.1 word frequencies, ``nnz_pad`` 128, queries of up
to 2,048 terms, top-16) in 4,096-doc segments, then serves it through
the path a user calls — ``FlashSearchSession`` (planner/``execute_plan``
-> ``PatternSearchEngine``) and its ``submit`` coalescer — once with the
default ``jnp`` backend and once with ``pallas_fused``: a cold and a warm
self-query, 16 concurrent ``submit``s and one L = 3 batch (paper Table
2). Every answer is checked against a plain numpy float64 scan of the
host corpus: scores to fp32 rounding, ids wherever scores are not tied,
and every self-query ranks its own document first at cosine 1.0. Each
Pallas backend of ``kernels/ops.py`` also runs once through the engine
at an aligned size, against the same reference, and must compile to a
``tpu_custom_call`` (a kernel that was silently interpreted fails).

Four chips (``--chips 4``). Only the cluster tier and what it is
compared with: 4 shards x 2 replicas spread over the chips
(``ShardRouter.device_of``) must return bit-identical answers to a
single-store scan of the union corpus, and every chip's
``bytes_in_use`` must grow.

The last line of stdout is one JSON object: ``{"ok": ..., "device":
{"platform", "kind", "count"}}``. The exit code is 0 only when every
phase passed on a TPU. Off a TPU it stops at once with ``ok: false``;
``--allow-cpu`` runs the phases anyway (a CPU rehearsal at a small
``--docs``, Pallas in interpret mode) and still ends ``ok: false``.
The store is written under ``.smoke_store/`` in the checkout and
removed at exit.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import numpy as np  # noqa: E402

TOL = 1e-6                  # cosine agreement: fp32 rounding, a few ulp
KERNEL_DOCS = 4096          # the per-kernel check: one aligned segment
ONE_CHIP_DOCS = 1_000_000
FOUR_CHIP_DOCS = 200_000
STORE_DIR = os.path.join(HERE, ".smoke_store")


def say(msg: str):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# the plain reference: float64 numpy over the host corpus
# ---------------------------------------------------------------------------
def reference_cos(corpus, q_ids: np.ndarray, q_vals: np.ndarray,
                  vocab: int, chunk: int = 1 << 17) -> np.ndarray:
    """Cosine of every query row [L, Qn] against every corpus row ->
    [L, n_docs] float64 (-inf where a norm is zero)."""
    ids, vals = corpus.ids, corpus.vals
    norms = corpus.norms.astype(np.float64)
    out = np.empty((q_ids.shape[0], corpus.n_docs))
    for l in range(q_ids.shape[0]):
        keep = q_ids[l] >= 0
        dense = np.zeros(vocab + 1)
        np.add.at(dense, q_ids[l][keep], q_vals[l][keep].astype(np.float64))
        qn = np.sqrt((q_vals[l][keep].astype(np.float64) ** 2).sum())
        for lo in range(0, corpus.n_docs, chunk):
            rows = slice(lo, lo + chunk)
            g = dense[np.where(ids[rows] >= 0, ids[rows], vocab)]
            corr = (g * vals[rows]).sum(1)
            denom = norms[rows] * qn
            with np.errstate(divide="ignore", invalid="ignore"):
                out[l, rows] = np.where(denom > 0, corr / denom, -np.inf)
    return out


def check_rows(doc_ids, scores, ref: np.ndarray, k: int, self_docs=None):
    """Top-k rows [L, k] vs the reference cosines [L, N]: positional
    scores match the reference's k best, every returned id really has
    its score (so ids differ only inside a tie), ids are unique, and a
    self-query's own doc leads at cosine 1.0. Returns a list of
    problems (empty: agreement)."""
    doc_ids = np.atleast_2d(np.asarray(doc_ids))
    scores = np.atleast_2d(np.asarray(scores, np.float64))
    bad = []
    for l in range(ref.shape[0]):
        want = -np.sort(-ref[l])[:k]
        ids, sc = doc_ids[l], scores[l]
        if not np.allclose(sc, want, rtol=0, atol=TOL):
            bad.append(f"row {l}: scores {sc[:4]}.. != ref {want[:4]}..")
            continue
        real = ids[ids >= 0]
        if real.size != np.unique(real).size:
            bad.append(f"row {l}: duplicate ids {ids}")
        if not np.allclose(ref[l][real], sc[ids >= 0], rtol=0, atol=TOL):
            bad.append(f"row {l}: an id does not have its score")
        if self_docs is not None:
            top = ids[np.abs(sc - sc[0]) <= TOL]
            if self_docs[l] not in top or abs(sc[0] - 1.0) > TOL:
                bad.append(f"row {l}: self-query {self_docs[l]} got "
                           f"{ids[0]} at {sc[0]!r}")
    return bad


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
class Run:
    def __init__(self):
        self.failed = []

    def phase(self, name, fn, *a, **kw):
        t0 = time.perf_counter()
        try:
            out = fn(*a, **kw)
        except Exception as e:           # every phase runs; any fault fails
            traceback.print_exc(file=sys.stderr)
            say(f"[{name}] FAILED after {time.perf_counter() - t0:.3f}s: "
                f"{type(e).__name__}: {str(e)[:500]}")
            self.failed.append(name)
            return None
        return out

    def expect(self, name, problems):
        for p in problems[:8]:
            say(f"[{name}]   mismatch: {p}")
        if problems:
            self.failed.append(name)
        return not problems


def queries(corpus, cfg, idxs):
    from repro.core import corpus as corpus_lib
    from repro.serve import Query
    qs = [corpus_lib.make_query(corpus, int(i), cfg.max_query_nnz)
          for i in idxs]
    return Query(np.stack([q[0] for q in qs]), np.stack([q[1] for q in qs]))


def kernel_checks(run, cfg, seed):
    """Each Pallas backend once through the engine at an aligned size
    (one 4096-doc slab), against the reference and jnp; its program must
    hold a tpu_custom_call."""
    from repro.core import corpus as corpus_lib
    from repro.core.engine import PatternSearchEngine
    from repro.distributed.meshctx import single_device_ctx
    corpus = corpus_lib.synthesize(KERNEL_DOCS, cfg.vocab_size,
                                   cfg.avg_nnz_per_doc, cfg.nnz_pad,
                                   seed=seed + 1)
    batches = {1: [5], 3: [17, 2000, KERNEL_DOCS - 1]}
    qs = {L: queries(corpus, cfg, d) for L, d in batches.items()}
    refs = {L: reference_cos(corpus, *q.rows(), cfg.vocab_size)
            for L, q in qs.items()}
    ctx = single_device_ctx()
    base = PatternSearchEngine(corpus, cfg, ctx, backend="jnp")
    want = {L: base.search(q) for L, q in qs.items()}
    for backend in ("pallas", "pallas_packed", "pallas_fused"):
        def one():
            eng = PatternSearchEngine(corpus, cfg, ctx, backend=backend)
            text = eng.lower(*qs[1].rows()).compile().as_text()
            kernel = "tpu_custom_call" in text
            notes = []
            for L, q in qs.items():
                t0 = time.perf_counter()
                got = eng.search(q)
                ms = (time.perf_counter() - t0) * 1e3
                bad = check_rows(got.doc_ids, got.scores, refs[L],
                                 cfg.top_k, self_docs=batches[L])
                same = int((got.doc_ids == want[L].doc_ids).sum())
                notes.append(f"L={L} agree={not bad} ids_equal_jnp="
                             f"{same}/{got.doc_ids.size} first_ms={ms:.3f}")
                run.expect(f"kernel {backend} L={L}", bad)
            say(f"[kernel] {backend}: tpu_custom_call={kernel} "
                + " ".join(notes))
            if not kernel:
                raise RuntimeError(f"{backend}: no tpu_custom_call in its "
                                   "compiled program (interpreted?)")
        run.phase(f"kernel {backend}", one)


def build_store(cfg, n_docs, seed):
    from repro.core import corpus as corpus_lib
    from repro.storage import FlashStore
    t0 = time.perf_counter()
    corpus = corpus_lib.synthesize(n_docs, cfg.vocab_size,
                                   cfg.avg_nnz_per_doc, cfg.nnz_pad,
                                   seed=seed)
    t1 = time.perf_counter()
    store = FlashStore.create(os.path.join(STORE_DIR, "store"),
                              vocab_size=cfg.vocab_size)
    store.append_corpus(corpus)
    t2 = time.perf_counter()
    st = store.stats()
    say(f"[build] {st.n_docs} docs, {st.n_segments} segments, "
        f"{st.n_items} stream words, {st.n_bytes / 2**20:.1f} MiB on disk | "
        f"synthesize {t1 - t0:.3f}s, append {t2 - t1:.3f}s, "
        f"store build {t2 - t0:.3f}s")
    return corpus, store


def serve_backend(run, cfg, corpus, store, backend, seed):
    """The main path for one backend: compile, cold, warm, 16 concurrent
    submits, one L = 3 batch — each checked against the reference."""
    from repro.serve import Query
    from repro.storage import FlashSearchSession
    tag = f"serve {backend}"
    rng = np.random.default_rng(seed + 7)
    n = corpus.n_docs
    sess = FlashSearchSession(store, cfg, backend=backend)
    try:
        # compile every L bucket the traffic reaches (the coalescer
        # flushes batches of 1..8) on one store-shaped slab
        slab = corpus.slice_rows(0, min(n, store.max_segment_docs))
        t_all = time.perf_counter()
        per = []
        for L in (1, 2, 4, 8):
            q = queries(corpus, cfg, rng.integers(0, n, L))
            t0 = time.perf_counter()
            sess.engine.search_streaming(*q.rows(), [slab])
            per.append(f"L{L}={time.perf_counter() - t0:.3f}s")
        say(f"[{tag}] compile {time.perf_counter() - t_all:.3f}s "
            f"({' '.join(per)}; {sess.compile_stats['n_traces']} programs)")

        def timed_search(label, q, self_docs):
            t0 = time.perf_counter()
            res = sess.search(q)
            ms = (time.perf_counter() - t0) * 1e3
            st = sess.last_stats
            ref = reference_cos(corpus, *q.rows(), cfg.vocab_size)
            ok = run.expect(f"{tag} {label}", check_rows(
                res.doc_ids, res.scores, ref, cfg.top_k, self_docs))
            say(f"[{tag}] {label}: {ms:.3f} ms, docs scored "
                f"{st.docs_scored}, segments {st.segments_scored}/"
                f"{st.segments_total}, slab cache hits {st.cache_hits} "
                f"misses {st.cache_misses} evictions {st.cache_evictions},"
                f" reference agree={ok}")
            return res

        d0 = int(rng.integers(0, n))
        q0 = queries(corpus, cfg, [d0])
        out = {"cold": timed_search("cold query (L=1)", q0, [d0])}
        out["warm"] = timed_search("warm query (L=1)", q0, [d0])

        docs16 = [int(x) for x in rng.integers(0, n, 16)]
        q16 = queries(corpus, cfg, docs16)
        q_ids, q_vals = q16.rows()
        results = [None] * 16
        errors = []
        barrier = threading.Barrier(16)

        def client(i):
            try:
                barrier.wait()
                results[i] = sess.submit(Query(q_ids[i], q_vals[i])).result(
                    timeout=900)
            except Exception as e:       # surfaced below, never a hang
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(16)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0]
        ids = np.stack([r.doc_ids for r in results])
        sc = np.stack([r.scores for r in results])
        ref = reference_cos(corpus, q_ids, q_vals, cfg.vocab_size)
        ok = run.expect(f"{tag} submit x16",
                        check_rows(ids, sc, ref, cfg.top_k, docs16))
        say(f"[{tag}] 16 concurrent submits: {wall:.3f}s wall, "
            f"{16 / wall:.3f} queries/s, reference agree={ok}")
        out["submit"] = (ids, sc)

        docs3 = [int(x) for x in rng.integers(0, n, 3)]
        out["batch3"] = timed_search("batch L=3", queries(corpus, cfg, docs3),
                                     docs3)
        cs = sess.cache_stats
        if cs is not None:
            say(f"[{tag}] slab cache lifetime: hits {cs.hits} misses "
                f"{cs.misses} evictions {cs.evictions}, "
                f"{sess.slab_cache.nbytes / 2**20:.1f} MiB resident")
        return out
    finally:
        sess.close()


def one_chip(run, cfg, args):
    run.phase("kernels", kernel_checks, run, cfg, args.seed)
    built = run.phase("build", build_store, cfg, args.docs, args.seed)
    if built is None:
        return
    corpus, store = built
    outs = {}
    for backend in ("jnp", "pallas_fused"):
        outs[backend] = run.phase(f"serve {backend}", serve_backend, run,
                                  cfg, corpus, store, backend, args.seed)
    if all(outs.values()):
        a, b = outs["jnp"]["submit"], outs["pallas_fused"]["submit"]
        say(f"[compare] pallas_fused vs jnp, 16 submits: ids equal "
            f"{int((a[0] == b[0]).sum())}/{a[0].size}, max |score diff| "
            f"{float(np.max(np.abs(a[1] - b[1]))):.3e}")


def bytes_in_use(device):
    """The device's allocated bytes (None where the backend reports no
    memory stats, as the CPU does)."""
    return (device.memory_stats() or {}).get("bytes_in_use")


def four_chips(run, cfg, args, devices):
    """The cluster tier alone, against a single-store scan of the union
    corpus on the first chip."""
    from repro.cluster import FlashClusterSession, build_sharded_store
    from repro.core import corpus as corpus_lib
    from repro.serve import Query
    from repro.storage import FlashSearchSession, FlashStore
    from repro.storage.store import _corpus_docs
    t0 = time.perf_counter()
    corpus = corpus_lib.synthesize(args.docs, cfg.vocab_size,
                                   cfg.avg_nnz_per_doc, cfg.nnz_pad,
                                   seed=args.seed)
    docs = _corpus_docs(corpus)
    union = FlashStore.create(os.path.join(STORE_DIR, "union"),
                              vocab_size=cfg.vocab_size)
    union.append_docs(docs)
    cluster = build_sharded_store(os.path.join(STORE_DIR, "cluster"), docs,
                                  n_shards=4, replicas=2, policy="range",
                                  vocab_size=cfg.vocab_size)
    del docs
    say(f"[build] {corpus.n_docs} docs: union store + 4 shards x 2 "
        f"replicas in {time.perf_counter() - t0:.3f}s")
    rng = np.random.default_rng(args.seed + 11)
    singles = [int(x) for x in rng.integers(0, corpus.n_docs, 8)]
    batch3 = [int(x) for x in rng.integers(0, corpus.n_docs, 3)]
    qs = [queries(corpus, cfg, [d]) for d in singles]
    qs.append(queries(corpus, cfg, batch3))
    for backend in ("jnp", "pallas_fused"):
        def one():
            tag = f"cluster {backend}"
            before = [bytes_in_use(d) for d in devices]
            with FlashClusterSession(cluster, cfg, backend=backend) as sess:
                r = sess.router
                placed = " ".join(
                    f"s{s}r{p}->{r.device_of(s, p).id}"
                    for s in range(4) for p in range(2))
                say(f"[{tag}] placement {placed}")
                t0 = time.perf_counter()
                got = [sess.search(q) for q in qs]
                ms = (time.perf_counter() - t0) * 1e3
                futs = [sess.submit(Query(*(x[0] for x in q.rows())))
                        for q in qs[:8]]
                sub = [f.result(timeout=900) for f in futs]
                after = [bytes_in_use(d) for d in devices]
            with FlashSearchSession(union, cfg, backend=backend) as one:
                want = [one.search(q) for q in qs]
            same = all(np.array_equal(g.doc_ids, w.doc_ids)
                       and np.array_equal(g.scores, w.scores)
                       for g, w in zip(got, want))
            same_sub = all(
                np.array_equal(s.doc_ids, w.doc_ids[0])
                and np.array_equal(s.scores, w.scores[0])
                for s, w in zip(sub, want))
            ref_bad = []
            for q, g, d in zip(qs, got, [[x] for x in singles] + [batch3]):
                ref = reference_cos(corpus, *q.rows(), cfg.vocab_size)
                ref_bad += check_rows(g.doc_ids, g.scores, ref, cfg.top_k, d)
            grew = [None if a is None or b is None else a - b
                    for a, b in zip(after, before)]
            say(f"[{tag}] {len(qs)} searches in {ms:.3f} ms; bit-identical "
                f"to the union store: searches={same} submits={same_sub}; "
                f"reference agree={not ref_bad}; bytes_in_use growth per "
                f"chip {grew}")
            run.expect(tag, ref_bad
                       + ([] if same and same_sub else ["not bit-identical"])
                       + [f"chip {i} bytes_in_use did not grow ({g})"
                          for i, g in enumerate(grew) if not g or g <= 0])
        run.phase(f"cluster {backend}", one)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip; 4: only the "
                         "sharded cluster tier over four chips")
    ap.add_argument("--docs", type=int, default=None,
                    help=f"corpus size (default {ONE_CHIP_DOCS:,} on one "
                         f"chip, {FOUR_CHIP_DOCS:,} on four)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run the phases off a TPU too (a rehearsal; "
                         "still ends ok: false)")
    args = ap.parse_args(argv)
    if args.docs is None:
        args.docs = ONE_CHIP_DOCS if args.chips == 1 else FOUR_CHIP_DOCS

    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.configs.paper_search import baseline
    cache = enable_compile_cache()
    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    say(f"[device] {device} jax {jax.__version__}, compile cache {cache}")
    run = Run()
    if d0.platform != "tpu":
        run.failed.append("no TPU")
        say(f"[device] no TPU: platform is {d0.platform!r}")
    if len(devices) < args.chips:
        run.failed.append("chips")
        say(f"[device] --chips {args.chips} needs {args.chips} devices, "
            f"found {len(devices)}")
    if (not run.failed) or (args.allow_cpu and "chips" not in run.failed):
        cfg = baseline()
        shutil.rmtree(STORE_DIR, ignore_errors=True)
        try:
            if args.chips == 1:
                one_chip(run, cfg, args)
            else:
                four_chips(run, cfg, args, devices)
        finally:
            shutil.rmtree(STORE_DIR, ignore_errors=True)
    ok = not run.failed
    if not ok:
        say(f"[result] FAILED: {', '.join(run.failed)}")
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)       # skip interpreter teardown of the runtime
