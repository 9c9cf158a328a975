"""Query planning and the shared plan executor (DESIGN.md §4.1).

Every scoring surface — single store, live memtable snapshot, sharded
cluster, micro-batched service — used to hand-roll the same implicit
scan: walk the manifest, filter, read + decode each survivor from
disk. This module makes that plan *explicit* and single-sourced:

    Planner.plan(view, q_ids[, snap])  ->  QueryPlan
    execute_plan(engine, view, plan, q_ids, q_vals, ...) -> SearchResult

A ``view`` duck-types the segment surface (``entries`` / ``segment`` /
``release`` / ``cache_token`` — a FlashStore or an ingest Snapshot).
The plan records one verdict per manifest segment (skip via the §3.2
vocabulary filter, or scan), the slab source for each survivor
(``cache``: already decoded + device-resident in the §4.2 SlabCache;
``disk``: mmap read -> ELL decode -> device_put), the memtable tail
when the view is a live snapshot, and the padded program shape. Steps
are ordered cache-first so the prefetcher thread overlaps every disk
decode behind the free cache hits.

The executor is the only scan loop in the tree: it prepares the query
once, streams the plan's steps through the §3.3 Prefetcher, dispatches
each slab's program as the slab lands without waiting for it, brings
every slab's top-k back in one copy, and then folds the per-slab
candidates in *manifest rank order* (memtable last) so the scan-order
optimization can never change score-tie breaking relative to a cold
scan. The cache is consulted at *execution* time (a
planned hit that was evicted in between simply degrades to a disk load
— plans are advisory about sources, never about correctness), and one
``SearchStats`` is filled, including the cache hit/miss/eviction
counters.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from repro.core import stream_format
from repro.core.corpus import Corpus
from repro.core.engine import _merge_results, _next_pow2
from repro.obs import NULL_REGISTRY, NULL_SPAN, stage
from repro.storage import filter as filter_lib
from repro.storage import postings as postings_lib
from repro.storage.prefetch import Prefetcher
from repro.storage.slabcache import SlabCache, slab_key

SOURCE_CACHE = "cache"
SOURCE_DISK = "disk"

MODE_EXACT = "exact"
MODE_APPROX = "approx"
MODE_AUTO = "auto"
MODES = (MODE_EXACT, MODE_APPROX, MODE_AUTO)
# "auto" takes the approximate tier only past this many snapshot docs:
# below it the exhaustive scan is already a handful of slabs and the
# posting traversal would cost more than it saves
DEFAULT_APPROX_MIN_DOCS = 4096


@dataclasses.dataclass(frozen=True)
class PlanStep:
    """One surviving segment in scan order. ``rank`` is its position in
    *manifest* order among the scored segments — the executor folds
    results by rank, so the cache-first scan order can never change the
    merge's tie-breaking relative to a cold manifest-order scan."""
    name: str
    n_docs: int
    source: str            # SOURCE_CACHE | SOURCE_DISK (advisory)
    rank: int              # manifest-order fold position


@dataclasses.dataclass
class QueryPlan:
    """Explicit per-query scan plan over one snapshot view."""
    steps: List[PlanStep]              # cache-first scan order
    skipped: List[str]                 # filter-pruned segment names
    segments_total: int
    slab_docs: int                     # padded program shape (§3.3)
    nnz_pad: int
    cache_token: object                # store identity for cache keys
    generation: int = 0                # generation the view's segment
                                       # list belongs to (capture-time
                                       # for snapshots): admission is
                                       # skipped once the live one
                                       # moves (see execute_plan)
    memtable: Optional[Corpus] = None  # live tail (unpadded), or None
    memtable_trunc: int = 0
    memtable_pad: int = 0              # doubling pad target for the tail
    fmt: str = "ell"                   # engine slab layout (§12.2):
                                       # "ell" or "fused:<block_docs>"
    mode: str = MODE_EXACT             # resolved per query: exact scans
                                       # every surviving slab; approx
                                       # takes the posting-candidate +
                                       # re-rank path per disk segment
    candidates: int = 0                # top-C pool size per segment row
                                       # (approx mode only)
    filtered: bool = False             # vocab-filter pruning ran — the
                                       # executor may attribute zero-
                                       # score survivors to filter FPs

    def key_for(self, name: str):
        return slab_key(self.cache_token, name, self.nnz_pad,
                        self.slab_docs, self.fmt)

    @property
    def n_cached(self) -> int:
        return sum(s.source == SOURCE_CACHE for s in self.steps)

    @property
    def n_disk(self) -> int:
        return sum(s.source == SOURCE_DISK for s in self.steps)

    @property
    def is_empty(self) -> bool:
        return not self.steps and self.memtable is None


class Planner:
    """Turns (snapshot view, query batch) into a QueryPlan. Stateless
    beyond its knobs, so one instance serves every query of a session."""

    def __init__(self, *, nnz_pad: int, rows: int, use_filter: bool = True,
                 cache: Optional[SlabCache] = None, fmt: str = "ell",
                 mode: str = MODE_EXACT, candidates: int = 0,
                 approx_min_docs: int = DEFAULT_APPROX_MIN_DOCS):
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        self.nnz_pad = nnz_pad
        self.rows = rows                # mesh rows the slab pad aligns to
        self.use_filter = use_filter
        self.cache = cache
        self.fmt = fmt                  # the engine's slab_fmt: cache
                                        # verdicts must probe the same
                                        # keys the executor will load
        self.mode = mode                # session default; plan() takes a
                                        # per-query override
        self.candidates = candidates    # default top-C pool per segment
        self.approx_min_docs = approx_min_docs

    def plan(self, view, q_ids: np.ndarray, snap=None, *,
             mode: Optional[str] = None,
             candidates: Optional[int] = None) -> QueryPlan:
        """``snap`` carries the memtable when ``view`` is a live
        Snapshot (the session passes the same object twice). ``mode`` /
        ``candidates`` override the session defaults for this query
        (the QueryOptions knobs); ``auto`` resolves against the view's
        total doc count here, where the manifest is already in hand."""
        entries = view.entries
        rows = self.rows
        slab_docs = -(-max(view.max_segment_docs, 1) // rows) * rows
        token = view.cache_token
        eff_mode = self.mode if mode is None else mode
        if eff_mode not in MODES:
            raise ValueError(
                f"mode must be one of {MODES}, got {eff_mode!r}")
        eff_cand = self.candidates if candidates is None else int(candidates)
        if eff_mode == MODE_AUTO:
            total_docs = sum(e.n_docs for e in entries)
            eff_mode = (MODE_APPROX if total_docs >= self.approx_min_docs
                        else MODE_EXACT)
        if eff_mode == MODE_APPROX and eff_cand <= 0:
            raise ValueError("approx mode needs a positive candidate "
                             "pool size (candidates)")
        # the query's probe state (dedup + splitmix64 mixes) is computed
        # ONCE here and reused for every segment verdict below — the
        # per-segment cost is a bitmap gather or a Bloom modulo only
        probe = filter_lib.QueryProbe(q_ids) if self.use_filter else None
        do_filter = probe is not None and probe.ids.size > 0
        cached: List[PlanStep] = []
        disk: List[PlanStep] = []
        skipped: List[str] = []
        # one segment handle held at a time: a skipped segment costs its
        # footer + filter pages, a survivor is reopened lazily by the
        # executor's loader (snapshot entries stay openable — the
        # pipeline defers GC while the snapshot lives)
        rank = 0
        for entry in entries:
            if do_filter:
                seg = view.segment(entry.name)
                hit_any = seg.vocab_filter.contains_any_probe(probe)
                view.release(entry.name)
                if not hit_any:
                    skipped.append(entry.name)
                    continue
            key = slab_key(token, entry.name, self.nnz_pad, slab_docs,
                           self.fmt)
            step = PlanStep(
                entry.name, entry.n_docs,
                SOURCE_CACHE if self.cache is not None
                and self.cache.peek(key) else SOURCE_DISK, rank)
            rank += 1
            (cached if step.source == SOURCE_CACHE else disk).append(step)
        mem_corpus, mem_trunc = (snap.memtable_corpus(self.nnz_pad)
                                 if snap is not None else (None, 0))
        mem_pad = 0
        if mem_corpus is not None:
            # reuse the segment program shape whenever the memtable fits;
            # a memtable that outgrows it pads to the next *doubling* so
            # interleaved append/search compiles O(log) shapes (§3.4)
            mem_pad = slab_docs
            while mem_pad < mem_corpus.n_docs:
                mem_pad *= 2
        return QueryPlan(steps=cached + disk, skipped=skipped,
                         segments_total=len(entries), slab_docs=slab_docs,
                         nnz_pad=self.nnz_pad, cache_token=token,
                         generation=view.generation,
                         memtable=mem_corpus, memtable_trunc=mem_trunc,
                         memtable_pad=mem_pad, fmt=self.fmt,
                         mode=eff_mode, candidates=eff_cand,
                         filtered=do_filter)


def execute_plan(engine, view, plan: QueryPlan, q_ids: np.ndarray,
                 q_vals: np.ndarray, *, stats,
                 cache: Optional[SlabCache] = None,
                 prefetch_depth: int = 2, span=NULL_SPAN,
                 registry=None):
    """Run one QueryPlan: prefetch + score its slab stream, mutating
    ``stats`` (a SearchStats) as slabs resolve. The shared scan loop
    behind every scoring surface (DESIGN.md §4.1).

    Slabs are *dispatched* in the plan's cache-first scan order (so the
    prefetcher overlaps disk decodes behind the free hits) and the host
    never waits on the device between them: the engine's ``prepare``
    runs once before the loop, ``dispatch`` once per slab, and
    ``collect`` once after it. The per-slab candidates are then
    *folded* in manifest rank order, memtable last — exactly the cold
    scan's fold. ``_merge_results`` breaks score ties by fold position,
    so without the rank fold a partially warm query could flip tied
    candidates relative to a cold one.

    ``span``/``registry`` are the §8 observability hooks: per-segment
    child spans (slab source, decode/upload ms) hang off ``span`` when
    a trace sampled this query (``NULL_SPAN`` otherwise — allocation-
    free), and each stage (decode, upload, score — one slab's dispatch —
    and merge) runs under ``obs.stage``: a ``repro.<stage>`` profiler
    annotation plus its ``stage_ms`` histogram. Neither touches the
    numeric path: scan order, fold order, and every array op are
    identical with observability on, off, or disabled."""
    reg = NULL_REGISTRY if registry is None else registry
    # the Obs.disabled() floor (§8.1): with a null registry AND no trace
    # span every stage is the shared no-op and the prefetcher skips its
    # clock too, so the disabled path costs zero clock reads per slab
    timed = not (reg is NULL_REGISTRY and span is NULL_SPAN)

    def load(step: PlanStep):
        """Prefetch-thread body: cache lookup, else mmap read -> ELL
        decode -> device upload (+ admission). At most ``prefetch_depth``
        segments are open during the scoring stream."""
        lspan = span.child("load", segment=step.name, rank=step.rank)
        if cache is not None:
            hit = cache.get(plan.key_for(step.name))
            if hit is not None:
                stats.cache_hits += 1
                stats.docs_scored += hit.n_docs
                stats.pairs_truncated += hit.n_trunc
                lspan.end(source=SOURCE_CACHE)
                return step, hit.slab
            stats.cache_misses += 1
        # the stream is a zero-copy mmap view: its page-ins land in the
        # decode stage, with the decode itself
        with stage(reg, lspan, "decode", segment=step.name) as dec:
            seg = view.segment(step.name)
            # approximate tier (§15): posting traversal picks the top-C
            # candidate pool, then ONLY those rows are decoded (page-
            # level partial decode) and re-ranked exactly through the
            # session backend. The mini-slab is keyed by the query, so
            # it is never admitted to the slab cache; a pre-postings
            # segment file (postings is None) takes the exhaustive load.
            approx = plan.mode == MODE_APPROX and seg.postings is not None
            rows = None                 # host ELL rows, uploaded below
            if approx:
                pool = seg.postings.candidates(q_ids, q_vals,
                                               plan.candidates)
                rows = postings_lib.gather_rows(seg, pool, plan.nnz_pad)
            elif plan.fmt.startswith("fused"):
                # the fused kernel decodes the Fig. 8 words on-device:
                # the stream is only *tiled* here (a boundary-index
                # pass), never staged through host ELL arrays (§12.2),
                # and the tiles upload as they are built, so this load's
                # upload stage is empty. Tiling copies, so the segment
                # can be released right after.
                slab, n_docs, n_trunc = engine.put_stream_slab(
                    seg.stream(), pad_docs_to=plan.slab_docs)
            else:
                rows = stream_format.decode_to_ell(seg.stream(),
                                                   plan.nnz_pad)
            view.release(step.name)
        if rows is not None:
            doc_ids, ids, vals, norms, n_trunc = rows
            n_docs = int(doc_ids.size)
        stats.docs_scored += n_docs
        stats.pairs_truncated += n_trunc
        if approx:
            stats.approx_segments += 1
            stats.candidates += n_docs
            if n_docs == 0:
                lspan.end(source=SOURCE_DISK, approx=True, candidates=0)
                return step, None
        with stage(reg, lspan, "upload", segment=step.name) as up:
            if rows is not None:
                # an approx pool pads to a pow2 capped at the plan shape:
                # pools of any size compile O(log slab_docs) programs
                pad = (min(plan.slab_docs, _next_pow2(n_docs)) if approx
                       else plan.slab_docs)
                slab = engine.put_slab(
                    Corpus(doc_ids, ids, vals, norms).pad_docs_to(pad))
        times = dict(decode_ms=round(dec.seconds * 1e3, 3),
                     upload_ms=round(up.seconds * 1e3, 3))
        if approx:
            lspan.end(source=SOURCE_DISK, approx=True, candidates=n_docs,
                      **times)
            return step, slab
        # admission is gated on the LIVE store generation still matching
        # the generation the plan's segment list was captured at: once a
        # fold/compact has moved it, this segment may be a graveyard
        # file a snapshot is straggling over — admitting it would undo
        # the precise invalidation and squat in the budget. The guard
        # runs under the cache lock (see SlabCache.put) so it cannot
        # race the fold's invalidate.
        if cache is not None:
            stats.cache_evictions += cache.put(
                plan.key_for(step.name), slab,
                n_docs=n_docs, n_trunc=n_trunc,
                admit=lambda: view.live_generation == plan.generation)
        lspan.end(source=SOURCE_DISK, **times)
        return step, slab

    if plan.is_empty:
        span.set(empty=True)
        return engine.empty_result(q_ids.shape[0])
    # one slot per scored segment in manifest order, + the memtable: the
    # view ``engine.dispatch`` returned for it
    started: List[Optional[object]] = [None] * (len(plan.steps) + 1)
    mem_slab = None
    if plan.memtable is not None:
        # stats land BEFORE the prefetcher (and its loader thread)
        # exists: += on shared counters from two threads would race
        stats.memtable_docs = plan.memtable.n_docs
        stats.docs_scored += plan.memtable.n_docs
        stats.pairs_truncated += plan.memtable_trunc
        mem_slab = plan.memtable.pad_docs_to(plan.memtable_pad)
    pf = Prefetcher(plan.steps, load, depth=prefetch_depth,
                    timed=timed) \
        if plan.steps else None
    try:
        # the merged query is built and uploaded once for the whole pass
        q = engine.prepare(q_ids, q_vals)
        if mem_slab is not None:
            # dispatched while the prefetcher's worker loads the first slabs
            with stage(reg, span, "score", segment="memtable") as mst:
                started[-1] = engine.dispatch(q, engine.put_slab(mem_slab))
            mst.span.set(source="memtable", docs=stats.memtable_docs)
        if pf is not None:
            for step, slab in pf:
                if slab is None:        # empty approx candidate pool
                    continue
                # no wait for the device here: each slab's program is
                # queued behind the last, and the loop's reference to the
                # slab goes with the next one (JAX keeps a dispatched
                # input alive until its program has run)
                with stage(reg, span, "score", segment=step.name,
                           rank=step.rank):
                    started[step.rank] = engine.dispatch(q, slab)
    finally:
        if pf is not None:
            pf.close()
    if pf is not None and timed:
        wait_ms = pf.consumer_wait_s * 1e3
        reg.histogram("stage_ms", stage="prefetch_wait").observe(wait_ms)
        span.set(prefetch_wait_ms=round(wait_ms, 3))
    # every slab's top-k comes back in one copy
    views = [v for v in started if v is not None]
    results = iter(engine.collect(views, q_ids, q_vals))
    folds = [None if v is None else next(results) for v in started]
    if plan.filtered:
        # a segment the vocab filter let through whose every real score
        # is exactly 0 had no query-term overlap: a filter false positive
        # (exact for bitmaps, the Bloom FPR made flesh) — surfaced per
        # query so the fleet can see when a filter has gone saturated
        for r in folds[:-1]:
            if r is None:
                continue
            fin = r.scores[np.isfinite(r.scores)]
            if fin.size == 0 or not np.any(fin != 0):
                stats.filter_fp_segments += 1
    n_folds = sum(r is not None for r in folds)
    with stage(reg, span, "merge", folds=n_folds):
        best = None
        for r in folds:
            if r is None:
                continue
            best = r if best is None else _merge_results(best, r,
                                                         engine.cfg.top_k)
    return best
