"""PatternSearchEngine — the paper's in-storage accelerator as a sharded
TPU service (DESIGN.md §2).

The corpus lives sharded across chip HBM: doc rows over the (pod, data)
mesh axes — the paper's K corpus partitions — and the merged query batch's
L value-columns over the ``model`` axis — the paper's L. Each device is one
"accelerator kernel": it scores its corpus shard against its query slice
(Pallas kernel on TPU, gather path on CPU), takes a local top-k, and a
hierarchical reduction returns the global winners. Only queries (in) and
top-k (out) cross the interconnect; the corpus never moves.

Streaming mode handles corpora larger than aggregate HBM: a pass scores
a query batch against a sequence of fixed-size device slabs in three
steps. ``prepare`` builds and uploads the merged query once; ``dispatch``
starts the program on each slab as it arrives, without reading its
result, so the device runs slab after slab while the host dispatches
the next (and the storage prefetcher, DESIGN.md §3, uploads the one
after); ``collect`` then brings every slab's top-k back in one copy, and
the top-k is merged across slabs on the host (DESIGN.md §4.1).

Serving mode (DESIGN.md §7) feeds ``search`` micro-batches of varying L
from the SearchService coalescer. To keep variable L cheap, query shapes
are *bucketed*: L pads to the next power-of-two multiple of the model
axis, and the merged id stream pads to a capacity proportional to that L
bucket — so a session that serves batches of any size up to ``max_batch``
compiles at most ``log2(max_batch) + 1`` programs instead of one per
distinct shape. ``compile_stats`` reports the traces actually taken.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Iterable, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from repro.distributed.compat import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.paper_search import SearchConfig
from repro.core import topk as topk_lib
from repro.core.corpus import Corpus
from repro.core.stream_format import VAL_MASK
from repro.distributed.meshctx import MeshCtx
from repro.kernels import fused as kfused
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.fused import PackedSlab
from repro.kernels.sparse_match_packed import pack as pack_ell
from repro.kernels.tiling import FixedTiling, TilingStrategy
from repro.obs import NULL_SPAN, default_obs, stage


@dataclasses.dataclass
class SearchResult:
    doc_ids: np.ndarray   # [L, k] int64 (-1 for no result)
    scores: np.ndarray    # [L, k] cosine


class DeviceSlab(NamedTuple):
    """A corpus slab already uploaded and sharded over the mesh — the unit
    the streaming path scores. Produced by ``put_slab`` (or by the storage
    prefetcher's background thread, DESIGN.md §3)."""
    ids: jax.Array        # [n, K] int32
    vals: jax.Array       # [n, K] float32
    norms: jax.Array      # [n] float32
    doc_ids: jax.Array    # [n] int32


SlabLike = Union[Corpus, DeviceSlab, PackedSlab]


class PreparedQuery(NamedTuple):
    """A query batch in the program's merged-stream form, padded to its
    compile bucket and uploaded: what every slab of a pass shares."""
    args: tuple         # (merged ids, merged vals, query norms) on device
    kwargs: dict        # the program's static arguments


def _require_integral_counts(vals: np.ndarray, backend: str):
    """The packed/fused backends carry values in the Fig. 8 12-bit count
    field — arbitrary floats would be silently clipped/rounded."""
    v = vals[vals != 0]
    if v.size and (not np.all(v == np.round(v)) or v.min() < 0
                   or v.max() > VAL_MASK):
        raise ValueError(
            f"backend={backend!r} needs integral counts in "
            f"[0, {VAL_MASK}] (Fig. 8 packing); use backend='jnp' or "
            "'pallas' for arbitrary float values")


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


class PatternSearchEngine:
    # set only on a view ``dispatch`` returned: its program's (vals, ids),
    # device arrays until ``collect`` brings them to the host
    _out: Optional[tuple] = None

    def __init__(self, corpus: Optional[Corpus], cfg: SearchConfig,
                 ctx: MeshCtx, backend: str = "jnp", obs=None,
                 tiling: Optional[TilingStrategy] = None):
        """``corpus=None`` builds a streaming-only engine (no resident
        corpus): callers must use ``search_streaming`` / ``put_slab``.
        ``obs`` (a ``repro.obs.Obs``) takes the compile-trace counter
        and each pass's ``slab_*`` stages; None uses the process
        default.
        ``tiling`` picks the fused backend's tile shapes (DESIGN.md
        §12.3); None uses ``FixedTiling`` at the config's shapes."""
        self.cfg = cfg
        self.ctx = ctx
        self.backend = backend
        self.obs = obs if obs is not None else default_obs()
        if corpus is None:
            corpus = Corpus.empty(cfg.nnz_pad)
        if corpus.ids.size and int(corpus.ids.max()) >= cfg.vocab_size:
            raise ValueError(
                f"corpus word ids reach {int(corpus.ids.max())} but "
                f"cfg.vocab_size={cfg.vocab_size}")
        rows = ctx.dp_size
        n = -(-corpus.n_docs // rows) * rows
        corpus = corpus.pad_docs_to(n)
        self.corpus = corpus
        self.tiling = tiling if tiling is not None else FixedTiling(
            cfg.block_docs, cfg.block_query)
        self.f_tiles: Optional[jax.Array] = None
        # the device a single-device mesh lives on: fused tiles are
        # placed there explicitly, never on JAX's default device
        self._device = (ctx.mesh.devices.flat[0] if ctx.mesh.size == 1
                        else None)
        if backend == "pallas_fused":
            # the fused kernel scores a single device's packed tiles;
            # sharded meshes keep the staged per-device kernels
            if ctx.mesh.size != 1:
                raise ValueError(
                    "backend='pallas_fused' is single-device (packed doc "
                    f"tiles are not mesh-sharded); mesh has {ctx.mesh.size}"
                    " devices — use 'pallas' or 'jnp' there")
            self._block_docs = self.tiling.doc_tile(
                nnz_pad=cfg.nnz_pad, n_docs=corpus.n_docs)
            # no host ELL staging, no per-array uploads: one packed
            # tile array is the whole resident corpus
            slab, _, self._resident_trunc = self._device_tiles(
                kfused.corpus_to_stream(corpus), corpus.n_docs)
            self.f_tiles = slab.tiles
            self.d_ids = self.d_vals = None
            self.d_norms = self.d_docids = None
        else:
            self._block_docs = cfg.block_docs
            spec = P(ctx.dp_axes, None)
            up_ids = corpus.ids
            if backend == "pallas_packed":
                # the packed kernel consumes Fig. 8 uint32 words, not
                # ELL int32 ids — uploading the raw ids scored every
                # document as all-zero (word 19-bit fields never match)
                _require_integral_counts(corpus.vals, backend)
                up_ids = pack_ell(corpus.ids, corpus.vals)
            self.d_ids = jax.device_put(up_ids,
                                        NamedSharding(ctx.mesh, spec))
            self.d_vals = jax.device_put(corpus.vals,
                                         NamedSharding(ctx.mesh, spec))
            self.d_norms = jax.device_put(
                corpus.norms, NamedSharding(ctx.mesh, P(ctx.dp_axes)))
            self.d_docids = jax.device_put(
                corpus.doc_ids.astype(np.int32),
                NamedSharding(ctx.mesh, P(ctx.dp_axes)))
        # compile-cache bookkeeping: one program per (L-bucket, Q-capacity,
        # n_docs) key; _trace_keys is appended at *trace* time inside the
        # jitted body, so it counts real recompiles, not call shapes
        self._trace_keys: list = []
        # registry handle resolved once: the jitted body's python side
        # effect stays one list append + one counter inc per real trace
        self._trace_counter = self.obs.registry.counter(
            "engine_compile_traces")
        self._search_fn = (self._build_fused() if backend == "pallas_fused"
                           else slab_program(cfg, ctx, backend,
                                             self._on_trace))

    def _on_trace(self, key):
        """Compile-cache bookkeeping, called once per trace of a jitted
        program (never on a jit cache hit)."""
        self._trace_keys.append(key)
        self._trace_counter.inc()

    def _build_fused(self):
        """The fused path's one dispatch: packed tiles + merged stream ->
        folded winners (kernels.fused, DESIGN.md §12). ``block_query``
        is static — the tiling strategy resolves it per L bucket, so it
        adds no program shapes beyond the bucket's."""
        cfg = self.cfg
        bd = self._block_docs
        on_trace = self._on_trace

        @functools.partial(jax.jit, static_argnames=("block_query",))
        def search(tiles, q_ids, q_vals, q_norms, block_query):
            on_trace((q_norms.shape[0], q_ids.shape[0], tiles.shape[0] * bd))
            return kops.fused_topk(tiles, q_ids, q_vals, q_norms,
                                   k=cfg.top_k, block_docs=bd,
                                   block_query=block_query)

        return search

    # ------------------------------------------------------------------
    def bucket_L(self, L: int) -> int:
        """The L compile bucket: next power of two of ceil(L / tp), times
        tp — so any batch size up to ``max_batch`` lands in one of
        ``log2(max_batch) + 1`` program shapes (DESIGN.md §7)."""
        tp = self.ctx.tp_size
        return _next_pow2(-(-L // tp)) * tp

    def bucket_Q(self, q_items: int, Lp: int) -> int:
        """Merged-stream capacity for an L bucket: ``Lp * block_query``
        items, doubling (power-of-two blocks) only when the batch's merged
        stream overflows it. Queries with nnz <= block_query therefore
        never add a program shape beyond their L bucket's."""
        cap = Lp * self.cfg.block_query
        return _next_pow2(-(-max(q_items, 1) // cap)) * cap

    def search(self, query, q_vals=None, *, options=None):
        """Public search surface. Typed form — ``search(Query(ids,
        vals), options=QueryOptions(...))`` — returns a
        ``SearchResponse``; positional ``search(q_ids, q_vals)``
        ``[L, Qn]`` arrays (pad < 0) remain as a deprecation shim
        returning the bare ``SearchResult`` (repro/serve/api.py). The
        resident engine is pure compute, so of the scheduling options
        only ``k`` applies here; deadlines/admission act in the serving
        layer above (DESIGN.md §7.3)."""
        # serve.api imported lazily: repro.serve imports this module
        # (SearchService stacks batches into engine calls), so a
        # module-level import here would be circular
        from repro.serve.api import (QueryStats, SearchResponse,
                                     coerce_request, truncate_k)
        q, options = coerce_request(query, q_vals, options,
                                    surface="PatternSearchEngine.search")
        res = self._search_arrays(*q.rows())
        if options is None:
            return res
        return SearchResponse(truncate_k(res, options.k), QueryStats(
            deadline_ms=options.deadline_ms, tenant=options.tenant))

    def search_typed(self, query, options=None, *, _span=None
                     ) -> SearchResult:
        """The raw typed surface the coalescing service dispatches to:
        no wrapping, no shim warning (see serve/search_service.py)."""
        return self._search_arrays(*query.rows())

    def prepare(self, q_ids: np.ndarray, q_vals: np.ndarray
                ) -> PreparedQuery:
        """q_ids/q_vals: [L, Qn] (pad < 0) -> the merged query that every
        slab of a pass is scored against, built and uploaded once. L is
        padded to its compile bucket (next power-of-two multiple of the
        model-axis size — the paper's L query batch, bucketed so the
        serving layer's variable batches reuse cached programs)."""
        with stage(self.obs.registry, NULL_SPAN, "slab_prep"):
            L_ = q_ids.shape[0]
            Lp = self.bucket_L(L_)
            if Lp != L_:
                pad_i = np.full((Lp - L_, q_ids.shape[1]), -1, q_ids.dtype)
                pad_v = np.zeros((Lp - L_, q_vals.shape[1]), q_vals.dtype)
                q_ids = np.concatenate([q_ids, pad_i])
                q_vals = np.concatenate([q_vals, pad_v])
            mi, mv = kops.merge_queries(q_ids, q_vals)
            # pad the merged stream to the bucket's fixed capacity
            pad = self.bucket_Q(mi.size, Lp)
            mi = np.pad(mi, (0, pad - mi.size), constant_values=-2)
            mv = np.pad(mv, ((0, pad - mv.shape[0]), (0, 0)))
            q_norms = np.sqrt((np.where(q_vals > 0, q_vals, 0) ** 2).sum(1))
            q_norms = np.maximum(q_norms, 1e-12).astype(np.float32)
            kwargs = ({"block_query": self.tiling.query_tile(Lp)}
                      if self.backend == "pallas_fused" else {})
            return PreparedQuery((jnp.asarray(mi), jnp.asarray(mv),
                                  jnp.asarray(q_norms)), kwargs)

    def _slab_args(self, slab: Optional[SlabLike]) -> tuple:
        """The program's corpus arguments: a device slab's arrays, or the
        resident corpus's when ``slab`` is None."""
        if slab is None:
            return ((self.f_tiles,) if self.backend == "pallas_fused" else
                    (self.d_ids, self.d_vals, self.d_norms, self.d_docids))
        if isinstance(slab, PackedSlab):
            return (slab.tiles,)
        return tuple(slab)

    def dispatch(self, q: PreparedQuery,
                 slab: Optional[SlabLike] = None) -> "PatternSearchEngine":
        """Start the program on ``slab`` (the resident corpus when None)
        and return at once: a view of this engine that holds the
        program's device (vals, ids), not yet read. The view holds
        neither the slab nor the query, so a streamed slab's device
        memory is freed once its program has run. A view, and not the
        bare arrays, so that ``_search_arrays`` stays the one place a
        slab's host top-k is made."""
        with stage(self.obs.registry, NULL_SPAN, "slab_dispatch"):
            view = object.__new__(PatternSearchEngine)
            view.__dict__.update(self.__dict__)
            view._out = self._search_fn(*self._slab_args(slab), *q.args,
                                        **q.kwargs)
            return view

    def collect(self, views, q_ids: np.ndarray,
                q_vals: np.ndarray) -> List[SearchResult]:
        """The host top-k of every view ``dispatch`` returned for the query
        batch ``q_ids``/``q_vals``: one ``jax.device_get`` starts every
        copy back before it waits on any, then each view's result is read
        through ``_search_arrays``."""
        with stage(self.obs.registry, NULL_SPAN, "slab_wait"):
            outs = jax.device_get([w._out for w in views])
        for w, out in zip(views, outs):
            w._out = out
        return [w._search_arrays(q_ids, q_vals) for w in views]

    def lower(self, q_ids: np.ndarray, q_vals: np.ndarray):
        """The ``jax.stages.Lowered`` program a ``[L, Qn]`` query batch
        runs against the resident corpus (``.compile().as_text()`` shows
        whether a Pallas kernel is in it as a ``tpu_custom_call``)."""
        q = self.prepare(q_ids, q_vals)
        return self._search_fn.lower(*self._slab_args(None), *q.args,
                                     **q.kwargs)

    def _search_arrays(self, q_ids: np.ndarray,
                       q_vals: np.ndarray) -> SearchResult:
        """q_ids/q_vals: [L, Qn] (pad < 0) -> the [L, k] top-k of one pass
        over the resident corpus: prepare, dispatch, collect (DESIGN.md
        §8.2). On a view ``dispatch`` returned, the program has already
        been started: its (vals, ids) are read (``collect`` has already
        brought them to the host) and cut to the batch's L rows."""
        L_ = q_ids.shape[0]
        if L_ == 0:
            # an empty batch has a well-defined answer, not a degenerate
            # program shape (bucket_L would still pad to tp, but the
            # [0, k] result needs no kernel at all)
            return self.empty_result(0)
        if self._out is None:
            view = self.dispatch(self.prepare(q_ids, q_vals))
            return self.collect([view], q_ids, q_vals)[0]
        v, i = self._out
        # ids come from local_topk / the fused epilogue already masked by
        # row validity; re-masking by isfinite here renamed real docs
        # with non-finite fp32 scores to -1 (see core.topk.local_topk)
        return SearchResult(doc_ids=np.asarray(i)[:L_].astype(np.int64),
                            scores=np.asarray(v)[:L_])

    # ------------------------------------------------------------------
    def search_streaming(self, q_ids, q_vals,
                         corpus_slabs: Iterable[SlabLike]) -> SearchResult:
        """Score a lazily-consumed sequence of corpus slabs larger than
        resident memory, merging top-k across slabs in their order
        (DESIGN.md §2).

        Each element may be a host ``Corpus`` (uploaded here) or an
        already-resident ``DeviceSlab`` (e.g. from the storage tier's
        background prefetcher, which overlaps disk read + decode + upload
        as well — DESIGN.md §3). The query is prepared once, each slab's
        program is dispatched as the slab arrives, and every top-k comes
        back in one copy. The iterable is never materialized, so
        store-backed iterators stream arbitrarily large corpora."""
        it = iter(corpus_slabs)
        first = next(it, None)
        if first is None:
            return self.empty_result(q_ids.shape[0])
        q = self.prepare(q_ids, q_vals)
        views = [self.dispatch(q, self._as_device(s))
                 for s in itertools.chain([first], it)]
        best: Optional[SearchResult] = None
        for r in self.collect(views, q_ids, q_vals):
            best = r if best is None else _merge_results(best, r,
                                                         self.cfg.top_k)
        return best

    @property
    def compile_stats(self) -> dict:
        """Programs actually traced so far: ``n_traces`` plus the (Lp, Qp,
        n_docs) key of each. The serving acceptance bound is
        ``n_traces <= log2(max_batch) + 1`` for a session whose queries
        stay within one Q capacity per L bucket."""
        return {"n_traces": len(self._trace_keys),
                "buckets": list(self._trace_keys)}

    def empty_result(self, n_queries: int) -> SearchResult:
        """The [L, k] no-result sentinel (id -1, score -inf)."""
        k = self.cfg.top_k
        return SearchResult(np.full((n_queries, k), -1, np.int64),
                            np.full((n_queries, k), -np.inf, np.float32))

    @property
    def slab_fmt(self) -> str:
        """The device-slab layout this engine scores — part of the slab
        cache key, so an ELL slab can never satisfy a fused lookup (the
        fused layout also depends on the doc-tile side)."""
        if self.backend == "pallas_fused":
            return f"fused:{self._block_docs}"
        return "ell"

    def put_slab(self, slab: Corpus) -> SlabLike:
        """Upload a host slab, sharded like the resident corpus. device_put
        is async: the transfer overlaps whatever is already enqueued.
        The fused backend re-encodes the corpus rows into packed doc
        tiles (``PackedSlab``); ELL backends upload the row arrays."""
        rows = self.ctx.dp_size
        slab = slab.pad_docs_to(-(-slab.n_docs // rows) * rows)
        if self.backend == "pallas_fused":
            return self._device_tiles(kfused.corpus_to_stream(slab),
                                      slab.n_docs)[0]
        ids = slab.ids
        if self.backend == "pallas_packed":
            _require_integral_counts(slab.vals, self.backend)
            ids = pack_ell(slab.ids, slab.vals)
        sh = NamedSharding(self.ctx.mesh, P(self.ctx.dp_axes, None))
        sh1 = NamedSharding(self.ctx.mesh, P(self.ctx.dp_axes))
        return DeviceSlab(
            jax.device_put(ids, sh), jax.device_put(slab.vals, sh),
            jax.device_put(slab.norms, sh1),
            jax.device_put(slab.doc_ids.astype(np.int32), sh1))

    def put_stream_slab(self, stream: np.ndarray, *,
                        pad_docs_to: Optional[int] = None
                        ) -> Tuple[PackedSlab, int, int]:
        """Fused-backend ingest straight from the Fig. 8 byte stream: a
        segment file becomes device tiles with *no* host ELL decode —
        the storage executor's fused load path (DESIGN.md §12.2).
        Returns ``(slab, n_docs, n_truncated)`` with the exact counts
        ``decode_to_ell`` would have reported."""
        if self.backend != "pallas_fused":
            raise ValueError("put_stream_slab is the fused-backend "
                             f"ingest; engine backend is {self.backend!r}")
        return self._device_tiles(stream, pad_docs_to)

    def _device_tiles(self, stream: np.ndarray, pad_docs_to: Optional[int]
                      ) -> Tuple[PackedSlab, int, int]:
        return kfused.device_tiles(
            stream, block_docs=self._block_docs, nnz_pad=self.cfg.nnz_pad,
            pad_docs_to=pad_docs_to, device=self._device)

    def _as_device(self, slab: Optional[SlabLike]) -> Optional[SlabLike]:
        if slab is None or isinstance(slab, (DeviceSlab, PackedSlab)):
            return slab
        return self.put_slab(slab)


def slab_program(cfg: SearchConfig, ctx: MeshCtx, backend: str,
                 on_trace=None):
    """The staged backends' jitted scoring program: a corpus slab sharded
    over ``ctx.mesh`` (ids, vals, norms, doc ids) + a merged query batch
    -> the global (vals [L, k], ids [L, k]). Each device scores its
    corpus shard against its query columns (``kops.correlate``), takes a
    local top-k, and a tree reduction over the corpus axes returns the
    winners. ``on_trace(key)`` runs once per trace (per compiled
    program) with the (L bucket, Q capacity, slab rows) key."""
    tp = ctx.tp_axis
    dp = ctx.dp_axes

    def local_score(ids, vals, norms, docids, q_ids, q_vals, q_norms):
        """Per-device: score local corpus shard x local query columns."""
        corr = kops.correlate(
            ids, vals, q_ids, q_vals, backend=backend,
            vocab_size=cfg.vocab_size, block_docs=cfg.block_docs,
            block_query=cfg.block_query)
        cos = kops.cosine_scores(corr, norms, q_norms)
        v, i = topk_lib.local_topk(cos, docids, cfg.top_k)
        # reduce across the corpus-shard (K) axes — paper's report path
        for ax in dp:
            v, i = topk_lib.tree_topk(v, i, cfg.top_k, ax)
        return v, i

    qcols_spec = P(None, tp)  # L value-columns over the model axis

    @jax.jit
    def search(ids, vals, norms, docids, q_ids, q_vals, q_norms):
        # python side effect: runs once per trace (i.e. per compiled
        # program), never on a jit cache hit
        if on_trace is not None:
            on_trace((q_norms.shape[0], q_ids.shape[0], ids.shape[0]))
        f = shard_map(
            local_score, mesh=ctx.mesh,
            in_specs=(P(dp, None), P(dp, None), P(dp), P(dp),
                      P(None), qcols_spec, P(tp)),
            out_specs=(P(tp, None), P(tp, None)),
            check_vma=False)
        return f(ids, vals, norms, docids, q_ids, q_vals, q_norms)

    return search


def _merge_results(a: SearchResult, b: SearchResult, k: int) -> SearchResult:
    """Merge two [L, k] candidate sets into the best k per row.

    Deterministic: descending score, stable within ties (a's candidates
    win over b's). Duplicate doc ids keep only their best-scoring entry,
    and no-result fillers (id < 0) never displace real candidates — any
    unfilled tail stays (-1, -inf).

    Vectorized (this runs once per slab on the serving hot path; the
    per-row Python loop it replaced was O(L*k*slabs) interpreter time —
    tests/test_merge_equivalence.py holds it to the loop's exact output)."""
    ids = np.concatenate([a.doc_ids, b.doc_ids], axis=1).astype(np.int64)
    sc = np.concatenate([a.scores, b.scores], axis=1).astype(np.float32)
    L, M = ids.shape
    # rank every candidate by descending score; stable, so a's candidates
    # win ties against b's and order within each input is preserved
    order = np.argsort(-sc, axis=1, kind="stable")
    rid = np.take_along_axis(ids, order, axis=1)
    rsc = np.take_along_axis(sc, order, axis=1)
    # keep a candidate iff it is valid (id >= 0) and the best-ranked
    # occurrence of its doc id: stable-sorting the ranked ids groups
    # duplicates while preserving rank order inside each group
    by_id = np.argsort(rid, axis=1, kind="stable")
    sid = np.take_along_axis(rid, by_id, axis=1)
    first = np.ones((L, M), bool)
    first[:, 1:] = sid[:, 1:] != sid[:, :-1]
    keep = np.zeros((L, M), bool)
    np.put_along_axis(keep, by_id, first & (sid >= 0), axis=1)
    # compact the keepers leftward in rank order into the [L, k] output
    pos = np.cumsum(keep, axis=1) - 1
    out_i = np.full((L, k), -1, np.int64)
    out_s = np.full((L, k), -np.inf, np.float32)
    rows, cols = np.nonzero(keep & (pos < k))
    out_i[rows, pos[rows, cols]] = rid[rows, cols]
    out_s[rows, pos[rows, cols]] = rsc[rows, cols]
    return SearchResult(out_i, out_s)
