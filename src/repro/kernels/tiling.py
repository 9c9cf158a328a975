"""Tiling strategies for the fused kernel (DESIGN.md §12.3).

The fused kernel's grid is (doc tiles, query tiles); its VMEM working
set per grid step is the packed doc tile (``block_docs * (1 + nnz_pad)``
uint32 words), the query tile (``block_query`` ids + ``block_query * L``
values), and the persistent correlation scratch (``block_docs * L``
fp32). The right shapes therefore depend on *corpus density* (nnz_pad:
denser docs want narrower doc tiles) and on the *L bucket* (wider
batches want narrower query tiles) — knobs the static SearchConfig
cannot see per query.

Strategy classes make the choice explicit and testable:

  - ``FixedTiling`` — always the config's ``block_docs``/``block_query``
    (the staged kernels' behavior; the default, so fused and staged
    share program-shape families);
  - ``AutoTiling`` — fits the working set to a VMEM budget, shrinking
    ``block_docs`` for dense corpora and ``block_query`` for wide L
    buckets, always in power-of-two steps so every chosen query tile
    divides the §7 merged-stream capacity.

The query-side choice is **memoized per L bucket**: for one strategy
instance, ``query_tile(Lp)`` is a pure function of the bucket, so the
autotuner can never add program shapes beyond the existing
``log2(max_batch) + 1`` compile-cache bound — one (Lp, Q-capacity)
bucket still maps to exactly one program (tests/test_tiling.py pins
this). The doc-side choice is made **once per corpus scope** (engine
construction), because it is part of the packed-slab layout and the
slab-cache key — re-tiling mid-session would orphan every cached slab.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

# the VMEM a v5e kernel's pipelined blocks may fill before Mosaic refuses
# it: a compile for a described v5e accepts double-buffered 7 MiB blocks
# and refuses 9 MiB ones ("ran out of memory in memory space vmem");
# tests/test_tpu_compile.py holds the kernels at paper widths to it
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024


def _pow2_floor(n: int) -> int:
    return 1 << max(int(n).bit_length() - 1, 0)


@dataclasses.dataclass(frozen=True)
class TileShape:
    """One resolved (doc, query) tile pair for a fused program."""
    block_docs: int
    block_query: int


class TilingStrategy:
    """Base: ``doc_tile`` once per corpus, ``query_tile`` per L bucket.

    Subclasses implement ``_doc_tile`` / ``_query_tile``; the base class
    owns the per-bucket memo table that the compile-cache invariant
    leans on (``bucket_shapes`` exposes it to tests and telemetry)."""

    def __init__(self):
        self._bucket_memo: Dict[int, int] = {}

    # -- corpus-scope choice (fixed for the engine's lifetime) ---------
    def doc_tile(self, *, nnz_pad: int, n_docs: int) -> int:
        bd = int(self._doc_tile(nnz_pad=nnz_pad, n_docs=max(n_docs, 1)))
        if bd < 1:
            raise ValueError(f"doc_tile must be >= 1, got {bd}")
        return bd

    # -- bucket-scope choice (memoized: one shape per L bucket) --------
    def query_tile(self, Lp: int) -> int:
        tq = self._bucket_memo.get(Lp)
        if tq is None:
            tq = int(self._query_tile(Lp=max(Lp, 1)))
            if tq < 1:
                raise ValueError(f"query_tile must be >= 1, got {tq}")
            self._bucket_memo[Lp] = tq
        return tq

    @property
    def bucket_shapes(self) -> Dict[int, int]:
        """L bucket -> chosen query tile, for every bucket seen so far.
        len(bucket_shapes) bounds the strategy's contribution to the
        program count: one entry, one (Lp, tq) family."""
        return dict(self._bucket_memo)

    def _doc_tile(self, *, nnz_pad: int, n_docs: int) -> int:
        raise NotImplementedError

    def _query_tile(self, *, Lp: int) -> int:
        raise NotImplementedError


class FixedTiling(TilingStrategy):
    """The config's static shapes, for every density and bucket — fused
    programs then live in the same shape families as the staged
    kernels'."""

    def __init__(self, block_docs: int, block_query: int):
        super().__init__()
        if block_docs < 1 or block_query < 1:
            raise ValueError("tile sides must be >= 1")
        self.block_docs = int(block_docs)
        self.block_query = int(block_query)

    def _doc_tile(self, *, nnz_pad: int, n_docs: int) -> int:
        return self.block_docs

    def _query_tile(self, *, Lp: int) -> int:
        return self.block_query


class AutoTiling(TilingStrategy):
    """Budget-driven shapes. Doc side: the largest power-of-two tile
    whose packed words + correlation scratch (at the reference L) fit
    half the budget — dense corpora (large nnz_pad) get narrower tiles.
    Query side: the largest power-of-two divisor of ``block_query``
    whose id+value tile fits the other half at the bucket's L — wide
    buckets get narrower query tiles (more grid steps, same VMEM).

    Both sides clamp to the config's static shapes as upper bounds, so
    AutoTiling only ever *shrinks* tiles — the merged-stream capacity
    (a multiple of ``block_query``) stays divisible by every choice.
    """

    def __init__(self, block_docs: int, block_query: int, *,
                 vmem_budget: int = DEFAULT_VMEM_BUDGET, ref_L: int = 8):
        super().__init__()
        if block_docs < 1 or block_query < 1:
            raise ValueError("tile sides must be >= 1")
        if vmem_budget < 4096:
            raise ValueError("vmem_budget unrealistically small")
        self.block_docs = int(block_docs)
        self.block_query = int(block_query)
        self.vmem_budget = int(vmem_budget)
        self.ref_L = int(ref_L)

    def _doc_tile(self, *, nnz_pad: int, n_docs: int) -> int:
        # per doc row: (1 + nnz_pad) packed words + ref_L fp32 scratch
        row_bytes = 4 * (1 + nnz_pad + self.ref_L)
        fit = _pow2_floor(max((self.vmem_budget // 2) // row_bytes, 1))
        return max(min(fit, self.block_docs, _pow2_floor(n_docs) * 2), 8)

    def _query_tile(self, *, Lp: int) -> int:
        # per query item: one id word + Lp fp32 value columns
        item_bytes = 4 * (1 + Lp)
        fit = _pow2_floor(max((self.vmem_budget // 2) // item_bytes, 1))
        tq = self.block_query
        while tq >= 16 and tq > fit:
            tq //= 2          # power-of-two descent: tq | block_query,
        return tq             # floored so it never halves below 8
