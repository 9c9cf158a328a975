"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the chip's own compiler (Mosaic for the Pallas kernels, XLA:TPU
for the rest) accepts or refuses them here, with no chip and no chip
time. Paper widths throughout (``configs.paper_search.baseline``: one
4096-doc segment slab, ``nnz_pad`` 128, ``block_docs`` 128, fused tile
capacity ``block_docs * (1 + nnz_pad)``, ``block_query`` 512).

The topology is described inside a module fixture — never at import —
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_search import baseline
from repro.core.engine import slab_program
from repro.distributed.meshctx import MeshCtx
from repro.kernels import fused, ops
from repro.kernels.sparse_match import SUBLANES, sparse_match
from repro.kernels.sparse_match_packed import sparse_match_packed

CFG = baseline()
SEG_DOCS = 4096                       # FlashStore's default segment


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to an enabled persistent
    # cache but cannot be read back without one: keep these out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _q_shapes(L, sharding):
    """Merged-stream operands of an L-query batch at its engine bucket:
    capacity Lp * block_query, values split into 3 * Lk bf16 rows."""
    Lp = 1 << (L - 1).bit_length()
    Qp = Lp * CFG.block_query
    lk = -(-Lp // SUBLANES) * SUBLANES
    return (jax.ShapeDtypeStruct((Qp, 1), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((3 * lk, Qp), jnp.bfloat16,
                                 sharding=sharding), lk, Qp)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("L", [1, 3])
def test_fused_kernel_compiles_at_paper_widths(one_chip, L):
    q_col, qv3, lk, _ = _q_shapes(L, one_chip)
    n_rows = fused.kernel_width(CFG.block_docs, CFG.nnz_pad) // fused.LANES
    tiles = jax.ShapeDtypeStruct((SEG_DOCS // CFG.block_docs, n_rows,
                                  fused.LANES), jnp.int32, sharding=one_chip)
    qn = jax.ShapeDtypeStruct((lk, 1), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda t, q, v, n: fused.fused_match_topk(
        t, q, v, n, block_docs=CFG.block_docs, kp=CFG.top_k,
        block_query=CFG.block_query))
    assert _has_kernel(fn.lower(tiles, q_col, qv3, qn).compile())


def test_fused_wrapper_compiles_with_fold(one_chip, monkeypatch):
    """The whole ``pallas_fused`` surface — query operands, kernel,
    per-tile fold — as the chip runs it (compiled, not interpreted)."""
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    n_rows = fused.kernel_width(CFG.block_docs, CFG.nnz_pad) // fused.LANES
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn = jax.jit(ops.fused_topk.__wrapped__,
                 static_argnames=("k", "block_docs", "block_query"))
    compiled = fn.lower(
        S((SEG_DOCS // CFG.block_docs, n_rows, fused.LANES), jnp.int32),
        S((4 * CFG.block_query,), jnp.int32),
        S((4 * CFG.block_query, 4), jnp.float32), S((4,), jnp.float32),
        k=CFG.top_k, block_docs=CFG.block_docs,
        block_query=CFG.block_query).compile()
    assert _has_kernel(compiled)


def test_sparse_match_compiles_at_paper_widths(one_chip):
    q_col, qv3, _, _ = _q_shapes(1, one_chip)
    ell = lambda dt: jax.ShapeDtypeStruct((SEG_DOCS, CFG.nnz_pad), dt,
                                          sharding=one_chip)
    fn = jax.jit(lambda a, b, q, v: sparse_match(
        a, b, q, v, block_docs=CFG.block_docs, block_query=CFG.block_query))
    compiled = fn.lower(ell(jnp.int32), ell(jnp.float32), q_col,
                        qv3).compile()
    assert _has_kernel(compiled)


def test_sparse_match_packed_compiles_at_paper_widths(one_chip):
    q_col, qv3, _, _ = _q_shapes(3, one_chip)
    words = jax.ShapeDtypeStruct((SEG_DOCS, CFG.nnz_pad), jnp.int32,
                                 sharding=one_chip)
    fn = jax.jit(lambda a, q, v: sparse_match_packed(
        a, q, v, block_docs=CFG.block_docs, block_query=CFG.block_query))
    assert _has_kernel(fn.lower(words, q_col, qv3).compile())


@pytest.mark.parametrize("L", [1, 3])
def test_engine_jnp_slab_program_compiles_at_paper_widths(topo, L):
    """The default backend's per-slab program (gather scoring, local
    top-k, shard_map over a one-chip mesh) for one 4096-doc segment."""
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    ctx = MeshCtx(mesh=mesh)
    Lp = 1 << (L - 1).bit_length()
    Qp = Lp * CFG.block_query
    sh = lambda *spec: NamedSharding(mesh, P(*spec))
    S = jax.ShapeDtypeStruct
    args = (S((SEG_DOCS, CFG.nnz_pad), jnp.int32, sharding=sh("data", None)),
            S((SEG_DOCS, CFG.nnz_pad), jnp.float32,
              sharding=sh("data", None)),
            S((SEG_DOCS,), jnp.float32, sharding=sh("data")),
            S((SEG_DOCS,), jnp.int32, sharding=sh("data")),
            S((Qp,), jnp.int32, sharding=sh()),
            S((Qp, Lp), jnp.float32, sharding=sh(None, "model")),
            S((Lp,), jnp.float32, sharding=sh("model")))
    compiled = slab_program(CFG, ctx, "jnp").lower(*args).compile()
    assert not _has_kernel(compiled)      # the gather path: plain XLA
    assert compiled.memory_analysis() is not None
