"""Pallas sparse_match kernel vs pure-jnp oracle: shape/dtype sweeps +
property-based invariants (assignment deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, strategies as st

from repro.core import corpus as corpus_lib
from repro.kernels import ops, ref
from repro.kernels.sparse_match import sparse_match


def _mk(D, K, Qn, L, vocab, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    ids = np.full((D, K), -1, np.int32)
    vals = np.zeros((D, K), dtype)
    for d in range(D):
        k = rng.integers(1, K + 1)
        ids[d, :k] = np.sort(rng.choice(vocab, k, replace=False))
        vals[d, :k] = rng.integers(1, 20, k)
    qid = np.full((L, Qn), -1, np.int32)
    qval = np.zeros((L, Qn), np.float32)
    for l in range(L):
        q = rng.integers(1, Qn + 1)
        qid[l, :q] = np.sort(rng.choice(vocab, q, replace=False))
        qval[l, :q] = rng.integers(1, 20, q)
    mi, mv = ops.merge_queries(qid, qval)
    return ids, vals, mi, mv


SWEEP = [
    # (D, K, Qn, L, vocab, block_docs, block_query)
    (8, 8, 8, 1, 64, 8, 8),
    (16, 16, 32, 2, 256, 8, 16),
    (32, 8, 16, 3, 128, 16, 16),
    (64, 32, 64, 1, 1024, 32, 64),
    (128, 16, 24, 4, 512, 64, 32),
    (24, 8, 8, 2, 64, 8, 8),          # D not a multiple of the block
]


@pytest.mark.parametrize("case", SWEEP)
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_kernel_matches_oracle(case, dtype):
    D, K, Qn, L, vocab, bd, bq = case
    ids, vals, mi, mv = _mk(D, K, Qn, L, vocab,
                            seed=hash(case) % 2**31, dtype=np.float32)
    vals = vals.astype(np.float32 if dtype == np.int32 else dtype)
    got = ops.correlate(jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(mi),
                        jnp.asarray(mv), backend="pallas",
                        block_docs=bd, block_query=bq)
    want = ref.sparse_match_ref(jnp.asarray(ids), jnp.asarray(vals),
                                jnp.asarray(mi), jnp.asarray(mv), vocab)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_kernel_bf16_vals():
    ids, vals, mi, mv = _mk(32, 16, 32, 2, 256, seed=7)
    got = ops.correlate(jnp.asarray(ids), jnp.asarray(vals, jnp.bfloat16),
                        jnp.asarray(mi), jnp.asarray(mv), backend="pallas",
                        block_docs=16, block_query=16)
    want = ref.sparse_match_ref(jnp.asarray(ids), jnp.asarray(vals),
                                jnp.asarray(mi), jnp.asarray(mv), 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_sentinels_never_match():
    """Doc padding (-1) and query padding (-2) must contribute nothing."""
    ids = np.full((8, 8), -1, np.int32)
    vals = np.ones((8, 8), np.float32) * 100
    mi = np.full((8,), -2, np.int32)
    mv = np.ones((8, 1), np.float32) * 100
    out = ops.correlate(jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(mi),
                        jnp.asarray(mv), backend="pallas",
                        block_docs=8, block_query=8)
    assert np.all(np.asarray(out) == 0)


def test_cosine_self_similarity_is_one():
    c = corpus_lib.synthesize(64, 512, 12, 16, seed=3)
    qi, qv = corpus_lib.make_query(c, 5, 16)
    mi, mv = ops.merge_queries(qi[None], qv[None])
    corr = ops.correlate(jnp.asarray(c.ids), jnp.asarray(c.vals),
                         jnp.asarray(mi), jnp.asarray(mv), backend="pallas",
                         block_docs=16, block_query=16)
    qn = jnp.asarray([np.sqrt((qv ** 2).sum())])
    cos = ops.cosine_scores(corr, jnp.asarray(c.norms), qn)
    assert np.argmax(np.asarray(cos)[:, 0]) == 5
    np.testing.assert_allclose(np.asarray(cos)[5, 0], 1.0, rtol=1e-5)


@settings(max_examples=25, deadline=None)
@given(
    d=st.integers(2, 24), k=st.integers(2, 12), qn=st.integers(2, 16),
    l=st.integers(1, 3), seed=st.integers(0, 2**20),
)
def test_property_kernel_equals_oracle(d, k, qn, l, seed):
    ids, vals, mi, mv = _mk(d, k, qn, l, 128, seed=seed)
    got = ops.correlate(jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(mi),
                        jnp.asarray(mv), backend="pallas",
                        block_docs=8, block_query=8)
    want = ref.sparse_match_ref(jnp.asarray(ids), jnp.asarray(vals),
                                jnp.asarray(mi), jnp.asarray(mv), 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**20))
def test_property_query_batching_linear(seed):
    """Scoring L queries in one batched call == L separate calls (the
    paper's K*L parallelization is exact, not approximate)."""
    rng = np.random.default_rng(seed)
    ids, vals, _, _ = _mk(16, 8, 8, 1, 64, seed=seed)
    qid = np.full((3, 8), -1, np.int32)
    qval = np.zeros((3, 8), np.float32)
    for l in range(3):
        q = rng.integers(1, 9)
        qid[l, :q] = np.sort(rng.choice(64, q, replace=False))
        qval[l, :q] = rng.integers(1, 9, q)
    mi, mv = ops.merge_queries(qid, qval)
    batched = ops.correlate(jnp.asarray(ids), jnp.asarray(vals),
                            jnp.asarray(mi), jnp.asarray(mv),
                            backend="pallas", block_docs=8, block_query=8)
    for l in range(3):
        mi1, mv1 = ops.merge_queries(qid[l:l + 1], qval[l:l + 1])
        single = ops.correlate(jnp.asarray(ids), jnp.asarray(vals),
                               jnp.asarray(mi1), jnp.asarray(mv1),
                               backend="pallas", block_docs=8, block_query=8)
        np.testing.assert_allclose(np.asarray(batched[:, l]),
                                   np.asarray(single[:, 0]), rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("platform,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None), ("metal", None)])
def test_interpret_mode_by_platform(monkeypatch, platform, interpret):
    """Pallas backends interpret on cpu (where the suites run), compile
    on tpu, and refuse every other platform instead of falling back to
    the interpreter in silence."""
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError, match=platform):
            ops.interpret_mode()
    else:
        assert ops.interpret_mode() is interpret


def test_pallas_backend_raises_on_unknown_platform(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    ids, vals, mi, mv = _mk(40, 3, 5, 1, 64, seed=1)   # shapes unique here
    with pytest.raises(RuntimeError, match="gpu"):
        ops.correlate(jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(mi),
                      jnp.asarray(mv), backend="pallas", block_docs=8,
                      block_query=8)
