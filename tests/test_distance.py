"""Distance-kernel parity tests.

Mirrors the reference's distancer unit tests
(``hnsw/distancer/l2_test.go``, ``dot_product_test.go`` etc.): every metric is
cross-checked against a trusted numpy implementation.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from weaviate_tpu.ops import (
    pairwise_distance,
    flat_search,
    gather_distance,
    normalize,
    merge_topk,
    masked_topk,
)
from weaviate_tpu.ops.distance import MASK_DISTANCE


def np_dist(q, c, metric):
    if metric == "l2-squared":
        return ((q[:, None, :] - c[None, :, :]) ** 2).sum(-1)
    if metric == "dot":
        return -(q @ c.T)
    if metric == "cosine":
        qn = q / np.linalg.norm(q, axis=-1, keepdims=True)
        cn = c / np.linalg.norm(c, axis=-1, keepdims=True)
        return 1.0 - qn @ cn.T
    if metric == "manhattan":
        return np.abs(q[:, None, :] - c[None, :, :]).sum(-1)
    if metric == "hamming":
        return (q[:, None, :] != c[None, :, :]).sum(-1).astype(np.float32)
    raise ValueError(metric)


@pytest.mark.parametrize("metric", ["l2-squared", "dot", "cosine", "manhattan", "hamming"])
def test_pairwise_matches_numpy(rng, metric):
    q = rng.standard_normal((4, 32)).astype(np.float32)
    c = rng.standard_normal((50, 32)).astype(np.float32)
    if metric == "hamming":
        q = (q > 0).astype(np.float32)
        c = (c > 0).astype(np.float32)
    qj, cj = jnp.asarray(q), jnp.asarray(c)
    if metric == "cosine":
        qj, cj = normalize(qj), normalize(cj)
    got = np.asarray(pairwise_distance(qj, cj, metric))
    want = np_dist(q, c, metric)
    # l2 uses the ||q||^2 - 2qc + ||c||^2 expansion (single MXU matmul);
    # cancellation costs ~1e-3 relative vs the direct form — irrelevant for
    # ranking, rescoring uses gather_distance (direct form).
    tol = 5e-3 if metric == "l2-squared" else 1e-4
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_flat_search_exact(rng):
    q = rng.standard_normal((3, 16)).astype(np.float32)
    c = rng.standard_normal((200, 16)).astype(np.float32)
    d, ids = flat_search(jnp.asarray(q), jnp.asarray(c), k=10, metric="l2-squared")
    want = np_dist(q, c, "l2-squared")
    want_ids = np.argsort(want, axis=1)[:, :10]
    np.testing.assert_array_equal(np.sort(np.asarray(ids), 1), np.sort(want_ids, 1))
    np.testing.assert_allclose(
        np.asarray(d), np.sort(want, axis=1)[:, :10], rtol=1e-4, atol=1e-4
    )


def test_flat_search_chunked_matches_single_shot(rng):
    q = rng.standard_normal((2, 8)).astype(np.float32)
    c = rng.standard_normal((103, 8)).astype(np.float32)  # non-multiple tail
    d1, i1 = flat_search(jnp.asarray(q), jnp.asarray(c), k=7, metric="dot")
    d2, i2 = flat_search(jnp.asarray(q), jnp.asarray(c), k=7, metric="dot", chunk_size=32)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


# approximate selection (``lax.approx_min_k``) lowers to an exact sort on
# the CPU, so the same assertions hold under both
APPROX = pytest.mark.parametrize("approx_recall", [0.0, 0.95],
                                 ids=["exact", "approx"])


@APPROX
def test_flat_search_masks(rng, approx_recall):
    q = rng.standard_normal((1, 8)).astype(np.float32)
    c = rng.standard_normal((20, 8)).astype(np.float32)
    valid = np.ones(20, bool)
    valid[5:] = False  # only ids 0..4 are live
    allow = np.zeros(20, bool)
    allow[[1, 3, 7]] = True  # filter allows 1,3,7 — 7 is dead
    d, ids = flat_search(
        jnp.asarray(q),
        jnp.asarray(c),
        k=5,
        metric="l2-squared",
        valid_mask=jnp.asarray(valid),
        allow_mask=jnp.asarray(allow),
        approx_recall=approx_recall,
    )
    ids = np.asarray(ids)[0]
    assert set(ids[ids >= 0]) == {1, 3}
    assert (ids[2:] == -1).all()


@APPROX
def test_flat_search_fully_masked_returns_sentinels(rng, approx_recall):
    """No live row is allowed: every slot is the sentinel (id -1,
    MASK_DISTANCE), whole and chunked with a tail."""
    q = rng.standard_normal((3, 8)).astype(np.float32)
    c = rng.standard_normal((103, 8)).astype(np.float32)
    valid = np.ones(103, bool)
    valid[50:] = False
    allow = ~valid  # only dead rows allowed
    for chunk in (0, 32):
        d, ids = flat_search(
            jnp.asarray(q), jnp.asarray(c), k=5, metric="l2-squared",
            valid_mask=jnp.asarray(valid), allow_mask=jnp.asarray(allow),
            chunk_size=chunk, approx_recall=approx_recall)
        assert (np.asarray(ids) == -1).all()
        assert (np.asarray(d) == np.float32(MASK_DISTANCE)).all()


@pytest.mark.parametrize("metric", ["l2-squared", "cosine"])
@pytest.mark.parametrize("chunk", [0, 32, 40], ids=["whole", "chunks", "tail"])
@pytest.mark.parametrize("with_valid", [True, False], ids=["valid", "novalid"])
def test_flat_search_a_mask_a_row_equals_a_call_a_mask(rng, metric, chunk,
                                                       with_valid):
    """A [B, N] allow mask filters row i of the queries by row i of the
    mask: bit-equal to B scans of the same queries under the [N] rows,
    among them a row that allows fewer than k and an all-False (padded)
    one. 128 rows: 32 divides them, 40 leaves a tail of 8."""
    b, n, k = 5, 128, 6
    q = rng.standard_normal((b, 16)).astype(np.float32)
    c = rng.standard_normal((n, 16)).astype(np.float32)
    if metric == "cosine":
        q, c = np.asarray(normalize(q)), np.asarray(normalize(c))
    valid = np.ones(n, bool)
    valid[100:] = False
    masks = rng.random((b, n)) < 0.4
    masks[1] = False
    masks[1, [3, 60, 125]] = True        # two live rows allowed: under k
    masks[4] = False                     # a padded row
    common = dict(
        k=k, metric=metric, chunk_size=chunk, precision="bf16",
        valid_mask=jnp.asarray(valid) if with_valid else None,
        corpus_sqnorms=(jnp.asarray((c * c).sum(1))
                        if metric == "l2-squared" else None))
    d2, i2 = flat_search(jnp.asarray(q), jnp.asarray(c),
                         allow_mask=jnp.asarray(masks), **common)
    d2, i2 = np.asarray(d2), np.asarray(i2)
    for row in range(b):
        d1, i1 = flat_search(jnp.asarray(q), jnp.asarray(c),
                             allow_mask=jnp.asarray(masks[row]), **common)
        np.testing.assert_array_equal(i2[row], np.asarray(i1)[row])
        np.testing.assert_array_equal(d2[row], np.asarray(d1)[row])
        hits = i2[row][i2[row] >= 0]
        assert masks[row][hits].all()    # no other row's mask leaks in
        live = masks[row] & valid if with_valid else masks[row]
        assert len(hits) == min(k, int(live.sum()))
    assert (i2[4] == -1).all() and (d2[4] >= 1e30).all()
    assert len(i2[1][i2[1] >= 0]) == (2 if with_valid else 3)


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
@pytest.mark.parametrize("b", [1, 4, 8])
def test_flat_search_normalizes_its_own_queries_when_told_to(rng, b,
                                                             precision):
    """``normalize_queries`` on RAW queries, handed over as a numpy array
    as ``FlatIndex`` does, answers as the eager ``normalize`` before the
    call did: the same float32 arithmetic, launched by the scan. Under a
    mask a row, with a zero query row (divided by eps: it stays zero)."""
    n, k = 96, 5
    q = (3.0 * rng.standard_normal((b, 16))).astype(np.float32)
    q[b - 1] = 0.0
    c = np.asarray(normalize(rng.standard_normal((n, 16)).astype(np.float32)))
    if precision == "bf16":
        # resident as a flat index keeps its rows
        c = jnp.asarray(c).astype(jnp.bfloat16)
    masks = rng.random((b, n)) < 0.5
    valid = np.ones(n, bool)
    valid[90:] = False
    for allow in (jnp.asarray(masks), None):
        common = dict(k=k, metric="cosine", precision=precision,
                      valid_mask=jnp.asarray(valid), allow_mask=allow,
                      chunk_size=32)
        d0, i0 = flat_search(normalize(jnp.asarray(q)), jnp.asarray(c),
                             **common)
        d1, i1 = flat_search(q, jnp.asarray(c), normalize_queries=True,
                             **common)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d0),
                                   rtol=0, atol=1e-6)
        # the zero row scores 1 - 0 against every allowed row
        live = np.asarray(d1)[b - 1] < 1e30
        assert live.any()
        np.testing.assert_array_equal(np.asarray(d1)[b - 1][live], 1.0)
    if b > 1:
        # the default leaves raw queries raw
        d_raw, _ = flat_search(q, jnp.asarray(c), **common)
        assert not np.allclose(np.asarray(d_raw)[0], np.asarray(d1)[0],
                               atol=1e-3)


def test_gather_distance(rng):
    q = rng.standard_normal((2, 8)).astype(np.float32)
    c = rng.standard_normal((30, 8)).astype(np.float32)
    cand = np.array([[0, 5, 7], [1, 2, 29]], np.int32)
    got = np.asarray(
        gather_distance(jnp.asarray(q), jnp.asarray(c), jnp.asarray(cand), "l2-squared")
    )
    full = np_dist(q, c, "l2-squared")
    want = np.stack([full[0, cand[0]], full[1, cand[1]]])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_merge_topk():
    va = jnp.asarray([[1.0, 3.0]])
    ia = jnp.asarray([[10, 30]], dtype=jnp.int32)
    vb = jnp.asarray([[0.5, 2.0]])
    ib = jnp.asarray([[5, 20]], dtype=jnp.int32)
    v, i = merge_topk(va, ia, vb, ib, 3)
    np.testing.assert_allclose(np.asarray(v)[0], [0.5, 1.0, 2.0])
    np.testing.assert_array_equal(np.asarray(i)[0], [5, 10, 20])


def test_masked_topk_all_masked():
    d = jnp.ones((1, 4))
    v, i = masked_topk(d, 2, mask=jnp.zeros(4, bool))
    assert (np.asarray(i) == -1).all()
