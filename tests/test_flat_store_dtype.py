"""A plain flat collection whose scan takes a bf16 product keeps its rows
resident in bfloat16, rounded ONCE on the write path (``index/flat.py
resident_dtype``, ``index/store.py``): the scan converts nothing, and what
it answers is what ``flat_search`` answers over the float32 rows, because
the per-scan convert it used to run rounds the same float32 values the same
way. Everything a caller reads back on the host stays float32."""

import jax.numpy as jnp
import numpy as np
import pytest

from weaviate_tpu.index.dynamic import DynamicIndex
from weaviate_tpu.index.flat import FlatIndex, resident_dtype
from weaviate_tpu.index.multivector import MultiVectorIndex
from weaviate_tpu.index.store import DeviceVectorStore
from weaviate_tpu.ops.distance import flat_search, normalize
from weaviate_tpu.schema.config import (
    DynamicIndexConfig,
    FlatIndexConfig,
    MultiVectorIndexConfig,
)

N, D, K = 3000, 48, 10
BF16_METRICS = ("cosine", "dot", "l2-squared")


def _rows(seed: int = 0, n: int = N) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((n, D)).astype(
        np.float32)


def _queries(rows: np.ndarray, b: int) -> np.ndarray:
    noise = np.random.default_rng(7).standard_normal((b, D)).astype(np.float32)
    return rows[:b] + 0.1 * noise


def _index(metric: str, precision: str = "bf16", rows=None,
           **kw) -> FlatIndex:
    idx = FlatIndex(D, FlatIndexConfig(distance=metric, precision=precision),
                    **kw)
    rows = _rows() if rows is None else rows
    # two feeds, the second overwriting a few rows of the first
    idx.add_batch(np.arange(2000), rows[:2000])
    idx.add_batch(np.arange(1990, len(rows)), rows[1990:])
    return idx


def _float32_rows(metric: str, rows: np.ndarray) -> jnp.ndarray:
    """The rows as the write path preps them, before any rounding."""
    vj = jnp.asarray(rows)
    return normalize(vj) if metric == "cosine" else vj


def _bits(a) -> np.ndarray:
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def _padded(idx: FlatIndex, prepped: jnp.ndarray):
    cap = idx.store.capacity
    full = jnp.zeros((cap, D), jnp.float32).at[: prepped.shape[0]].set(prepped)
    valid = jnp.zeros((cap,), jnp.bool_).at[: prepped.shape[0]].set(True)
    return full, valid


def _reference(idx: FlatIndex, metric: str, rows: np.ndarray, q: np.ndarray,
               allow=None, precision: str = "bf16"):
    """``flat_search`` over the FLOAT32 rows: the program every scan ran
    before the rows were resident in bfloat16."""
    full, valid = _padded(idx, _float32_rows(metric, rows))
    qj = jnp.asarray(q)
    if metric == "cosine":
        qj = normalize(qj)
    d, ids = flat_search(
        qj, full, k=K, metric=metric, valid_mask=valid,
        allow_mask=None if allow is None else jnp.asarray(allow),
        corpus_sqnorms=(jnp.sum(full ** 2, axis=-1)
                        if metric == "l2-squared" else None),
        precision=precision)
    return np.asarray(ids), np.asarray(d)


@pytest.mark.parametrize("metric", BF16_METRICS)
def test_rows_are_the_float32_rows_rounded_once(metric):
    rows = _rows()
    idx = _index(metric, rows=rows)
    assert idx.store.dtype == jnp.bfloat16 == idx.store.corpus.dtype
    want = _float32_rows(metric, rows)
    # normalise first, round second: bit for bit what the scan's convert
    # made of the float32 store on every request
    np.testing.assert_array_equal(
        _bits(idx.store.corpus[:N]), _bits(want.astype(jnp.bfloat16)))
    # the norms are the float32 rows', not the rounded rows'
    np.testing.assert_array_equal(
        np.asarray(idx.store.sqnorms[:N]),
        np.asarray(jnp.sum(want ** 2, axis=-1)))
    rounded = jnp.sum(want.astype(jnp.bfloat16).astype(jnp.float32) ** 2, -1)
    assert not np.array_equal(np.asarray(idx.store.sqnorms[:N]),
                              np.asarray(rounded))


def _masks(form: str, b: int, cap: int):
    """(what ``FlatIndex._scan`` takes, what ``flat_search`` takes)."""
    rng = np.random.default_rng(11)
    if form == "none":
        return None, None
    if form == "one":
        m = np.zeros(cap, bool)
        m[:N] = rng.random(N) < 0.3
        return [m] * b, m
    if form == "few":  # fewer than k allowed rows
        m = np.zeros(cap, bool)
        m[rng.choice(N, 3, replace=False)] = True
        return [m] * b, m
    stacked = np.zeros((b, cap), bool)
    stacked[:, :N] = rng.random((b, N)) < 0.3
    stacked[-1, :] = False
    stacked[-1, [5, 6]] = True  # one member with fewer than k allowed
    return [stacked[i].copy() for i in range(b)], stacked


@pytest.mark.parametrize("form,b", [
    ("none", 1), ("none", 4), ("none", 8),
    ("one", 1), ("one", 4), ("one", 8),
    ("row", 4), ("row", 8),
    ("few", 1), ("few", 4), ("few", 8),
])
@pytest.mark.parametrize("metric", BF16_METRICS)
def test_search_answers_as_the_scan_over_float32_rows(metric, form, b):
    rows = _rows()
    idx = _index(metric, rows=rows)
    q = _queries(rows, b)
    masks, allow = _masks(form, b, idx.store.capacity)
    if form == "row":
        # a mask a query row reaches the scan from a coalesced group of
        # members whose filters differ: the dispatcher's leader calls this
        ids, d = idx._scan(q, K, masks, [1] * b, 0.0)
    else:
        res = idx.search(q, K, None if masks is None else masks[0],
                         approx_recall=0.0)
        ids, d = res.ids, res.dists
    want_ids, want_d = _reference(idx, metric, rows, q, allow)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(d, want_d, rtol=1e-6, atol=1e-6)
    if form == "few":
        assert (ids[:, 3:] == -1).all() and (ids[:, :3] >= 0).all()


@pytest.mark.parametrize("metric,precision", [
    ("cosine", "fp32"), ("l2-squared", "fp32"),
    ("manhattan", "bf16"), ("hamming", "bf16"),
])
def test_float32_rows_are_kept_where_the_scan_reads_them(metric, precision):
    rows = _rows()
    if metric == "hamming":
        rows = np.round(rows)
    idx = _index(metric, precision, rows=rows)
    assert resident_dtype(idx.config) == jnp.float32
    assert idx.store.corpus.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(idx.store.corpus[:N]),
        np.asarray(_float32_rows(metric, rows)))
    q = _queries(rows, 4)
    if metric == "hamming":
        q = np.round(q)
    res = idx.search(q, K, approx_recall=0.0)
    want_ids, want_d = _reference(idx, metric, rows, q, precision=precision)
    np.testing.assert_array_equal(res.ids, want_ids)
    np.testing.assert_allclose(res.dists, want_d, rtol=1e-6, atol=1e-6)


def test_the_multivector_fde_plane_stays_float32():
    idx = MultiVectorIndex(16, MultiVectorIndexConfig(
        ksim=2, dproj=4, repetitions=2))
    assert idx.inner.config.precision == "bf16"
    assert idx.inner.store.dtype == jnp.float32
    assert idx.inner.store.corpus.dtype == jnp.float32


def test_a_dynamic_index_keeps_float32_rows_through_its_upgrade():
    rows = _rows(3, 900)
    cfg = DynamicIndexConfig(
        distance="l2-squared", threshold=500, cutover_background=False,
        hnsw={"max_connections": 16, "ef_construction": 64, "ef": 64})
    idx = DynamicIndex(D, cfg)
    idx.add_batch(np.arange(300), rows[:300])
    store = idx.inner.store
    assert not idx.upgraded and idx.inner.config.precision == "bf16"
    assert store.dtype == jnp.float32 == store.corpus.dtype
    idx.add_batch(np.arange(300, 900), rows[300:])
    assert idx.upgraded
    # the store was handed over wholesale, rows as they were written
    assert idx.inner.backend.store is store
    np.testing.assert_array_equal(np.asarray(store.corpus[:900]), rows)
    q = _queries(rows, 8)
    exact = np.argsort(((q[:, None, :] - rows[None]) ** 2).sum(-1),
                       axis=1)[:, :K]
    got = idx.search(q, K).ids
    hits = sum(len(set(g) & set(w)) for g, w in zip(got, exact))
    assert hits >= 0.95 * exact.size


def test_the_resident_bytes_halve():
    rows = _rows()
    narrow = _index("cosine", rows=rows)
    wide = _index("cosine", rows=rows, float32_rows=True)
    cap = narrow.store.capacity
    assert wide.store.capacity == cap
    side = cap * (1 + 4)  # the validity mask and the float32 norms
    assert wide.store.nbytes - side == cap * D * 4
    assert narrow.store.nbytes - side == cap * D * 2
    assert narrow.hbm_bytes() == narrow.store.nbytes


@pytest.mark.parametrize("metric", BF16_METRICS)
def test_get_is_float32_from_both_tiers(metric):
    rows = _rows()
    idx = _index(metric, rows=rows)
    ids = np.array([0, 7, 1995, N - 1])
    want = np.asarray(
        _float32_rows(metric, rows)[ids].astype(jnp.bfloat16).astype(
            jnp.float32))
    hot = idx.store.get(ids)
    assert hot.dtype == np.float32
    np.testing.assert_array_equal(hot, want)
    assert idx.demote_device() > 0
    warm = idx.store.get(ids)
    assert warm.dtype == np.float32
    np.testing.assert_array_equal(warm, want)
    # the warm tier scores with numpy: its mirror is float32, never ml_dtypes
    assert idx.store.host_arrays[0].dtype == np.float32
    assert idx.store.host_bytes > idx.store.capacity * D * 4


@pytest.mark.parametrize("metric", BF16_METRICS)
def test_demote_promote_round_trip_is_bit_equal(metric):
    rows = _rows()
    idx = _index(metric, rows=rows)
    corpus, valid, sqnorms = (np.asarray(a) for a in idx.store.snapshot())
    q = _queries(rows, 4)
    before = idx.search(q, K, approx_recall=0.0)
    freed = idx.demote_device()
    warm = idx.search(q, K)  # answered by the host tier, from float32
    assert (warm.ids[:, 0] == before.ids[:, 0]).all()
    # what the tiering controller makes room for: the resident width, not
    # the float32 mirror's
    assert idx.promote_bytes() == freed < idx.host_tier_bytes()
    assert idx.promote_device() == freed
    assert idx.promote_bytes() == 0
    after = idx.store.snapshot()
    assert after[0].dtype == jnp.bfloat16
    np.testing.assert_array_equal(_bits(after[0]), _bits(corpus))
    np.testing.assert_array_equal(np.asarray(after[1]), valid)
    np.testing.assert_array_equal(np.asarray(after[2]), sqnorms)
    again = idx.search(q, K, approx_recall=0.0)
    np.testing.assert_array_equal(again.ids, before.ids)
    np.testing.assert_array_equal(again.dists, before.dists)


@pytest.mark.parametrize("demoted", [False, True], ids=["hot", "warm"])
@pytest.mark.parametrize("metric", BF16_METRICS)
def test_save_load_round_trip_is_bit_equal(tmp_path, metric, demoted):
    rows = _rows()
    idx = _index(metric, rows=rows)
    corpus, _valid, sqnorms = (np.asarray(a) for a in idx.store.snapshot())
    if demoted:
        idx.demote_device()  # the file holds the resident width all the same
    path = str(tmp_path / "vectors.bin")
    assert idx.save_vectors(path, {"from": "test"})
    back = FlatIndex(D, FlatIndexConfig(distance=metric))
    assert back.load_vectors(path) == {"from": "test"}
    assert back.store.corpus.dtype == jnp.bfloat16
    np.testing.assert_array_equal(_bits(back.store.corpus[:N]),
                                  _bits(corpus[:N]))
    np.testing.assert_array_equal(np.asarray(back.store.sqnorms[:N]),
                                  sqnorms[:N])
    assert back.count() == N


@pytest.mark.parametrize("metric", BF16_METRICS)
def test_a_float32_checkpoint_loads_into_the_same_rows(tmp_path, metric):
    """A checkpoint from before the rows were resident in bfloat16: the
    float32 store of those days wrote float32 rows (normalised for cosine)
    and their norms; loading rounds them as every scan used to."""
    rows = _rows()
    old = DeviceVectorStore(D, dtype=jnp.float32,
                            normalized=(metric == "cosine"))
    old.put(np.arange(N), rows)
    path = str(tmp_path / "vectors.bin")
    old.save(path, {"v": 1})
    fed = _index(metric, rows=rows)
    loaded = FlatIndex(D, FlatIndexConfig(distance=metric))
    assert loaded.load_vectors(path) == {"v": 1}
    assert loaded.store.corpus.dtype == jnp.bfloat16
    np.testing.assert_array_equal(_bits(loaded.store.corpus[:N]),
                                  _bits(fed.store.corpus[:N]))
    np.testing.assert_array_equal(np.asarray(loaded.store.sqnorms[:N]),
                                  np.asarray(fed.store.sqnorms[:N]))
    q = _queries(rows, 4)
    a, b = loaded.search(q, K), fed.search(q, K)
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_array_equal(a.dists, b.dists)
