"""Multi-device serving path: the 8-device virtual CPU mesh must be used by
the REAL search path (Collection -> Shard -> index), not just the raw
kernels. Mirrors the reference's in-process multi-node component tests
(``adapters/repos/db/clusterintegrationtest/``)."""

import numpy as np
import pytest

from weaviate_tpu.core.db import DB
from weaviate_tpu.parallel.runtime import default_mesh
from weaviate_tpu.schema.config import (
    CollectionConfig,
    DataType,
    FlatIndexConfig,
    HNSWIndexConfig,
    Property,
)


@pytest.fixture(autouse=True, scope="module")
def _mesh_on():
    """conftest defaults WEAVIATE_TPU_MESH=off for suite speed; this module
    exists to exercise the mesh serving path, so force it on."""
    from weaviate_tpu.parallel import runtime
    from weaviate_tpu.parallel.mesh import make_mesh

    runtime.set_mesh(make_mesh(8))
    yield
    runtime.reset()


def _mk_db(tmp_dbdir, name, index_config=None):
    db = DB(tmp_dbdir)
    cfg = CollectionConfig(
        name=name,
        properties=[Property(name="title", data_type=DataType.TEXT)],
        vector_config=index_config or FlatIndexConfig(),
    )
    db.create_collection(cfg)
    return db, db.get_collection(name)


def test_default_mesh_is_multi_device():
    mesh = default_mesh()
    assert mesh is not None, "conftest forces an 8-device CPU platform"
    assert mesh.devices.size == 8


def test_flat_store_is_row_sharded(tmp_dbdir):
    db, col = _mk_db(tmp_dbdir, "MeshFlat")
    try:
        rng = np.random.default_rng(0)
        from weaviate_tpu.storage.objects import StorageObject

        vecs = rng.standard_normal((64, 16)).astype(np.float32)
        objs = [
            StorageObject(uuid="", collection="", properties={"title": f"t{i}"}, vector=vecs[i])
            for i in range(64)
        ]
        col.put_batch(objs)
        shard = col._get_shard("shard0")
        store = shard.vector_index().store
        assert store.mesh is not None
        assert len(store.corpus.sharding.device_set) == 8
    finally:
        db.close()


@pytest.mark.parametrize("metric", ["cosine", "l2-squared"])
def test_mesh_flat_rows_are_bfloat16_and_answer_as_one_chip(metric):
    """The mesh store follows the single-chip rule (``index/flat.py
    resident_dtype``): a bf16 product keeps its rows in bfloat16, rounded
    once on the write path, and the sharded scan answers what the one-chip
    scan answers over the float32 rows."""
    import jax.numpy as jnp

    from weaviate_tpu.index.flat import FlatIndex
    from weaviate_tpu.ops.distance import flat_search, normalize

    rng = np.random.default_rng(5)
    n, d, k = 1500, 32, 10
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    idx = FlatIndex(d, FlatIndexConfig(distance=metric))
    idx.add_batch(np.arange(n), vecs)
    corpus, valid, sqnorms = idx.store.snapshot()
    assert idx.store.mesh is not None and corpus.dtype == jnp.bfloat16
    assert len(corpus.sharding.device_set) == 8
    queries = vecs[:4] + 0.1 * rng.standard_normal((4, d)).astype(np.float32)
    got = idx.search(queries, k, approx_recall=0.0)

    rows, qj = jnp.asarray(vecs), jnp.asarray(queries)
    if metric == "cosine":
        rows, qj = normalize(rows), normalize(qj)
    np.testing.assert_array_equal(
        np.asarray(corpus[:n]).view(np.uint16),
        np.asarray(rows.astype(jnp.bfloat16)).view(np.uint16))
    cap = corpus.shape[0]
    full = jnp.zeros((cap, d), jnp.float32).at[:n].set(rows)
    want_d, want_ids = flat_search(
        qj, full, k=k, metric=metric,
        valid_mask=jnp.zeros((cap,), jnp.bool_).at[:n].set(True),
        corpus_sqnorms=(jnp.sum(full ** 2, axis=-1)
                        if metric == "l2-squared" else None),
        precision="bf16")
    np.testing.assert_array_equal(got.ids, np.asarray(want_ids))
    np.testing.assert_allclose(got.dists, np.asarray(want_d),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("index_config", [
    FlatIndexConfig(distance="l2-squared", precision="fp32"),
    HNSWIndexConfig(distance="l2-squared", ef=64, ef_construction=64,
                    max_connections=16, precision="fp32"),
])
def test_collection_search_on_mesh_matches_bruteforce(tmp_dbdir, index_config):
    db, col = _mk_db(tmp_dbdir, "MeshSearch", index_config)
    try:
        rng = np.random.default_rng(1)
        from weaviate_tpu.storage.objects import StorageObject

        n, d, k = 300, 24, 10
        vecs = rng.standard_normal((n, d)).astype(np.float32)
        objs = [
            StorageObject(uuid="", collection="", properties={"title": f"doc {i}"}, vector=vecs[i])
            for i in range(n)
        ]
        uuids = col.put_batch(objs)

        queries = vecs[:8] + 0.01 * rng.standard_normal((8, d)).astype(
            np.float32)
        res = col.vector_search_batch(queries, k)

        # brute-force ground truth over the original vectors
        d2 = ((queries[:, None, :] - vecs[None, :, :]) ** 2).sum(-1)
        gt = np.argsort(d2, axis=1)[:, :k]
        for qi in range(8):
            got = {o.uuid for o, _ in res[qi]}
            want = {uuids[j] for j in gt[qi]}
            overlap = len(got & want) / k
            floor = 1.0 if isinstance(index_config, FlatIndexConfig) else 0.9
            assert overlap >= floor, f"q{qi}: overlap {overlap}"
    finally:
        db.close()


def test_mesh_filtered_search(tmp_dbdir):
    from weaviate_tpu.inverted.filters import Filter
    from weaviate_tpu.storage.objects import StorageObject

    db, col = _mk_db(tmp_dbdir, "MeshFiltered")
    try:
        rng = np.random.default_rng(2)
        n, d = 200, 16
        vecs = rng.standard_normal((n, d)).astype(np.float32)
        objs = [
            StorageObject(
                uuid="", collection="", properties={"title": "even" if i % 2 == 0 else "odd"},
                vector=vecs[i],
            )
            for i in range(n)
        ]
        col.put_batch(objs)
        flt = Filter(operator="Equal", path=["title"], value="even")
        res = col.vector_search(vecs[3], k=5, flt=flt)
        assert len(res) == 5
        for o, _ in res:
            assert o.properties["title"] == "even"
    finally:
        db.close()


def test_a_mesh_flat_index_keeps_one_mask_a_batch():
    """The mesh scan takes one mask a batch, so a ``FlatIndex`` over a mesh
    store does not declare ``per_row_masks``: concurrent requests with
    different masks never share a batch, and each answers inside its own."""
    import threading

    from weaviate_tpu.index.flat import FlatIndex

    idx = FlatIndex(16, FlatIndexConfig(distance="l2-squared"))
    assert idx.store.mesh is not None
    assert not idx._dispatcher.per_row_masks
    rng = np.random.default_rng(4)
    vecs = rng.standard_normal((200, 16)).astype(np.float32)
    idx.add_batch(np.arange(200), vecs)
    masks = [np.arange(200) % 3 == r for r in range(3)]
    handed, real = [], idx._dispatcher.run_batch

    def run_batch(q, k, allow, **kw):
        handed.append((q.shape[0], allow))
        return real(q, k, allow, **kw)

    idx._dispatcher.run_batch = run_batch
    got = {}

    def client(i):
        got[i] = idx.search(vecs[i][None], 5, masks[i % 3])

    threads = [threading.Thread(target=client, args=(i,)) for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    assert sum(rows for rows, _ in handed) == 12
    assert all(any(m is allow for m in masks) for _, allow in handed)
    for i, res in got.items():
        assert res.ids[0, 0] == i            # its own row: its mask has it
        assert masks[i % 3][res.ids[0]].all()


def test_sharded_maxsim_matches_single_device():
    """Late-interaction rescore sharded over the candidate axis of the
    8-device mesh must match the single-device einsum exactly (the
    long-context tier's sequence-parallel analogue)."""
    import numpy as np

    from weaviate_tpu.index.multivector import maxsim_scores
    from weaviate_tpu.parallel import runtime
    from weaviate_tpu.parallel.sharded_search import sharded_maxsim

    rng = np.random.default_rng(0)
    c, tmax, tq, d = 37, 12, 5, 16  # c NOT divisible by 8 (pads)
    toks = rng.standard_normal((c, tmax, d)).astype(np.float32)
    mask = rng.random((c, tmax)) < 0.8
    mask[:, 0] = True  # every candidate has >= 1 token
    q = rng.standard_normal((tq, d)).astype(np.float32)

    mesh = runtime.default_mesh()
    assert mesh is not None and mesh.size == 8
    via_entry = maxsim_scores(q, toks, mask)  # routes through the mesh
    # reference: plain einsum on one device
    import jax.numpy as jnp

    sims = jnp.einsum("qd,ctd->cqt", jnp.asarray(q), jnp.asarray(toks))
    sims = jnp.where(jnp.asarray(mask)[:, None, :], sims, -jnp.inf)
    best = jnp.where(jnp.isfinite(sims.max(2)), sims.max(2), 0.0)
    want = np.asarray(best.sum(1))
    np.testing.assert_allclose(via_entry, want, rtol=1e-5)


def test_multivector_search_on_mesh(tmp_dbdir):
    """End-to-end MUVERA search with the mesh active: candidates shard
    across devices in the rescore tier; ranking matches content."""
    import numpy as np

    from weaviate_tpu.index.multivector import MultiVectorIndex
    from weaviate_tpu.schema.config import MultiVectorIndexConfig

    rng = np.random.default_rng(1)
    idx = MultiVectorIndex(16, MultiVectorIndexConfig(rescore_limit=32))
    sets = []
    for i in range(64):
        t = rng.standard_normal((4 + i % 5, 16)).astype(np.float32)
        t /= np.linalg.norm(t, axis=1, keepdims=True) + 1e-12
        sets.append(t)
    idx.add_batch_multi(np.arange(64, dtype=np.int64), sets)
    q = sets[17] + 0.01 * rng.standard_normal(sets[17].shape).astype(
        np.float32)
    res = idx.search_multi(q, 5)
    assert res.ids[0, 0] == 17
