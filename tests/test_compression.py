"""Quantizer tests: kernel exactness + recall gates + index integration.

Mirrors the reference's compressed recall tests
(``hnsw/compress_recall_test.go``, ``compressionhelpers/*_test.go``): assert
distance-kernel semantics exactly, then gate recall@k floors on clustered
data (the realistic embedding regime) with the rescore tier enabled.
"""

import numpy as np
import pytest

from weaviate_tpu.compression import (
    BinaryQuantizer,
    ProductQuantizer,
    RotationalQuantizer,
    ScalarQuantizer,
    segmented_kmeans,
)
from weaviate_tpu.index.flat import FlatIndex, make_flat
from weaviate_tpu.schema.config import (
    BQConfig,
    FlatIndexConfig,
    PQConfig,
    RQConfig,
    SQConfig,
)


def clustered(rng, n, d, n_clusters=32, spread=0.15):
    centers = rng.standard_normal((n_clusters, d)).astype(np.float32)
    assign = rng.integers(0, n_clusters, size=n)
    return (centers[assign] + spread * rng.standard_normal((n, d))).astype(
        np.float32
    )


def exact_topk(queries, corpus, k, metric="l2-squared"):
    if metric == "l2-squared":
        d = (
            (queries**2).sum(1)[:, None]
            - 2 * queries @ corpus.T
            + (corpus**2).sum(1)[None, :]
        )
    elif metric == "cosine":
        qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)
        cn = corpus / np.linalg.norm(corpus, axis=1, keepdims=True)
        d = 1 - qn @ cn.T
    else:
        raise ValueError(metric)
    return np.argsort(d, axis=1, kind="stable")[:, :k]


def recall_at_k(got_ids, want_ids):
    hits = 0
    for g, w in zip(got_ids, want_ids):
        hits += len(set(g.tolist()) & set(w.tolist()))
    return hits / want_ids.size


# ---------------------------------------------------------------------------
# kmeans
# ---------------------------------------------------------------------------


def test_segmented_kmeans_reduces_distortion(rng):
    data = clustered(rng, 512, 16, n_clusters=8)[None, :, :]  # 1 segment
    cents = segmented_kmeans(data, 8, iters=10)
    d2 = ((data[0][:, None, :] - cents[0][None, :, :]) ** 2).sum(-1).min(1)
    # Lloyd's on 8 well-separated clusters should land near the true centers.
    assert d2.mean() < 0.5


# ---------------------------------------------------------------------------
# quantizer semantics
# ---------------------------------------------------------------------------


def test_bq_hamming_matches_numpy(rng):
    d = 70  # non-multiple of 32 exercises the pad path
    v = rng.standard_normal((40, d)).astype(np.float32)
    q = rng.standard_normal((5, d)).astype(np.float32)
    bq = BinaryQuantizer(d, "hamming")
    enc = bq.encode(v)

    from weaviate_tpu.compression import DeviceArraySet

    store = DeviceArraySet(bq.fields())
    store.put(np.arange(40), enc)
    dists, ids = bq.search(bq.prep(q), store, 40, store.valid_mask, 0)
    dists, ids = np.asarray(dists), np.asarray(ids)

    qb = (q > 0).astype(np.uint8)
    vb = (v > 0).astype(np.uint8)
    want = (qb[:, None, :] != vb[None, :, :]).sum(-1)
    for i in range(5):
        got = {int(a): float(x) for a, x in zip(ids[i], dists[i]) if a >= 0}
        for j in range(40):
            assert got[j] == pytest.approx(want[i, j], abs=0.5)


def test_sq_roundtrip_error_bounded(rng):
    d = 32
    v = rng.standard_normal((300, d)).astype(np.float32)
    sq = ScalarQuantizer(d, "l2-squared")
    sq.fit(v)
    enc = sq.encode(v)
    dec = sq.a + sq.s * enc["codes"].astype(np.float32)
    assert np.abs(dec - np.clip(v, sq.a, sq.a + 255 * sq.s)).max() <= sq.s


def test_pq_decode_matches_codebooks(rng):
    d, m = 32, 8
    v = clustered(rng, 600, d)
    pq = ProductQuantizer(d, "l2-squared", PQConfig(segments=m))
    pq.fit(v)
    enc = pq.encode(v[:10])
    dec = pq.decode(enc["codes"])
    assert dec.shape == (10, d)
    # reconstruction must beat the zero-vector baseline by a wide margin
    assert ((dec - v[:10]) ** 2).sum() < 0.5 * (v[:10] ** 2).sum()


def test_rq_rotation_is_orthogonal():
    rq = RotationalQuantizer(48, "l2-squared", RQConfig())
    rq.fit(np.zeros((4, 48), np.float32))
    r = rq.rotation
    assert np.allclose(r @ r.T, np.eye(r.shape[0]), atol=1e-4)


def test_quantizer_state_roundtrip(rng):
    d = 32
    v = clustered(rng, 600, d)
    for q in (
        ScalarQuantizer(d, "l2-squared"),
        ProductQuantizer(d, "l2-squared", PQConfig(segments=8)),
        RotationalQuantizer(d, "l2-squared", RQConfig()),
    ):
        q.fit(v)
        state = q.state_dict()
        fresh = type(q)(d, "l2-squared")
        fresh.load_state_dict(state)
        e1 = q.encode(v[:5])
        e2 = fresh.encode(v[:5])
        for key in e1:
            np.testing.assert_array_equal(e1[key], e2[key])


# ---------------------------------------------------------------------------
# recall gates (clustered data + rescore, reference compress_recall_test.go)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "qcfg,floor",
    [
        (SQConfig(rescore_limit=80), 0.95),
        (RQConfig(rescore_limit=80), 0.92),
        (PQConfig(segments=16, rescore_limit=100), 0.80),
        (BQConfig(rescore_limit=150), 0.60),
    ],
    ids=["sq", "rq", "pq", "bq"],
)
def test_compressed_recall_floor(rng, qcfg, floor):
    n, d, k, nq = 3000, 64, 10, 32
    corpus = clustered(rng, n, d)
    queries = corpus[rng.choice(n, nq, replace=False)] + 0.02 * rng.standard_normal(
        (nq, d)
    ).astype(np.float32)
    queries = queries.astype(np.float32)

    idx = make_flat(d, FlatIndexConfig(distance="l2-squared", quantizer=qcfg))
    idx.add_batch(np.arange(n), corpus)
    assert idx.quantizer.fitted
    res = idx.search(queries, k)
    want = exact_topk(queries, corpus, k)
    r = recall_at_k(res.ids, want)
    assert r >= floor, f"recall {r:.3f} < floor {floor} for {qcfg.kind}"


def test_quantized_flat_prefit_exact(rng):
    """Below min_training the index answers exactly from host originals."""
    n, d = 50, 16
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    idx = make_flat(d, FlatIndexConfig(distance="l2-squared", quantizer=SQConfig()))
    idx.add_batch(np.arange(n), corpus)
    assert not idx.quantizer.fitted
    res = idx.search(corpus[:5], 1)
    np.testing.assert_array_equal(res.ids[:, 0], np.arange(5))


def test_quantized_flat_delete_and_filter(rng):
    n, d = 600, 32
    corpus = clustered(rng, n, d)
    idx = make_flat(d, FlatIndexConfig(distance="l2-squared", quantizer=SQConfig()))
    idx.add_batch(np.arange(n), corpus)
    assert idx.quantizer.fitted

    q = corpus[:4]
    res = idx.search(q, 1)
    np.testing.assert_array_equal(res.ids[:, 0], np.arange(4))

    idx.delete(np.arange(4))
    res = idx.search(q, 1)
    assert all(res.ids[:, 0] != np.arange(4))

    allow = np.zeros(n, bool)
    allow[100:110] = True
    res = idx.search(q, 5, allow_list=allow)
    valid = res.ids[res.ids >= 0]
    assert len(valid) and np.all((valid >= 100) & (valid < 110))


def test_quantized_flat_cosine(rng):
    n, d = 600, 32
    corpus = clustered(rng, n, d)
    idx = make_flat(d, FlatIndexConfig(distance="cosine", quantizer=RQConfig()))
    idx.add_batch(np.arange(n), corpus)
    queries = corpus[:8] * 3.0  # scale-invariance check
    res = idx.search(queries, 1)
    np.testing.assert_array_equal(res.ids[:, 0], np.arange(8))


def test_make_flat_dispatch():
    assert isinstance(make_flat(8, FlatIndexConfig()), FlatIndex)
    qi = make_flat(8, FlatIndexConfig(quantizer=BQConfig()))
    assert qi.stats()["quantizer"] == "bq"


def test_quantized_flat_prefit_pads_to_k(rng):
    """Pre-fit exact fallback must honor the [B, k] shape contract."""
    corpus = rng.standard_normal((5, 16)).astype(np.float32)
    idx = make_flat(16, FlatIndexConfig(distance="l2-squared", quantizer=SQConfig()))
    idx.add_batch(np.arange(5), corpus)
    res = idx.search(corpus[:2], 10)
    assert res.ids.shape == (2, 10)
    assert (res.ids[:, 5:] == -1).all()


def test_quantizer_metric_validation():
    from weaviate_tpu.compression import build_quantizer

    with pytest.raises(ValueError):
        build_quantizer(SQConfig(), 16, "manhattan")
    with pytest.raises(ValueError):
        build_quantizer(SQConfig(), 16, "hamming")
    assert build_quantizer(BQConfig(), 16, "hamming") is not None


def test_generic_config_with_quantizer_builds_every_index_type():
    """as_type must preserve the quantizer object (not a flattened dict)."""
    from weaviate_tpu.core.shard import build_vector_index
    from weaviate_tpu.schema.config import VectorIndexConfig

    for t in ("flat", "hnsw", "dynamic"):
        cfg = VectorIndexConfig(
            index_type=t, distance="l2-squared", quantizer=SQConfig()
        )
        idx = build_vector_index(16, cfg)
        assert idx is not None


def test_hnsw_quantized_cosine_rescore_distances(rng):
    """Rescore must normalize queries: dists are true cosine distances even
    for scaled queries (regression: un-normalized rescore)."""
    from weaviate_tpu.index.hnsw import HNSWIndex
    from weaviate_tpu.schema.config import HNSWIndexConfig

    n, d = 600, 32
    corpus = clustered(rng, n, d)
    idx = HNSWIndex(
        d,
        HNSWIndexConfig(
            distance="cosine", quantizer=SQConfig(rescore_limit=60),
            flat_search_cutoff=0,
        ),
    )
    idx.add_batch(np.arange(n), corpus)
    res = idx.search(corpus[:4] * 7.5, 1)  # scaled queries
    np.testing.assert_array_equal(res.ids[:, 0], np.arange(4))
    # self-distance in cosine is ~0 regardless of query scale
    assert np.all(res.dists[:, 0] < 1e-2)


def _flat_topk_reference(backend, queries, k, allow):
    """``QuantizedBackend.flat_topk`` for a fitted, device-resident backend,
    written out step by step: prep, one code scan of the top
    max(4k, rescoreLimit, k), the exact rescore of those candidates."""
    import jax.numpy as jnp

    from weaviate_tpu.index.flat import exact_rescore

    qrep = backend.prep_queries(queries)
    cap = backend.codes.capacity
    mask = backend.codes.valid_mask
    if allow is not None:
        al = np.pad(allow, (0, max(0, cap - len(allow))))[:cap]
        mask = mask & jnp.asarray(al)
    fetch = max(4 * k, backend.quantizer.config.rescore_limit, k)
    chunk = backend.config.search_chunk_size
    _, ids = backend.quantizer.search(qrep.code, backend.codes, fetch, mask,
                                      chunk if cap > chunk else 0)
    res = exact_rescore(qrep.host, np.asarray(ids), backend.originals,
                        backend.metric, k)
    d = res.dists.astype(np.float32).copy()
    ids = res.ids.astype(np.int64)
    d[ids < 0] = np.inf
    pad = k - ids.shape[1]
    return (np.pad(d, ((0, 0), (0, pad)), constant_values=np.inf),
            np.pad(ids, ((0, 0), (0, pad)), constant_values=-1))


@pytest.mark.parametrize("metric", ["l2-squared", "cosine"])
@pytest.mark.parametrize("qcfg", [
    SQConfig(), RQConfig(), RQConfig(bits=1), BQConfig(),
    PQConfig(segments=8)], ids=["sq", "rq", "brq", "bq", "pq"])
def test_flat_topk_called_directly_keeps_its_answers(qcfg, metric):
    """The compressed HNSW paths call ``flat_topk`` on their own backend,
    inside their own batching: it scans, and rescores, every call itself,
    to the bit, whatever a flat collection's searches share."""
    from weaviate_tpu.index.hnsw import HNSWIndex
    from weaviate_tpu.schema.config import HNSWIndexConfig

    rng = np.random.default_rng(1234)
    n, d = 700, 32
    corpus = clustered(rng, n, d)
    backend = HNSWIndex(d, HNSWIndexConfig(
        distance=metric, quantizer=qcfg)).backend
    backend.put(np.arange(n), corpus)
    assert backend.quantizer.fitted
    queries = (corpus[:6] + 0.05 * rng.standard_normal((6, d))).astype(
        np.float32)
    allow = rng.random(n - 50) < 0.4
    for k, mask in ((10, None), (3, allow), (7, None)):
        got_d, got_ids = backend.flat_topk(queries, k, mask)
        want_d, want_ids = _flat_topk_reference(backend, queries, k, mask)
        np.testing.assert_array_equal(got_ids, want_ids)
        np.testing.assert_array_equal(got_d, want_d)
        if mask is not None:
            assert mask[got_ids[got_ids >= 0]].all()


# -- raw-vector residency tiers (VERDICT r3 #4: beyond-HBM corpus tier) ------


@pytest.mark.parametrize("tier", ["ram16", "disk16"])
def test_raw_tier_parity_with_ram(rng, tier, tmp_path):
    """fp16 RAM / fp16 disk-memmap originals must serve the rescore tier
    with the same results as fp32 RAM (codes in HBM are identical; only
    the rescore gather touches the tier). The int8 tier is NOT in this
    parametrization on purpose: at d=64 this corpus's neighbor gaps sit at
    the SQ8 quantization-step scale, which is outside that tier's design
    envelope — it gets its own test at its design shape below."""
    n, d, k, nq = 4000, 64, 10, 32
    corpus = clustered(rng, n, d)
    queries = corpus[rng.choice(n, nq, replace=False)] + 0.02 * \
        rng.standard_normal((nq, d)).astype(np.float32)

    base = make_flat(d, FlatIndexConfig(
        distance="cosine", quantizer=BQConfig(rescore_limit=150)))
    base.add_batch(np.arange(n), corpus)

    cfg = FlatIndexConfig(
        distance="cosine", quantizer=BQConfig(rescore_limit=150),
        raw_tier=tier,
        raw_path=str(tmp_path / "raw.bin") if tier.startswith("disk")
        else None)
    idx = make_flat(d, cfg)
    # two put calls: the second forces memmap ensure_capacity growth
    idx.add_batch(np.arange(n // 2), corpus[: n // 2])
    idx.add_batch(np.arange(n // 2, n), corpus[n // 2:])

    rb = base.search(queries, k)
    rt = idx.search(queries, k)
    agree = np.mean([
        len(set(rb.ids[i].tolist()) & set(rt.ids[i].tolist())) / k
        for i in range(nq)])
    assert agree >= 0.95, f"{tier} diverged from ram tier: {agree}"
    if tier.startswith("disk"):
        import os

        assert os.path.exists(cfg.raw_path)
        itemsize = 2 if tier == "disk16" else 1
        assert idx.backend.originals.nbytes >= n * d * itemsize
    assert idx.backend.codes.nbytes > 0  # HBM footprint reportable


def test_disk8_tier_recall_at_design_shape(tmp_path):
    """The int8 disk tier (bq100m's rescore tier: 1 B/dim on disk) must
    hold >= 0.97 recall@10 against the EXACT fp32 ranking at its design
    shape — high-d embedding corpora (d >= 256, LAION-like cluster noise)
    where the per-row SQ8 step is ~4x below the inter-neighbor gap scale.
    (Per-dim sigma ~ 1/sqrt(d) on unit rows, so precision IMPROVES with
    dimension; low-d near-tie corpora are out of envelope by design.)"""
    import os

    rng = np.random.default_rng(0)
    n, d, k, nq = 4000, 256, 10, 32
    centers = rng.standard_normal((64, d)).astype(np.float32)
    corpus = centers[rng.integers(0, 64, n)] + 0.45 * \
        rng.standard_normal((n, d)).astype(np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)
    queries = corpus[rng.choice(n, nq, replace=False)] + 0.05 * \
        rng.standard_normal((nq, d)).astype(np.float32)
    qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)
    gt = np.argsort(-(qn @ corpus.T), axis=1)[:, :k]

    cfg = FlatIndexConfig(
        distance="cosine", quantizer=BQConfig(rescore_limit=150),
        raw_tier="disk8", raw_path=str(tmp_path / "raw8.bin"))
    idx = make_flat(d, cfg)
    idx.add_batch(np.arange(n), corpus)
    r = idx.search(queries, k)
    rec = np.mean([len(set(r.ids[i].tolist()) & set(gt[i].tolist())) / k
                   for i in range(nq)])
    assert rec >= 0.97, f"disk8 recall vs exact fp32: {rec}"
    # 1 byte/dim on disk + 8 B/row decode params
    assert os.path.getsize(cfg.raw_path) >= n * d
    assert idx.backend.originals.nbytes >= n * (d + 8)


def test_sq8_host_store_roundtrip(rng):
    """The int8 tier's per-row affine decode must reconstruct unit vectors
    to well under the inter-neighbor distance scale (<1% relative error),
    and survive capacity growth with decode params intact."""
    from weaviate_tpu.compression.store import HostVectorStore

    d = 96
    v = rng.standard_normal((512, d)).astype(np.float32)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    st = HostVectorStore(d, capacity=16, dtype=np.int8)
    st.put(np.arange(256), v[:256])
    st.put(np.arange(256, 512), v[256:])  # forces growth
    back = st.get(np.arange(512))
    rel = np.linalg.norm(back - v, axis=1)  # rows are unit norm
    assert float(rel.max()) < 0.01, float(rel.max())
    ids, vecs = st.all_live()
    assert len(ids) == 512 and np.allclose(vecs, back)
    assert st.sample(32).dtype == np.float32


def test_disk16_tier_via_shard_path(tmp_path):
    """build_vector_index resolves a PER-INDEX raw16.bin under the index
    dir without mutating the shared config (two shards of one collection
    must never memmap the same file)."""
    from weaviate_tpu.core.shard import build_vector_index

    cfg = FlatIndexConfig(distance="l2-squared",
                          quantizer=SQConfig(rescore_limit=40),
                          raw_tier="disk16")
    idx = build_vector_index(8, cfg, path=str(tmp_path / "vec"))
    idx2 = build_vector_index(8, cfg, path=str(tmp_path / "vec2"))
    assert cfg.raw_path is None  # shared config untouched
    assert idx.backend.originals.path.endswith("vec/raw16.bin")
    assert idx2.backend.originals.path.endswith("vec2/raw16.bin")
    rng = np.random.default_rng(0)
    corpus = rng.standard_normal((2000, 8)).astype(np.float32)
    idx.add_batch(np.arange(2000), corpus)
    res = idx.search(corpus[:4], 5)
    assert (res.ids[:, 0] == np.arange(4)).all()
