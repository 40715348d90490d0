"""Pallas fused flat-search kernel (interpret mode on the CPU mesh).

Reference test model: distancer differential tests — the fused kernel
must agree with the XLA two-stage path on ids and distances.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from weaviate_tpu.ops.distance import flat_search
from weaviate_tpu.ops.pallas_flat import pallas_flat_topk


def _data(n=4096, d=64, b=8, seed=0):
    rng = np.random.default_rng(seed)
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    q = corpus[:b] + 0.1 * rng.standard_normal((b, d)).astype(np.float32)
    sq = (corpus * corpus).sum(1).astype(np.float32)
    return q, corpus, sq


def test_matches_xla_path_exact_ids():
    q, corpus, sq = _data()
    mask = np.ones(len(corpus), np.float32)
    v, i = pallas_flat_topk(jnp.asarray(q), jnp.asarray(corpus),
                            jnp.asarray(sq), jnp.asarray(mask), 10,
                            chunk_size=1024, interpret=True)
    gv, gi = flat_search(jnp.asarray(q), jnp.asarray(corpus), k=10,
                         metric="l2-squared",
                         corpus_sqnorms=jnp.asarray(sq), precision="bf16")
    agree = np.mean([len(set(np.asarray(i)[r]) & set(np.asarray(gi)[r]))
                     for r in range(len(q))]) / 10
    assert agree >= 0.95  # bf16 rounding may swap near-ties
    assert np.allclose(np.sort(np.asarray(v), axis=1),
                       np.sort(np.asarray(gv), axis=1), rtol=1e-2,
                       atol=1e-2)


def test_bucketed_fold_path_matches_exact():
    """fold>1 engages the strided bucket index math that serves at 1M
    scale (the fold-scaling rule keeps k=10 test corpora exact, so this
    pins k=2: 16*64*4 = 4096 <= n → fold=16). Ids must reconstruct
    through loc*folds + j exactly; top-1 is always exact under bucketing
    and top-2 may only miss on a true bucket collision."""
    q, corpus, sq = _data(n=4096, d=64, b=16, seed=3)
    mask = np.ones(len(corpus), np.float32)
    v, i = pallas_flat_topk(jnp.asarray(q), jnp.asarray(corpus),
                            jnp.asarray(sq), jnp.asarray(mask), 2,
                            chunk_size=2048, interpret=True)
    gv, gi = flat_search(jnp.asarray(q), jnp.asarray(corpus), k=2,
                         metric="l2-squared",
                         corpus_sqnorms=jnp.asarray(sq), precision="bf16")
    v, i, gv, gi = map(np.asarray, (v, i, gv, gi))
    # the true nearest neighbor is each query's own corpus row; a
    # bucket can hide at most the SECOND hit, never the first
    assert (i[:, 0] == gi[:, 0]).all()
    agree = np.mean([len(set(i[r]) & set(gi[r])) for r in range(16)]) / 2
    assert agree >= 0.9
    assert np.allclose(v[:, 0], gv[:, 0], rtol=1e-2, atol=1e-2)
    # ids are in-range and distances are real recomputable values;
    # atol scales with the bf16 cancellation error of q²-2qc+c² whose
    # terms are O(d)=O(64) even when the distance itself is ~0
    sel = corpus[i.reshape(-1)].reshape(16, 2, -1)
    d_chk = ((q[:, None, :] - sel) ** 2).sum(-1)
    assert np.allclose(d_chk, v, rtol=2e-2, atol=0.5)


def test_mask_excludes_and_pads():
    q, corpus, sq = _data(n=2048)
    mask = np.zeros(len(corpus), np.float32)
    mask[:64] = 1.0  # only 64 candidates allowed
    v, i = pallas_flat_topk(jnp.asarray(q), jnp.asarray(corpus),
                            jnp.asarray(sq), jnp.asarray(mask), 10,
                            chunk_size=512, interpret=True)
    i = np.asarray(i)
    live = i[i >= 0]
    assert (live < 64).all()
    # chunks with zero allowed rows contribute only -1 sentinels
    assert (np.asarray(v) <= 1e30).all()


def test_fully_masked_returns_sentinels():
    q, corpus, sq = _data(n=1024)
    mask = np.zeros(len(corpus), np.float32)
    v, i = pallas_flat_topk(jnp.asarray(q), jnp.asarray(corpus),
                            jnp.asarray(sq), jnp.asarray(mask), 5,
                            chunk_size=512, interpret=True)
    assert (np.asarray(i) == -1).all()


def test_rejects_non_divisible_chunk():
    q, corpus, sq = _data(n=1000)
    with pytest.raises(ValueError, match="chunk"):
        pallas_flat_topk(jnp.asarray(q), jnp.asarray(corpus),
                         jnp.asarray(sq),
                         jnp.asarray(np.ones(1000, np.float32)), 5,
                         chunk_size=512, interpret=True)


def test_failure_of_enabled_kernel_propagates(monkeypatch):
    """An ENABLED kernel that cannot lower raises into the request: the
    serving path never gives way to the XLA path in silence (a chip run
    that meant to exercise the kernel must not pass without it)."""
    import tempfile

    import weaviate_tpu.ops.pallas_flat as pf
    from weaviate_tpu.core.db import DB
    from weaviate_tpu.schema.config import (
        CollectionConfig,
        FlatIndexConfig,
        Property,
    )
    from weaviate_tpu.storage.objects import StorageObject

    monkeypatch.setenv("WEAVIATE_TPU_PALLAS_FLAT", "on")
    calls = []

    def boom(*a, **kw):
        calls.append(1)
        raise RuntimeError("no pallas lowering on this backend")

    monkeypatch.setattr(pf, "pallas_flat_topk", boom)
    db = DB(tempfile.mkdtemp())
    db.create_collection(CollectionConfig(
        name="PL", properties=[Property(name="t")],
        vector_config=FlatIndexConfig(distance="l2-squared",
                                      precision="bf16",
                                      flat_approx_recall=0.99)))
    col = db.get_collection("PL")
    vecs = np.eye(16, dtype=np.float32)
    col.put_batch([StorageObject(
        uuid=f"ef000000-0000-0000-0000-{i:012d}", collection="PL",
        properties={"t": f"d{i}"}, vector=vecs[i]) for i in range(16)])
    # the conftest forces an 8-device CPU mesh, which routes through the
    # mesh path before the pallas hook; pallas serves single-device
    idx = next(iter(col._shards.values()))._vector_indexes[""]
    idx.store.mesh = None
    for _ in range(2):
        with pytest.raises(RuntimeError, match="no pallas lowering"):
            col.vector_search(vecs[5], k=2)
    assert len(calls) == 2  # no latch: every request meets the kernel
    # switched off, the same collection answers from the XLA path
    monkeypatch.setenv("WEAVIATE_TPU_PALLAS_FLAT", "off")
    hits = col.vector_search(vecs[5], k=2)
    assert hits[0][0].properties["t"] == "d5"
    assert len(calls) == 2
    db.close()
