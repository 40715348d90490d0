"""Device-resident layer-0 beam search vs the host lockstep loop.

Reference test model: hnsw recall tests — the device walk must match the
host walk's recall on the same graph, handle tombstones (traversable,
not returned), and track graph mutations through the adjacency mirror.
"""

import json

import jax
import numpy as np
import pytest

from weaviate_tpu.index.hnsw.hnsw import HNSWIndex
from weaviate_tpu.schema.config import HNSWIndexConfig

# a test that builds a 3k-node graph and compiles the beam program
# (~10-20s each on the virtual-CPU platform) is full-CI tier, not tier-1;
# the walk's selection rule builds nothing and runs in tier-1
slow = pytest.mark.slow


def _build(n=3000, d=32, seed=0, **kw):
    rng = np.random.default_rng(seed)
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    cfg = HNSWIndexConfig(distance="l2-squared", ef_construction=64,
                          max_connections=12, device_beam=True, **kw)
    idx = HNSWIndex(d, cfg)
    for s in range(0, n, 1000):
        e = min(n, s + 1000)
        idx.add_batch(np.arange(s, e, dtype=np.int64), corpus[s:e])
    return idx, corpus, rng


def _recall(idx, corpus, rng, k=10, nq=32):
    q = corpus[:nq] + 0.05 * rng.standard_normal(
        (nq, corpus.shape[1])).astype(np.float32)
    res = idx.search(q, k)
    d2 = ((q[:, None, :] - corpus[None]) ** 2).sum(-1)
    gt = np.argsort(d2, axis=1)[:, :k]
    return sum(len(set(res.ids[i].tolist()) & set(gt[i].tolist()))
               for i in range(nq)) / (nq * k)


@pytest.mark.parametrize("env, config_on, stray_file, built", [
    ("on", False, False, True),
    ("off", True, False, False),
    ("bogus", True, False, False),
    (None, True, False, True),
    (None, False, False, False),
    (None, False, True, False),
], ids=["env-on", "env-off", "env-bogus", "config-on", "config-off",
        "stray-verdict-file"])
def test_walk_selected_by_env_then_config(env, config_on, stray_file, built,
                                          tmp_path, monkeypatch):
    """WEAVIATE_TPU_DEVICE_BEAM (on/1/true enable, any other non-empty
    value disables), else config.device_beam, else off; nothing else
    selects the walk, a verdict file someone left behind included."""
    monkeypatch.delenv("WEAVIATE_TPU_DEVICE_BEAM", raising=False)
    if env is not None:
        monkeypatch.setenv("WEAVIATE_TPU_DEVICE_BEAM", env)
    if stray_file:
        p = tmp_path / "verdicts.json"  # the deleted flags file's format
        p.write_text(json.dumps({"device_beam": {
            "enabled": True, "platform": jax.default_backend()}}))
        monkeypatch.setenv("WEAVIATE_TPU_PERF_FLAGS", str(p))
    idx = HNSWIndex(8, HNSWIndexConfig(distance="l2-squared",
                                       precision="fp32",
                                       device_beam=config_on))
    assert (idx._device_beam is not None) is built


@slow
def test_device_beam_active_and_recall():
    idx, corpus, rng = _build()
    assert idx._device_beam is not None, "device beam not enabled"
    assert _recall(idx, corpus, rng) >= 0.9


@slow
def test_device_beam_matches_host_walk():
    idx, corpus, rng = _build()
    q = corpus[:16] + 0.05 * rng.standard_normal((16, 32)).astype(
        np.float32)
    dev = idx.search(q, 10)
    # same index, device path off
    idx._device_beam = None
    idx.graph.dirty_hook = None
    host = idx.search(q, 10)
    agree = np.mean([
        len(set(dev.ids[i].tolist()) & set(host.ids[i].tolist())) / 10
        for i in range(16)])
    assert agree >= 0.9, agree


@slow
def test_construction_beam_builds_searchable_graph():
    """ef_construction walks run on device (VERDICT r3 #5): the graph built
    by the device construction beam must reach the same recall as the host
    construction walk."""
    idx, corpus, rng = _build(seed=3)
    assert idx._device_beam is not None
    # construction actually used the device path (would be False had every
    # sub-batch fallen back to the host walk)
    assert getattr(idx, "_beam_proven", False), \
        "construction never used the device beam"
    dev_recall = _recall(idx, corpus, rng)

    # host-constructed twin: same data, beam disabled from the start
    rng2 = np.random.default_rng(3)
    corpus2 = rng2.standard_normal((3000, 32)).astype(np.float32)
    cfg = HNSWIndexConfig(distance="l2-squared", ef_construction=64,
                          max_connections=12, device_beam=False)
    host_idx = HNSWIndex(32, cfg)
    for s in range(0, 3000, 1000):
        host_idx.add_batch(np.arange(s, s + 1000, dtype=np.int64),
                           corpus2[s:s + 1000])
    host_recall = _recall(host_idx, corpus2, rng2)
    assert dev_recall >= 0.9, dev_recall
    assert dev_recall >= host_recall - 0.05, (dev_recall, host_recall)


@slow
def test_construction_beam_cosine():
    rng = np.random.default_rng(11)
    n, d = 2000, 24
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True) + 1e-12
    cfg = HNSWIndexConfig(distance="cosine", ef_construction=48,
                          max_connections=12, device_beam=True)
    idx = HNSWIndex(d, cfg)
    idx.add_batch(np.arange(n, dtype=np.int64), corpus)
    assert getattr(idx, "_beam_proven", False)
    q = corpus[:24] + 0.05 * rng.standard_normal((24, d)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True) + 1e-12
    res = idx.search(q, 10)
    gt = np.argsort(1.0 - q @ corpus.T, axis=1)[:, :10]
    recall = np.mean([
        len(set(res.ids[i].tolist()) & set(gt[i].tolist())) / 10
        for i in range(24)])
    assert recall >= 0.9, recall


@slow
def test_tombstones_traversable_not_returned():
    idx, corpus, rng = _build(n=1500)
    dead = np.arange(0, 1500, 3, dtype=np.int64)
    idx.delete(dead)
    q = corpus[1:2] + 0.01 * rng.standard_normal((1, 32)).astype(
        np.float32)
    res = idx.search(q, 20)
    live = res.ids[res.ids >= 0]
    assert len(live) and not set(live.tolist()) & set(dead.tolist())


@slow
def test_mirror_tracks_incremental_inserts():
    idx, corpus, rng = _build(n=1000)
    assert _recall(idx, corpus, rng) >= 0.85  # syncs the mirror once
    extra = rng.standard_normal((500, 32)).astype(np.float32)
    idx.add_batch(np.arange(1000, 1500, dtype=np.int64), extra)
    q = extra[:8]
    res = idx.search(q, 5)
    # the new points are their own nearest neighbors: the mirror must have
    # scattered the fresh adjacency rows before this search
    hits = sum(1000 + i in set(res.ids[i].tolist()) for i in range(8))
    assert hits >= 7, res.ids[:, 0]


@slow
def test_filtered_queries_stay_on_host_path():
    idx, corpus, rng = _build(n=1200)
    allow = np.zeros(2048, bool)
    allow[:600] = True
    q = corpus[:4]
    res = idx.search(q, 5, allow_list=allow[:idx.graph.capacity]
                     if idx.graph.capacity < 2048 else allow)
    live = res.ids[res.ids >= 0]
    assert (live < 600).all()


@slow
def test_cosine_metric_normalizes_queries():
    rng = np.random.default_rng(3)
    n, d = 1200, 24
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    cfg = HNSWIndexConfig(distance="cosine", ef_construction=48,
                          max_connections=8, device_beam=True)
    idx = HNSWIndex(d, cfg)
    idx.add_batch(np.arange(n, dtype=np.int64), corpus)
    assert idx._device_beam is not None
    # deliberately UNNORMALIZED query with a large norm
    q = (corpus[7] * 5.0)[None, :]
    res = idx.search(q, 5)
    assert res.ids[0, 0] == 7
    # cosine distance of a vector with itself ~ 0 (not negative/off-scale)
    assert -1e-3 <= float(res.dists[0, 0]) < 0.05


@slow
def test_masked_device_beam_filtered_search():
    """High-selectivity filters now ride the device beam too (VERDICT r3
    #3: the `allow_list is None` restriction is gone): the walk stays
    unfiltered (ACORN-style connectivity) while the device tracks the
    best ALLOWED nodes seen; results must be allowed-only and match the
    host sweep's recall."""
    idx, corpus, rng = _build(n=3000, seed=5)
    assert idx._device_beam is not None
    n = 3000
    allow = np.zeros(idx.graph.capacity, bool)
    allow[rng.choice(n, int(0.6 * n), replace=False)] = True
    # selectivity 60% > filter_flat_selectivity -> sweep tier; force the
    # cutoff low so the flat tier can't absorb it
    idx.config.flat_search_cutoff = 10

    q = corpus[:24] + 0.05 * rng.standard_normal((24, 32)).astype(np.float32)
    dev = idx.search(q, 10, allow_list=allow)
    assert getattr(idx, "_beam_proven", False), \
        "filtered search never used the device beam"
    live = dev.ids[dev.ids >= 0]
    assert len(live) and allow[live].all()

    d2 = ((q[:, None, :] - corpus[None]) ** 2).sum(-1)
    d2[:, ~allow[:n]] = np.inf
    gt = np.argsort(d2, axis=1)[:, :10]
    dev_recall = np.mean([
        len(set(dev.ids[i].tolist()) & set(gt[i].tolist())) / 10
        for i in range(24)])

    idx._device_beam = None
    idx.graph.dirty_hook = None
    host = idx.search(q, 10, allow_list=allow)
    host_recall = np.mean([
        len(set(host.ids[i].tolist()) & set(gt[i].tolist())) / 10
        for i in range(24)])
    assert dev_recall >= 0.85, dev_recall
    assert dev_recall >= host_recall - 0.05, (dev_recall, host_recall)


@slow
def test_masked_device_beam_respects_deletes():
    """Tombstoned ids must not surface through the kept track even when
    the allowlist still has them set."""
    idx, corpus, rng = _build(n=1500, seed=7)
    idx.config.flat_search_cutoff = 10
    allow = np.ones(idx.graph.capacity, bool)
    dead = np.arange(0, 1500, 3, dtype=np.int64)
    idx.delete(dead)
    q = corpus[1:9] + 0.01 * rng.standard_normal((8, 32)).astype(np.float32)
    res = idx.search(q, 20, allow_list=allow)
    live = res.ids[res.ids >= 0]
    assert len(live) and not set(live.tolist()) & set(dead.tolist())
