"""Mesh-sharded search tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from weaviate_tpu.ops import flat_search
from weaviate_tpu.parallel import (
    make_mesh,
    shard_corpus,
    sharded_flat_search,
    distributed_step,
)


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest must force 8 virtual devices"
    return make_mesh(8)


def test_sharded_matches_single_device(mesh, rng=None):
    rng = np.random.default_rng(7)
    n, d, b, k = 1024, 32, 4, 10
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    valid = np.ones(n, bool)
    valid[100:200] = False
    q = rng.standard_normal((b, d)).astype(np.float32)

    cj, vj = shard_corpus(jnp.asarray(corpus), jnp.asarray(valid), mesh)
    dist_s, ids_s = sharded_flat_search(
        cj, vj, jnp.asarray(q), k, metric="l2-squared", mesh=mesh, precision="fp32"
    )
    dist_1, ids_1 = flat_search(
        jnp.asarray(q), jnp.asarray(corpus), k, metric="l2-squared",
        valid_mask=jnp.asarray(valid),
    )
    np.testing.assert_allclose(np.asarray(dist_s), np.asarray(dist_1), rtol=2e-3, atol=2e-3)
    # ids may differ on exact ties; compare sets per query
    for a, b_ in zip(np.asarray(ids_s), np.asarray(ids_1)):
        assert set(a) == set(b_)


def test_distributed_step_ingest_then_search(mesh):
    rng = np.random.default_rng(3)
    n, d, b, k, m = 512, 16, 2, 5, 8
    corpus = jnp.zeros((n, d), jnp.float32)
    valid = jnp.zeros((n,), bool)
    cj, vj = shard_corpus(corpus, valid, mesh)

    new_vecs = rng.standard_normal((m, d)).astype(np.float32)
    # spread ids across different device ranges
    new_ids = np.asarray([0, 1, 70, 130, 200, 300, 400, 500], np.int32)
    q = new_vecs[:b]  # query with inserted vectors

    cj, vj, dists, ids = distributed_step(
        cj, vj, jnp.asarray(new_ids), jnp.asarray(new_vecs), jnp.asarray(q),
        k=k, metric="l2-squared", mesh=mesh, precision="fp32",
    )
    ids = np.asarray(ids)
    dists = np.asarray(dists)
    # each query's nearest neighbor is its own inserted id at distance ~0
    for qi in range(b):
        assert ids[qi, 0] == new_ids[qi]
        assert dists[qi, 0] == pytest.approx(0.0, abs=1e-4)
    # only the 8 inserted ids are live
    live = np.asarray(jax.device_get(vj)).sum()
    assert live == m


def test_make_mesh_raises_when_the_platform_has_too_few_devices():
    """No substitute devices: a mesh stands on the devices asked for."""
    have = len(jax.devices())
    with pytest.raises(ValueError, match=f"has {have}"):
        make_mesh(have + 1)
    assert make_mesh().devices.size == have


def test_default_mesh_lets_a_failing_platform_raise(monkeypatch):
    """A platform that does not come up is an error at the first use of
    the mesh — never "single-host mode" on whatever is left."""
    from weaviate_tpu.parallel import runtime

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.delenv("WEAVIATE_TPU_MESH", raising=False)
    monkeypatch.setattr(jax, "devices", boom)
    runtime.reset()
    try:
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            runtime.default_mesh()
    finally:
        runtime.reset()


def test_device_report_splits_bytes_by_shard(mesh):
    """The /v1/nodes device block: platform, kind, count and per-device
    bytes — on the CPU backend summed from the shards of live arrays, so a
    row-sharded corpus shows up as equal shares, not on device 0."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from weaviate_tpu.parallel.runtime import device_report

    before = device_report()
    assert before["platform"] == "cpu" and before["count"] == len(
        jax.devices())
    assert before["kind"] == jax.devices()[0].device_kind
    rows = jax.device_put(
        np.zeros((8 * 1024, 64), np.float32),
        NamedSharding(mesh, P("shard", None)))
    one = jax.device_put(np.zeros((1024, 64), np.float32), jax.devices()[0])
    after = device_report()
    grew = [a - b for a, b in zip(after["bytes_in_use"],
                                  before["bytes_in_use"])]
    share = 1024 * 64 * 4
    assert grew[0] == 2 * share and grew[1:] == [share] * 7
    del rows, one
