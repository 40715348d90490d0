"""Persistent compilation cache + shape-bucket prewarming (ISSUE 12).

The tentpole proof lives here: a subprocess populates the persistent
cache, the process restarts, and the restarted node's FIRST search
dispatch reports zero ``phase=compile`` device time (only ``cache_hit``/
``execute``) while returning bit-identical top-k to the cold run. The
satellite surfaces ride along — the ``/v1/debug/compile`` readiness
plane, the ``warming`` health field, the tightened budget knobs, and the
tiering-promotion / rebalance-warming compile-free paths.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from weaviate_tpu.monitoring import devtime
from weaviate_tpu.monitoring.metrics import DEVICE_TIME_SECONDS
from weaviate_tpu.utils import compile_cache, prewarm

REPO = Path(__file__).resolve().parent.parent


def _compile_observations() -> int:
    """Total ``phase=compile`` observations across every label set."""
    return sum(v for key, v in DEVICE_TIME_SECONDS._totals.items()
               if ("phase", "compile") in key)


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    # the suite may run where a harness placed jax's cache from outside;
    # these tests pin the unplaced behavior (and the one that pins the
    # placed behavior sets the variable for its own child)
    monkeypatch.delenv(compile_cache.ENV_JAX_DIR, raising=False)
    compile_cache.reset_for_tests()
    prewarm.reset_for_tests()
    devtime.reset()
    yield
    compile_cache.reset_for_tests()
    prewarm.reset_for_tests()
    devtime.reset()


# ---------------------------------------------------------------------------
# compile_cache wiring


class TestCompileCache:
    def test_disabled_by_default(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
        assert compile_cache.resolve_base_dir() is None
        assert not compile_cache.enabled()
        assert compile_cache.configure() is None

    def test_kill_switch_beats_explicit_dir(self, monkeypatch, tmp_path):
        monkeypatch.setenv(compile_cache.ENV_SWITCH, "off")
        assert compile_cache.configure(str(tmp_path / "cc")) is None
        assert not compile_cache.enabled()

    def test_configure_keys_directory_on_versions_and_topology(
            self, tmp_path):
        import jax
        import jaxlib

        path = compile_cache.configure(str(tmp_path / "cc"))
        assert path is not None and os.path.isdir(path)
        leaf = os.path.basename(path)
        assert jax.__version__ in leaf
        assert jaxlib.__version__ in leaf
        assert jax.default_backend() in leaf
        assert f"d{jax.device_count()}" in leaf
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enabled()
        st = compile_cache.stats()
        assert st["enabled"] and st["dir"] == path

    def test_env_dir_beats_knob(self, monkeypatch, tmp_path):
        from weaviate_tpu.utils.runtime_config import COMPILE_CACHE_DIR

        monkeypatch.setenv(compile_cache.ENV_DIR, str(tmp_path / "env"))
        COMPILE_CACHE_DIR.set_override(str(tmp_path / "knob"))
        try:
            assert compile_cache.resolve_base_dir() == str(
                tmp_path / "env")
        finally:
            COMPILE_CACHE_DIR.clear_override()
        # knob alone resolves too
        COMPILE_CACHE_DIR.set_override(str(tmp_path / "knob"))
        try:
            monkeypatch.delenv(compile_cache.ENV_DIR)
            assert compile_cache.resolve_base_dir() == str(
                tmp_path / "knob")
        finally:
            COMPILE_CACHE_DIR.clear_override()

    def test_jax_env_dir_is_used_as_is(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR places the cache from outside: the
        server's configure() call writes entries DIRECTLY there — no
        keyed sub-directory, no other directory set in code."""
        placed = tmp_path / "placed"
        child = (
            "import json, os, jax, jax.numpy as jnp\n"
            "from weaviate_tpu import server\n"
            "from weaviate_tpu.utils import compile_cache\n"
            "used = compile_cache.configure(\n"
            "    compile_cache.resolve_base_dir()\n"
            "    or server.DEFAULT_COMPILE_CACHE_DIR)\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.ones((5,))).block_until_ready()\n"
            "print(json.dumps({'used': used,\n"
            "    'jax': jax.config.jax_compilation_cache_dir,\n"
            "    'stats': compile_cache.stats()}))\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(placed),
                   PYTHONPATH=str(REPO))
        out = subprocess.run([sys.executable, "-c", child], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        got = json.loads(out.stdout.strip().splitlines()[-1])
        assert got["used"] == got["jax"] == got["stats"]["dir"] == str(
            placed)
        assert got["stats"]["misses"] >= 1
        names = os.listdir(placed)
        assert names and all(
            os.path.isfile(placed / n) for n in names), names

    def test_server_default_is_fixed_dir_in_checkout(self):
        """Unplaced, the server's cache base is <checkout>/.jax_cache —
        never under the data directory, and no component of the keyed
        path is a temporary name, a pid or a time."""
        from weaviate_tpu import server

        assert compile_cache.resolve_base_dir() is None
        assert server.DEFAULT_COMPILE_CACHE_DIR == str(REPO / ".jax_cache")
        keyed = compile_cache.keyed_dir(server.DEFAULT_COMPILE_CACHE_DIR)
        assert os.path.dirname(keyed) == str(REPO / ".jax_cache")
        import re

        assert re.fullmatch(r"jax[\d.]+\w*-jaxlib[\d.]+\w*-cpu-d\d+",
                            os.path.basename(keyed)), keyed

    def test_configure_after_first_compile_engages_cache(self, tmp_path):
        """jax latches its cache check on the FIRST compile of the
        process; configure() must unlatch it so mid-process (re)config
        actually engages — not just config-before-any-jit."""
        import jax
        import jax.numpy as jnp

        # latch the once-per-process check with the cache OFF
        jax.jit(lambda x: x + 1)(jnp.ones((3,))).block_until_ready()
        assert compile_cache.configure(str(tmp_path / "cc")) is not None
        _h0, m0 = compile_cache.counters()
        jax.jit(lambda x: x * 2 + 1)(
            jnp.ones((4, 3))).block_until_ready()
        _h1, m1 = compile_cache.counters()
        assert m1 > m0, "cache never engaged after mid-process configure"
        assert compile_cache.stats()["entries"] > 0

    def test_event_listener_counts_hits_and_misses(self):
        from weaviate_tpu.monitoring.metrics import COMPILE_CACHE_EVENTS

        h0 = COMPILE_CACHE_EVENTS.value(event="hit")
        m0 = COMPILE_CACHE_EVENTS.value(event="miss")
        compile_cache._note_event("/jax/compilation_cache/cache_hits")
        compile_cache._note_event("/jax/compilation_cache/cache_misses")
        compile_cache._note_event("/jax/compilation_cache/cache_hits")
        compile_cache._note_event("/jax/some_other_event")  # ignored
        assert compile_cache.counters() == (2, 1)
        assert COMPILE_CACHE_EVENTS.value(event="hit") == h0 + 2
        assert COMPILE_CACHE_EVENTS.value(event="miss") == m0 + 1


# ---------------------------------------------------------------------------
# prewarm manifest + driver


def _flat_collection(tmp_path, name="Warmed", n=64, d=16):
    from weaviate_tpu.core.db import DB
    from weaviate_tpu.schema.config import (
        CollectionConfig,
        FlatIndexConfig,
    )
    from weaviate_tpu.storage.objects import StorageObject

    db = DB(str(tmp_path / "db"))
    col = db.create_collection(CollectionConfig(
        name=name,
        vector_config=FlatIndexConfig(distance="l2-squared")))
    rng = np.random.default_rng(11)
    col.put_batch([
        StorageObject(uuid=f"00000000-0000-0000-0000-{i:012d}",
                      collection=name, properties={"i": i},
                      vector=rng.standard_normal(d).astype(np.float32))
        for i in range(n)
    ])
    return db, col


class TestPrewarmDriver:
    def test_manifest_programs_resolve(self):
        """Every registered program must be a real module-level attribute
        — a renamed jit must update the manifest (the graftlint rule
        catches the reverse direction: a new jit missing from it)."""
        import importlib

        for prog in prewarm.MANIFEST:
            mod, attr = prog.rsplit(".", 1)
            m = importlib.import_module(f"weaviate_tpu.{mod}")
            assert hasattr(m, attr), (
                f"manifest program {prog!r} does not resolve")

    def test_buckets_knob_parses_and_falls_back(self):
        from weaviate_tpu.utils.runtime_config import PREWARM_BUCKETS

        PREWARM_BUCKETS.set_override("16, 8,junk,0,8")
        try:
            assert prewarm.buckets() == [8, 16]
        finally:
            PREWARM_BUCKETS.clear_override()
        assert prewarm.buckets() == [8, 16, 32, 64]

    def test_plan_and_run_warm_the_lattice(self, tmp_path):
        from weaviate_tpu.monitoring.metrics import PREWARM_PROGRAMS
        from weaviate_tpu.monitoring.tracing import TRACER

        db, col = _flat_collection(tmp_path)
        try:
            specs = prewarm.plan_for_collection(col, bucket_list=[8, 16])
            assert len(specs) == 2
            w0 = PREWARM_PROGRAMS.value(outcome="warmed")
            TRACER.clear()
            report = prewarm.prewarm_collection(
                col, reason="test", bucket_list=[8, 16], block=True,
                force=True)
            assert len(report.warmed) == 2 and not report.failed
            assert report.to_dict()["coverage"] == 1.0
            assert PREWARM_PROGRAMS.value(outcome="warmed") == w0 + 2
            spans = [s for s in TRACER.recent(limit=512)
                     if s["name"] == "compile.prewarm"]
            assert {s["attributes"]["bucket"] for s in spans} == {8, 16}
            st = prewarm.stats()
            assert any(b.endswith("@16") for b in st["warmed_buckets"])
            assert not st["warming"]
        finally:
            db.close()

    def test_empty_and_disabled_paths(self, tmp_path, monkeypatch):
        from weaviate_tpu.core.db import DB
        from weaviate_tpu.schema.config import (
            CollectionConfig,
            FlatIndexConfig,
        )

        db = DB(str(tmp_path / "db"))
        try:
            col = db.create_collection(CollectionConfig(
                name="Empty",
                vector_config=FlatIndexConfig(distance="l2-squared")))
            # un-ingested index: no programs to pin
            assert prewarm.plan_for_collection(col) == []
            # disabled (no cache, no env): triggers are inert
            monkeypatch.delenv(prewarm.ENV_SWITCH, raising=False)
            assert not prewarm.enabled()
            assert prewarm.prewarm_collection(col, block=True) is None
            # env opt-in without a cache still enables the driver
            monkeypatch.setenv(prewarm.ENV_SWITCH, "on")
            assert prewarm.enabled()
        finally:
            db.close()

    def test_rewarm_of_live_index_is_skipped_not_redispatched(
            self, tmp_path):
        """Tiering thrash re-promotes the same open shard over and over;
        re-running its lattice against live traffic buys nothing — the
        per-index memo skips it. A rebuilt index (new object) warms
        afresh."""
        db, col = _flat_collection(tmp_path, name="Rewarm")
        try:
            first = prewarm.prewarm_collection(
                col, reason="test", bucket_list=[8], block=True,
                force=True)
            assert first.warmed == ["Rewarm/shard0/@8"]
            again = prewarm.prewarm_collection(
                col, reason="test", bucket_list=[8], block=True,
                force=True)
            assert again.warmed == []
            assert again.skipped == ["Rewarm/shard0/@8"]
        finally:
            db.close()

    def test_non_resident_index_reports_skipped(self, tmp_path):
        from weaviate_tpu.monitoring.metrics import PREWARM_PROGRAMS

        db, col = _flat_collection(tmp_path, name="Demoted")
        try:
            shard = col._get_shard("shard0")
            (idx,) = shard._vector_indexes.values()
            idx.demote_device()
            s0 = PREWARM_PROGRAMS.value(outcome="skipped")
            report = prewarm.prewarm_collection(
                col, reason="test", bucket_list=[8, 16], block=True,
                force=True)
            assert report.warmed == []
            assert report.skipped == ["Demoted/shard0/@8",
                                      "Demoted/shard0/@16"]
            assert report.to_dict()["coverage"] == 0.0
            assert PREWARM_PROGRAMS.value(outcome="skipped") == s0 + 2
        finally:
            db.close()

    def test_failed_spec_is_counted_not_raised(self):
        class Boom:
            def search(self, q, k):
                raise RuntimeError("no device")

        spec = prewarm._Spec("C", "shard0", "", Boom(), 8, 8, 10)
        report = prewarm._run([spec], reason="test")
        assert report.failed == ["C/shard0/@8"] and not report.warmed

    def test_async_run_reports_warming_until_idle(self, tmp_path):
        db, col = _flat_collection(tmp_path, name="Async")
        try:
            assert not prewarm.warming()
            prewarm.prewarm_collection(col, reason="test",
                                       bucket_list=[8], block=False,
                                       force=True)
            # registered synchronously: no scheduling race for readiness
            assert prewarm.warming()
            assert prewarm.wait_idle(timeout=30.0)
            assert prewarm.stats()["last_run"]["warmed"]
        finally:
            db.close()


# ---------------------------------------------------------------------------
# readiness surface: /v1/debug/compile + the warming health field


class TestDebugSurface:
    def test_debug_compile_and_ready_warming(self, tmp_path):
        from werkzeug.test import Client

        from weaviate_tpu.api.rest import RestAPI

        db, col = _flat_collection(tmp_path, name="Surface")
        try:
            prewarm.prewarm_collection(col, reason="test",
                                       bucket_list=[8], block=True,
                                       force=True)
            devtime.record("B", "S", "single", (8, 16), 0.5)
            api = RestAPI(db)
            client = Client(api)
            r = client.get("/v1/debug/compile")
            assert r.status_code == 200
            body = json.loads(r.get_data(as_text=True))
            assert body["cache"]["enabled"] is False
            assert body["prewarm"]["manifest"] == sorted(prewarm.MANIFEST)
            assert any(b.endswith("@8")
                       for b in body["prewarm"]["warmed_buckets"])
            assert body["devtime"]["phases"]["compile"] >= 1
            assert "B/S/single/(8, 16)" in body["devtime"]["identities"]
            # health carries the warming gate field
            r = client.get("/v1/.well-known/ready")
            assert r.status_code == 200
            assert json.loads(r.get_data(as_text=True)) == {
                "warming": False}
        finally:
            db.close()

    def test_debug_compile_is_qos_exempt(self):
        from weaviate_tpu.api.rest import RestAPI

        assert "debug_compile" in RestAPI._QOS_EXEMPT


# ---------------------------------------------------------------------------
# budget knobs: the compile-driven workarounds are tunable now


class TestBudgetKnobs:
    def test_finish_budget_rides_the_knob(self):
        from weaviate_tpu.cluster.node import ClusterNode
        from weaviate_tpu.utils.runtime_config import (
            CLUSTER_FINISH_BUDGET_S,
        )

        node = ClusterNode.__new__(ClusterNode)  # knob-only property
        assert node.finish_budget == ClusterNode.FINISH_BUDGET == 10.0
        CLUSTER_FINISH_BUDGET_S.set_override(2.5)
        try:
            assert node.finish_budget == 2.5
        finally:
            CLUSTER_FINISH_BUDGET_S.clear_override()
        assert node.finish_budget == 10.0


# ---------------------------------------------------------------------------
# the restart proof (acceptance): cache populated -> process restart ->
# first search dispatch is compile-free and bit-identical


_RESTART_CHILD = r"""
import json, os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["WEAVIATE_TPU_MESH"] = "off"
import numpy as np
from weaviate_tpu.utils import compile_cache
assert compile_cache.configure(sys.argv[1]) is not None
from weaviate_tpu.index.hnsw.hnsw import HNSWIndex
from weaviate_tpu.schema.config import HNSWIndexConfig
rng = np.random.default_rng(7)
n, d = 192, 16
corpus = rng.standard_normal((n, d)).astype(np.float32)
idx = HNSWIndex(d, HNSWIndexConfig(
    distance="l2-squared", ef_construction=32, max_connections=8,
    device_beam=True))
idx.add_batch(np.arange(n, dtype=np.int64), corpus)
assert idx._device_beam is not None, "device beam must drive this proof"
q = corpus[:4] + np.float32(0.01)
t0 = time.perf_counter()
res = idx.search(q, 5)
first_ms = (time.perf_counter() - t0) * 1000
from weaviate_tpu.monitoring import devtime
from weaviate_tpu.monitoring.metrics import DEVICE_TIME_SECONDS
compile_obs = sum(v for key, v in DEVICE_TIME_SECONDS._totals.items()
                  if ("phase", "compile") in key)
print(json.dumps({
    "snapshot": devtime.snapshot(),
    "phases": devtime.phase_counts(),
    "compile_obs": compile_obs,
    "cache": compile_cache.stats(),
    "ids": np.asarray(res.ids).tolist(),
    "dists": [[float(x) for x in row] for row in np.asarray(res.dists)],
    "first_ms": first_ms,
}))
"""


def _run_child(code: str, *args: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["WEAVIATE_TPU_MESH"] = "off"
    out = subprocess.run(
        [sys.executable, "-c", code, *args], cwd=str(REPO), env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, f"child failed:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_restart_pays_zero_compile_and_is_bit_identical(tmp_path):
    cache = str(tmp_path / "cc")
    cold = _run_child(_RESTART_CHILD, cache)
    # cold process: the one search identity paid a true compile, and the
    # cache recorded misses it wrote back as entries
    assert list(cold["snapshot"].values()) == ["compile"]
    assert cold["cache"]["misses"] > 0 and cold["cache"]["entries"] > 0

    warm = _run_child(_RESTART_CHILD, cache)
    # restarted process: the SAME first dispatch deserialized off disk —
    # zero phase=compile device time anywhere, only cache_hit/execute
    assert list(warm["snapshot"].values()) == ["cache_hit"]
    assert warm["compile_obs"] == 0
    assert warm["phases"]["compile"] == 0
    assert warm["cache"]["hits"] > 0 and warm["cache"]["misses"] == 0
    # ... and the answers are bit-identical to the cold run
    assert warm["ids"] == cold["ids"]
    assert warm["dists"] == cold["dists"]


# regression for the tightened seed-write workaround: a prewarmed
# (persistent-cache-warmed) node completes the seed write within the
# NORMAL op budget — the 120s tracing-e2e deadline is a cold-cache
# allowance, not a structural requirement

_SEED_CHILD = r"""
import json, os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["WEAVIATE_TPU_MESH"] = "off"
cache_dir, data_dir, phase = sys.argv[1], sys.argv[2], sys.argv[3]
import numpy as np
from weaviate_tpu.utils import compile_cache
assert compile_cache.configure(cache_dir) is not None
from weaviate_tpu.cluster import ClusterNode, InProcTransport
from weaviate_tpu.cluster.resilience import Deadline
from weaviate_tpu.schema.config import (CollectionConfig, HNSWIndexConfig,
                                        Property, ReplicationConfig,
                                        ShardingConfig)
from weaviate_tpu.storage.objects import StorageObject
node = ClusterNode("n0", ["n0"], InProcTransport({}, "n0"), data_dir)
stop = time.monotonic() + 10
while not node.raft.is_leader():
    assert time.monotonic() < stop, "no leader"
    time.sleep(0.02)
node.create_collection(CollectionConfig(
    name="Seeded", properties=[Property(name="body")],
    vector_config=HNSWIndexConfig(distance="l2-squared",
                                  ef_construction=32, max_connections=8,
                                  device_beam=True),
    sharding=ShardingConfig(desired_count=2),
    replication=ReplicationConfig(factor=1)))
rng = np.random.default_rng(3)
objs = [StorageObject(uuid=f"00000000-0000-0000-0000-{i:012d}",
                      collection="Seeded", properties={"body": f"d{i}"},
                      vector=rng.standard_normal(16).astype(np.float32))
        for i in range(32)]
budget = float(node.op_budget) if phase == "warm" else 120.0
t0 = time.perf_counter()
node.put_batch("Seeded", objs, consistency="ONE",
               deadline=Deadline(budget, op="seed"))
dt = time.perf_counter() - t0
node.quiesce(); node.close()
print(json.dumps({"seed_s": dt, "budget": budget}))
"""


def test_prewarmed_node_seed_write_within_normal_op_budget(tmp_path):
    cache = str(tmp_path / "cc")
    cold = _run_child(_SEED_CHILD, cache, str(tmp_path / "n-cold"),
                      "cold")
    assert cold["budget"] == 120.0
    # fresh process, warmed cache, FRESH data dir: the whole first-touch
    # apply path (shard open, index creation, construction compile) fits
    # the normal op budget — DeadlineExceeded would fail the child
    warm = _run_child(_SEED_CHILD, cache, str(tmp_path / "n-warm"),
                      "warm")
    assert warm["budget"] < 120.0
    assert warm["seed_s"] < warm["budget"]


# ---------------------------------------------------------------------------
# tiering promotion: first post-promotion query is compile-free


def test_promotion_prewarms_lattice_first_query_compile_free(
        tmp_path, monkeypatch):
    from weaviate_tpu.cluster.resilience import Deadline
    from weaviate_tpu.core.db import DB
    from weaviate_tpu.schema.config import (
        CollectionConfig,
        HNSWIndexConfig,
        MultiTenancyConfig,
    )
    from weaviate_tpu.storage.objects import StorageObject
    from weaviate_tpu.utils.runtime_config import PREWARM_BUCKETS

    monkeypatch.setenv(prewarm.ENV_SWITCH, "on")
    PREWARM_BUCKETS.set_override("8,16")
    d = 16
    db = DB(str(tmp_path / "db"), tiering_budget_bytes=1 << 62)
    try:
        col = db.create_collection(CollectionConfig(
            name="Promo",
            vector_config=HNSWIndexConfig(
                distance="l2-squared", ef_construction=32,
                max_connections=8, device_beam=True),
            multi_tenancy=MultiTenancyConfig(enabled=True)))
        col.add_tenant("t0")
        rng = np.random.default_rng(5)
        vecs = rng.standard_normal((96, d)).astype(np.float32)
        col.put_batch([
            StorageObject(uuid=f"t0-{i:06d}", collection="Promo",
                          properties={"i": i}, vector=vecs[i],
                          tenant="t0")
            for i in range(96)], tenant="t0")
        q = vecs[:4] + np.float32(0.01)
        col.vector_search_batch(q, 10, tenant="t0",
                                deadline=Deadline(60.0, op="warm"))

        # drain the idle tenant all the way to disk
        db.tiering.cold_after_s = 0.0
        time.sleep(0.01)
        db.tiering.tick()
        db.tiering.tick()
        states = {k: e["state"]
                  for k, e in db.tiering.stats()["tenants"].items()}
        assert states.get("Promo/t0") == "cold", states
        db.tiering.cold_after_s = 3600.0

        # first touch promotes; the promotion fires the async lattice
        # prewarm (buckets 8 and 16) once the shard is device-resident
        res = col.vector_search_batch(q, 10, tenant="t0",
                                      deadline=Deadline(60.0, op="cold"))
        assert all(len(r) == 10 for r in res)
        assert prewarm.wait_idle(timeout=60.0), "promotion prewarm hung"
        st = prewarm.stats()
        assert any(b.startswith("Promo/tenant-t0/") and b.endswith("@16")
                   for b in st["warmed_buckets"]), st["warmed_buckets"]

        # a batch landing in the NEVER-QUERIED pow2 bucket (12 -> 16)
        # must execute, not compile: the lattice was warmed for it
        before = _compile_observations()
        res = col.vector_search_batch(
            np.repeat(q, 3, axis=0), 10, tenant="t0",
            deadline=Deadline(60.0, op="bucket16"))
        assert all(len(r) == 10 for r in res)
        assert _compile_observations() == before, \
            "post-promotion query in a prewarmed bucket paid a compile"
    finally:
        PREWARM_BUCKETS.clear_override()
        db.close()


# ---------------------------------------------------------------------------
# rebalance warming leg: first post-flip query on the destination is
# compile-free


def test_rebalance_warming_leg_first_postflip_query_compile_free(
        tmp_path, monkeypatch):
    from weaviate_tpu.cluster import ClusterNode, InProcTransport
    from weaviate_tpu.cluster.rebalance import Move
    from weaviate_tpu.schema.config import (
        CollectionConfig,
        HNSWIndexConfig,
        Property,
        ReplicationConfig,
        ShardingConfig,
    )
    from weaviate_tpu.storage.objects import StorageObject
    from weaviate_tpu.utils.runtime_config import PREWARM_BUCKETS

    monkeypatch.setenv(prewarm.ENV_SWITCH, "on")
    PREWARM_BUCKETS.set_override("8")
    registry = {}
    ids = ["n0", "n1"]
    nodes = [ClusterNode(nid, ids, InProcTransport(registry, nid),
                         str(tmp_path / nid)) for nid in ids]
    try:
        stop = time.monotonic() + 10
        while not any(n.raft.is_leader() for n in nodes):
            assert time.monotonic() < stop, "no leader"
            time.sleep(0.02)
        leader = next(n for n in nodes if n.raft.is_leader())
        leader.create_collection(CollectionConfig(
            name="Moved", properties=[Property(name="body")],
            vector_config=HNSWIndexConfig(
                distance="l2-squared", ef_construction=32,
                max_connections=8, device_beam=True),
            sharding=ShardingConfig(desired_count=1),
            replication=ReplicationConfig(factor=1)))
        stop = time.monotonic() + 10
        while not all(n.db.has_collection("Moved") for n in nodes):
            assert time.monotonic() < stop, "schema replication"
            time.sleep(0.02)
        rng = np.random.default_rng(9)
        vecs = rng.standard_normal((64, 16)).astype(np.float32)
        from weaviate_tpu.cluster.resilience import Deadline

        nodes[0].put_batch("Moved", [
            StorageObject(uuid=f"00000000-0000-0000-0000-{i:012d}",
                          collection="Moved",
                          properties={"body": f"d{i}"}, vector=vecs[i])
            for i in range(64)], consistency="ONE",
            deadline=Deadline(120.0, op="seed"))

        src = nodes[0]._state_for("Moved").replicas(0)[0]
        dst = next(n for n in ids if n != src)
        devtime.reset()
        before_move = _compile_observations()
        mids = nodes[0].rebalancer.execute(
            [Move("Moved", 0, src, dst)], wait=True, timeout=120.0)
        assert len(mids) == 1
        stop = time.monotonic() + 10
        while nodes[0].fsm.rebalance_ledger[mids[0]]["state"] != "dropped":
            assert time.monotonic() < stop, "move did not complete"
            time.sleep(0.05)

        # the warming leg ran: destination warmed its bucket-8 lattice
        # (paying the compile OFF the serving path, during the move)
        st = prewarm.stats()
        assert any(b.startswith("Moved/shard0/") and b.endswith("@8")
                   for b in st["warmed_buckets"]), st["warmed_buckets"]
        assert _compile_observations() > before_move

        # first post-flip query against the destination's own copy:
        # zero new compile-phase device time
        dst_node = next(n for n in nodes if n.id == dst)
        shard = dst_node.db.get_collection("Moved")._get_shard("shard0")
        (idx,) = shard._vector_indexes.values()
        before = _compile_observations()
        res = idx.search(vecs[:4] + np.float32(0.01), 5)
        assert (np.asarray(res.ids) >= 0).all()
        assert _compile_observations() == before, \
            "post-flip query on the warmed destination paid a compile"
    finally:
        PREWARM_BUCKETS.clear_override()
        for n in nodes:
            n.quiesce()
        for n in nodes:
            n.close()
