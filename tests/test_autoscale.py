"""Closed-loop autoscaling suite (docs/autoscale.md).

Covers the autoscale-decision ledger FSM (lifecycle, illegal
transitions, the single-live-decision invariant, coordinator-takeover
re-commit, compaction, snapshot/restore), the serving-signal plumbing
(limiter p99 EWMA, per-lane shed-rate EWMA, the gossip ``serving``
advert, worst-not-mean aggregation), the hysteretic policy (oscillating
load at the threshold produces ZERO actions, cooldown and a live
rebalance ledger block evaluation, scale-in refused below min_nodes /
replication factor, follower ticks no-op), leader-crash recovery
(a ``decided`` entry is aborted by the next leader; a crashed
``actuating`` drain resumes on adoption), the worker/REST control
surface, and THE acceptance chaos scenario: a diurnal traffic ramp
(~10x) grows the cluster 3 -> 6 under seeded drop/latency faults with
one leader killed between decision-journal and actuation, then shrinks
back — p99 inside SLO, zero lost acked writes, zero writes rejected
during scale-in, and a compile-free joiner.
"""

import itertools
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from weaviate_tpu.cluster import (
    ChaosTransport,
    ClusterNode,
    InProcTransport,
)
from weaviate_tpu.cluster.autoscale import INTERVAL_S, Autoscaler
from weaviate_tpu.cluster.fsm import AUTOSCALE_TERMINAL, SchemaFSM
from weaviate_tpu.monitoring.metrics import AUTOSCALE_DECISIONS
from weaviate_tpu.monitoring.tracing import TRACER
from weaviate_tpu.schema.config import (
    CollectionConfig,
    FlatIndexConfig,
    Property,
    ReplicationConfig,
    ShardingConfig,
)
from weaviate_tpu.serving.limiter import AIMDLimiter
from weaviate_tpu.serving.qos import (
    AdmissionController,
    LaneConfig,
    QosRejected,
)
from weaviate_tpu.storage.objects import StorageObject
from weaviate_tpu.utils.runtime_config import (
    AUTOSCALE_COOLDOWN_S,
    AUTOSCALE_ENABLED,
    AUTOSCALE_MAX_NODES,
    AUTOSCALE_MIN_NODES,
    AUTOSCALE_P99_TARGET_MS,
)

# fault the replica data plane only: raft/gossip control stays clean so
# leadership, the ledger, and gossip liveness survive under fire
DATA_TYPES = (
    "replica_prepare", "replica_commit", "replica_abort", "replica_delete",
    "object_digest", "object_fetch", "object_push",
    "hashtree_leaves", "hashtree_items", "shard_export", "shard_drop",
)


@pytest.fixture(autouse=True)
def _clear_autoscale_knobs():
    yield
    for dv in (AUTOSCALE_ENABLED, AUTOSCALE_P99_TARGET_MS,
               AUTOSCALE_COOLDOWN_S, AUTOSCALE_MIN_NODES,
               AUTOSCALE_MAX_NODES):
        dv.clear_override()


def wait_for(pred, timeout=10.0, interval=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timeout waiting for {msg}")


def _leader(nodes):
    for n in nodes:
        if n.raft.is_leader():
            return n
    return None


def _cfg(factor=1, shards=6, name="Doc"):
    return CollectionConfig(
        name=name,
        properties=[Property(name="body")],
        vector_config=FlatIndexConfig(distance="l2-squared",
                                      precision="fp32"),
        sharding=ShardingConfig(desired_count=shards),
        replication=ReplicationConfig(factor=factor),
    )


def _objs(n, dims=8, start=0, name="Doc"):
    out = []
    for i in range(start, start + n):
        v = np.zeros(dims, np.float32)
        v[i % dims] = 1.0
        out.append(StorageObject(
            uuid=f"00000000-0000-0000-0000-{i:012d}",
            collection=name,
            properties={"body": f"doc {i}"},
            vector=v,
        ))
    return out


def _make_cluster(tmp_path, ids, chaos_seed=None):
    registry = {}
    nodes, chaos = [], {}
    for i, nid in enumerate(ids):
        t = InProcTransport(registry, nid)
        if chaos_seed is not None:
            t = ChaosTransport(t, seed=chaos_seed + i)
            chaos[nid] = t
        nodes.append(ClusterNode(nid, ids, t, str(tmp_path / nid)))
    wait_for(lambda: any(n.raft.is_leader() for n in nodes),
             msg="leader election")
    return nodes, registry, chaos


def _teardown(nodes):
    for n in nodes:
        try:
            n.quiesce()
        except Exception:
            pass
    for n in nodes:
        try:
            n.close()
        except Exception:
            pass


def _add_node(registry, ids_now, nid, tmp_path, chaos=None,
              chaos_seed=None):
    t = InProcTransport(registry, nid)
    if chaos is not None:
        t = ChaosTransport(t, seed=chaos_seed)
        chaos[nid] = t
    return ClusterNode(nid, sorted(set(ids_now) | {nid}), t,
                       str(tmp_path / nid))


def _converge(nodes, cls, rounds=20):
    for _ in range(rounds):
        if sum(n.anti_entropy_once(cls) for n in nodes) == 0:
            return
    raise AssertionError(f"no zero-move anti-entropy round in {rounds}")


def _sig(nodes=1, p99=0.0, shed=0.0, hbm=0.0, depth=0, debt=0):
    return {"nodes": nodes, "p99_worst_ms": p99, "shed_rate_max": shed,
            "hbm_pressure": hbm, "ingest_queue_depth": depth,
            "compaction_debt_bytes": debt}


# far over / inside / far under the default 750ms target band
HIGH = _sig(p99=2000.0)
OK = _sig(p99=400.0)
LOW = _sig(p99=10.0)


# ---------------------------------------------------------------------------
# decision-ledger FSM unit coverage


class TestAutoscaleLedgerFSM:
    def _fsm(self):
        return SchemaFSM(db=None)

    def _entry(self, did="d1", direction="out", node="", ts=1.0):
        return {"id": did, "direction": direction, "node": node,
                "coordinator": "n0", "created_ts": ts, "reason": "test"}

    def test_decision_lifecycle(self):
        fsm = self._fsm()
        r = fsm.apply({"op": "autoscale_decision", "entry": self._entry()})
        assert r["ok"] and r["id"] == "d1"
        e = fsm.autoscale_ledger["d1"]
        assert e["state"] == "decided"
        assert e["node"] == "" and e["error"] == ""
        assert fsm.apply({"op": "autoscale_advance", "id": "d1",
                          "state": "actuating", "node": "n9"})["ok"]
        assert fsm.autoscale_ledger["d1"]["node"] == "n9"
        assert fsm.apply({"op": "autoscale_advance", "id": "d1",
                          "state": "done"})["ok"]
        assert fsm.autoscale_ledger["d1"]["state"] == "done"

    def test_illegal_transitions_rejected(self):
        fsm = self._fsm()
        fsm.apply({"op": "autoscale_decision", "entry": self._entry()})
        # decided cannot skip straight to done
        assert not fsm.apply({"op": "autoscale_advance", "id": "d1",
                              "state": "done"})["ok"]
        fsm.apply({"op": "autoscale_advance", "id": "d1",
                   "state": "actuating"})
        # actuating cannot regress
        assert not fsm.apply({"op": "autoscale_advance", "id": "d1",
                              "state": "decided"})["ok"]
        fsm.apply({"op": "autoscale_advance", "id": "d1", "state": "done"})
        # terminal is terminal
        for state in ("decided", "actuating", "aborted"):
            assert not fsm.apply({"op": "autoscale_advance", "id": "d1",
                                  "state": state})["ok"]
        assert not fsm.apply({"op": "autoscale_advance", "id": "d1",
                              "state": "warming"})["ok"]
        assert not fsm.apply({"op": "autoscale_advance", "id": "zz",
                              "state": "done"})["ok"]

    def test_single_live_decision_and_duplicate_id(self):
        fsm = self._fsm()
        assert fsm.apply({"op": "autoscale_decision",
                          "entry": self._entry("d1")})["ok"]
        # the loop is a singleton: a second live decision is refused
        r = fsm.apply({"op": "autoscale_decision",
                       "entry": self._entry("d2", direction="in")})
        assert not r["ok"] and "still" in r["error"]
        fsm.apply({"op": "autoscale_advance", "id": "d1",
                   "state": "aborted"})
        # a terminal entry frees the slot; a duplicate id never lands
        assert fsm.apply({"op": "autoscale_decision",
                          "entry": self._entry("d2")})["ok"]
        assert not fsm.apply({"op": "autoscale_decision",
                              "entry": self._entry("d1")})["ok"]

    def test_required_fields_and_direction_validated(self):
        fsm = self._fsm()
        for missing in ("id", "direction", "coordinator"):
            e = self._entry()
            del e[missing]
            r = fsm.apply({"op": "autoscale_decision", "entry": e})
            assert not r["ok"] and missing in r["error"]
        r = fsm.apply({"op": "autoscale_decision",
                       "entry": self._entry(direction="sideways")})
        assert not r["ok"] and "direction" in r["error"]

    def test_same_state_recommit_is_coordinator_takeover(self):
        fsm = self._fsm()
        fsm.apply({"op": "autoscale_decision", "entry": self._entry()})
        fsm.apply({"op": "autoscale_advance", "id": "d1",
                   "state": "actuating", "node": "n9"})
        r = fsm.apply({"op": "autoscale_advance", "id": "d1",
                       "state": "actuating", "coordinator": "n7",
                       "ts": 9.0})
        assert r["ok"]
        e = fsm.autoscale_ledger["d1"]
        assert e["coordinator"] == "n7" and e["updated_ts"] == 9.0

    def test_forget_compacts_terminal_only(self):
        fsm = self._fsm()
        fsm.apply({"op": "autoscale_decision", "entry": self._entry("d1")})
        fsm.apply({"op": "autoscale_advance", "id": "d1",
                   "state": "aborted", "ts": 100.0})
        fsm.apply({"op": "autoscale_decision",
                   "entry": self._entry("d2", ts=2.0)})
        # the live d2 survives every compaction
        r = fsm.apply({"op": "autoscale_forget", "before": 200.0})
        assert r == {"ok": True, "removed": 1}
        assert set(fsm.autoscale_ledger) == {"d2"}
        fsm.apply({"op": "autoscale_advance", "id": "d2",
                   "state": "aborted", "ts": 500.0})
        # before-ts keeps younger terminal entries
        assert fsm.apply({"op": "autoscale_forget",
                          "before": 200.0})["removed"] == 0
        assert fsm.apply({"op": "autoscale_forget"})["removed"] == 1


def test_autoscale_ledger_survives_snapshot_restore(tmp_path):
    from weaviate_tpu.core.db import DB

    db_a = DB(str(tmp_path / "a"))
    db_b = DB(str(tmp_path / "b"))
    try:
        a, b = SchemaFSM(db_a), SchemaFSM(db_b)
        a.apply({"op": "autoscale_decision", "entry": {
            "id": "d1", "direction": "in", "node": "n2",
            "coordinator": "n0", "created_ts": 1.0, "reason": "low"}})
        a.apply({"op": "autoscale_advance", "id": "d1",
                 "state": "actuating"})
        b.restore(a.snapshot())
        assert b.autoscale_ledger["d1"]["state"] == "actuating"
        assert b.autoscale_ledger["d1"]["node"] == "n2"
    finally:
        db_a.close()
        db_b.close()


# ---------------------------------------------------------------------------
# serving-signal plumbing: limiter EWMA, shed EWMA, the gossip advert


def test_limiter_p99_ewma_smooths_window_p99():
    lim = AIMDLimiter(window=4)
    assert lim.p99_ewma == 0.0
    for _ in range(4):
        lim.record(0.1)
    assert lim.p99_ewma == pytest.approx(0.1)
    for _ in range(4):
        lim.record(0.3)
    assert lim.p99_ewma == pytest.approx(0.7 * 0.1 + 0.3 * 0.3)


def test_serving_stats_shed_rate_ewma_rises_and_decays():
    clk = {"t": 100.0}
    qos = AdmissionController(
        limiter=AIMDLimiter(initial=1, min_limit=1, max_limit=1, window=4),
        lanes=(LaneConfig("interactive", weight=8, max_queue_depth=0),),
        clock=lambda: clk["t"])
    base = qos.serving_stats()
    assert base["shed_rate"] == {"interactive": 0.0}
    assert set(base) == {"shed_rate", "p99_ewma_ms", "p99_target_ms"}
    held = qos.acquire("interactive")  # the only slot
    with pytest.raises(QosRejected):
        qos.acquire("interactive")  # depth 0: sheds, never queues
    held.__exit__(None, None, None)
    clk["t"] += 5.0
    burst = qos.serving_stats()["shed_rate"]["interactive"]
    assert 0.05 < burst <= 1.0  # one shed of two arrivals, tau-smoothed
    # a quiet window decays toward zero instead of freezing the burst
    clk["t"] += 5.0
    assert qos.serving_stats()["shed_rate"]["interactive"] < burst


def test_capacity_meta_carries_serving_block(tmp_path):
    node = ClusterNode("s0", ["s0"], InProcTransport({}, "s0"),
                       str(tmp_path / "s0"))
    try:
        wait_for(lambda: node.raft.is_leader(), msg="singleton leader")
        meta = node._capacity_meta()
        srv = meta["serving"]
        assert set(srv) >= {"shed_rate", "p99_ewma_ms", "p99_target_ms",
                            "ingest_queue_depth", "compaction_debt_bytes"}
        # the serving block composes WITH an injected capacity view
        node.capacity_fn = lambda: {"hbm_budget": 10, "hbm_used": 5}
        meta = node._capacity_meta()
        assert meta["hbm_budget"] == 10 and "serving" in meta
        # surfaced to operators next to the rebalance state
        view = node.cluster_view()
        assert "autoscale" in view
        assert view["autoscale"]["ledger"] == []
        # the evaluation tick rides the DB cycle runner
        stats = node.db.cycles.stats()
        assert "autoscale" in stats
        assert INTERVAL_S > 0
    finally:
        node.close()


def test_signal_aggregation_is_worst_not_mean_and_skips_dead():
    class _Gossip:
        def __init__(self, meta, alive):
            self._meta, self._alive = meta, alive

        def node_meta(self):
            return dict(self._meta)

        def alive(self, nid):
            return nid in self._alive

    meta = {
        "b": {"hbm_budget": 100.0, "hbm_used": 80.0,
              "serving": {"p99_ewma_ms": 50.0,
                          "shed_rate": {"interactive": 0.2, "batch": 0.0},
                          "ingest_queue_depth": 5,
                          "compaction_debt_bytes": 7}},
        # dead node: its (stale, huge) advert must not drive a decision
        "c": {"hbm_budget": 1.0, "hbm_used": 1.0,
              "serving": {"p99_ewma_ms": 9000.0,
                          "shed_rate": {"interactive": 1.0}}},
    }
    node = SimpleNamespace(
        id="a", all_nodes=["a", "b", "c"],
        gossip=_Gossip(meta, alive={"b"}),
        _capacity_meta=lambda: {
            "hbm_budget": 100.0, "hbm_used": 10.0,
            "serving": {"p99_ewma_ms": 500.0, "shed_rate": {},
                        "ingest_queue_depth": 2,
                        "compaction_debt_bytes": 3}})
    sig = Autoscaler(node).signals()
    assert sig["nodes"] == 2
    assert sig["p99_worst_ms"] == 500.0  # worst of the LIVE set
    assert sig["shed_rate_max"] == 0.2
    assert sig["hbm_pressure"] == pytest.approx(90.0 / 200.0)
    assert sig["ingest_queue_depth"] == 7
    assert sig["compaction_debt_bytes"] == 10


def test_classify_bands_have_a_dead_zone(tmp_path):
    node = SimpleNamespace(id="a")
    a = Autoscaler(node)
    AUTOSCALE_P99_TARGET_MS.set_override(750.0)
    knobs = Autoscaler._knobs()
    assert a._classify(_sig(p99=2000.0), knobs) == "high"
    assert a._classify(_sig(shed=0.10), knobs) == "high"
    assert a._classify(_sig(hbm=0.95), knobs) == "high"
    assert a._classify(_sig(p99=10.0), knobs) == "low"
    # the dead zone: inside the target but not far under it
    assert a._classify(_sig(p99=400.0), knobs) == "ok"
    # any single elevated term vetoes the low band
    assert a._classify(_sig(p99=10.0, hbm=0.6), knobs) == "ok"
    assert a._classify(_sig(p99=10.0, shed=0.01), knobs) == "ok"


# ---------------------------------------------------------------------------
# the hysteretic policy


def _single(tmp_path, nid="a0", registry=None):
    registry = {} if registry is None else registry
    node = ClusterNode(nid, [nid], InProcTransport(registry, nid),
                       str(tmp_path / nid))
    wait_for(lambda: node.raft.is_leader(), msg="singleton leader")
    return node, registry


def test_oscillating_load_at_threshold_produces_zero_actions(tmp_path):
    node, _ = _single(tmp_path)
    try:
        AUTOSCALE_ENABLED.set_override(True)
        a = node.autoscaler
        feed = itertools.cycle([HIGH, OK])
        a.signals_fn = lambda: dict(next(feed))
        a.provision_fn = lambda: pytest.fail("oscillation must not scale")
        worst = 0
        for _ in range(40):
            st = a.tick()
            worst = max(worst, st["breach_out"], st["breach_in"])
        assert node.fsm.autoscale_ledger == {}
        assert worst < a.breach_ticks  # the fuse never completes
    finally:
        _teardown([node])


def test_sustained_breach_scales_out_then_cooldown_holds(tmp_path):
    node, registry = _single(tmp_path)
    extra = []
    try:
        AUTOSCALE_ENABLED.set_override(True)
        node.create_collection(_cfg(factor=1, shards=4))
        node.put_batch("Doc", _objs(10), consistency="ONE")
        out_before = AUTOSCALE_DECISIONS.value(direction="out")

        def provision():
            extra.append(_add_node(registry, node.all_nodes, "a1",
                                   tmp_path))
            return "a1"

        a = node.autoscaler
        a.signals_fn = lambda: dict(HIGH)
        a.provision_fn = provision
        for _ in range(a.breach_ticks):
            a.tick()
        wait_for(lambda: any(
            e["state"] == "done"
            for e in node.fsm.autoscale_ledger.values()),
            timeout=30.0, msg="scale-out decision done")
        assert "a1" in node.all_nodes
        (entry,) = node.fsm.autoscale_ledger.values()
        assert entry["direction"] == "out" and entry["node"] == "a1"
        assert entry["coordinator"] == "a0"
        assert AUTOSCALE_DECISIONS.value(direction="out") \
            == out_before + 1

        # every decision is ONE trace with its actuation legs as children
        spans = TRACER.recent(limit=4096)
        root = next(s for s in spans if s["name"] == "autoscale.decide"
                    and s["attributes"].get("decision_id") == entry["id"])
        kids = {s["name"] for s in spans
                if s["parentSpanId"] == root["spanId"]}
        assert {"autoscale.provision", "autoscale.join"} <= kids

        # the actuation armed the cooldown: sustained pressure does not
        # double-scale inside the quiet window
        st = a.status()
        assert st["cooldown_remaining_s"] > 0
        for _ in range(a.breach_ticks + 2):
            st = a.tick()
        assert len(node.fsm.autoscale_ledger) == 1
        assert st["breach_out"] == 0  # cooldown returns before the fuse

        # force-evaluate (the operator override) skips the cooldown gate
        # but NEVER the safety guards
        a.provision_fn = None
        st = a.tick(force=True)
        assert st["last_refusal"] == "no provision hook"
        assert len(node.fsm.autoscale_ledger) == 1
    finally:
        _teardown([node] + extra)


def test_live_rebalance_ledger_blocks_evaluation(tmp_path):
    node, _ = _single(tmp_path)
    try:
        AUTOSCALE_ENABLED.set_override(True)
        r = node.raft.submit({"op": "rebalance_plan", "entry": {
            "id": "m1", "class": "Doc", "shard": 0, "src": "a0",
            "dst": "aX", "tenant": "", "prev_nodes": ["a0"],
            "final_nodes": ["aX"], "coordinator": "a0",
            "created_ts": 1.0}})
        assert r.get("ok")
        a = node.autoscaler
        a.signals_fn = lambda: dict(HIGH)
        for _ in range(a.breach_ticks + 2):
            st = a.tick()
        assert st["last_refusal"] == "rebalance ledger live"
        assert st["breach_out"] == 0  # blocked before the fuse burns
        assert node.fsm.autoscale_ledger == {}
        # the migration going terminal unblocks the loop
        node.raft.submit({"op": "rebalance_advance", "id": "m1",
                          "state": "aborted"})
        for _ in range(a.breach_ticks):
            st = a.tick()
        assert st["last_refusal"] == "no provision hook"
    finally:
        _teardown([node])


def test_scale_in_refused_below_min_nodes(tmp_path):
    node, _ = _single(tmp_path)
    try:
        AUTOSCALE_ENABLED.set_override(True)
        a = node.autoscaler
        a.signals_fn = lambda: dict(LOW)
        for _ in range(a.breach_ticks):
            st = a.tick()
        assert "floor" in st["last_refusal"]
        assert st["breach_in"] == 0  # refusal resets the fuse
        assert node.fsm.autoscale_ledger == {}
    finally:
        _teardown([node])


def test_scale_in_refused_below_replication_factor(tmp_path):
    nodes, _, _ = _make_cluster(tmp_path, ["f0", "f1", "f2"])
    try:
        AUTOSCALE_ENABLED.set_override(True)
        AUTOSCALE_MIN_NODES.set_override(1)
        leader = _leader(nodes)
        leader.create_collection(_cfg(factor=3, shards=2))
        wait_for(lambda: all(n.db.has_collection("Doc") for n in nodes),
                 msg="schema replication")
        a = leader.autoscaler
        a.signals_fn = lambda: dict(LOW, nodes=3)
        for _ in range(a.breach_ticks):
            st = a.tick()
        # min_nodes says 1, but a factor=3 collection pins the floor at 3
        assert "floor 3" in st["last_refusal"]
        assert leader.fsm.autoscale_ledger == {}

        # a follower's tick never evaluates, whatever its signals say
        follower = next(n for n in nodes if n is not leader)
        fa = follower.autoscaler
        fa.signals_fn = lambda: dict(HIGH)
        for _ in range(fa.breach_ticks + 2):
            st = fa.tick()
        assert st["leader"] is False
        assert st["breach_out"] == 0 and st["breach_in"] == 0
        assert follower.fsm.autoscale_ledger == {}
    finally:
        _teardown(nodes)


# ---------------------------------------------------------------------------
# leader-crash recovery through the ledger


def test_decided_entry_aborted_by_next_leader(tmp_path):
    nodes, _, chaos = _make_cluster(tmp_path, ["k0", "k1", "k2"],
                                    chaos_seed=71)
    try:
        AUTOSCALE_ENABLED.set_override(True)
        for n in nodes:
            n.autoscaler.signals_fn = lambda: dict(OK)
        leader = _leader(nodes)
        a = leader.autoscaler
        a.signals_fn = lambda: dict(HIGH)
        a.provision_fn = lambda: "never-booted"
        # the worker dies between journal and actuation — a SIGKILLed
        # leader as the rest of the cluster sees it
        a.crash_points.add("actuate")
        a.tick(force=True)
        others = [n for n in nodes if n is not leader]
        wait_for(lambda: any(
            e["state"] == "decided"
            for e in others[0].fsm.autoscale_ledger.values()),
            msg="decided entry replicated")

        # kill the old leader (full partition), elect a successor
        for n in others:
            chaos[n.id].partition(leader.id)
        chaos[leader.id].program(None, partition=True)
        wait_for(lambda: _leader(others) is not None, timeout=20.0,
                 msg="new leader after kill")
        new_leader = _leader(others)
        wait_for(lambda: not new_leader.gossip.alive(leader.id),
                 timeout=20.0, msg="old leader dead per gossip")

        # the next leader's routine tick adopts the orphaned decision:
        # decided == the dead leader's pressure read, which is stale —
        # the adoption verdict is ABORT, journaled, never silent
        def adopted():
            _leader(others).autoscaler.tick()
            return any(e["state"] == "aborted"
                       for e in new_leader.fsm.autoscale_ledger.values())

        wait_for(adopted, timeout=20.0, msg="adoption abort journaled")
        (entry,) = new_leader.fsm.autoscale_ledger.values()
        assert "coordinator lost" in entry["error"]
        assert entry["coordinator"] == new_leader.id  # takeover stamped
    finally:
        for ct in chaos.values():
            ct.clear()
        _teardown(nodes)


def test_crashed_actuating_drain_resumes_on_adoption(tmp_path):
    nodes, _, _ = _make_cluster(tmp_path, ["r0", "r1", "r2"])
    try:
        AUTOSCALE_ENABLED.set_override(True)
        leader = _leader(nodes)
        leader.create_collection(_cfg(factor=1, shards=4))
        wait_for(lambda: all(n.db.has_collection("Doc") for n in nodes),
                 msg="schema replication")
        leader.put_batch("Doc", _objs(12), consistency="ONE")

        released = []
        a = leader.autoscaler
        a.signals_fn = lambda: dict(LOW, nodes=3)
        a.decommission_fn = released.append
        a.crash_points.add("drain")
        for _ in range(a.breach_ticks):
            a.tick()
        # the worker journaled decided -> actuating (victim stamped),
        # then died before the drain
        wait_for(lambda: any(
            e["state"] == "actuating"
            for e in leader.fsm.autoscale_ledger.values())
            and not a.status()["actuating"],
            msg="crash left an actuating entry")
        (entry,) = leader.fsm.autoscale_ledger.values()
        victim = entry["node"]
        assert victim and victim != leader.id
        assert victim in leader.all_nodes

        # the restarted coordinator's next tick adopts its own entry:
        # actuating has a journaled target, and drain is re-runnable —
        # the verdict is RESUME, driven to done
        a.crash_points.clear()
        a.signals_fn = lambda: dict(OK)
        a.tick()
        wait_for(lambda: leader.fsm.autoscale_ledger[entry["id"]]["state"]
                 == "done", timeout=30.0, msg="resumed drain done")
        assert victim not in leader.all_nodes
        assert released == [victim]
        # zero-lost-writes contract of the underlying drain
        for o in _objs(12):
            assert leader.get("Doc", o.uuid, consistency="ONE") is not None
    finally:
        _teardown(nodes)


# ---------------------------------------------------------------------------
# control surface: worker verb + REST endpoint


def test_worker_ctl_autoscale_verbs(tmp_path):
    from weaviate_tpu.cluster.worker import WorkerControl

    node, _ = _single(tmp_path, nid="w0")
    try:
        ctl = WorkerControl(node)
        r = ctl.handle({"type": "ctl_autoscale", "action": "status"})
        assert r["ok"] and r["autoscale"]["enabled"] is False
        r = ctl.handle({"type": "ctl_autoscale", "action": "enable"})
        assert r["ok"] and r["autoscale"]["enabled"] is True
        assert AUTOSCALE_ENABLED.get() is True
        r = ctl.handle({"type": "ctl_autoscale", "action": "evaluate"})
        assert r["ok"] and "breach_out" in r["autoscale"]
        r = ctl.handle({"type": "ctl_autoscale", "action": "disable"})
        assert r["ok"] and r["autoscale"]["enabled"] is False
        r = ctl.handle({"type": "ctl_autoscale", "action": "explode"})
        assert not r["ok"] and "unknown autoscale action" in r["error"]
    finally:
        _teardown([node])


def test_rest_autoscale_endpoint_and_debug_serving(tmp_path):
    import json
    import urllib.error
    import urllib.request

    from weaviate_tpu.api.rest import RestAPI

    def call(base, method, path, body=None):
        req = urllib.request.Request(
            base + path,
            data=None if body is None else json.dumps(body).encode(),
            method=method, headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req) as r:
                d = r.read()
                return r.status, (json.loads(d) if d else None)
        except urllib.error.HTTPError as e:
            return e.code, None

    node, _ = _single(tmp_path, nid="s0")
    try:
        api = RestAPI(node.db, cluster=node)
        srv = api.serve(host="127.0.0.1", port=0, background=True)
        base = f"http://127.0.0.1:{srv.server_port}"
        try:
            status, out = call(base, "GET", "/v1/cluster/autoscale")
            assert status == 200
            assert out["autoscale"]["enabled"] is False
            assert out["autoscale"]["ledger"] == []
            status, _ = call(base, "POST", "/v1/cluster/autoscale",
                             {"action": "enable"})
            assert status == 200 and AUTOSCALE_ENABLED.get() is True
            status, out = call(base, "POST", "/v1/cluster/autoscale",
                               {"action": "evaluate"})
            assert status == 200 and "breach_out" in out["autoscale"]
            status, _ = call(base, "POST", "/v1/cluster/autoscale",
                             {"action": "sideways"})
            assert status == 422
            status, _ = call(base, "POST", "/v1/cluster/autoscale",
                             {"action": "disable"})
            assert status == 200 and AUTOSCALE_ENABLED.get() is False
            # the serving advert is visible in the operator debug view
            status, view = call(base, "GET", "/v1/debug/cluster")
            assert status == 200
            assert "serving" in view["nodes"]["s0"]["meta"]
        finally:
            api.shutdown()
    finally:
        _teardown([node])


# ---------------------------------------------------------------------------
# THE acceptance scenario: diurnal ramp, 3 -> 6 -> 3 under chaos with a
# leader killed between decision-journal and actuation


class TestDiurnalRamp:
    def test_chaos_diurnal_ramp_3_to_6_and_back(self, tmp_path,
                                                monkeypatch):
        # the join's warming leg must actually run, so the compile-free
        # assertion below measures the real prewarm-before-traffic path
        monkeypatch.setenv("WEAVIATE_TPU_PREWARM", "on")
        from weaviate_tpu.monitoring import devtime
        from weaviate_tpu.utils import prewarm

        AUTOSCALE_ENABLED.set_override(True)
        AUTOSCALE_P99_TARGET_MS.set_override(200.0)
        AUTOSCALE_COOLDOWN_S.set_override(0.6)
        AUTOSCALE_MIN_NODES.set_override(3)
        AUTOSCALE_MAX_NODES.set_override(6)

        ids = ["d0", "d1", "d2"]
        nodes, registry, chaos = _make_cluster(tmp_path, ids,
                                               chaos_seed=1300)
        cluster = {n.id: n for n in nodes}  # id -> running node
        dead: set[str] = set()  # partitioned ("killed") node ids
        retired: list[str] = []  # drained nodes pending close
        prov_state = {"next": 3}
        out_before = AUTOSCALE_DECISIONS.value(direction="out")
        in_before = AUTOSCALE_DECISIONS.value(direction="in")

        def live_nodes():
            return [n for nid, n in cluster.items() if nid not in dead]

        def any_live():
            return (_leader(live_nodes()) or live_nodes()[0])

        # offered-load model, fed straight into each node's AIMD limiter
        # (the limiter is injectable by design — docs/autoscale.md): the
        # advertised p99 is load seconds spread over live capacity, so
        # joining nodes genuinely lower the signal the loop reads and
        # draining nodes raise it — a closed loop, not a script.
        phase = {"load": 0.3}  # 0.3/3 nodes = 100ms: the ok band

        def feed():
            live = live_nodes()
            lat = phase["load"] / max(1, len(live))
            for n in live:
                lim = n.db.qos.limiter
                for _ in range(lim.window):
                    lim.record(lat)

        def provision():
            nid = f"d{prov_state['next']}"
            prov_state["next"] += 1
            joiner = _add_node(registry, list(any_live().all_nodes), nid,
                               tmp_path, chaos=chaos,
                               chaos_seed=1400 + prov_state["next"])
            chaos[nid].program(None, drop=0.02, jitter=0.005,
                               types=DATA_TYPES)
            tune(joiner)
            cluster[nid] = joiner
            return nid

        def tune(n):
            n.db.qos.limiter.window = 4
            a = n.autoscaler
            a.provision_fn = provision
            a.decommission_fn = retired.append

        for n in nodes:
            tune(n)

        # seeded drop + latency faults on the data plane for the whole
        # scenario; raft/gossip stay clean so the ledger survives
        for ct in chaos.values():
            ct.program(None, drop=0.02, jitter=0.005, types=DATA_TYPES)

        acked: list[str] = []
        frozen: list[str] = []
        lats: list[float] = []
        stop = threading.Event()

        def writer():
            i = 1000
            while not stop.is_set():
                batch = _objs(1, start=i)
                try:
                    any_live().put_batch("Doc", batch, consistency="ONE")
                    acked.extend(o.uuid for o in batch)
                except Exception as e:  # noqa: BLE001 — triaged below
                    if "frozen" in str(e):
                        frozen.append(str(e))
                i += 1
                time.sleep(0.01)

        def searcher():
            q = np.zeros((8,), np.float32)
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    any_live().vector_search("Doc", q, k=3)
                    lats.append(time.perf_counter() - t0)
                except Exception as e:  # noqa: BLE001 — triaged below
                    if "frozen" in str(e):
                        frozen.append(str(e))
                time.sleep(0.01)

        threads = [threading.Thread(target=writer, daemon=True),
                   threading.Thread(target=searcher, daemon=True)]
        try:
            leader = _leader(nodes)
            leader.create_collection(_cfg(factor=1, shards=8))
            wait_for(lambda: all(n.db.has_collection("Doc")
                                 for n in nodes), msg="schema replication")
            nodes[0].put_batch("Doc", _objs(40), consistency="ONE")
            for t in threads:
                t.start()

            def ledger():
                return dict(any_live().fsm.autoscale_ledger)

            def membership():
                return sorted(any_live().all_nodes)

            def settled():
                return (all(e["state"] in AUTOSCALE_TERMINAL
                            for e in ledger().values())
                        and not any(
                            e["state"] not in ("dropped", "aborted")
                            for e in
                            any_live().fsm.rebalance_ledger.values()))

            # the first scale-out decision dies between journal and
            # actuation: the coordinating leader is killed right after
            # the decided entry lands
            first_leader = leader
            first_leader.autoscaler.crash_points.add("actuate")

            # ---- daytime ramp: offered load ~10x -------------------------
            phase["load"] = 1.1  # 3 nodes: 367ms >> 200ms target
            killed = healed = False
            deadline = time.monotonic() + 240.0
            while time.monotonic() < deadline:
                feed()
                for n in list(live_nodes()):
                    try:
                        n.autoscaler.tick()
                    except Exception:
                        pass  # a deposed leader's submit may race
                if not killed and any(
                        e["state"] == "decided"
                        and e["coordinator"] == first_leader.id
                        for e in ledger().values()):
                    others = [n for n in live_nodes()
                              if n is not first_leader]
                    for n in others:
                        chaos[n.id].partition(first_leader.id)
                    chaos[first_leader.id].program(None, partition=True)
                    dead.add(first_leader.id)
                    killed = True
                if killed and not healed and any(
                        e["state"] == "aborted"
                        and "coordinator lost" in e.get("error", "")
                        for e in ledger().values()):
                    # the next leader adopted (and aborted) the dead
                    # leader's decision — "restart" the killed node
                    for ct in chaos.values():
                        ct.clear()
                        ct.program(None, drop=0.02, jitter=0.005,
                                   types=DATA_TYPES)
                    for n in cluster.values():
                        n.breakers.reset()
                    dead.discard(first_leader.id)
                    healed = True
                if len(membership()) >= 6 and settled():
                    break
                time.sleep(0.1)
            assert killed, "the first decision never journaled"
            assert healed, "no adoption abort from the next leader"
            assert len(membership()) >= 6, \
                f"never scaled to 6: {membership()}"
            aborted = [e for e in ledger().values()
                       if e["state"] == "aborted"
                       and e["coordinator"] != first_leader.id
                       and "coordinator lost" in e.get("error", "")]
            assert aborted, "the killed decision was not adopted"

            # the loop's own signal is back inside SLO at 6 nodes: the
            # same peak load spread over doubled capacity reads under
            # the 200ms target (let the EWMAs converge first)
            for _ in range(12):
                feed()
                time.sleep(0.02)
            sig = any_live().autoscaler.signals()
            assert sig["p99_worst_ms"] <= 200.0, sig

            # compile-free joiner: the join prewarmed the migrated
            # shards' program lattice before the routing flip, so the
            # joiner's first served query pays zero phase=compile device
            # time (devtime shows cache_hit/execute only)
            prewarm.wait_idle()
            joiner = cluster[f"d{prov_state['next'] - 1}"]
            compile_before = devtime.phase_counts()["compile"]
            q = np.zeros((8,), np.float32)
            for _ in range(20):  # retry through seeded drops
                try:
                    joiner.vector_search("Doc", q, k=3)
                    break
                except Exception:  # noqa: BLE001 — chaos fault
                    time.sleep(0.1)
            assert devtime.phase_counts()["compile"] == compile_before

            # ---- night: load falls away, the cluster shrinks back -------
            phase["load"] = 0.15  # low band at any size down to 3
            deadline = time.monotonic() + 240.0
            while time.monotonic() < deadline:
                feed()
                for n in list(live_nodes()):
                    try:
                        n.autoscaler.tick()
                    except Exception:
                        pass
                # a drained + decommissioned node is closed for real
                while retired:
                    nid = retired.pop()
                    gone = cluster.pop(nid, None)
                    if gone is not None:
                        _teardown([gone])
                if len(membership()) <= 3 and settled():
                    break
                time.sleep(0.1)
            assert len(membership()) <= 3, \
                f"never shrank back: {membership()}"

            stop.set()
            for t in threads:
                t.join(timeout=5)

            # ---- acceptance assertions -----------------------------------
            # zero writes rejected during scale-in (or ever): drains are
            # durability-preserving, never write-shedding
            assert not frozen, f"writes rejected: {frozen[:3]}"

            # serving p99 inside a sane wall-clock SLO throughout
            assert lats, "the searcher never completed a query"
            lats.sort()
            p99 = lats[min(len(lats) - 1, int(0.99 * (len(lats) - 1)))]
            assert p99 < 2.0, f"client p99 {p99:.3f}s out of SLO"

            # zero lost acked writes: heal, converge, then every acked
            # object must answer through routing
            for ct in chaos.values():
                ct.clear()
            survivors = list(cluster.values())
            for n in survivors:
                n.breakers.reset()
            wait_for(lambda: _leader(survivors) is not None,
                     msg="leadership after final heal")
            _converge(survivors, "Doc", rounds=30)
            reader = survivors[0]
            for uid in [o.uuid for o in _objs(40)] + acked:
                got = reader.get("Doc", uid, consistency="ONE")
                assert got is not None, f"lost acked write {uid}"

            # the decision ledger tells the whole story: >= 3 journaled
            # scale-outs (one aborted by adoption), >= 3 scale-ins
            assert AUTOSCALE_DECISIONS.value(direction="out") \
                - out_before >= 3
            assert AUTOSCALE_DECISIONS.value(direction="in") \
                - in_before >= 3
            done = [e for e in ledger().values() if e["state"] == "done"]
            assert sum(e["direction"] == "out" for e in done) >= 3
            assert sum(e["direction"] == "in" for e in done) >= 3

            # every decision is one trace; join and drain legs both ran
            spans = TRACER.recent(limit=TRACER.max_spans)
            roots = {s["spanId"]: s for s in spans
                     if s["name"] == "autoscale.decide"}
            legs = {s["name"] for s in spans
                    if s["parentSpanId"] in roots}
            assert {"autoscale.provision", "autoscale.join",
                    "autoscale.drain"} <= legs
        finally:
            stop.set()
            for ct in chaos.values():
                ct.clear()
            _teardown(list(cluster.values()))
