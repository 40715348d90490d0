"""Concurrent searches on a flat collection share scans: ``FlatIndex``
behind the coalescing dispatcher, by count and structure (never by wall
clock). N threads get row for row what serial ``search`` returns in fewer
batches than requests; whatever decides the compiled program or its arrays
keeps requests apart, and an allow mask does not: filtered requests share a
scan whatever their masks, a mask a row; a lone search runs one B = 1 scan and waits for
nothing; a request queued behind a running batch is led by hand-off, not by
its poll tick; an error reaches every member of its batch; every row bucket
and mask form is compiled at the first search, not under a later batch."""

import threading
import time

import numpy as np
import pytest

from weaviate_tpu.index import dispatch, flat
from weaviate_tpu.index.dispatch import CoalescingDispatcher, dispatch_group
from weaviate_tpu.index.flat import ROW_BUCKETS, FlatIndex
from weaviate_tpu.schema.config import FlatIndexConfig

D, ROWS = 32, 600
JOIN_S = 30


def _index(metric="cosine"):
    idx = FlatIndex(D, FlatIndexConfig(distance=metric))
    rng = np.random.default_rng(7)
    vecs = rng.standard_normal((ROWS, D)).astype(np.float32)
    idx.add_batch(np.arange(ROWS), vecs)
    queries = (vecs[:64] + 0.1 * rng.standard_normal((64, D))).astype(
        np.float32)
    return idx, queries


class _Gate:
    """Wraps an index's ``run_batch``: holds the FIRST batch until the
    test opens the gate (so what queues behind it is decided by the test,
    not the scheduler) and records every batch's rows, k and tags (a
    request's tag rides in its query's last component)."""

    def __init__(self, idx):
        self.idx, self.open, self.calls = idx, threading.Event(), []
        self.real = idx._dispatcher.run_batch
        idx._dispatcher.run_batch = self

    def __call__(self, q, k, masks, tier_key, rows):
        assert self.open.wait(JOIN_S)
        assert sum(rows) == q.shape[0]
        assert masks is None or len(masks) == len(rows)
        self.calls.append({"rows": q.shape[0], "k": k, "tier_key": tier_key,
                           "filtered": masks is not None,
                           "masks": len({id(m) for m in masks or ()})})
        return self.real(q, k, masks, tier_key=tier_key, rows=rows)

    def close(self):
        self.idx._dispatcher.run_batch = self.real

    def wait_leading(self):
        deadline = time.monotonic() + JOIN_S
        while not self.idx._dispatcher._draining:
            assert time.monotonic() < deadline, "nobody took the lead"
            time.sleep(0.001)

    def wait_pending(self, n):
        deadline = time.monotonic() + JOIN_S
        while time.monotonic() < deadline:
            with self.idx._dispatcher._lock:
                if len(self.idx._dispatcher._pending) >= n:
                    return
            time.sleep(0.001)
        raise AssertionError(f"{n} requests never queued")


def _behind_a_held_batch(idx, leader, queued):
    """Run ``leader`` (its lone batch is held), pile ``queued`` up behind
    it, let go, join; returns what the runner was handed, batch by batch."""
    gate = _Gate(idx)
    first, e1 = _run_threads([leader])
    gate.wait_leading()
    rest, e2 = _run_threads(queued)
    gate.wait_pending(len(queued))
    gate.open.set()
    _join(first + rest, e1 + e2)
    gate.close()
    return gate.calls


def _run_threads(fns):
    errs, threads = [], []

    def guard(fn):
        try:
            fn()
        except BaseException as e:  # the test re-raises it below
            errs.append(e)

    for fn in fns:
        threads.append(threading.Thread(target=guard, args=(fn,)))
        threads[-1].start()
    return threads, errs


def _join(threads, errs):
    for t in threads:
        t.join(JOIN_S)
        assert not t.is_alive()
    if errs:
        raise errs[0]


def _assert_same_answers(got, want):
    """Row for row the same hits. A B = 1 and a B > 1 product differ in
    their last bits (7.6e-6 here), so a rank may differ inside a tie, or
    at the k-th place where the tie's other half fell off the end."""
    assert got.ids.shape == want.ids.shape
    np.testing.assert_allclose(got.dists, want.dists, rtol=1e-5, atol=1e-4)
    for g, w, d in zip(got.ids, want.ids, want.dists):
        for j in np.flatnonzero(g != w):
            tied = np.flatnonzero(np.abs(d - d[j]) <= 1e-4)
            assert g[j] in w[tied] or j == len(w) - 1, (g, w, d)


@pytest.mark.parametrize("metric", ["cosine", "l2-squared"])
@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
def test_concurrent_searches_match_serial_in_fewer_batches(metric, masked):
    idx, queries = _index(metric)
    n = 40
    ks = [5 if i % 4 else 9 for i in range(n)]          # mixed k
    mask = None
    if masked:
        mask = np.zeros(ROWS, bool)
        mask[::3] = True
    # equal content, a different array object per request: one tenant's
    # precomputed mask arriving with each request
    serial = [idx.search(queries[i][None], ks[i],
                         None if mask is None else mask.copy())
              for i in range(n)]
    gate = _Gate(idx)
    got = {}

    def client(i):
        got[i] = idx.search(queries[i][None], ks[i],
                            None if mask is None else mask.copy())

    first, errs1 = _run_threads([lambda: client(0)])
    gate.wait_leading()
    rest, errs2 = _run_threads(
        [lambda i=i: client(i) for i in range(1, n)])
    gate.wait_pending(n - 1)
    gate.open.set()
    _join(first + rest, errs1 + errs2)
    for i in range(n):
        assert got[i].ids.shape == (1, ks[i])
        _assert_same_answers(got[i], serial[i])
        if masked:
            assert mask[got[i].ids[0]].all()
    # the leader's lone batch, then the 39 queued: full batches per k
    assert gate.calls[0]["rows"] == 1
    cap = ROW_BUCKETS[-1]
    assert sorted((c["k"], c["rows"]) for c in gate.calls[1:]) == sorted(
        [(5, cap)] * (30 // cap) + [(5, 30 % cap)]
        + [(9, cap)] * (9 // cap) + [(9, 9 % cap)])
    assert all(c["filtered"] == masked for c in gate.calls)


def test_incompatible_requests_never_share_a_batch():
    """k, ``approx_recall``, the ``dispatch_group`` token, the residency
    epoch and filtered against unfiltered each keep a request out of the
    others' batch; two requests equal in all of them share one, and two
    filtered ones share a scan under DIFFERENT masks, their answers
    differing as the masks do."""
    idx, queries = _index()
    idx.search(queries[:1], 5)
    gate = _Gate(idx)
    mask_a = np.zeros(ROWS, bool)
    mask_a[::2] = True
    mask_b = ~mask_a
    answers = {}

    def in_group(q):
        with dispatch_group(("hybrid", "rankedFusion")):
            return idx.search(q, 5)

    variants = {
        "leader": lambda q: idx.search(q, 5),
        "base1": lambda q: idx.search(q, 5),
        "base2": lambda q: idx.search(q, 5),
        "k": lambda q: idx.search(q, 6),
        "mask_a": lambda q: answers.setdefault(
            "a", idx.search(q, 5, mask_a)),
        "mask_b": lambda q: answers.setdefault(
            "b", idx.search(q, 5, mask_b)),
        "approx": lambda q: idx.search(q, 5, approx_recall=0.5),
        "token": in_group,
        "epoch": lambda q: idx.search(q, 5),
    }
    names = list(variants)

    def send(name):
        q = queries[:1].copy()
        q[0, -1] = names.index(name)        # the tag the runner reads
        return lambda: variants[name](q)

    real, seen = gate.real, []

    def recording(q, k, masks, tier_key, rows):
        seen.append(sorted(names[int(t)] for t in q[:, -1]))
        return real(q, k, masks, tier_key=tier_key, rows=rows)

    gate.real = recording
    threads, errs = _run_threads([send("leader")])
    gate.wait_leading()
    queued = [n for n in names if n not in ("leader", "epoch")]
    more, errs2 = _run_threads([send(n) for n in queued])
    gate.wait_pending(len(queued))
    # enqueued under the next residency epoch: what a demote + promote
    # between two arrivals leaves behind
    idx._residency_epoch += 1
    late, errs3 = _run_threads([send("epoch")])
    gate.wait_pending(len(queued) + 1)
    gate.open.set()
    _join(threads + more + late, errs + errs2 + errs3)
    shared = (["base1", "base2"], ["mask_a", "mask_b"])
    assert sorted(seen) == sorted(
        list(shared) + [[n] for n in names
                        if n not in shared[0] + shared[1]]), seen
    assert (answers["a"].ids % 2 == 0).all()
    assert (answers["b"].ids % 2 == 1).all()


@pytest.mark.parametrize("metric", ["cosine", "l2-squared"])
def test_different_masks_share_scans_each_answer_inside_its_own_mask(
        metric, monkeypatch):
    """Requests whose masks all differ (in length too: each is as long as
    the doc-id space was when its filter resolved) share scans, a mask a
    query row; a group of 5 pads to 8 rows whose padded masks allow
    nothing; a request of several rows repeats its one mask over them."""
    idx, queries = _index(metric)
    rng = np.random.default_rng(3)
    k, n = 5, 14
    masks = [rng.random(ROWS - 40 * (i % 3)) < 0.3 for i in range(n)]
    masks[2] = np.zeros(ROWS, bool)
    masks[2][[5, 9, 11]] = True              # allows fewer than k rows
    wide = 6                                 # this client sends two rows

    def ask(i):
        q = queries[i:i + 2] if i == wide else queries[i][None]
        return idx.search(q, k, masks[i])

    serial = [ask(i) for i in range(n)]
    scans = []
    real_scan = flat.flat_search

    def scan(q, *a, allow_mask=None, **kw):
        scans.append((q.shape[0], np.asarray(allow_mask)))
        return real_scan(q, *a, allow_mask=allow_mask, **kw)

    monkeypatch.setattr(flat, "flat_search", scan)

    def held(leader, queued):
        got = {}
        calls = _behind_a_held_batch(
            idx, lambda: got.update({leader: ask(leader)}),
            [lambda i=i: got.update({i: ask(i)}) for i in queued])
        assert sorted(got) == sorted([leader, *queued])
        for i, res in got.items():
            _assert_same_answers(res, serial[i])
            hit = res.ids[res.ids >= 0]
            assert masks[i][hit].all(), i    # inside ITS mask, no other's
        return calls

    # 13 requests, 14 rows behind the leader: two scans, not thirteen
    calls = held(0, range(1, n))
    # (the two-row request falls into the first or the second of them)
    assert [(c["rows"], c["masks"]) for c in calls] in (
        [(1, 1), (8, 8), (6, 5)], [(1, 1), (8, 7), (6, 6)])
    assert (serial[2].ids[0, 3:] == -1).all() and serial[2].ids[0, 2] >= 0
    assert [rows for rows, _ in scans[-3:]] == [1, 8, 8]
    assert [m.shape for _, m in scans[-3:]] == [
        (idx.capacity,), (8, idx.capacity), (8, idx.capacity)]
    assert not scans[-1][1][6:].any()        # padded rows allow nothing
    # a group of 5 pads to 8
    calls = held(0, range(1, 6))
    assert [(c["rows"], c["masks"]) for c in calls] == [(1, 1), (5, 5)]
    rows, stacked = scans[-1]
    assert rows == 8 and stacked.shape == (8, idx.capacity)
    assert stacked[:5].any(axis=1).all() and not stacked[5:].any()
    # two rows, one mask: the same mask row twice, then the next member's
    calls = held(0, [wide, 7])
    assert [(c["rows"], c["masks"]) for c in calls] == [(1, 1), (3, 2)]
    rows, stacked = scans[-1]
    assert rows == 4
    first = 0 if stacked[0, :len(masks[wide])].tolist() == \
        masks[wide].tolist() else 1
    np.testing.assert_array_equal(stacked[first], stacked[first + 1])
    np.testing.assert_array_equal(
        stacked[first, :len(masks[wide])], masks[wide])
    assert not stacked[first, len(masks[wide]):].any()


def test_a_lone_search_runs_one_b1_scan_and_waits_for_nothing(monkeypatch):
    idx, queries = _index()
    idx.search(queries[:1], 5)              # compiles every bucket
    scans, waits = [], []
    real_scan, real_wait = flat.flat_search, threading.Event.wait
    me = threading.get_ident()

    def scan(q, *a, **kw):
        scans.append(tuple(q.shape))
        return real_scan(q, *a, **kw)

    def wait(self, timeout=None):
        if threading.get_ident() == me:
            waits.append(timeout)
        return real_wait(self, timeout)

    monkeypatch.setattr(flat, "flat_search", scan)
    monkeypatch.setattr(threading.Event, "wait", wait)
    before = idx._dispatcher.handoffs
    for i in range(5):
        res = idx.search(queries[i][None], 5)
        assert res.ids.shape == (1, 5)
    assert scans == [(1, D)] * 5
    assert waits == []
    assert idx._dispatcher.ticks_expired == 0
    assert idx._dispatcher.handoffs == before
    # wider than the largest bucket: at its own width, as before
    wide = np.tile(queries, (2, 1))[:ROW_BUCKETS[-1] + 6]
    assert idx.search(wide, 5).ids.shape == (ROW_BUCKETS[-1] + 6, 5)
    assert scans[-1] == (ROW_BUCKETS[-1] + 6, D)


def test_rows_are_padded_to_the_buckets_and_dropped_before_hand_back(
        monkeypatch):
    idx, queries = _index("l2-squared")
    idx.search(queries[:1], 5)
    scans = []
    real_scan = flat.flat_search

    def scan(q, *a, **kw):
        scans.append(q.shape[0])
        return real_scan(q, *a, **kw)

    monkeypatch.setattr(flat, "flat_search", scan)
    one_by_one = np.concatenate(
        [idx.search(queries[i][None], 5).ids for i in range(20)])
    scans.clear()
    for rows, bucket in ((1, 1), (2, 4), (3, 4), (4, 4), (5, 8), (8, 8)):
        res = idx.search(queries[:rows], 5)
        assert res.ids.shape == res.dists.shape == (rows, 5)
        np.testing.assert_array_equal(res.ids[:20], one_by_one[:rows])
        assert scans[-1] == bucket
    assert set(scans) == set(ROW_BUCKETS)


def test_a_request_queued_behind_a_batch_is_led_by_hand_off(monkeypatch):
    """With the poll tick out of reach (an hour), the only way the queued
    request is ever led is the yielding leader's hand-off."""
    monkeypatch.setattr(dispatch, "POLL_TICK_S", 3600.0)
    gate, running = threading.Event(), threading.Event()
    calls = []

    def run_batch(q, k, allow):
        calls.append(q.shape[0])
        running.set()
        assert gate.wait(JOIN_S)
        return (np.zeros((q.shape[0], k), np.int64),
                np.zeros((q.shape[0], k), np.float32))

    disp = CoalescingDispatcher(run_batch)
    out = {}

    def client(i):
        out[i] = disp.search(np.full((1, 4), float(i), np.float32), 3)

    first, e1 = _run_threads([lambda: client(0)])
    assert running.wait(JOIN_S)
    rest, e2 = _run_threads([lambda: client(1), lambda: client(2)])
    deadline = time.monotonic() + JOIN_S
    while len(disp._pending) < 2 and time.monotonic() < deadline:
        time.sleep(0.001)
    gate.set()
    _join(first + rest, e1 + e2)
    assert sorted(out) == [0, 1, 2]
    assert calls == [1, 2]
    assert disp.handoffs == 1
    assert disp.ticks_expired == 0


def test_a_waiter_woken_while_a_leader_is_active_waits_again(monkeypatch):
    """An heir may wake after a newcomer has taken the lead: it neither
    leads beside it (``run_batch`` is single-flight) nor loses its answer."""
    monkeypatch.setattr(dispatch, "POLL_TICK_S", 3600.0)
    gate, running = threading.Event(), threading.Event()
    active, calls = [], []

    def run_batch(q, k, allow):
        active.append(1)
        assert len(active) == 1             # single-flight
        calls.append(q.shape[0])
        running.set()
        assert gate.wait(JOIN_S)
        active.pop()
        return (np.zeros((q.shape[0], k), np.int64),
                np.zeros((q.shape[0], k), np.float32))

    disp = CoalescingDispatcher(run_batch)
    out = {}

    def client(i):
        out[i] = disp.search(np.full((1, 4), float(i), np.float32), 3)

    first, e1 = _run_threads([lambda: client(0)])
    assert running.wait(JOIN_S)
    second, e2 = _run_threads([lambda: client(1)])
    deadline = time.monotonic() + JOIN_S
    while not disp._pending and time.monotonic() < deadline:
        time.sleep(0.001)
    waiter = disp._pending[0]
    waiter.event.set()                      # woken, no answer, a leader on
    while waiter.event.is_set() and time.monotonic() < deadline:
        time.sleep(0.001)
    assert not waiter.event.is_set() and not waiter.done
    gate.set()
    _join(first + second, e1 + e2)
    assert sorted(out) == [0, 1] and calls == [1, 1]
    assert disp.ticks_expired == 0


def test_an_error_reaches_every_member_and_the_index_stays_usable(
        monkeypatch):
    idx, queries = _index()
    want = idx.search(queries[:1], 5)
    gate = _Gate(idx)
    boom = {"left": 1}
    real_dispatch = idx._dispatch

    def failing(qj, *a, **kw):
        if qj.shape[0] > 1 and boom["left"]:
            boom["left"] -= 1
            raise RuntimeError("scan failed")
        return real_dispatch(qj, *a, **kw)

    monkeypatch.setattr(idx, "_dispatch", failing)
    outcomes = {}

    def client(i):
        try:
            outcomes[i] = idx.search(queries[i][None], 5)
        except RuntimeError as e:
            outcomes[i] = e

    first, e1 = _run_threads([lambda: client(0)])
    gate.wait_leading()
    rest, e2 = _run_threads([lambda i=i: client(i) for i in range(1, 6)])
    gate.wait_pending(5)
    gate.open.set()
    _join(first + rest, e1 + e2)
    assert not isinstance(outcomes[0], Exception)    # its own lone batch
    assert all(isinstance(outcomes[i], RuntimeError) for i in range(1, 6))
    np.testing.assert_array_equal(idx.search(queries[:1], 5).ids, want.ids)
    assert not idx._dispatcher._draining and not idx._dispatcher._pending


def test_a_demote_between_enqueue_and_drain_reroutes_the_batch():
    idx, queries = _index("l2-squared")
    idx.search(queries[:1], 5)
    gate = _Gate(idx)
    got = {}

    def client(i):
        got[i] = idx.search(queries[i][None], 5)

    first, e1 = _run_threads([lambda: client(0)])
    gate.wait_leading()
    rest, e2 = _run_threads([lambda i=i: client(i) for i in range(1, 6)])
    gate.wait_pending(5)
    epoch = idx._residency_epoch
    assert idx.demote_device() > 0
    assert idx._residency_epoch == epoch + 1
    gate.open.set()
    _join(first + rest, e1 + e2)
    # answered by the warm host tier (exact fp32), all six of them
    assert not idx.device_resident
    assert [c["rows"] for c in gate.calls] == [1, 5]
    assert [c["tier_key"][0] for c in gate.calls] == [epoch, epoch]
    for i in range(6):
        _assert_same_answers(got[i], idx.search(queries[i][None], 5))
    assert idx.promote_device() > 0
    assert idx._residency_epoch == epoch + 2
    assert idx.search(queries[:1], 5).ids[0, 0] == 0


@pytest.fixture
def compiles():
    """Counts XLA backend compiles of this process while it is open."""
    import jax.monitoring

    count = {"n": 0}

    def listener(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            count["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield count
    finally:
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(listener)


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["plain", "filtered"])
@pytest.mark.parametrize("metric", ["cosine", "l2-squared"])
def test_every_bucket_is_compiled_at_the_first_search(metric, filtered,
                                                      compiles):
    """After the first search of a program no batch of 1-8 rows compiles:
    filtered, neither under one mask (the [capacity] form) nor under
    masks that differ (the [rows, capacity] form, 2-8 rows)."""
    idx, queries = _index(metric)
    rng = np.random.default_rng(5)
    # a mask a client, of differing lengths; None where nothing filters
    masks = [rng.random(ROWS - 7 * i) < 0.5 if filtered else None
             for i in range(24)]
    idx.search(queries[:1], 7, masks[0])
    assert compiles["n"] > 0
    assert (idx.capacity, 7, filtered, 0.0) in idx._warm_programs
    after_first = compiles["n"]
    sizes = (1, 2, 3, 4, 5, 7, 8)
    for round_ in range(2):
        for rows in sizes:
            assert idx.search(queries[:rows], 7,
                              masks[rows]).ids.shape == (rows, 7)
        # behind a held batch: groups of every size up to the cap, each
        # member under its own mask, then all under ONE mask object
        for own_mask in (True, False):
            for waiting in (2, 3, 5, 8, 23):
                calls = _behind_a_held_batch(
                    idx, lambda: idx.search(queries[0][None], 7, masks[0]),
                    [lambda i=i: idx.search(
                        queries[i][None], 7, masks[i if own_mask else 0])
                     for i in range(1, waiting + 1)])
                assert sum(c["rows"] for c in calls) == waiting + 1
                if filtered:
                    assert calls[1]["masks"] == (
                        min(waiting, ROW_BUCKETS[-1]) if own_mask else 1)
                assert compiles["n"] == after_first, (round_, waiting)
    # a capacity the store grows into is compiled at ITS first search
    rng = np.random.default_rng(1)
    grown = idx.capacity
    idx.add_batch(np.arange(ROWS, grown + 1),
                  rng.standard_normal((grown + 1 - ROWS, D)).astype(
                      np.float32))
    assert idx.capacity > grown
    idx.search(queries[:1], 7, masks[0])
    assert compiles["n"] > after_first
    assert {p[0] for p in idx._warm_programs} == {idx.capacity}
    regrown = compiles["n"]
    for rows in sizes:
        idx.search(queries[:rows], 7, masks[rows])
    assert compiles["n"] == regrown


@pytest.mark.parametrize("metric", ["cosine", "l2-squared"])
def test_a_batch_makes_no_eager_call_on_its_way_to_the_device(
        metric, monkeypatch):
    """The one-chip path hands the scan a host array and nothing else:
    ``index.flat`` without a ``jax.numpy`` to upload with (no
    ``jnp.asarray``) and no eager ``normalize`` (cosine: the jitted scan
    normalises; the programs are compiled by the first search, so nothing
    traces it again), yet the answers are those of normalised queries."""
    import types

    from weaviate_tpu.ops import distance

    idx, queries = _index(metric)
    raw = (5.0 * queries).astype(np.float32)    # far from unit length
    want = idx.search(raw[:8], 5)               # compiles every bucket
    if metric == "cosine":
        # scale leaves a cosine answer alone: the scan normalised
        _assert_same_answers(want, idx.search(queries[:8], 5))

    def no_normalize(*_a, **_kw):
        raise AssertionError("eager normalize on the search path")

    monkeypatch.setattr(distance, "normalize", no_normalize)
    monkeypatch.setattr(flat, "jnp", types.SimpleNamespace())
    handed = []
    real_scan = flat.flat_search

    def scan(q, *a, **kw):
        handed.append((type(q), q.dtype, kw["normalize_queries"]))
        return real_scan(q, *a, **kw)

    monkeypatch.setattr(flat, "flat_search", scan)
    for rows in (1, 3, 8):
        got = idx.search(raw[:rows], 5)
        np.testing.assert_array_equal(got.ids, want.ids[:rows])
    assert handed == [(np.ndarray, np.float32, metric == "cosine")] * 3


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["plain", "filtered"])
@pytest.mark.parametrize("metric", ["cosine", "l2-squared"])
def test_the_warm_up_asks_for_the_forms_a_batch_asks_for(metric, filtered,
                                                         compiles,
                                                         monkeypatch):
    """``_warm_buckets`` goes through ``_scan``: every later group of 1, 3
    or 8 rows, one mask or a mask a member, calls ``flat_search`` in a
    form (query argument's type and shape, the mask's rank, the static
    flag) the first search already called it in, and compiles nothing."""
    idx, queries = _index(metric)   # k = 11: no other test's programs
    rng = np.random.default_rng(11)
    masks = [rng.random(ROWS - 5 * i) < 0.5 if filtered else None
             for i in range(9)]
    forms = []
    real_scan = flat.flat_search

    def scan(q, corpus, **kw):
        allow = kw["allow_mask"]
        forms.append((type(q), q.shape, corpus.shape[0],
                      None if allow is None else allow.ndim,
                      kw["k"], kw["normalize_queries"]))
        return real_scan(q, corpus, **kw)

    monkeypatch.setattr(flat, "flat_search", scan)
    idx.search(queries[:1], 11, masks[0])
    warmed, after_first = set(forms), compiles["n"]
    assert after_first > 0
    assert {f[0] for f in warmed} == {np.ndarray}
    assert {f[5] for f in warmed} == {metric == "cosine"}
    forms.clear()
    for rows in (1, 3, 8):
        assert idx.search(queries[:rows], 11,
                          masks[rows]).ids.shape == (rows, 11)
    for waiting in (2, 7):      # groups of 3 and 8, a mask a member
        calls = _behind_a_held_batch(
            idx, lambda: idx.search(queries[0][None], 11, masks[0]),
            [lambda i=i: idx.search(queries[i][None], 11, masks[i])
             for i in range(1, waiting + 1)])
        assert [c["rows"] for c in calls] == [1, waiting]
    assert forms and set(forms) <= warmed, set(forms) - warmed
    assert compiles["n"] == after_first
