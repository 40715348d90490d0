"""Concurrent searches on a compressed flat collection share one code scan:
``QuantizedFlatIndex`` behind the coalescing dispatcher, by count and
structure (never by wall clock). N threads get row for row what serial
``search`` returns in fewer code scans than requests, each batch prepped as
one block padded to a row bucket; each request rescores its own candidates
under its own span; a lone search runs one B = 1 scan and waits for nothing;
a different k, mask, group token or residency epoch keeps requests apart; a
demotion between enqueue and drain re-routes the batch to the host and still
answers exactly; every row bucket is compiled at the first search; an error
reaches every member of its batch and the index stays usable."""

import threading
import time

import numpy as np
import pytest

from weaviate_tpu.index.dispatch import dispatch_group
from weaviate_tpu.index.flat import ROW_BUCKETS, QuantizedFlatIndex, make_flat
from weaviate_tpu.monitoring.tracing import TRACER
from weaviate_tpu.schema.config import FlatIndexConfig, RQConfig, SQConfig

D, ROWS = 32, 600
JOIN_S = 30
# an RQ index over cosine (its queries normalised before they are queued)
# and an SQ index over l2 (its queries queued as sent)
KINDS = {"rq-cosine": ("cosine", RQConfig), "sq-l2": ("l2-squared", SQConfig)}


def _index(kind):
    metric, qcfg = KINDS[kind]
    idx = make_flat(D, FlatIndexConfig(distance=metric, quantizer=qcfg()))
    rng = np.random.default_rng(7)
    centers = rng.standard_normal((16, D))
    vecs = (centers[rng.integers(0, 16, ROWS)]
            + 0.3 * rng.standard_normal((ROWS, D))).astype(np.float32)
    idx.add_batch(np.arange(ROWS), vecs)
    assert isinstance(idx, QuantizedFlatIndex) and idx.quantizer.fitted
    queries = (vecs[:64] + 0.05 * rng.standard_normal((64, D))).astype(
        np.float32)
    return idx, queries, vecs


def _exact(vecs, q, k, metric, allow=None):
    """Exact top-k ids by numpy, over the rows ``allow`` lets through."""
    if metric == "cosine":
        vecs = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
        d = 1.0 - vecs @ (q / np.linalg.norm(q))
    else:
        d = ((vecs - q) ** 2).sum(axis=1)
    if allow is not None:
        d = np.where(allow, d, np.inf)
    return np.argsort(d, kind="stable")[:k]


class _Gate:
    """Wraps an index's ``run_batch``: holds the FIRST batch until the
    test opens the gate (so what queues behind it is decided by the test,
    not the scheduler) and records every batch's rows, k and mask."""

    def __init__(self, idx):
        self.idx, self.open, self.calls = idx, threading.Event(), []
        self.real = idx._dispatcher.run_batch
        idx._dispatcher.run_batch = self

    def __call__(self, q, k, allow):
        assert self.open.wait(JOIN_S)
        self.calls.append({"rows": q.shape[0], "k": k, "allow": allow})
        return self.real(q, k, allow)

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


def _behind_a_held_batch(idx, leader, queued, before_release=None):
    """Run ``leader`` (its lone batch is held), pile ``queued`` up behind
    it, run ``before_release``, let go, join; returns what the runner was
    handed, batch by batch."""
    gate = _Gate(idx)
    first, e1 = _run_threads([leader])
    gate.wait_leading()
    rest, e2 = _run_threads(queued)
    gate.wait_pending(len(queued))
    if before_release is not None:
        before_release()
    gate.open.set()
    _join(first + rest, e1 + e2)
    gate.close()
    return gate.calls


def _count_stages(idx, monkeypatch):
    """Rows of every ``quantizer.prep`` and ``quantizer.search`` call."""
    preps, scans = [], []
    quantizer = idx.quantizer
    real_prep, real_search = quantizer.prep, quantizer.search

    def prep(q):
        preps.append(q.shape[0])
        return real_prep(q)

    def search(qrep, *a):
        scans.append(qrep.shape[0])
        return real_search(qrep, *a)

    monkeypatch.setattr(quantizer, "prep", prep)
    monkeypatch.setattr(quantizer, "search", search)
    return preps, scans


def _assert_same_answers(got, want):
    """Row for row the same hits at the same exact distances. A B = 1 and
    a B > 1 code scan may differ in their last bits, so a candidate tied at
    the last fetched place may differ; the rescored top k does not."""
    np.testing.assert_array_equal(got.ids, want.ids)
    np.testing.assert_allclose(got.dists, want.dists, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
def test_concurrent_searches_match_serial_in_fewer_scans(kind, masked,
                                                         monkeypatch):
    idx, queries, _ = _index(kind)
    n = 24
    ks = [5 if i % 4 else 9 for i in range(n)]          # mixed k
    mask = None
    if masked:
        mask = np.arange(ROWS) % 3 == 0
    # equal content, a different array object per request: one tenant's
    # precomputed mask arriving with each request
    serial = [idx.search(queries[i][None], ks[i],
                         None if mask is None else mask.copy())
              for i in range(n)]
    preps, scans = _count_stages(idx, monkeypatch)
    got = {}

    def client(i):
        got[i] = idx.search(queries[i][None], ks[i],
                            None if mask is None else mask.copy())

    calls = _behind_a_held_batch(
        idx, lambda: client(0), [lambda i=i: client(i) for i in range(1, n)])
    for i in range(n):
        assert got[i].ids.shape == (1, ks[i])
        _assert_same_answers(got[i], serial[i])
        if masked:
            assert mask[got[i].ids[0]].all()
    # the leader's lone batch, then the 23 queued: full batches per k
    cap = ROW_BUCKETS[-1]
    assert calls[0]["rows"] == 1
    assert sorted((c["k"], c["rows"]) for c in calls[1:]) == sorted(
        [(5, cap)] * (18 // cap) + [(5, 18 % cap)] + [(9, 5)])
    assert all((c["allow"] is not None) == masked for c in calls)
    # one prep of the padded block and one scan a batch
    assert len(scans) == len(calls) < n
    assert preps == scans and sorted(scans) == [1, 4, 8, 8, 8]


def test_requests_apart_in_k_mask_group_or_epoch_never_share_a_batch():
    """k, the allow mask (by content), the ``dispatch_group`` token, the
    residency epoch and filtered against unfiltered each keep a request
    out of the others' batch; two requests equal in all of them share one,
    equal masks in different arrays too. The l2 index queues a query as
    sent, so its last component carries a tag the runner reads."""
    idx, queries, _ = _index("sq-l2")
    idx.search(queries[:1], 5)
    mask_a = np.arange(ROWS) % 2 == 0
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
        "mask_a_copy": lambda q: answers.setdefault(
            "a_copy", idx.search(q, 5, mask_a.copy())),
        "mask_b": lambda q: answers.setdefault(
            "b", idx.search(q, 5, mask_b)),
        "token": in_group,
        "epoch": lambda q: idx.search(q, 5),
    }
    names = list(variants)

    def send(name):
        q = queries[:1].copy()
        q[0, -1] = names.index(name)        # the tag the runner reads
        return lambda: variants[name](q)

    gate = _Gate(idx)
    seen = []
    real = gate.real

    def recording(q, k, allow):
        seen.append(sorted(names[int(t)] for t in q[:, -1]))
        return real(q, k, allow)

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
    gate.close()
    shared = (["base1", "base2"], ["mask_a", "mask_a_copy"])
    assert sorted(seen) == sorted(
        list(shared) + [[n] for n in names
                        if n not in shared[0] + shared[1]]), seen
    assert (answers["a"].ids % 2 == 0).all()
    assert (answers["a_copy"].ids % 2 == 0).all()
    assert (answers["b"].ids % 2 == 1).all()


@pytest.mark.parametrize("kind", KINDS)
def test_a_lone_search_runs_one_b1_scan_and_waits_for_nothing(kind,
                                                              monkeypatch):
    idx, queries, _ = _index(kind)
    idx.search(queries[:1], 5)              # compiles every bucket
    preps, scans = _count_stages(idx, monkeypatch)
    waits = []
    real_wait = threading.Event.wait
    me = threading.get_ident()

    def wait(self, timeout=None):
        if threading.get_ident() == me:
            waits.append(timeout)
        return real_wait(self, timeout)

    monkeypatch.setattr(threading.Event, "wait", wait)
    handoffs = idx._dispatcher.handoffs
    for i in range(5):
        assert idx.search(queries[i][None], 5).ids.shape == (1, 5)
    assert preps == scans == [1] * 5
    assert waits == []
    assert idx._dispatcher.ticks_expired == 0
    assert idx._dispatcher.handoffs == handoffs
    # a block of rows pads to its bucket; wider than the largest bucket it
    # runs at its own width
    for rows, bucket in ((3, 4), (5, 8), (ROW_BUCKETS[-1] + 6,
                                         ROW_BUCKETS[-1] + 6)):
        got = idx.search(np.tile(queries, (2, 1))[:rows], 5)
        assert got.ids.shape == (rows, 5)
        assert preps[-1] == scans[-1] == bucket


def test_each_request_rescores_its_own_candidates_under_its_own_span():
    """The leader's trace holds the batch's one ``qflat.prepare`` and one
    ``qflat.scan`` (its ``batch`` the member rows before padding); every
    request's own trace holds its own ``qflat.rescore`` of ``fetch``
    candidates; the warm-up's scans land in no request's trace."""
    idx, queries, _ = _index("rq-cosine")
    k, fetch = 5, idx.backend.flat_fetch(5)
    traces = {}

    def client(i):
        with TRACER.span("client", parent=None) as root:
            traces[i] = root.trace_id
            idx.search(queries[i][None], k)

    TRACER.clear()
    _behind_a_held_batch(idx, lambda: client(0),
                         [lambda i=i: client(i) for i in range(1, 6)])
    spans = [s for s in TRACER.recent(limit=500)
             if s["traceId"] in traces.values()]

    def named(name):
        return [s for s in spans if s["name"] == name]

    assert sorted(s["attributes"]["batch"]
                  for s in named("qflat.scan")) == [1, 5]
    assert all(s["attributes"]["fetch"] == fetch
               and s["attributes"]["quantizer"] == "rq"
               for s in named("qflat.scan"))
    assert len(named("qflat.prepare")) == 2
    assert sorted(s["traceId"] for s in named("qflat.rescore")) == sorted(
        traces.values())
    assert all(s["attributes"]["candidates"] == fetch
               for s in named("qflat.rescore"))
    assert len(named("qflat.warm")) == 1


@pytest.mark.parametrize("kind", KINDS)
def test_a_demote_between_enqueue_and_drain_reroutes_the_batch(kind):
    idx, queries, vecs = _index(kind)
    metric = KINDS[kind][0]
    idx.search(queries[:1], 5)
    got = {}
    epoch = idx._residency_epoch

    def demote():
        assert idx.demote_device() > 0
        assert idx._residency_epoch == epoch + 1

    calls = _behind_a_held_batch(
        idx, lambda: got.update({0: idx.search(queries[0][None], 5)}),
        [lambda i=i: got.update({i: idx.search(queries[i][None], 5)})
         for i in range(1, 6)], before_release=demote)
    # both batches answered from the host originals, exactly
    assert not idx.device_resident
    assert [c["rows"] for c in calls] == [1, 5]
    for i in range(6):
        np.testing.assert_array_equal(
            got[i].ids[0], _exact(vecs, queries[i], 5, metric))
        # the warm tier's own exact search: the same ids, its l2 in the
        # expanded form, so the last bits of a distance may differ
        alone = idx.search(queries[i][None], 5)
        np.testing.assert_array_equal(got[i].ids, alone.ids)
        np.testing.assert_allclose(got[i].dists, alone.dists,
                                   rtol=1e-5, atol=1e-4)
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
@pytest.mark.parametrize("kind", KINDS)
def test_every_bucket_is_compiled_at_the_first_search(kind, filtered,
                                                      compiles):
    """After the first search of a (capacity, fetch) no batch of 1-8 rows
    compiles, alone or behind a held batch, under one mask or many."""
    idx, queries, _ = _index(kind)
    k = 13 if filtered else 11      # a fetch no other test compiles
    rng = np.random.default_rng(5)
    masks = [rng.random(ROWS - 7 * i) < 0.5 if filtered else None
             for i in range(24)]
    idx.search(queries[:1], k, masks[0])
    assert compiles["n"] > 0
    assert (idx.backend.codes.capacity,
            idx.backend.flat_fetch(k)) in idx._warm_programs
    after_first = compiles["n"]
    for rows in (1, 2, 3, 4, 5, 7, 8):
        assert idx.search(queries[:rows], k,
                          masks[rows]).ids.shape == (rows, k)
    for waiting in (2, 3, 5, 8, 23):
        calls = _behind_a_held_batch(
            idx, lambda: idx.search(queries[0][None], k, masks[0]),
            [lambda i=i: idx.search(queries[i][None], k, masks[0])
             for i in range(1, waiting + 1)])
        assert sum(c["rows"] for c in calls) == waiting + 1
        assert compiles["n"] == after_first, waiting


def test_an_error_reaches_every_member_and_the_index_stays_usable(
        monkeypatch):
    idx, queries, _ = _index("rq-cosine")
    want = idx.search(queries[:1], 5)
    boom = {"left": 1}
    real = idx.backend.code_candidates

    def failing(q, *a, **kw):
        if q.shape[0] > 1 and boom["left"]:
            boom["left"] -= 1
            raise RuntimeError("scan failed")
        return real(q, *a, **kw)

    monkeypatch.setattr(idx.backend, "code_candidates", failing)
    outcomes = {}

    def client(i):
        try:
            outcomes[i] = idx.search(queries[i][None], 5)
        except RuntimeError as e:
            outcomes[i] = e

    calls = _behind_a_held_batch(
        idx, lambda: client(0), [lambda i=i: client(i) for i in range(1, 6)])
    assert [c["rows"] for c in calls] == [1, 5]
    assert not isinstance(outcomes[0], Exception)    # its own lone batch
    assert all(isinstance(outcomes[i], RuntimeError) for i in range(1, 6))
    np.testing.assert_array_equal(idx.search(queries[:1], 5).ids, want.ids)
    assert not idx._dispatcher._draining and not idx._dispatcher._pending
