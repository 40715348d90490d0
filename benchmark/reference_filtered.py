"""The plain reference for filtered search: seeded bags of tags, a query's
allowed rows, and the numbers compared when every query carries its own
filter.

Numpy on the host, nothing of the program: a row is allowed when every tag
of the query is in the row's bag, and the reference answer is the
``min(k, allowed)`` nearest allowed rows at ``reference.Scan``'s arithmetic
(operands rounded to bf16, float32 products and sums), scanned over the
allowed rows only, which is the scan with every other row at +inf.

Numbers compared for a set of served answers:

``filter_violations``  served hits whose row lacks a tag of the query.
``short_answers``      answers with fewer hits than ``min(k, allowed)``.
``bad_hits``           served hits that are repeated within one answer, name
                       no acknowledged row, or come after the k-th.
``rank_gap``           as ``reference.compare_answers``', over the allowed
                       rows and the ranks the reference itself fills.
``dist_err``           as there, over the served hits that are allowed.

All but the last two are exact comparisons: limit 0. ``scale`` is the median
reference k-th distance of the queries whose filter allows k rows or more.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference


# -- seeded data ------------------------------------------------------------

def make_bags(tags: dict, vocabulary: int, rows: int, seed: int) -> np.ndarray:
    """``rows`` x ``draws`` tag ids from ``seed``: each row's bag is
    ``draws`` draws with replacement from a Zipf law over ``vocabulary``
    tags (tag 0 the most popular), ascending, a repeated draw as -1."""
    rng = np.random.default_rng([seed, 3])
    p = 1.0 / np.arange(1, vocabulary + 1) ** float(tags["zipf_exponent"])
    bags = np.sort(rng.choice(vocabulary, size=(rows, tags["draws"]),
                              p=p / p.sum()).astype(np.int32), axis=1)
    bags[:, 1:][bags[:, 1:] == bags[:, :-1]] = -1
    return bags


def make_filters(per_query: list[int], bags: np.ndarray,
                 query_rows: np.ndarray, seed: int) -> list[tuple[int, ...]]:
    """One filter per query: as many tags as a uniform draw from
    ``per_query`` says, without replacement from the bag of the row the
    query was made from, so every filter allows at least that row."""
    rng = np.random.default_rng([seed, 5])
    out = []
    for r in query_rows:
        bag = bags[r][bags[r] >= 0]
        n = min(int(rng.choice(per_query)), len(bag))
        out.append(tuple(sorted(rng.choice(bag, size=n, replace=False)
                                .tolist())))
    return out


def tag_text(tag: int) -> str:
    return f"t{tag}"


def bag_texts(bag: np.ndarray) -> list[str]:
    return [tag_text(t) for t in bag.tolist() if t >= 0]


# -- allowed rows -----------------------------------------------------------

class Bags:
    """Which rows carry a tag: the bags grouped by tag once (one sort), so
    that ten thousand filters are not ten thousand passes over the bags.
    ``allowed`` is by definition ``all((bags == t).any(axis=1) for t in
    tags)``; a test holds it to that."""

    def __init__(self, bags: np.ndarray):
        self.rows = len(bags)
        flat = bags.ravel()
        order = np.argsort(flat, kind="stable")     # row-major: rows ascend
        self._rows = order // bags.shape[1]
        self._start = np.searchsorted(
            flat[order], np.arange(int(flat.max(initial=-1)) + 2))

    def rows_with(self, tag: int) -> np.ndarray:
        if not 0 <= tag < len(self._start) - 1:
            return np.empty(0, np.int64)
        return self._rows[self._start[tag]:self._start[tag + 1]]

    def allowed(self, tags: tuple[int, ...]) -> np.ndarray:
        """Ascending ids of the rows that carry every tag of ``tags``."""
        rows = self.rows_with(tags[0])
        for t in tags[1:]:
            has = np.zeros(self.rows, bool)
            has[self.rows_with(t)] = True
            rows = rows[has[rows]]
        return rows


def topk_allowed(scan: reference.Scan, bags: Bags, queries: np.ndarray,
                 filters: list[tuple[int, ...]], k: int):
    """(distances [Q, k], row ids [Q, k]) of each query's nearest allowed
    rows under its own filter, sorted by distance then id; +inf and -1
    where fewer than k rows are allowed. Queries under one filter share one
    scan over that filter's rows alone, which is the scan over all rows
    with every other row at +inf."""
    dists = np.full((len(queries), k), np.inf, np.float32)
    ids = np.full((len(queries), k), -1, np.int64)
    groups: dict[tuple[int, ...], list[int]] = {}
    for j, tags in enumerate(filters):
        groups.setdefault(tags, []).append(j)
    for tags, members in groups.items():
        rows = bags.allowed(tags)
        if len(rows):
            d, i = reference.Scan(scan.distance, scan.corpus[rows],
                                  scan.arithmetic).topk(queries[members], k)
            dists[members] = d
            ids[members] = np.where(i >= 0, rows[np.maximum(i, 0)], -1)
    return dists, ids


# -- the comparison ---------------------------------------------------------

def compare_answers(scan: reference.Scan, bags: Bags, queries: np.ndarray,
                    filters: list[tuple[int, ...]], k: int,
                    answers: list[tuple[int, np.ndarray, np.ndarray]]) -> dict:
    """``answers``: (query index, served row ids, served distances) for
    every answered request. The reference is computed once per distinct
    query and every answer is held against it."""
    used = sorted({qi for qi, _, _ in answers})
    slot = {qi: j for j, qi in enumerate(used)}
    allowed = [bags.allowed(filters[qi]) for qi in used]
    ref_d, _ = topk_allowed(scan, bags, queries[used],
                            [filters[qi] for qi in used], k)
    want = np.array([min(k, len(rows)) for rows in allowed])
    full = ref_d[want == k, -1]
    scale = float(np.median(full if len(full) else ref_d[ref_d < np.inf]))
    got_i = np.full((len(answers), k), -1, np.int64)
    got_d = np.zeros((len(answers), k), np.float32)
    which = np.empty(len(answers), np.int64)
    ok = np.zeros((len(answers), k), bool)   # served, in range and allowed
    bad = violations = short = 0
    for a, (qi, ids, dists) in enumerate(answers):
        which[a] = j = slot[qi]
        m = min(len(ids), k)
        got_i[a, :m], got_d[a, :m] = ids[:m], dists[:m]
        known = (got_i[a] >= 0) & (got_i[a] < bags.rows)
        ok[a] = known & np.isin(got_i[a], allowed[j])
        bad += (len(ids) - m) + int(m - known.sum()) \
            + int(known.sum() - len(set(got_i[a][known].tolist())))
        violations += int((known & ~ok[a]).sum())
        short += int(len(ids) < want[j])
    pair = np.where(ok, scan.pair_distance(
        queries[used][which], np.where(ok, got_i, -1)), 0.0)
    filled = ok & (np.arange(k)[None, :] < want[which][:, None])
    gaps = np.where(filled, pair - np.where(filled, ref_d[which], 0.0), 0.0)
    errs = np.abs(np.where(ok, got_d, 0.0) - pair)
    counts = np.array([len(rows) for rows in allowed])
    return {
        "bad_hits": bad,
        "filter_violations": violations,
        "short_answers": short,
        "rank_gap": float(gaps.max(initial=0.0) / scale),
        "dist_err": float(errs.max(initial=0.0) / scale),
        "scale": scale,
        "answers": len(answers),
        "distinct_queries": len(used),
        "allowed_rows": {
            **{f"p{p}": float(np.percentile(counts, p))
               for p in (25, 50, 75, 90, 99)},
            "share_at_most_k": float(np.mean(counts <= k))},
    }


def control_answers(distance: str, corpus: np.ndarray, bags: Bags,
                    queries: np.ndarray, filters: list[tuple[int, ...]],
                    k: int, used: list[int]):
    """The control: this reference put in the program's place, computed in
    int8 — the nearest precision below the configuration's bf16 — under the
    same filters. Answers in ``compare_answers``' form, one per query index
    in ``used``, each as long as its filter allows."""
    low = reference.Scan(distance, corpus, "int8")
    d, i = topk_allowed(low, bags, queries[used],
                        [filters[qi] for qi in used], k)
    return [(qi, i[j][i[j] >= 0], d[j][i[j] >= 0])
            for j, qi in enumerate(used)]
