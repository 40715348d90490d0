"""The plain reference for hybrid search: seeded passages and query texts,
BM25 as published, the dense leg from ``reference.Scan``, the two fusions of
``hybrid_fusion.go``, and the numbers compared when every request carries a
vector and a text.

Numpy on the host, nothing of the program. A hybrid answer is the fusion of
both whole legs: the ``depth`` best passages by BM25 and the ``depth`` nearest
by the configured distance, each leg weighted (``1 - alpha`` / ``alpha``), the
``k`` best fused scores served. A leg whose weight is 0 is not run, as
upstream.

BM25 (Robertson/Zaragoza, the form Lucene and the upstream use): a text is
split on non-alphanumerics, lower-cased, the ``en`` stopwords dropped; a
passage's length is the number of tokens left, ``avgdl`` their mean over the
live passages, ``N`` their number, ``n`` a term's passages, ``idf = ln(1 + (N -
n + 0.5) / (n + 0.5))``, a term's part ``idf * tf * (k1 + 1) / (tf + k1 * (1 -
b + b * dl / avgdl))``, summed over the query's distinct terms (operator
``Or``). All in float64: the program's engine is float32, and the limits
leave that room.

relativeScoreFusion: a leg's scores are min-max normalised over its own
``depth`` best (a leg of one distinct score reads 1), so its cut-off score is
its ``lo`` and normalises to 0. rankedFusion: ``weight / (60 + rank)``, rank 0
the best. Departures from ``hybrid_fusion.go``: none in the arithmetic; the
dense leg's score is the negated distance, as the program's.

**Tie-proof.** Passages of equal length that match the same query words score
exactly alike, often at the cut-off. So a served id is never judged by
whether the reference picked it, but by the fused score the reference gives
THAT id from its own per-leg lists: ``upper`` with every tie broken in the
id's favour and ``lower`` with every tie broken against it (scores within
``TIE_EPS`` of each other, as a share of the leg's largest magnitude, count
as tied). Under relativeScoreFusion a tie at the cut-off is worth 0 either
way, so ``upper == lower`` but for rounding; under rankedFusion a tie moves a
rank.

Numbers compared for a set of served answers:

``bad_hits``       served hits repeated within one answer, naming no loaded
                   row, or beyond the k-th. Exact: limit 0.
``short_answers``  answers with fewer hits than ``min(k, rows that either
                   leg reaches)``. Exact: limit 0.
``score_gap``      widest (the reference's rank-r fused score, ties against
                   it - ``upper`` of the id served at rank r), in fused-score
                   units (the scale is 1 by construction).
``score_err``      widest distance of a served ``score`` from the interval
                   [``lower``, ``upper``] of its id.
"""

from __future__ import annotations

import math
import re

import numpy as np

from benchmark import reference

# the upstream's ``en`` stopword preset (inverted/stopwords/presets.go)
STOPWORDS_EN = (
    "a an and are as at be but by for if in into is it no not of on or such "
    "that the their then there these they this to was will with").split()
RANKED_FUSION_OFFSET = 60.0
TIE_EPS = 1e-5
_SPLIT = re.compile(r"[^0-9A-Za-z]+")


# -- seeded text ------------------------------------------------------------

def words(vocabulary: int) -> np.ndarray:
    """The vocabulary by Zipf rank: the most frequent ranks are spelled as
    the stopwords (so stopword removal takes from this text the share it
    takes from English), every other rank r as ``w<r>``."""
    out = [f"w{r}" for r in range(vocabulary)]
    out[:len(STOPWORDS_EN)] = STOPWORDS_EN[:vocabulary]
    return np.array(out, object)


def _zipf(text: dict) -> np.ndarray:
    p = 1.0 / np.arange(1, text["vocabulary"] + 1) ** float(
        text["zipf_exponent"])
    return p / p.sum()


def make_passages(text: dict, rows: int, seed: int) -> list[np.ndarray]:
    """One array of word ranks a passage: lengths from a log-normal law of
    the stated mean and sigma clipped to [min, max], words from a Zipf law
    over the vocabulary."""
    rng = np.random.default_rng([seed, 21])
    ln = text["length"]
    mu = math.log(ln["mean"]) - ln["sigma"] ** 2 / 2
    lengths = np.clip(np.rint(rng.lognormal(mu, ln["sigma"], rows)),
                      ln["min"], ln["max"]).astype(np.int64)
    flat = rng.choice(text["vocabulary"], size=int(lengths.sum()),
                      p=_zipf(text)).astype(np.int32)
    return np.split(flat, np.cumsum(lengths)[:-1])


def passage_texts(text: dict, passages: list[np.ndarray]) -> list[str]:
    spell = words(text["vocabulary"])
    return [" ".join(spell[p]) for p in passages]


def make_query_texts(text: dict, passages: list[np.ndarray],
                     query_rows: np.ndarray, seed: int) -> list[str]:
    """One text a query: ``words`` low..high of them (uniform), no word
    twice; two thirds (rounded up) from the non-stopword words of the passage
    the query's vector was made from, the rest from the Zipf law over the
    whole vocabulary (so it may be a stopword, or a very common word)."""
    rng = np.random.default_rng([seed, 23])
    spell, cdf = words(text["vocabulary"]), np.cumsum(_zipf(text))
    low, high = text["query_words"]
    out = []
    for r in query_rows:
        n = int(rng.integers(low, high + 1))
        own = np.unique(passages[r][passages[r] >= len(STOPWORDS_EN)])
        picked = rng.choice(own, size=min(len(own), math.ceil(2 * n / 3)),
                            replace=False).tolist()
        while len(picked) < n:
            w = min(int(np.searchsorted(cdf, rng.random())), len(cdf) - 1)
            if w not in picked:
                picked.append(w)
        out.append(" ".join(spell[rng.permutation(picked)]))
    return out


# -- BM25 -------------------------------------------------------------------

def tokenize(text: str) -> list[str]:
    stop = set(STOPWORDS_EN)
    return [t for t in _SPLIT.split(text.lower()) if t and t not in stop]


class Bm25:
    """The passages by term (one sort), so that a query reads its terms'
    postings and not every passage. ``scores(text)`` is by definition the sum
    over the query's distinct terms of the formula in the module's
    docstring, for every passage; a test holds it to a loop that says so."""

    def __init__(self, texts: list[str], k1: float, b: float):
        self.k1, self.b, self.rows = float(k1), float(b), len(texts)
        self.term_ids: dict[str, int] = {}
        docs, terms = [], []
        lengths = np.zeros(len(texts), np.float64)
        for d, text in enumerate(texts):
            toks = tokenize(text)
            lengths[d] = len(toks)
            for t in toks:
                terms.append(self.term_ids.setdefault(t, len(self.term_ids)))
            docs.extend([d] * len(toks))
        pair = np.array(terms, np.int64) * len(texts) + np.array(
            docs, np.int64)
        pair, tf = np.unique(pair, return_counts=True)
        self._docs = pair % len(texts)
        self._tf = tf.astype(np.float64)
        self._start = np.searchsorted(
            pair // len(texts), np.arange(len(self.term_ids) + 1))
        self.lengths = lengths
        self.avgdl = float(lengths.mean())

    @property
    def postings(self) -> int:
        return len(self._docs)

    def scores(self, text: str) -> np.ndarray:
        """BM25 of every passage for ``text`` (0: no term of it matches)."""
        out = np.zeros(self.rows, np.float64)
        for term in dict.fromkeys(tokenize(text)):
            t = self.term_ids.get(term)
            if t is None:
                continue
            docs = self._docs[self._start[t]:self._start[t + 1]]
            tf = self._tf[self._start[t]:self._start[t + 1]]
            n = len(docs)
            idf = math.log(1.0 + (self.rows - n + 0.5) / (n + 0.5))
            out[docs] += idf * tf * (self.k1 + 1) / (tf + self.k1 * (
                1 - self.b + self.b * self.lengths[docs] / self.avgdl))
        return out


# -- the legs and their fusion ----------------------------------------------

class Leg:
    """One leg of one query: its ``depth`` best scores (higher is better),
    best first, with their row ids, and its weight."""

    def __init__(self, kind: str, ids: np.ndarray, scores: np.ndarray,
                 weight: float):
        self.kind = kind
        self.ids = np.asarray(ids, np.int64)
        self.scores = np.asarray(scores, np.float64)
        self.weight = float(weight)

    def parts(self, s: np.ndarray, fusion: str, depth: int):
        """(upper, lower) of this leg's part of the fused score of rows
        whose leg scores are ``s`` (-inf: the leg does not reach the row)."""
        s = np.asarray(s, np.float64)
        if not len(self.scores):
            return np.zeros(len(s)), np.zeros(len(s))
        eps = TIE_EPS * float(np.abs(self.scores).max())
        cut = self.scores[-1] if len(self.scores) >= depth else -np.inf
        reached = np.isfinite(s)
        if fusion == "relativeScoreFusion":
            lo, hi = self.scores[-1], self.scores[0]
            norm = np.ones(len(s)) if hi - lo <= 0 else \
                (np.where(reached, s, lo) - lo) / (hi - lo)
            part = np.where(reached & (s >= cut - eps),
                            self.weight * np.maximum(norm, 0.0), 0.0)
            return part, part
        if fusion != "rankedFusion":
            raise ValueError(f"no reference for fusion {fusion!r}")
        # best rank: only the rows clearly above stand before it; worst:
        # every row not clearly below does, and a row at the cut-off may
        # have been left out of the leg altogether
        above = (self.scores[None, :] > s[:, None] + eps).sum(axis=1)
        others = (self.scores[None, :] >= s[:, None] - eps).sum(axis=1) - 1
        upper = np.where(reached & (above < depth),
                         self.weight / (RANKED_FUSION_OFFSET + above), 0.0)
        lower = np.where(reached & (s - eps > cut),
                         self.weight / (RANKED_FUSION_OFFSET
                                        + np.maximum(others, 0)), 0.0)
        return upper, lower


def fuse_lists(legs: list[Leg], k: int, fusion: str):
    """What a server that holds these leg lists serves: (row ids, fused
    scores), best first, ties to the row that came first in the legs. Used
    for the controls, which put a leg computed otherwise in the program's
    place, never for the reference side of a comparison."""
    fused: dict[int, float] = {}
    for leg in legs:
        if not len(leg.ids):
            continue
        if fusion == "relativeScoreFusion":
            lo, hi = leg.scores.min(), leg.scores.max()
            part = np.ones(len(leg.ids)) if hi - lo <= 0 else \
                (leg.scores - lo) / (hi - lo)
        else:
            part = 1.0 / (RANKED_FUSION_OFFSET + np.arange(len(leg.ids)))
        for i, p in zip(leg.ids.tolist(), (leg.weight * part).tolist()):
            fused[i] = fused.get(i, 0.0) + p
    order = sorted(fused.items(), key=lambda t: -t[1])[:k]
    return (np.array([i for i, _ in order], np.int64),
            np.array([s for _, s in order], np.float32))


class Hybrid:
    """The reference for one collection: BM25 over its passages and a scan
    over its vectors, ``alpha`` weighing the dense leg. A leg of weight 0
    is not run."""

    def __init__(self, scan: reference.Scan, bm25: Bm25, alpha: float,
                 fusion: str, depth: int):
        self.scan, self.bm25, self.fusion, self.depth = \
            scan, bm25, fusion, depth
        self.sparse_weight, self.dense_weight = 1.0 - alpha, alpha

    def sparse_scores(self, text: str) -> np.ndarray:
        """BM25 of every passage; -inf where no term of the text matches."""
        s = self.bm25.scores(text)
        return np.where(s > 0, s, -np.inf)

    def sparse_leg(self, scores: np.ndarray) -> Leg:
        reach = np.flatnonzero(np.isfinite(scores))
        order = reach[np.lexsort((reach, -scores[reach]))][:self.depth]
        return Leg("sparse", order, scores[order], self.sparse_weight)

    def dense_legs(self, vectors: np.ndarray, scan=None) -> list:
        """One dense leg a query (None each where the leg is not run)."""
        if self.dense_weight <= 0:
            return [None] * len(vectors)
        d, i = (scan or self.scan).topk(
            vectors, min(self.depth, len(self.scan.corpus)))
        return [Leg("dense", i[j], -d[j].astype(np.float64),
                    self.dense_weight) for j in range(len(vectors))]


class Query:
    """One query's legs, and the fused score the reference gives any row."""

    def __init__(self, hybrid: Hybrid, vector: np.ndarray, text: str,
                 dense):
        self.hybrid, self.vector = hybrid, vector
        self.legs: list[Leg] = []
        self.sparse_scores = None
        if hybrid.sparse_weight > 0:
            self.sparse_scores = hybrid.sparse_scores(text)
            self.legs.append(hybrid.sparse_leg(self.sparse_scores))
        if dense is not None:
            self.legs.append(dense)

    def reach(self) -> np.ndarray:
        """The rows either leg reaches."""
        return np.unique(np.concatenate(
            [leg.ids for leg in self.legs] or [np.empty(0, np.int64)]))

    def cutoff_tie(self) -> bool:
        """Whether passages beyond the sparse leg score exactly as its
        last."""
        leg = self.legs[0] if self.sparse_scores is not None else None
        return leg is not None and len(leg.ids) >= self.hybrid.depth and int(
            (self.sparse_scores == leg.scores[-1]).sum()) > int(
            (leg.scores == leg.scores[-1]).sum())

    def bounds(self, ids: np.ndarray):
        """(upper, lower) of the fused score of each of ``ids`` (0 for an
        id that names no row)."""
        h = self.hybrid
        upper, lower = np.zeros(len(ids)), np.zeros(len(ids))
        known = (ids >= 0) & (ids < h.bm25.rows)
        safe = np.where(known, ids, 0)
        for leg in self.legs:
            if leg.kind == "sparse":
                s = self.sparse_scores[safe]
            else:
                s = -h.scan.pair_distance(
                    self.vector[None, :], safe[None, :])[0].astype(
                        np.float64)
            u, lo = leg.parts(np.where(known, s, -np.inf), h.fusion, h.depth)
            upper += u
            lower += lo
        return upper, lower

    def serve(self, k: int):
        return fuse_lists(self.legs, k, self.hybrid.fusion)


# -- the comparison ---------------------------------------------------------

def compare_answers(hybrid: Hybrid, vectors: np.ndarray, texts: list[str],
                    k: int, answers: list[tuple[int, np.ndarray, np.ndarray]]
                    ) -> dict:
    """``answers``: (query index, served row ids, served scores) for every
    answered request. The reference's legs are computed once per distinct
    query and every answer to it is held against them."""
    by_query: dict[int, list] = {}
    for qi, ids, scores in answers:
        by_query.setdefault(qi, []).append(
            (np.asarray(ids, np.int64), np.asarray(scores, np.float64)))
    used = sorted(by_query)
    dense = hybrid.dense_legs(vectors[used])
    bad = short = ties = 0
    gap = err = 0.0
    for j, qi in enumerate(used):
        q = Query(hybrid, vectors[qi], texts[qi], dense[j])
        reach = q.reach()
        own = np.sort(q.bounds(reach)[1])[::-1][:k]
        ties += int(q.cutoff_tie())
        seen = set()
        for ids, scores in by_query[qi]:
            m = min(len(ids), k)
            known = (ids[:m] >= 0) & (ids[:m] < hybrid.bm25.rows)
            bad += (len(ids) - m) + int(m - known.sum()) + int(
                known.sum() - len(set(ids[:m][known].tolist())))
            short += int(len(ids) < min(k, len(reach)))
            key = (ids[:m].tobytes(), scores[:m].tobytes())
            if key in seen:     # a repeated query gives a repeated answer
                continue
            seen.add(key)
            upper, lower = q.bounds(ids[:m])
            r = min(m, len(own))
            gap = max(gap, float(np.max(np.where(
                known[:r], own[:r] - upper[:r], 0.0), initial=0.0)))
            err = max(err, float(np.max(np.where(known, np.maximum(
                scores[:m] - upper, lower - scores[:m]), 0.0), initial=0.0)))
    return {
        "bad_hits": bad, "short_answers": short,
        "score_gap": gap, "score_err": err,
        "answers": len(answers), "distinct_queries": len(used),
        "cutoff_ties": ties,
    }


def control_answers(hybrid: Hybrid, corpus: np.ndarray, vectors: np.ndarray,
                    texts: list[str], k: int, used: list[int],
                    control: str = "int8"):
    """The controls: this reference put in the program's place with one leg
    computed otherwise. ``int8``: the dense leg in symmetric int8, the
    nearest precision below the configuration's bf16. ``dense_only``: the
    sparse leg shed and the dense leg fused alone under its own weight (what
    a server serves that waited for one leg only). Answers in
    ``compare_answers``' form, one per query index in ``used``."""
    if control not in ("int8", "dense_only"):
        raise ValueError(f"unknown control {control!r}")
    dense = hybrid.dense_legs(
        vectors[used], reference.Scan(hybrid.scan.distance, corpus, "int8")
        if control == "int8" else None)
    out = []
    for j, qi in enumerate(used):
        q = Query(hybrid, vectors[qi], texts[qi], dense[j])
        if control == "dense_only":
            q.legs = [leg for leg in q.legs if leg.kind == "dense"]
        out.append((qi, *q.serve(k)))
    return out
