"""The plain reference for a flat collection under 8-bit rotational
quantization (``cohere-768-rq``): seeded clustered rows, the documented
arithmetic of the code space, the exact float32 rescore, and the numbers
compared when every request is one query vector.

Numpy on the host, float32, nothing of the program. What the configuration's
``rq_layout`` states, written down:

- the rotation ``Q . sign(diag R)`` of the QR of a seeded
  ``standard_normal((rdims, rdims))`` (``rotation_seed``);
- a row (unit: cosine) rotated, ``lower`` = its min, ``step`` = (max - min) /
  255, ``codes`` = rint((r - lower) / step) clipped to 0..255;
- the code-space distance of a query ``q`` (unit, rotated): ``1 - (step .
  (q_rot . codes) + lower . sum(q_rot))``;
- the ``fetch`` = max(4k, rescoreLimit, k) best by that distance, their
  exact float32 distance ``1 - q . x`` over the unit originals, the k best.

The exact flat top-k (every row's float32 distance) is worked out in blocks.

**Seeded data** (the configuration's ``data``). ``rows / cluster_rows``
centres, unit and isotropic; a row is ``unit(centre + spread x g /
sqrt(dims))`` for a centre drawn at random, ``g`` standard-normal; a query
is a distinct row + ``query_noise x g / sqrt(dims)`` (the noise a tenth of
the row's norm, as ``cohere-768-flat``'s queries). On isotropic rows the
ten best of a query are far apart next to the code space's error, so any
candidate count finds them; within a cluster of ``cluster_rows`` the best
lie close enough that the 8-bit error reorders them, and the over-fetch and
the rescore decide the answer. The two parameters were fixed once, on
``benchmark/recall_sweep_rq.py`` (the configuration has the grid).

Numbers compared for a set of served answers:

``bad_hits``           served hits missing (fewer than k), repeated within
                       one answer, naming no loaded row, or beyond the k-th.
                       Exact: limit 0.
``dist_err``           widest |served distance - exact float32 distance of
                       that id| over every answer (absolute: cosine distances
                       are the unit). Holds the program to "every served
                       distance is the exact one": a code-space distance is
                       off by ~3e-4, a bfloat16 rescore by ~1e-4.
``recall_miss``        1 - the mean share of the plain reference's k best
                       (the pipeline above) that were served, over every
                       answer to a seeded sample of ``reference_queries`` of
                       the window's distinct queries.
``exact_recall_miss``  the same against the exact flat top-k: what the
                       approximation (``fetch`` candidates in code space)
                       costs. Fewer candidates show here.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference

BLOCK = 32768           # rows rotated or scored at once: bounds host memory
LEVELS = 255            # 8-bit codes: 0..255


# -- seeded data --------------------------------------------------------------

def make_rows(data: dict, dims: int, rows: int, seed: int) -> np.ndarray:
    """``rows`` x ``dims`` unit float32 rows around ``rows / cluster_rows``
    seeded centres."""
    rng = np.random.default_rng([seed, 0])
    clusters = max(1, rows // int(data["cluster_rows"]))
    centres = reference.unit(
        rng.standard_normal((clusters, dims), dtype=np.float32))
    which = rng.integers(0, clusters, rows)
    spread = np.float32(data["spread"] / np.sqrt(dims))
    out = np.empty((rows, dims), np.float32)
    for lo in range(0, rows, BLOCK):
        hi = min(lo + BLOCK, rows)
        g = rng.standard_normal((hi - lo, dims), dtype=np.float32)
        out[lo:hi] = reference.unit(centres[which[lo:hi]] + spread * g)
    return out


def make_queries(data: dict, corpus: np.ndarray, n: int, seed: int):
    """``n`` queries = distinct rows + noise. Returns (queries, the row each
    was made from)."""
    rng = np.random.default_rng([seed, 7])
    rows = rng.choice(len(corpus), size=n, replace=False)
    dims = corpus.shape[1]
    noise = rng.standard_normal((n, dims), dtype=np.float32)
    scale = np.float32(data["query_noise"] / np.sqrt(dims))
    return corpus[rows] + scale * noise, rows


# -- the arithmetic -----------------------------------------------------------

def layout(cfg: dict) -> dict:
    """The configuration's ``rq_layout`` with what its collection block
    posts: the code width and ``rescoreLimit``."""
    rq = cfg["collection"]["vectorIndexConfig"]["rq"]
    if rq["bits"] != 8:
        raise ValueError(f"no reference for {rq['bits']}-bit codes")
    return {**cfg["rq_layout"], "rescore_limit": rq["rescoreLimit"]}


def rotation(layout: dict) -> np.ndarray:
    """[rdims, rdims] orthogonal: Q . sign(diag R) of a seeded Gaussian."""
    n = layout["rdims"]
    g = np.random.default_rng(layout["rotation_seed"]).standard_normal(
        (n, n)).astype(np.float32)
    q, r = np.linalg.qr(g)
    return (q * np.sign(np.diag(r))[None, :]).astype(np.float32)


def fetch_count(layout: dict, k: int) -> int:
    return max(4 * k, layout["rescore_limit"], k)


def _distance(queries: np.ndarray, rows: np.ndarray,
              arithmetic: str = "fp32") -> np.ndarray:
    """[Q, D] x [Q, C, D] -> [Q, C] cosine distances of unit vectors, the
    operands rounded to ``arithmetic`` (the control's), sums in float32."""
    if arithmetic == "bf16":
        queries, rows = reference.to_bf16(queries), reference.to_bf16(rows)
    elif arithmetic == "int8":
        scale = reference.int8_scale(queries, rows)
        queries = reference.to_int8(queries, scale)
        rows = reference.to_int8(rows, scale)
    elif arithmetic != "fp32":
        raise ValueError(f"unknown arithmetic {arithmetic!r}")
    return np.float32(1.0) - np.einsum("qd,qcd->qc", queries, rows)


def best(d: np.ndarray, ids: np.ndarray, k: int):
    """The k smallest of each row of ``d``, by distance then id."""
    kk = min(k, d.shape[1])
    part = np.argpartition(d, kk - 1, axis=1)[:, :kk]
    pd = np.take_along_axis(d, part, axis=1)
    pi = np.take_along_axis(ids, part, axis=1)
    order = np.lexsort((pi, pd), axis=1)
    return (np.take_along_axis(pd, order, axis=1),
            np.take_along_axis(pi, order, axis=1))


def _blocked_best(n: int, k: int, score):
    """The k best of ``score(lo, hi)`` ([Q, hi - lo] distances of rows
    lo..hi) over rows 0..n, a block of rows at a time."""
    best_d = best_i = None
    for lo in range(0, n, BLOCK):
        hi = min(lo + BLOCK, n)
        d = score(lo, hi)
        d, ids = best(d, np.broadcast_to(np.arange(lo, hi), d.shape), k)
        if best_d is not None:
            d, ids = best(np.concatenate([best_d, d], axis=1),
                          np.concatenate([best_i, ids], axis=1), k)
        best_d, best_i = d, ids
    return best_d, best_i


class RQ:
    """The unit originals and their 8-bit codes, as the configuration lays
    them out."""

    def __init__(self, corpus: np.ndarray, layout: dict):
        self.rows = reference.unit(corpus)
        self.layout = layout
        self.rot = rotation(layout)
        n = len(self.rows)
        self.codes = np.empty((n, layout["rdims"]), np.uint8)
        self.lower = np.empty(n, np.float32)
        self.step = np.empty(n, np.float32)
        for lo in range(0, n, BLOCK):
            r = self.rotate(self.rows[lo:lo + BLOCK])
            low, high = r.min(axis=1), r.max(axis=1)
            step = np.maximum(high - low, np.float32(1e-12)) / np.float32(
                LEVELS)
            self.codes[lo:lo + BLOCK] = np.clip(
                np.rint((r - low[:, None]) / step[:, None]), 0, LEVELS)
            self.lower[lo:lo + BLOCK], self.step[lo:lo + BLOCK] = low, step

    def rotate(self, v: np.ndarray) -> np.ndarray:
        pad = self.layout["rdims"] - v.shape[1]
        if pad:
            v = np.pad(v, ((0, 0), (0, pad)))
        return v @ self.rot

    def code_topk(self, q_rot: np.ndarray, fetch: int):
        """(code-space distances, row ids) [Q, fetch] of rotated unit
        queries."""
        q_sum = q_rot.sum(axis=1)[:, None]
        return _blocked_best(len(self.codes), fetch, lambda lo, hi: (
            np.float32(1.0)
            - (self.step[None, lo:hi]
               * (q_rot @ self.codes[lo:hi].astype(np.float32).T)
               + q_sum * self.lower[None, lo:hi])))

    def pair_distance(self, queries: np.ndarray, ids: np.ndarray,
                      arithmetic: str = "fp32") -> np.ndarray:
        """Exact distance of each unit query to each of its own ``ids``
        [Q, C]; ids naming no row read +inf."""
        ok = (ids >= 0) & (ids < len(self.rows))
        d = _distance(queries, self.rows[np.where(ok, ids, 0)], arithmetic)
        return np.where(ok, d, np.inf).astype(np.float32)

    def answer(self, queries: np.ndarray, k: int, fetch: int,
               arithmetic: str = "fp32"):
        """(distances, ids) [Q, k]: the fetch best in code space, rescored
        exactly (at ``arithmetic``), the k best of them."""
        q = reference.unit(queries)
        _, cand = self.code_topk(self.rotate(q), fetch)
        return best(self.pair_distance(q, cand, arithmetic), cand, k)

    def exact_topk(self, queries: np.ndarray, k: int):
        """(distances, ids) [Q, k] over every row, float32."""
        q = reference.unit(queries)
        return _blocked_best(len(self.rows), k, lambda lo, hi: (
            np.float32(1.0) - q @ self.rows[lo:hi].T))


# -- the comparison -----------------------------------------------------------

def compare_answers(ref: RQ, queries: np.ndarray, k: int,
                    answers: list[tuple[int, np.ndarray, np.ndarray]],
                    sampled: list[int]) -> dict:
    """``answers``: (query index, served row ids, served distances) for every
    answered query vector; ``sampled``: the query indexes whose answers are
    also held to both top-k lists."""
    got_i = np.full((len(answers), k), -1, np.int64)
    got_d = np.full((len(answers), k), np.inf, np.float32)
    which = np.array([qi for qi, _, _ in answers], np.int64)
    bad = 0
    for a, (_, ids, dists) in enumerate(answers):
        m = min(len(ids), k)
        got_i[a, :m], got_d[a, :m] = ids[:m], dists[:m]
        valid = (got_i[a] >= 0) & (got_i[a] < len(ref.rows))
        bad += k - len(set(got_i[a][valid].tolist())) + max(0, len(ids) - k)
    # distinct (query, served ids) rows: repeated queries give repeated
    # answers, so their exact distances are worked out once each
    key = np.concatenate([which[:, None], got_i], axis=1)
    uniq, inverse = np.unique(key, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    pair = ref.pair_distance(reference.unit(queries[uniq[:, 0]]),
                             uniq[:, 1:])[inverse]
    valid = (got_i >= 0) & (got_i < len(ref.rows))
    errs = np.abs(np.where(valid, got_d, 0.0) - np.where(valid, pair, 0.0))
    slot = {qi: j for j, qi in enumerate(sampled)}
    held = [a for a in range(len(answers)) if which[a] in slot]
    # no answer held: nothing of either top k was served
    rq_hit = ex_hit = [0.0]
    if held:
        _, rq_i = ref.answer(queries[sampled], k,
                             fetch_count(ref.layout, k))
        _, ex_i = ref.exact_topk(queries[sampled], k)
        rq_hit = [len(set(got_i[a].tolist()) & set(
            rq_i[slot[which[a]]].tolist())) / k for a in held]
        ex_hit = [len(set(got_i[a].tolist()) & set(
            ex_i[slot[which[a]]].tolist())) / k for a in held]
    return {"bad_hits": bad, "dist_err": float(errs.max(initial=0.0)),
            "recall_miss": 1.0 - float(np.mean(rq_hit)),
            "exact_recall_miss": 1.0 - float(np.mean(ex_hit)),
            "answers": len(answers), "sampled_queries": len(sampled),
            "held_answers": len(held)}


def control_answers(ref: RQ, queries: np.ndarray, k: int, used: list[int],
                    arithmetic: str):
    """The control: the plain reference put in the program's place with its
    rescore computed at a lower precision (``bf16``, the nearest below the
    configuration's float32; ``int8``). Answers in ``compare_answers``'
    form, one per query index in ``used``."""
    d, i = ref.answer(queries[used], k, fetch_count(ref.layout, k),
                      arithmetic)
    return [(qi, i[j], d[j]) for j, qi in enumerate(used)]
