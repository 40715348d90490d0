"""The plain reference and the comparison that decides ``correct``.

Everything here is numpy on the host and imports nothing of the program:
seeded data, a brute-force scan at the collection's stated arithmetic
(inputs rounded to bfloat16, products and sums in float32 — a bf16 x bf16
product is exact in float32, so the device differs only by the order of the
float32 sum), the same scan at the nearest precision below it (symmetric
int8, the control), and the numbers compared.

Numbers compared for a set of served answers (each has its own limit in the
workload's file):

``bad_hits``   served hits that are missing (fewer than k), repeated within
               one answer, or name a row that was never acknowledged. Exact
               comparison: limit 0.
``rank_gap``   widest gap by which the reference distance of the id served
               at rank r lies above the reference's own rank-r distance, as
               a share of ``scale`` (the median reference k-th distance).
               Proof against ties; an altered or wrongly selected id reads
               ~0.1-1, exact selection reads rounding.
``dist_err``   widest |served distance - reference distance of that served
               id|, as a share of ``scale``. Lower-precision arithmetic
               shows here first.
"""

from __future__ import annotations

import numpy as np

BLOCK = 65536   # corpus rows scored at once: bounds host memory


# -- seeded data ------------------------------------------------------------

def make_rows(data: dict, dims: int, rows: int, seed: int,
              stream: int = 0) -> np.ndarray:
    """``rows`` x ``dims`` float32 from ``seed``; ``stream`` separates the
    preload from rows made later in the same run."""
    rng = np.random.default_rng([seed, stream])
    if data["kind"] == "normal":
        return rng.standard_normal((rows, dims), dtype=np.float32)
    if data["kind"] == "uniform_int":
        return rng.integers(data["low"], data["high"] + 1,
                            (rows, dims)).astype(np.float32)
    raise ValueError(f"unknown data kind {data['kind']!r}")


def make_queries(data: dict, corpus: np.ndarray, n: int, seed: int,
                 rows: np.ndarray | None = None):
    """``n`` queries = distinct corpus rows + noise. Returns (queries, the
    corpus row each was made from)."""
    rng = np.random.default_rng([seed, 7])
    if rows is None:
        rows = rng.choice(len(corpus), size=n, replace=False)
    base = corpus[rows]
    if data["kind"] == "normal":
        noise = rng.standard_normal(base.shape, dtype=np.float32)
        return base + np.float32(data["query_noise"]) * noise, rows
    r = int(data["query_noise"])
    noise = rng.integers(-r, r + 1, base.shape).astype(np.float32)
    return np.clip(base + noise, data["low"], data["high"]), rows


# -- arithmetic -------------------------------------------------------------

def to_bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16 (ties to even), kept in
    float32 — what ``astype(bfloat16)`` does to a finite value."""
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32)
    bits = (bits + np.uint32(0x7FFF) + ((bits >> 16) & 1)) \
        & np.uint32(0xFFFF0000)
    return bits.view(np.float32)


def int8_scale(*arrays: np.ndarray) -> np.float32:
    return np.float32(max(float(np.abs(a).max()) for a in arrays) / 127.0)


def to_int8(x: np.ndarray, scale: np.float32) -> np.ndarray:
    """Symmetric int8 with one scale, kept in float32 (products of two such
    values and their sums over D <= 1536 are exact in float32)."""
    return np.clip(np.rint(x / scale), -127, 127).astype(np.float32) * scale


def unit(x: np.ndarray) -> np.ndarray:
    n = np.sqrt(np.einsum("ij,ij->i", x, x, dtype=np.float32))[:, None]
    return (x / np.maximum(n, np.float32(1e-12))).astype(np.float32)


class Scan:
    """Brute-force scorer for one (distance, arithmetic): operands are
    rounded to ``arithmetic``, products and sums are float32."""

    def __init__(self, distance: str, corpus: np.ndarray,
                 arithmetic: str = "bf16"):
        if distance not in ("cosine", "l2-squared"):
            raise ValueError(f"no reference for distance {distance!r}")
        self.distance = distance
        self.corpus = unit(corpus) if distance == "cosine" else corpus
        self.arithmetic = arithmetic
        self._scale = None

    def _prep(self, queries: np.ndarray) -> np.ndarray:
        return unit(queries) if self.distance == "cosine" else \
            np.asarray(queries, np.float32)

    def _round(self, x: np.ndarray, queries: np.ndarray) -> np.ndarray:
        if self.arithmetic == "bf16":
            return to_bf16(x)
        if self.arithmetic == "int8":
            if self._scale is None:
                self._scale = int8_scale(self.corpus, queries)
            return to_int8(x, self._scale)
        raise ValueError(f"unknown arithmetic {self.arithmetic!r}")

    def _dist(self, q: np.ndarray, q_r: np.ndarray, block: np.ndarray,
              block_r: np.ndarray) -> np.ndarray:
        ip = q_r @ block_r.T
        if self.distance == "cosine":
            return np.float32(1.0) - ip
        q_sq = np.einsum("ij,ij->i", q, q)[:, None]
        c_sq = np.einsum("ij,ij->i", block, block)[None, :]
        return np.maximum(q_sq - np.float32(2.0) * ip + c_sq, 0.0)

    def topk(self, queries: np.ndarray, k: int, live: int | None = None):
        """(distances [Q, k], row ids [Q, k]) over the first ``live`` rows,
        sorted by distance then id."""
        q = self._prep(queries)
        q_r = self._round(q, q)
        n = len(self.corpus) if live is None else live
        best_d = np.full((len(q), k), np.inf, np.float32)
        best_i = np.full((len(q), k), -1, np.int64)
        for lo in range(0, n, BLOCK):
            block = self.corpus[lo:min(lo + BLOCK, n)]
            d = self._dist(q, q_r, block, self._round(block, q))
            kk = min(k, d.shape[1])
            sel = np.argpartition(d, kk - 1, axis=1)[:, :kk]
            cat_d = np.concatenate(
                [best_d, np.take_along_axis(d, sel, axis=1)], axis=1)
            cat_i = np.concatenate([best_i, sel + lo], axis=1)
            sel = np.argpartition(cat_d, k - 1, axis=1)[:, :k]
            best_d = np.take_along_axis(cat_d, sel, axis=1)
            best_i = np.take_along_axis(cat_i, sel, axis=1)
        order = np.lexsort((best_i, best_d), axis=1)
        return (np.take_along_axis(best_d, order, axis=1),
                np.take_along_axis(best_i, order, axis=1))

    def pair_distance(self, queries: np.ndarray, ids: np.ndarray):
        """Distance of each query to each of its own ``ids`` [Q, k]; rows
        outside the corpus read as +inf."""
        q = self._prep(queries)
        q_r = self._round(q, q)
        ok = (ids >= 0) & (ids < len(self.corpus))
        rows = self.corpus[np.where(ok, ids, 0)]          # [Q, k, D]
        rows_r = self._round(rows.reshape(-1, rows.shape[-1]), q) \
            .reshape(rows.shape)
        ip = np.einsum("qd,qkd->qk", q_r, rows_r)
        if self.distance == "cosine":
            d = np.float32(1.0) - ip
        else:
            d = np.maximum(
                np.einsum("qd,qd->q", q, q)[:, None] - np.float32(2.0) * ip
                + np.einsum("qkd,qkd->qk", rows, rows), 0.0)
        return np.where(ok, d, np.inf).astype(np.float32)


# -- the comparison ---------------------------------------------------------

def compare_answers(scan: Scan, queries: np.ndarray, k: int,
                    answers: list[tuple[int, np.ndarray, np.ndarray]],
                    live: int | None = None) -> dict:
    """``answers``: (query index, served row ids, served distances) for every
    answered query vector. The reference is computed once per distinct query
    and every answer is held against it."""
    used = sorted({qi for qi, _, _ in answers})
    slot = {qi: j for j, qi in enumerate(used)}
    ref_d, _ = scan.topk(queries[used], k, live)
    scale = float(np.median(ref_d[:, -1]))
    n_live = len(scan.corpus) if live is None else live
    got_i = np.full((len(answers), k), -1, np.int64)
    got_d = np.full((len(answers), k), np.inf, np.float32)
    which = np.empty(len(answers), np.int64)
    bad = 0
    for a, (qi, ids, dists) in enumerate(answers):
        which[a] = slot[qi]
        m = min(len(ids), k)
        got_i[a, :m], got_d[a, :m] = ids[:m], dists[:m]
        valid = (got_i[a] >= 0) & (got_i[a] < n_live)
        bad += int(k - len(set(got_i[a][valid].tolist())))
        if len(ids) > k:
            bad += len(ids) - k
    # distinct (query, served ids) rows: repeated queries give repeated
    # answers, so the pair distances are worked out once each
    key = np.concatenate([which[:, None], got_i], axis=1)
    uniq, inverse = np.unique(key, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    pair = scan.pair_distance(queries[used][uniq[:, 0]], uniq[:, 1:])[inverse]
    valid = (got_i >= 0) & (got_i < n_live)
    pair = np.where(valid, pair, 0.0)
    gaps = np.where(valid, pair - ref_d[which], 0.0)
    errs = np.abs(np.where(valid, got_d, 0.0) - pair)
    return {
        "bad_hits": bad,
        "rank_gap": float(gaps.max(initial=0.0) / scale),
        "dist_err": float(errs.max(initial=0.0) / scale),
        "scale": scale,
        "answers": len(answers),
        "distinct_queries": len(used),
    }


def control_answers(distance: str, corpus: np.ndarray, queries: np.ndarray,
                    k: int, used: list[int], live: int | None = None):
    """The control: the reference put in the program's place, computed in
    int8 — the nearest precision below the configuration's bf16. Returns
    answers in ``compare_answers``' form, one per query index in ``used``."""
    low = Scan(distance, corpus, "int8")
    d, i = low.topk(queries[used], k, live)
    return [(qi, i[j], d[j]) for j, qi in enumerate(used)]


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number compared beside its limit; ``correct`` is all within."""
    compared = {name: {"value": numbers[name], "limit": limit}
                for name, limit in limits.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in compared.values())
    return bool(ok), compared
