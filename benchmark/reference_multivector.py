"""The plain reference for late-interaction (multi-vector) search: seeded
ColBERT-style token sets, exact MaxSim (Chamfer) of a query against EVERY
passage, and the numbers compared when every request is one token set.

Numpy on the host, nothing of the program: no fixed-dimensional encoding, no
candidates. The score of a passage for a query is the sum over the query's
tokens of the largest dot product with one of the passage's tokens; operands
are rounded to the configuration's arithmetic (bfloat16; a bf16 x bf16
product is exact in float32), products and sums are float32. Passages are
scored in blocks of equal token count, so nothing is padded and it fits.

**Seeded data** (the configuration's ``assumed``). A passage is the hybrid
configuration's passage (``reference_hybrid.make_passages``: word ranks from
a Zipf law). Its tokens: ``[CLS]``, ``[D]``, one token a word and a second
piece for a seeded 0.3 of the words (in place), ``[SEP]``, cut at
``doc_maxlen``: ``min(doc_maxlen, 3 + round(1.3 x words))`` tokens. A token's
vector is ``unit(centroid of its word + topic_weight x the direction of its
passage's topic + context_noise x g / sqrt(D))``, ``g`` standard-normal, one
seeded unit centroid a vocabulary word (and one for each of the three
markers), one seeded unit direction a topic, ``rows / topic_rows`` topics
dealt to the passages at random. A contextual embedding carries its
passage's subject as well as its word: at the configuration's weights tokens
of one word lie close (cosine ~0.62 across topics, ~0.92 within one), of
different words far (~0 across topics, ~0.30 within one). So a query's best
passages are those of its source's topic, ordered by the words they share
with it: a topic about as large as the candidate list makes the list's
length matter, and a recall means something. (With the word alone, 100,000
isotropic centroids have no neighbourhoods for SimHash buckets to keep: at
50,000 passages recall@10 of 1,024 candidates reads 0.37; the
configuration's ``assumed`` has the readings and how the two parameters were
fixed.) A query
is ``query_tokens`` tokens in its source passage's topic: the words of a
text drawn as the hybrid cell draws it (two thirds from the source passage),
each re-noised, then ``[MASK]`` tokens: re-noised copies of those words in
turn, as ColBERT's query augmentation fills the query to its length.

**Tie-proof.** A served id is never judged by whether the reference picked
it, but by the score the reference gives THAT id.

Numbers compared for a set of served answers:

``bad_hits``       served hits repeated within one answer, naming no loaded
                   row, or beyond the k-th. Exact: limit 0.
``short_answers``  answers with fewer than ``min(k, rows)`` hits. Exact.
``score_err``      widest |served score - reference MaxSim of that id|, as a
                   share of ``scale`` (the median reference k-th score over
                   the sampled queries). Holds the rescore to the stated
                   arithmetic: lower precision shows here first.
``order_gap``      widest (served score at rank r+1 - served score at rank
                   r, where positive), as a share of ``scale``: the served
                   list is sorted by the exact score.
``rank_gap``       widest (the reference's own rank-r score - the reference
                   score of the id served at rank r), as a share of
                   ``scale``; and ``recall_miss`` = 1 - ``recall_at_10`` (the
                   mean share of the reference's k best that were served):
                   what the approximation (candidates by FDE product) costs.
                   These two need the scan of every passage, 15.6 GFLOP a
                   query at 50,000 passages, so they are read on a seeded
                   sample of the window's distinct queries
                   (``reference_queries`` of the workload); every other
                   number is read on every answer of the window.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import reference, reference_hybrid

MARKERS = 3             # [CLS], [D], [SEP]
BLOCK_BYTES = 192 << 20  # one block's [Q x Tq, tokens] float32 products
THREADS = 4
CHUNK = 65536           # tokens made at once


# -- seeded token sets --------------------------------------------------------

def centroids(vocabulary: int, dims: int, seed: int) -> np.ndarray:
    """One unit vector a vocabulary word, then one a marker."""
    rng = np.random.default_rng([seed, 31])
    return reference.unit(rng.standard_normal(
        (vocabulary + MARKERS, dims), dtype=np.float32))


def _noised(cent: np.ndarray, words: np.ndarray, directions: np.ndarray,
            topic: np.ndarray, noise: float, rng) -> np.ndarray:
    """unit(centroid of each word + the direction of its topic + noise x g
    / sqrt(D)), a chunk at a time in one reused buffer: the whole corpus'
    temporaries would cost more in page faults than the arithmetic."""
    dims = cent.shape[1]
    out = np.empty((len(words), dims), np.float32)
    buf = np.empty((CHUNK, dims), np.float32)
    for lo in range(0, len(words), CHUNK):
        hi = min(lo + CHUNK, len(words))
        g = rng.standard_normal((hi - lo, dims), dtype=np.float32,
                                out=buf[:hi - lo])
        g *= np.float32(noise / np.sqrt(dims))
        g += cent[words[lo:hi]]
        g += directions[topic[lo:hi]]
        g /= np.sqrt(np.einsum("ij,ij->i", g, g))[:, None]
        out[lo:hi] = g
    return out


def passage_token_words(data: dict, passages: list[np.ndarray],
                        seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(the word of every token of every passage, flat; offsets [rows + 1]).
    Which words get a second piece: those with the smallest seeded keys of
    their passage, as many as make ``round(pieces_per_word x words)``."""
    vocab, maxlen = data["text"]["vocabulary"], data["doc_maxlen"]
    n_words = np.array([len(p) for p in passages])
    flat = np.concatenate(passages).astype(np.int64)
    row = np.repeat(np.arange(len(passages)), n_words)
    start = np.concatenate(([0], np.cumsum(n_words)))
    extra = np.rint(data["pieces_per_word"] * n_words).astype(np.int64) \
        - n_words
    keys = np.random.default_rng([seed, 33]).random(len(flat))
    order = np.lexsort((keys, row))         # by passage, then by key
    rank = np.empty(len(flat), np.int64)
    rank[order] = np.arange(len(flat)) - start[row]
    pieces = 1 + (rank < extra[row])
    # [CLS] [D] before a passage's first word, [SEP] after its last
    words = np.repeat(flat, pieces)
    pos = np.concatenate(([0], np.cumsum(pieces)))[start]   # [rows + 1]
    words = np.insert(words, np.repeat(pos[:-1], 2),
                      np.tile([vocab, vocab + 1], len(passages)))
    pos = pos + 2 * np.arange(len(passages) + 1)
    words = np.insert(words, pos[1:], vocab + 2)
    pos = pos + np.arange(len(passages) + 1)
    keep = np.arange(len(words)) - np.repeat(pos[:-1], np.diff(pos)) < maxlen
    offsets = np.concatenate(([0], np.cumsum(np.minimum(np.diff(pos), maxlen))))
    return words[keep], offsets


def topics(data: dict, dims: int, rows: int, seed: int):
    """(the topic of every passage, topic_weight x a unit direction a
    topic)."""
    rng = np.random.default_rng([seed, 41])
    n = max(1, rows // data["topic_rows"])
    directions = reference.unit(
        rng.standard_normal((n, dims), dtype=np.float32))
    return rng.integers(0, n, rows), \
        np.float32(data["topic_weight"]) * directions


def make_token_sets(data: dict, dims: int, passages: list[np.ndarray],
                    seed: int):
    """(tokens [sum T, dims] float32, offsets [rows + 1])."""
    cent = centroids(data["text"]["vocabulary"], dims, seed)
    words, offsets = passage_token_words(data, passages, seed)
    topic, directions = topics(data, dims, len(passages), seed)
    return _noised(cent, words, directions,
                   np.repeat(topic, np.diff(offsets)), data["context_noise"],
                   np.random.default_rng([seed, 35])), offsets


def make_queries(data: dict, dims: int, passages: list[np.ndarray], n: int,
                 seed: int):
    """``n`` queries [n, query_tokens, dims] float32, each from a distinct
    source passage; also the source rows."""
    rows = np.random.default_rng([seed, 7]).choice(
        len(passages), size=n, replace=False)
    texts = reference_hybrid.make_query_texts(
        data["text"], passages, rows, seed)
    rank = {w: r for r, w in enumerate(
        reference_hybrid.words(data["text"]["vocabulary"]))}
    tq = data["query_tokens"]
    words = np.empty((n, tq), np.int64)
    for i, text in enumerate(texts):
        own = [rank[w] for w in text.split()]
        words[i] = [own[j % len(own)] for j in range(tq)]
    cent = centroids(data["text"]["vocabulary"], dims, seed)
    topic, directions = topics(data, dims, len(passages), seed)
    flat = _noised(cent, words.reshape(-1), directions,
                   np.repeat(topic[rows], tq), data["context_noise"],
                   np.random.default_rng([seed, 37]))
    return flat.reshape(n, tq, dims), rows


# -- exact MaxSim -------------------------------------------------------------

class MaxSim:
    """Every passage's token set, rounded once to ``arithmetic``, scored in
    blocks of passages of one token count. ``int8`` is symmetric with one scale over passages and
    queries (``queries`` given at construction: the scale needs them)."""

    def __init__(self, tokens: np.ndarray, offsets: np.ndarray,
                 arithmetic: str = "bf16", queries: np.ndarray | None = None):
        self.rows = len(offsets) - 1
        self.arithmetic = arithmetic
        self._scale = None
        if arithmetic == "int8":
            self._scale = reference.int8_scale(tokens, queries)
        elif arithmetic != "bf16":
            raise ValueError(f"unknown arithmetic {arithmetic!r}")
        self.offsets = offsets
        self.tokens = self._round(tokens)
        lengths = np.diff(offsets)
        # the rows of each token count: scored as one [n, T, D] block
        self.groups = [(int(t), np.flatnonzero(lengths == t))
                       for t in np.unique(lengths)]

    def _round(self, x: np.ndarray) -> np.ndarray:
        """``x`` [n, D] rounded to the arithmetic, a chunk of rows at a time
        (the whole corpus' temporaries cost more than the rounding)."""
        out = np.empty(x.shape, np.float32)
        for lo in range(0, len(x), CHUNK):
            part = x[lo:lo + CHUNK]
            out[lo:lo + CHUNK] = reference.to_bf16(part) \
                if self.arithmetic == "bf16" \
                else reference.to_int8(part, self._scale)
        return out

    def scores(self, queries: np.ndarray) -> np.ndarray:
        """[Q, Tq, D] -> MaxSim of every query with every passage
        [Q, rows] float32."""
        n_q, tq, dims = queries.shape
        flat_q = self._round(np.ascontiguousarray(
            queries, np.float32).reshape(n_q * tq, dims))
        out = np.empty((n_q, self.rows), np.float32)
        jobs = []
        for t, ids in self.groups:
            step = max(1, BLOCK_BYTES // (4 * n_q * tq * t))
            jobs += [(t, ids[lo:lo + step]) for lo in range(0, len(ids), step)]

        def run(job):
            t, ids = job
            take = (self.offsets[ids][:, None] + np.arange(t)).reshape(-1)
            sims = flat_q @ self.tokens[take].T
            out[:, ids] = sims.reshape(n_q, tq, len(ids), t).max(
                axis=3).sum(axis=1, dtype=np.float32)

        with ThreadPoolExecutor(THREADS) as pool:
            for _ in pool.map(run, jobs):
                pass
        return out

    def pair_scores(self, query: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """MaxSim of one query [Tq, D] with each of ``ids``; -inf for an id
        that names no row."""
        q = self._round(np.ascontiguousarray(query, np.float32))
        out = np.full(len(ids), -np.inf, np.float32)
        for j, i in enumerate(ids.tolist()):
            if 0 <= i < self.rows:
                toks = self.tokens[self.offsets[i]:self.offsets[i + 1]]
                out[j] = (q @ toks.T).max(axis=1).sum(dtype=np.float32)
        return out

    def topk(self, queries: np.ndarray, k: int):
        """(scores [Q, k], row ids [Q, k]), best first, ties to the lower
        id."""
        s = self.scores(queries)
        k = min(k, self.rows)
        sel = np.argpartition(-s, k - 1, axis=1)[:, :k]
        top = np.take_along_axis(s, sel, axis=1)
        order = np.lexsort((sel, -top), axis=1)
        return (np.take_along_axis(top, order, axis=1),
                np.take_along_axis(sel, order, axis=1))


# -- the comparison -----------------------------------------------------------

def sample_queries(used: list[int], n: int, seed: int) -> list[int]:
    """A seeded choice of ``n`` of the window's distinct queries."""
    if len(used) <= n:
        return list(used)
    pick = np.random.default_rng([seed, 39]).choice(
        len(used), size=n, replace=False)
    return [used[i] for i in np.sort(pick)]


def compare_answers(ref: MaxSim, queries: np.ndarray, k: int,
                    answers: list[tuple[int, np.ndarray, np.ndarray]],
                    sampled: list[int]) -> dict:
    """``answers``: (query index, served row ids, served MaxSim scores) for
    every answered request. The scan of every passage runs for the
    ``sampled`` query indices only; every answer is held to the pair
    scores of its own ids."""
    top_s, top_i = ref.topk(queries[sampled], k)
    slot = {qi: j for j, qi in enumerate(sampled)}
    scale = float(np.median(top_s[:, -1]))
    want = min(k, ref.rows)
    bad = short = 0
    err = order = gap = 0.0
    recalls: dict[int, float] = {}
    seen: dict[int, set] = {}
    for qi, ids, scores in answers:
        ids = np.asarray(ids, np.int64)
        scores = np.asarray(scores, np.float32)
        m = min(len(ids), k)
        known = (ids[:m] >= 0) & (ids[:m] < ref.rows)
        bad += (len(ids) - m) + int(m - known.sum()) + int(
            known.sum() - len(set(ids[:m][known].tolist())))
        short += int(len(ids) < want)
        key = (ids[:m].tobytes(), scores[:m].tobytes())
        if key in seen.setdefault(qi, set()):
            continue    # a repeated query gave the answer it gave before
        seen[qi].add(key)
        pair = ref.pair_scores(queries[qi], ids[:m])
        err = max(err, float(np.max(
            np.abs(scores[:m] - pair)[known], initial=0.0)))
        order = max(order, float(np.max(np.diff(scores[:m]), initial=0.0)))
        if qi in slot:
            j = slot[qi]
            r = min(m, top_s.shape[1])
            gap = max(gap, float(np.max(np.where(
                known[:r], top_s[j, :r] - pair[:r], 0.0), initial=0.0)))
            recalls[qi] = min(recalls.get(qi, 1.0), len(
                set(ids[:m].tolist()) & set(top_i[j].tolist())) / want)
    recall = float(np.mean(list(recalls.values()))) if recalls else 0.0
    return {
        "bad_hits": bad, "short_answers": short,
        "score_err": err / scale, "order_gap": order / scale,
        "rank_gap": gap / scale, "recall_miss": 1.0 - recall,
        "recall_at_10": recall, "scale": scale, "answers": len(answers),
        "distinct_queries": len(seen), "sampled_queries": len(recalls),
    }


def control_answers(tokens: np.ndarray, offsets: np.ndarray,
                    queries: np.ndarray, k: int, sampled: list[int],
                    control: str = "int8"):
    """The control: this reference put in the program's place, its MaxSim
    computed in symmetric int8, the nearest precision below the
    configuration's bfloat16. Answers in ``compare_answers``' form, one per
    query index in ``sampled``."""
    if control != "int8":
        raise ValueError(f"unknown control {control!r}")
    low = MaxSim(tokens, offsets, "int8", queries[sampled])
    s, i = low.topk(queries[sampled], k)
    return [(qi, i[j], s[j]) for j, qi in enumerate(sampled)]
