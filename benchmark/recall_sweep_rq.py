"""What the candidates cost under 8-bit rotational quantization, read without
the server: recall@10 against the exact float32 top 10 of "the 10 best by
exact distance among the C best in code space", for C = k (no over-fetch)
and C = the configuration's fetch, on ``reference_rq``'s seeded clustered
rows at a grid of (``cluster_rows``, ``spread``). Numpy on the host, the
plain reference's own arithmetic; a second reading rounds the rotated query
to bfloat16 before its product with the codes, as the device's scan does.
``benchmark/configs/cohere-768-rq.json`` took its two data parameters from
this script. No device number comes from here.

    python3 -m benchmark.recall_sweep_rq --seed 7 [--rows 200000] \\
        [--queries 128] [--grid 200:0.5 --grid 1000:0.5 ...]
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from benchmark import reference, reference_rq

HERE = os.path.dirname(os.path.abspath(__file__))


def recall(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.mean([len(set(g.tolist()) & set(w.tolist())) / len(w)
                          for g, w in zip(got, want)]))


def sweep(cfg: dict, data: dict, rows: int, n_queries: int,
          seed: int) -> dict:
    layout, k = reference_rq.layout(cfg), cfg["k"]
    fetch = reference_rq.fetch_count(layout, k)
    corpus = reference_rq.make_rows(data, cfg["dims"], rows, seed)
    queries, _ = reference_rq.make_queries(data, corpus, n_queries, seed)
    ref = reference_rq.RQ(corpus, layout)
    del corpus
    exact_d, exact_i = ref.exact_topk(queries, fetch)
    exact_i = exact_i[:, :k]
    q = reference.unit(queries)
    # median distance from the k-th best to the next and to the fetch-th
    out = {"seed": seed, "rows": rows, "queries": n_queries,
           "cluster_rows": data["cluster_rows"], "spread": data["spread"],
           "gap_k_next": float(np.median(exact_d[:, k] - exact_d[:, k - 1])),
           "gap_k_fetch": float(np.median(exact_d[:, -1]
                                          - exact_d[:, k - 1]))}
    q_rot = ref.rotate(q)
    # the second reading: the device's product takes the rotated query in
    # bfloat16 (the codes are exact there)
    for name, q_code in (("fp32", q_rot),
                         ("bf16_query", reference.to_bf16(q_rot))):
        _, cand = ref.code_topk(q_code, fetch)
        for c in (k, fetch):
            d = ref.pair_distance(q, cand[:, :c])
            _, ids = reference_rq.best(d, cand[:, :c], k)
            out[f"recall_{name}_{c}"] = recall(ids, exact_i)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--queries", type=int, default=128)
    ap.add_argument("--grid", action="append", default=[],
                    help="cluster_rows:spread")
    args = ap.parse_args()
    with open(os.path.join(HERE, "configs", "cohere-768-rq.json")) as f:
        cfg = json.load(f)
    grid = [tuple(g.split(":")) for g in args.grid] or [
        (cfg["data"]["cluster_rows"], cfg["data"]["spread"])]
    for cluster_rows, spread in grid:
        data = {**cfg["data"], "cluster_rows": int(cluster_rows),
                "spread": float(spread)}
        print(json.dumps(sweep(cfg, data, args.rows or cfg["rows"],
                               args.queries, args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
