"""What the candidates cost, read without the server: recall@10 and
``rank_gap`` of "the 10 best by exact MaxSim among the C best by FDE product"
for C = 16 .. 1,024 and for a given (ksim, dprojections, repetitions), on the
configuration's seeded data at full rows. Numpy on the host: the program's
``MuveraEncoder`` makes the FDEs (it is numpy itself), the FDE products and
their top C are exact float32, the MaxSim is ``reference_multivector``'s.
``benchmark/configs/msmarco-128-multivector.json`` took its ``rescore_limit``
readings, its data's two topic parameters and ``what_a_breach_reads`` from
this script (PR 35); it reproduced the chip's in-process readings of that PR
to the fourth digit. ~80 s and ~5 GB at 50,000 passages; no device number
comes from here.

    python3 -m benchmark.recall_sweep_multivector --seed 7 \\
        [--encoder 4,16,10 --encoder 4,16,2] [--topic-rows 200] \\
        [--topic-weight 0.7] [--queries 128] [--rows 50000]
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from benchmark import reference_hybrid, reference_multivector

HERE = os.path.dirname(os.path.abspath(__file__))
CANDIDATES = (16, 32, 64, 128, 256, 512, 1024)


def sweep(cfg: dict, data: dict, rows: int, n_queries: int, seed: int,
          encoders: list[tuple[int, int, int]]) -> dict:
    from weaviate_tpu.index.multivector import MuveraEncoder

    dims, k = cfg["dims"], cfg["k"]
    passages = reference_hybrid.make_passages(data["text"], rows, seed)
    tokens, offsets = reference_multivector.make_token_sets(
        data, dims, passages, seed)
    queries, source = reference_multivector.make_queries(
        data, dims, passages, n_queries, seed)
    exact = reference_multivector.MaxSim(tokens, offsets).scores(queries)
    best = np.argsort(-exact, axis=1, kind="stable")[:, :k]
    top = np.take_along_axis(exact, best, axis=1)
    scale = float(np.median(top[:, -1]))
    out = {"seed": seed, "rows": rows, "queries": n_queries, "scale": scale,
           "topic_rows": data["topic_rows"],
           "topic_weight": data["topic_weight"],
           "source_is_best": float(np.mean(best[:, 0] == source))}
    sets = [tokens[offsets[i]:offsets[i + 1]] for i in range(rows)]
    for ksim, dproj, reps in encoders:
        enc = MuveraEncoder(dims, ksim=ksim, dproj=dproj, repetitions=reps)
        fdes = np.concatenate([enc.encode_docs(sets[lo:lo + 100])
                               for lo in range(0, rows, 100)])
        products = np.stack([enc.encode_query(q) for q in queries]) @ fdes.T
        order = np.argsort(-products, axis=1, kind="stable")
        read = {}
        for c in CANDIDATES:
            recalls, gaps = [], []
            for j in range(n_queries):
                cand = order[j, :c]
                served = np.argsort(-exact[j, cand], kind="stable")[:k]
                recalls.append(len(set(cand[served].tolist())
                                   & set(best[j].tolist())) / k)
                gaps.append(float(np.max(
                    top[j, :len(served)] - exact[j, cand[served]])))
            read[str(c)] = {"recall_at_10": float(np.mean(recalls)),
                            "rank_gap": max(gaps) / scale,
                            # as the cell reads them: on 64 queries at a time
                            "of_64": [[float(np.mean(recalls[lo:lo + 64])),
                                       max(gaps[lo:lo + 64]) / scale]
                                      for lo in range(0, n_queries - 63, 64)]}
        read["no_rescore"] = {
            "recall_at_10": float(np.mean([
                len(set(order[j, :k].tolist()) & set(best[j].tolist())) / k
                for j in range(n_queries)])),
            "rank_gap": float(np.max(top - np.take_along_axis(
                exact, order[:, :k], axis=1))) / scale}
        out[f"ksim {ksim}, dprojections {dproj}, repetitions {reps}"] = read
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int)
    ap.add_argument("--queries", type=int, default=128)
    ap.add_argument("--topic-rows", type=int)
    ap.add_argument("--topic-weight", type=float)
    ap.add_argument("--encoder", action="append",
                    help="ksim,dprojections,repetitions; may repeat")
    args = ap.parse_args()
    with open(os.path.join(
            HERE, "configs", "msmarco-128-multivector.json")) as f:
        cfg = json.load(f)
    data = dict(cfg["data"])
    if args.topic_rows is not None:
        data["topic_rows"] = args.topic_rows
    if args.topic_weight is not None:
        data["topic_weight"] = args.topic_weight
    muvera = cfg["collection"]["vectorIndexConfig"]["multivector"]["muvera"]
    encoders = [tuple(int(x) for x in e.split(","))
                for e in args.encoder or []] or [
        (muvera["ksim"], muvera["dprojections"], muvera["repetitions"])]
    print(json.dumps(sweep(cfg, data, args.rows or cfg["rows"], args.queries,
                           args.seed, encoders)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
