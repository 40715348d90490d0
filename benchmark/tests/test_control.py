"""The control has to come out as not correct: the plain reference, put in
the program's place and computed in int8 (the nearest precision below the
configurations' bf16), fails each cell's limits; the reference at the stated
precision passes them. Pure numpy at a size a test run can hold.
"""

from __future__ import annotations

import glob
import json
import os

import pytest

from benchmark import reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = sorted(glob.glob(os.path.join(HERE, "workloads", "*.json")))


@pytest.mark.parametrize("path", CELLS, ids=os.path.basename)
@pytest.mark.parametrize("seed", [1, 2147483659, 3000000019])
def test_int8_control_fails_and_bf16_passes(path, seed):
    with open(path) as f:
        work = json.load(f)
    with open(os.path.join(HERE, "configs", work["config"] + ".json")) as f:
        cfg = json.load(f)
    corpus = reference.make_rows(cfg["data"], cfg["dims"], 20000, seed)
    queries, _ = reference.make_queries(cfg["data"], corpus, 128, seed)
    used = list(range(len(queries)))
    scan = reference.Scan(cfg["distance"], corpus)
    limits = {k: v for k, v in work["limits"].items()
              if k in ("bad_hits", "rank_gap", "dist_err")}
    d, i = scan.topk(queries, cfg["k"])
    sound = reference.compare_answers(
        scan, queries, cfg["k"], [(q, i[q], d[q]) for q in used])
    assert reference.verdict(sound, limits)[0]
    control = reference.compare_answers(
        scan, queries, cfg["k"], reference.control_answers(
            cfg["distance"], corpus, queries, cfg["k"], used))
    ok, compared = reference.verdict(control, limits)
    assert not ok, compared
