"""The control: the plain reference in the program's place, one step below
what the configuration states, which ``correct`` has to refuse.

* top-k: exhaustive BM25 summed in float32 instead of float64;
* AND: the conjunction of the two shortest lists only, breaking the stated
  guarantee that every list of the query filters the answer.

``readings`` gives the numbers a run would compare if the control had
answered its window: the same corpus, window queries and check sample as a
run of that seed, with every due request answered.
"""

from __future__ import annotations

import numpy as np

from harness import corpus as corpus_mod
from harness import reference, traffic as traffic_mod
from harness.cell import Reservoir, rng_stream


class ControlEngine:
    """Serves the control's answers through the engines' call signatures."""

    backend, interpret, resident = "control", False, "control"

    def __init__(self, lists, freqs, scoring: dict):
        self.lists = lists
        self.bm25 = reference.BM25(lists, freqs, k1=scoring["k1"], b=scoring["b"],
                                   levels=scoring["norm_levels"])
        self.stats = {}

    def topk_batch(self, queries, k):
        return [self.bm25.topk(q, k, accumulate=np.float32) if q else
                (np.zeros(0, np.int64), np.zeros(0)) for q in queries]

    def intersect_batch(self, queries):
        return [reference.intersect_two_shortest(self.lists, q) for q in queries]


def readings(cell, seed: int, seconds: float, closed_qps: float = 0.0) -> dict:
    """{"wrong_answers", "compared"} of the control on one seed's run; a
    closed loop is taken to serve ``closed_qps`` queries per second, in
    whole replays of its log."""
    tr, cfg = cell.traffic, cell.config
    lists, freqs, order = corpus_mod.make_corpus(rng_stream(seed, 0), cfg["corpus"])
    rank_to_list = np.argsort(order)
    rw = rng_stream(seed, 2)
    if tr["loop"] == "open":
        n = max(int(round(float(tr["rate_qps"]) * seconds)), 1)
        queries = traffic_mod.query_log(rw, tr, rank_to_list, n, 2)
    else:
        log_q = traffic_mod.query_log(rw, tr, rank_to_list,
                                      int(tr["log_queries"]), 2)
        replays = max(int(round(closed_qps * seconds / len(log_q))), 1)
        queries = log_q * replays
    ctl = ControlEngine(lists, freqs, cfg["scoring"])
    res = Reservoir(rng_stream(seed, 3), int(tr["check_sample"]),
                    lambda q: sum(len(lists[t]) for t in q))
    k = int(tr.get("k", 0))
    for i, q in enumerate(queries):
        res(i, q, None)
    wrong = 0
    sample = res.sample()
    for i, (q, _) in sample.items():
        if tr["operation"] == "topk":
            got = ctl.topk_batch([q], k)[0]
            ok = reference.same_topk(got, ctl.bm25.topk(q, k))
        else:
            got = ctl.intersect_batch([q])[0]
            ok = reference.same_and(got, reference.intersect(lists, q))
        wrong += not ok
    return {"wrong_answers": wrong, "compared": len(sample)}
