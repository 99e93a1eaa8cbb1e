"""Query logs and arrival schedules, from a traffic file and a seed.

Every seed gets the same work in another order, so that runs of different
seeds measure the same load.  A query is drawn as a tuple of popularity
ranks from a stream fixed by the traffic file's ``log_seed``: the query
lengths and the ranks are each a fixed grid of quantiles of their law,
combined into queries by that stream.  The run's seed shuffles the order
of the queries and of the gaps between arrivals (another fixed quantile
grid), and the corpus maps ranks to list ids by the seed.

Traffic file keys read here:

* ``query_len``: {length: weight}, the law of terms per query;
* ``popularity``: {"s": Zipf exponent over list-length rank (0 = uniform),
  "skip_top": the longest lists, treated as stop words and never drawn};
* ``log_seed``: the stream the query sets are drawn from;
* ``rate_qps``: arrivals per second of an open loop (Poisson).
"""

from __future__ import annotations

import numpy as np


def _grid(n: int) -> np.ndarray:
    """n mid-point quantile levels in (0, 1)."""
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def _inverse_cdf(weights: np.ndarray, u: np.ndarray) -> np.ndarray:
    """Index drawn at quantile level u from a discrete law."""
    cdf = np.cumsum(weights) / np.sum(weights)
    return np.minimum(np.searchsorted(cdf, u, side="right"), len(cdf) - 1)


def query_lengths(rng, law: dict, n: int) -> np.ndarray:
    lens = np.array(sorted(int(k) for k in law), np.int64)
    w = np.array([float(law[str(k)]) for k in lens])
    return rng.permutation(lens[_inverse_cdf(w, _grid(n))])


def popularity_weights(n_ranks: int, pop: dict) -> np.ndarray:
    """Weight of each length rank 0..n_ranks-1 (0 = longest list)."""
    r = np.arange(1, n_ranks + 1, dtype=np.float64)
    w = r ** -float(pop.get("s", 1.0))
    w[: int(pop.get("skip_top", 0))] = 0.0
    return w


def query_log(rng, traffic: dict, rank_to_list: np.ndarray, n: int,
              stream: int) -> list[list[int]]:
    """n queries of distinct list ids under the mix's laws: query set
    ``stream`` of the traffic's ``log_seed``, in the order ``rng`` draws."""
    ranks = rank_tuples(traffic, len(rank_to_list), n, stream)
    return [[int(rank_to_list[r]) for r in ranks[i]]
            for i in rng.permutation(n)]


def rank_tuples(traffic: dict, n_ranks: int, n: int, stream: int) -> list:
    """n queries as tuples of distinct popularity ranks."""
    rng = np.random.default_rng([int(traffic["log_seed"]), int(stream)])
    lens = query_lengths(rng, traffic["query_len"], n)
    w = popularity_weights(n_ranks, traffic["popularity"])
    ranks = rng.permutation(_inverse_cdf(w, _grid(int(lens.sum()))))
    ends = np.cumsum(lens)
    starts = ends - lens
    # a query may not hold one term twice: swap a repeated slot with a
    # random slot elsewhere until none is left (popular ranks recur)
    for _ in range(64):
        bad = []
        for s, e in zip(starts, ends):
            q = ranks[s:e]
            if len(np.unique(q)) < len(q):
                _, first = np.unique(q, return_index=True)
                dup = np.setdiff1d(np.arange(len(q)), first)
                bad.extend(s + dup)
        if not bad:
            break
        other = rng.integers(0, len(ranks), len(bad))
        ranks[bad], ranks[other] = ranks[other], ranks[bad].copy()
    return [list(dict.fromkeys(int(r) for r in ranks[s:e]))
            for s, e in zip(starts, ends)]


def poisson_due(rng, rate: float, n: int) -> np.ndarray:
    """Due times (s, from 0) of n Poisson arrivals at ``rate``: the gaps are
    the exponential law's quantile grid, shuffled, so every seed's
    schedule spans the same time."""
    gaps = -np.log1p(-_grid(n)) / float(rate)
    return np.cumsum(rng.permutation(gaps)) - gaps.min()
