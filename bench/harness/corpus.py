"""Corpus generation for the benchmark: posting lists and term frequencies.

A vectorized copy of the clustered two-state generator the system's own
data module uses, with the same laws:

* docID gaps come from a two-state chain over {dense, sparse}: dense gaps
  ~ Geometric(1 / mean_dense_gap), sparse gaps ~ Geometric(1 /
  mean_sparse_gap).  The chain stays dense with probability ``p_stay``
  and stays sparse with probability ``stay_s``, chosen so that a share
  ``frac_dense`` of the gaps is dense: the law the system generator's
  docstring states (85% dense gaps; its loop uses ``stay_s`` as the
  probability of leaving the sparse state, which makes about 99.9% of
  the gaps dense);
* term frequencies come from a second sticky chain over {hot, cold}: hot
  tf ~ Zipf(zipf_hot), cold tf ~ Zipf(zipf_cold), capped at ``max_tf``;
  hot stays hot with probability ``p_stay``, cold leaves with probability
  ``1 - stay_c``.

A run of a sticky chain is geometric in length, so the chain is drawn run
by run instead of posting by posting: generating 24M postings takes
seconds, not a minute.  List lengths are rank-Zipf (``zipf_lengths``), the
longest holding ``max_list_share`` of the postings.  Every list's docIDs
are confined to the configuration's document universe ``n_docs``: a list
whose expected span would pass it gets a smaller sparse-gap mean, and any
overshoot left is squeezed out without breaking strict increase.

Every parameter of both laws comes from the configuration's file.
"""

from __future__ import annotations

import numpy as np

# a list whose expected span passes this share of the universe gets a
# smaller sparse-gap mean
SPAN_FILL = 0.9


def zipf_lengths(n_lists: int, total: int, max_len: int) -> np.ndarray:
    """Rank-frequency Zipf lengths ``max_len * r^-s``, s bisected so that
    they sum to about ``total``; descending by rank."""
    r = np.arange(1, n_lists + 1, dtype=np.float64)
    lo, hi = 0.0, 8.0
    for _ in range(60):
        s = (lo + hi) / 2
        if (max_len * r**-s).sum() > total:
            lo = s
        else:
            hi = s
    return np.maximum(max_len * r**-hi, 1).astype(np.int64)


def sticky_states(rng: np.random.Generator, n: int, leave_a: float,
                  leave_b: float, p_a: float) -> np.ndarray:
    """Bool states (True = state a) of a two-state chain that leaves a with
    probability ``leave_a`` and b with ``leave_b``; the first state is a
    with probability ``p_a``.  Drawn run by run: a run is geometric."""
    first_a = rng.random() < p_a
    runs: list[np.ndarray] = []
    total = 0
    leave_a, leave_b = max(leave_a, 1e-12), max(leave_b, 1e-12)
    pair = 1.0 / leave_a + 1.0 / leave_b  # mean length of a pair of runs
    while total < n:
        m = int((n - total) / pair) + 8
        la = rng.geometric(leave_a, size=m)
        lb = rng.geometric(leave_b, size=m)
        pairs = np.stack([la, lb] if first_a else [lb, la], axis=1).ravel()
        runs.append(pairs)
        total += int(pairs.sum())
    lens = np.concatenate(runs)
    ends = np.cumsum(lens)
    cut = int(np.searchsorted(ends, n)) + 1
    lens = lens[:cut]
    is_a = np.zeros(cut, bool)
    is_a[(0 if first_a else 1)::2] = True
    return np.repeat(is_a, lens)[:n]


def _stay_other(p_stay: float, frac: float) -> float:
    """The system generator's ``stay_s`` / ``stay_c``, clipped as it clips
    them."""
    s = 1.0 - (1.0 - p_stay) * frac / max(1e-9, 1.0 - frac)
    return min(max(s, 0.5), 0.99999)


def posting_list(rng: np.random.Generator, n: int, n_docs: int, *,
                 mean_dense_gap: float, mean_sparse_gap: float,
                 p_stay: float, frac_dense: float) -> np.ndarray:
    """One strictly increasing int64 docID list of length n in [0, n_docs)."""
    if n > n_docs:
        raise ValueError(f"a list of {n} postings cannot fit {n_docs} documents")
    leave_s = 1.0 - _stay_other(p_stay, frac_dense)  # sparse -> dense
    sparse = (1.0 - p_stay) / (1.0 - p_stay + leave_s)  # stationary share
    mean = (1 - sparse) * mean_dense_gap + sparse * mean_sparse_gap
    if n * mean > SPAN_FILL * n_docs:
        # keep the dense law, shrink the sparse mean to fit the universe
        want = SPAN_FILL * n_docs / n
        mean_sparse_gap = max(
            (want - (1 - sparse) * mean_dense_gap) / sparse, 1.0
        )
    states = sticky_states(rng, n, 1.0 - p_stay, leave_s, frac_dense)
    gd = rng.geometric(min(1.0, 1.0 / mean_dense_gap), size=n)
    gs = rng.geometric(min(1.0, 1.0 / mean_sparse_gap), size=n)
    docs = np.cumsum(np.where(states, gd, gs).astype(np.int64)) - 1
    if docs[-1] >= n_docs:
        # squeeze the excess over 0, 1, 2, ... so the last docID fits
        idx = np.arange(n, dtype=np.int64)
        excess = docs - idx
        docs = idx + excess * (n_docs - n) // (excess[-1] + 1)
    return docs


def term_freqs(rng: np.random.Generator, n: int, *, zipf_hot: float,
               zipf_cold: float, p_stay: float, frac_hot: float,
               max_tf: int) -> np.ndarray:
    """tf >= 1 of each of n postings: clustered Zipf, int64."""
    hot = sticky_states(rng, n, 1.0 - p_stay,
                        1.0 - _stay_other(p_stay, frac_hot), frac_hot)
    tf = np.where(hot, rng.zipf(zipf_hot, size=n), rng.zipf(zipf_cold, size=n))
    return np.minimum(tf, max_tf).astype(np.int64)


def make_corpus(rng: np.random.Generator, corpus: dict):
    """(lists, freqs, ranks) for a configuration's ``corpus`` section.

    The lists' contents come from the configuration's own ``seed``, so every
    run indexes the same documents; ``rng`` (the run's seed) chooses which
    list id each list gets.  The engines compile their programs for the
    arena's shapes, which a list order keeps and other contents would not,
    so runs of any seed share one set of compiled programs.  ``ranks[t]`` is
    list t's rank by length (0 = longest)."""
    lens = zipf_lengths(
        int(corpus["n_lists"]), int(corpus["postings"]),
        int(corpus["postings"] * corpus["max_list_share"]),
    )
    gap_law, tf_law = corpus["gap_law"], corpus["tf_law"]
    n_docs = int(corpus["n_docs"])
    content = np.random.default_rng(int(corpus["seed"]))
    by_rank = [posting_list(content, int(n), n_docs, **gap_law) for n in lens]
    tf_by_rank = [term_freqs(content, int(n), **tf_law) for n in lens]
    order = rng.permutation(len(lens))  # list id -> length rank
    return ([by_rank[r] for r in order], [tf_by_rank[r] for r in order], order)
