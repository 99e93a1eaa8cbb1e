"""The benchmark's vectorized generator keeps the system generator's laws:
the term-frequency chain as the system codes it, and the docID chain as its
docstring states it (a share ``frac_dense`` of dense gaps)."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from harness import corpus, traffic  # noqa: E402
from repro.data.postings import make_freqs  # noqa: E402

N = 1_000_000
SEEDS = range(4)
CORPUS = json.loads((ROOT / "bench/configs/gov2-web.json").read_text())["corpus"]
GAP, TF = CORPUS["gap_law"], CORPUS["tf_law"]


def stated_chain_list(rng, n, mean_dense_gap, mean_sparse_gap, p_stay,
                      frac_dense):
    """The system generator's docID loop, posting by posting, with the
    transition its docstring states: stay dense w.p. ``p_stay``, stay sparse
    w.p. ``stay_s``, so that a share ``frac_dense`` of the gaps is dense."""
    stay_s = 1.0 - (1.0 - p_stay) * frac_dense / (1.0 - frac_dense)
    states = np.empty(n, dtype=bool)  # True = dense
    u = rng.random(n)
    s = rng.random() < frac_dense
    for i in range(n):
        states[i] = s
        s = (u[i] < p_stay) if s else (u[i] >= stay_s)
    gd = rng.geometric(1.0 / mean_dense_gap, size=n)
    gs = rng.geometric(1.0 / mean_sparse_gap, size=n)
    return np.cumsum(np.where(states, gd, gs).astype(np.int64)) - 1


def gap_stats(lists):
    g = np.concatenate([np.diff(x) for x in lists])
    return dict(mean=g.mean(), small=(g <= 2).mean(), big=(g >= 20).mean(),
                first=np.mean([x[0] for x in lists]))


def tf_stats(freqs):
    f = np.concatenate(freqs)
    return dict(one=(f == 1).mean(), median=np.median(f),
                q90=np.quantile(f, 0.9), max=f.max())


@pytest.fixture(scope="module")
def both():
    sys_lists = [stated_chain_list(np.random.default_rng(s), N, **GAP)
                 for s in SEEDS]
    sys_freqs = make_freqs(np.random.default_rng(9), sys_lists, **TF)
    ours = [corpus.posting_list(np.random.default_rng(s), N, 10**9, **GAP)
            for s in SEEDS]
    our_freqs = [corpus.term_freqs(np.random.default_rng(s), N, **TF)
                 for s in SEEDS]
    return sys_lists, sys_freqs, ours, our_freqs


def test_gap_law_matches_the_system_generator(both):
    sys_lists, _, ours, _ = both
    a, b = gap_stats(sys_lists), gap_stats(ours)
    assert abs(a["small"] - b["small"]) < 0.01  # dense gaps of 1-2
    assert abs(a["big"] - b["big"]) < 0.01      # sparse gaps, about 15%
    assert abs(a["mean"] - b["mean"]) / a["mean"] < 0.15
    # 85% dense gaps (mean 1.3, so 94.7% of them are 1 or 2), 15% sparse
    # (mean 1850): about 80.5% of gaps are 1-2, 15% are 20 or more
    assert 0.78 < b["small"] < 0.83 and 0.13 < b["big"] < 0.17
    assert 230 < b["mean"] < 330


def test_tf_law_matches_the_system_generator(both):
    _, sys_freqs, _, our_freqs = both
    a, b = tf_stats(sys_freqs), tf_stats(our_freqs)
    assert abs(a["one"] - b["one"]) < 0.03
    assert a["median"] == b["median"]
    assert abs(a["q90"] - b["q90"]) <= max(2.0, 0.25 * a["q90"])
    assert b["max"] <= 4096 and np.concatenate(our_freqs).min() >= 1


def test_lists_strictly_increase_inside_the_universe():
    rng = np.random.default_rng(5)
    for n, universe in ((1, 10), (1000, 1000), (50_000, 60_000),
                        (300_000, 8_841_823), (960_000, 24_622_347)):
        x = corpus.posting_list(rng, n, universe, **GAP)
        assert len(x) == n and x.dtype == np.int64
        assert x[0] >= 0 and x[-1] < universe
        assert np.all(np.diff(x) > 0)
    with pytest.raises(ValueError):
        corpus.posting_list(rng, 11, 10, **GAP)


def test_sticky_states_stationary_share():
    rng = np.random.default_rng(1)
    s = corpus.sticky_states(rng, 2_000_000, 0.01, 0.04, 0.5)
    assert abs(s.mean() - 0.8) < 0.02  # 0.04 / (0.01 + 0.04)
    runs = np.diff(np.flatnonzero(np.diff(s.astype(np.int8)) != 0))
    assert 20 < runs.mean() < 80  # runs of 100 and 25 alternate


def test_corpus_contents_fixed_list_order_seeded():
    spec = dict(n_lists=64, postings=100_000, max_list_share=0.04, n_docs=10**6,
                seed=7, gap_law=GAP, tf_law=TF)
    a = corpus.make_corpus(np.random.default_rng(3), spec)
    b = corpus.make_corpus(np.random.default_rng(3), spec)
    c = corpus.make_corpus(np.random.default_rng(4), spec)
    assert all(np.array_equal(x, y) for x, y in zip(a[0], b[0]))
    assert all(np.array_equal(x, y) for x, y in zip(a[1], b[1]))
    assert not np.array_equal(a[2], c[2])
    # the same lists and tfs under another order of list ids
    by_rank = lambda out: {int(r): (x, f) for x, f, r in zip(*out)}  # noqa: E731
    ra, rc = by_rank(a), by_rank(c)
    for r in ra:
        assert np.array_equal(ra[r][0], rc[r][0]) and np.array_equal(ra[r][1], rc[r][1])
    la = sorted((len(x) for x in a[0]), reverse=True)
    assert la == [len(ra[r][0]) for r in range(64)]  # rank 0 is the longest
    assert abs(sum(la) - 100_000) < 1_000 and max(la) == 4_000
    spec["seed"] = 8
    d = corpus.make_corpus(np.random.default_rng(3), spec)
    assert not np.array_equal(d[0][0], a[0][0])


def test_query_log_laws():
    tr = {"query_len": {"1": 0.1, "2": 0.3, "3": 0.6},
          "popularity": {"s": 1.0, "skip_top": 2}, "log_seed": 5}
    rank_to_list = np.arange(100)[::-1]
    q1 = traffic.query_log(np.random.default_rng(1), tr, rank_to_list, 1000, 2)
    q2 = traffic.query_log(np.random.default_rng(2), tr, rank_to_list, 1000, 2)
    q3 = traffic.query_log(np.random.default_rng(1), tr, rank_to_list, 1000, 3)
    assert q1 != q2 and sorted(q1) == sorted(q2)  # the same queries, reordered
    assert sorted(q1) != sorted(q3)  # another stream, another set
    assert q1 == traffic.query_log(np.random.default_rng(1), tr, rank_to_list, 1000, 2)
    lens = np.array([len(q) for q in q1])
    assert abs(lens.mean() - 2.5) < 0.01
    for q in q1:
        assert len(set(q)) == len(q)
        assert not {99, 98} & set(q)  # ranks 0 and 1 are stop words
    terms = np.concatenate(q1)
    hot, cold = (terms == 97).sum(), (terms == 0).sum()
    assert hot > 20 * max(cold, 1)  # Zipf over rank
