"""Property tests for the ranked (Block-Max BM25 top-k) subsystem (ISSUE-3).

Covers the acceptance surface:

* the float32 BM25 scoring contract is bit-identical across the three
  kernel backends (numpy mirror / jnp ref / pallas) and matches the scalar
  formula;
* block-max admissibility: no block's true maximum contract score exceeds
  its quantized u8 upper bound, and list upper bounds dominate blocks;
* the Block-Max engine returns top-k IDENTICAL to the exhaustive-scoring
  oracle (docIDs AND scores, ties broken by ascending docID) on random
  clustered corpora, across backends, both residency modes, and edge-case
  queries (empty, single-term, duplicate-term, k > collection).

Runs under real hypothesis or the seeded shim in tests/_hypothesis_shim.py.
"""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.index import (
    TAG_BITVECTOR,
    TAG_EF,
    TAG_VBYTE,
    build_partitioned_index,
)
from repro.data.postings import make_freqs, make_queries, make_ranked_corpus
from repro.kernels.bm25_score.ops import bm25_score_probe, bm25_score_rows
from repro.kernels.vbyte_decode.kernel import BLOCK_VALS
from repro.ranked.bm25 import (
    DEFAULT_BM25,
    dequant_norm,
    exhaustive_topk,
    idf,
    quantize_norms,
    score_tf,
)
from repro.ranked.topk_engine import TopKEngine

K1P1 = np.float32(DEFAULT_BM25.k1 + 1.0)


def _mk_index(seed, n_lists=5, max_len=1_500, min_len=80):
    rng = np.random.default_rng(seed)
    lists, freqs = make_ranked_corpus(
        rng, n_lists=n_lists, min_len=min_len, max_len=max_len,
        mean_dense_gap=2.13, frac_dense=0.8,
    )
    return build_partitioned_index(lists, "optimal", freqs=freqs), lists, freqs


# ---------------------------------------------------------------------------
# scoring contract
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_score_backends_bit_identical(seed):
    """All three backends produce the same f32 bits, probe and all-lane."""
    idx, lists, freqs = _mk_index(seed)
    a, r = idx.arena, idx.arena.ranked
    rng = np.random.default_rng(seed + 1)
    lob = a.part_list[a.part_of_block]

    # probe op over located rows (exact members and misses mixed)
    C = 300
    t_sel = rng.integers(0, len(lists), C)
    probes = np.array([
        lists[int(t)][rng.integers(0, len(lists[int(t)]))]
        if i % 3 else rng.integers(0, int(lists[int(t)][-1]) + 1)
        for i, t in enumerate(t_sel)
    ])
    keys = np.clip(probes, 0, a.stride - 1) + t_sel * a.stride
    krow = np.searchsorted(a.block_keys, keys, side="left")
    past = krow >= a.list_blk_offsets[t_sel + 1]
    rows = np.minimum(krow, a.n_blocks - 1)
    pe = np.where(past, 0, probes)
    idf_rows = r.idf[lob[rows]]
    outs = {
        be: bm25_score_probe(
            a.lens, a.data, r.freq_lens, r.freq_data, r.norm_q,
            a.block_base, rows, pe, idf_rows, r.norm_table, K1P1, backend=be,
        )
        for be in ("numpy", "ref", "pallas")
    }
    assert np.array_equal(outs["numpy"], outs["ref"])
    assert np.array_equal(outs["numpy"], outs["pallas"])

    # all-lane op over random rows
    rows2 = rng.integers(0, a.n_blocks, 21)
    idf2 = r.idf[lob[rows2]]
    lanes = {
        be: bm25_score_rows(
            r.freq_lens, r.freq_data, r.norm_q, rows2, idf2, r.norm_table,
            K1P1, backend=be,
        )
        for be in ("numpy", "ref", "pallas")
    }
    lv = a.lane_valid[rows2]
    assert np.array_equal(lanes["numpy"][lv], lanes["ref"][lv])
    assert np.array_equal(lanes["numpy"][lv], lanes["pallas"][lv])


def test_probe_matches_scalar_contract():
    """The fused probe equals score_tf on members, 0.0 on non-members."""
    idx, lists, freqs = _mk_index(11)
    a, r = idx.arena, idx.arena.ranked
    qn, kmin, kstep = quantize_norms(idx.doc_lens, idx.avg_dl)
    lob = a.part_list[a.part_of_block]
    rng = np.random.default_rng(0)
    for t, seq in enumerate(lists):
        xs = np.unique(np.concatenate([
            seq[rng.integers(0, len(seq), 30)],
            rng.integers(0, int(seq[-1]) + 2, 30),
        ]))
        keys = np.clip(xs, 0, a.stride - 1) + t * a.stride
        krow = np.searchsorted(a.block_keys, keys, side="left")
        past = krow >= a.list_blk_offsets[t + 1]
        rows = np.minimum(krow, a.n_blocks - 1)
        got = bm25_score_probe(
            a.lens, a.data, r.freq_lens, r.freq_data, r.norm_q,
            a.block_base, rows, np.where(past, 0, xs), r.idf[lob[rows]],
            r.norm_table, K1P1, backend="numpy",
        )
        got = np.where(past, np.float32(0.0), got)
        ks = np.searchsorted(seq, xs)
        for i, x in enumerate(xs):
            if ks[i] < len(seq) and seq[ks[i]] == x:
                want = score_tf(
                    freqs[t][ks[i]],
                    dequant_norm(qn[x], kmin, kstep),
                    r.idf[t],
                )
                assert got[i] == np.float32(want), (t, x)
            else:
                assert got[i] == 0.0, (t, x)


def test_idf_positive_and_monotone():
    df = np.array([1, 10, 100, 1000])
    v = idf(1000, df)
    assert (v > 0).all()
    assert (np.diff(v) < 0).all()


# ---------------------------------------------------------------------------
# block-max admissibility
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_block_max_admissible(seed):
    """No block's true max contract score exceeds its quantized bound; list
    upper bounds dominate their blocks' bounds."""
    idx, lists, freqs = _mk_index(seed, n_lists=4, max_len=2_000)
    a, r = idx.arena, idx.arena.ranked
    bounds = r.block_bounds()
    lob = a.part_list[a.part_of_block]
    # true per-lane scores via the numpy mirror
    scores = bm25_score_rows(
        r.freq_lens, r.freq_data, r.norm_q,
        np.arange(a.n_blocks, dtype=np.int64), r.idf[lob], r.norm_table,
        K1P1, backend="numpy",
    )
    scores = np.where(a.lane_valid, scores, np.float32(0.0))
    true_max = scores.max(axis=1)
    assert (true_max <= bounds).all(), "quantized bound below true block max"
    # bounds are tight-ish: within one quantization step + eps
    step = float(r.bound_scale)
    assert (bounds - true_max <= step + 1e-6).all()
    # list upper bounds dominate
    for t in range(idx.n_lists):
        r0, r1 = int(a.list_blk_offsets[t]), int(a.list_blk_offsets[t + 1])
        if r1 > r0:
            assert r.list_ub[t] >= bounds[r0:r1].max() - 1e-7


def test_norm_quantization_roundtrip():
    rng = np.random.default_rng(5)
    dl = rng.integers(1, 5_000, 4_000)
    avg = float(dl.mean())
    q, kmin, kstep = quantize_norms(dl, avg)
    k_hat = dequant_norm(q, kmin, kstep)
    k_true = DEFAULT_BM25.k1 * (
        1 - DEFAULT_BM25.b + DEFAULT_BM25.b * dl / avg
    )
    # 256 linear levels: dequantized norm within half a step of the truth
    half_step = (k_true.max() - k_true.min()) / 255 / 2
    assert np.abs(k_hat - k_true).max() <= half_step * 1.01 + 1e-7


# ---------------------------------------------------------------------------
# top-k identity vs the exhaustive oracle
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    k=st.sampled_from([1, 3, 10]),
)
def test_topk_identical_to_exhaustive_all_backends(seed, k):
    idx, lists, freqs = _mk_index(seed)
    rng = np.random.default_rng(seed + 2)
    queries = [
        [int(t) for t in q]
        for ar in (1, 2, 3)
        for q in make_queries(rng, len(lists), 4, ar)
    ]
    queries += [[], [0, 0], [1, 1, 1, 2]]
    want = exhaustive_topk(idx, queries, k)
    for be in ("numpy", "ref", "pallas"):
        got = TopKEngine(idx, backend=be).topk_batch(queries, k)
        for qi, ((gd, gs), (wd, ws)) in enumerate(zip(got, want)):
            assert np.array_equal(gd, wd), (be, k, queries[qi])
            assert np.array_equal(gs, ws), (be, k, queries[qi])


def test_topk_kernel_residency_matches_mirror():
    """resident="kernel" (HBM-style: no impact mirror; pruning through the
    blockmax_pivot kernel, rescoring through the fused bm25 kernel)
    returns the same results as the mirror path -- on every backend,
    sharded and unsharded."""
    idx, lists, _ = _mk_index(21, n_lists=4, max_len=900)
    rng = np.random.default_rng(3)
    queries = [[int(t) for t in q] for q in make_queries(rng, 4, 6, 2)]
    want = exhaustive_topk(idx, queries, 5)
    engines = [
        TopKEngine(idx, backend=be, resident="kernel")
        for be in ("numpy", "ref", "pallas")
    ] + [TopKEngine(idx, backend="ref", resident="kernel", shards=2)]
    for eng in engines:
        got = eng.topk_batch(queries, 5)
        for (gd, gs), (wd, ws) in zip(got, want):
            assert np.array_equal(gd, wd), (eng.backend, eng.sharded)
            assert np.array_equal(gs, ws), (eng.backend, eng.sharded)
        assert eng.stats["pivot_chunks"] > 0  # the pivot kernel really ran


def test_topk_edge_cases():
    idx, lists, _ = _mk_index(31, n_lists=4, max_len=600)
    eng = TopKEngine(idx)
    n_total = len(np.unique(np.concatenate(lists)))
    # k exceeding every candidate set: full ranking, still identical
    want = exhaustive_topk(idx, [[0, 1, 2, 3]], n_total + 50)[0]
    got = eng.topk_batch([[0, 1, 2, 3]], n_total + 50)[0]
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])
    assert len(got[0]) == n_total  # every doc of the union, exactly once
    # empty query
    gd, gs = eng.topk_batch([[]], 10)[0]
    assert gd.size == 0 and gs.size == 0
    # single-term: ranking of the list itself
    gd, gs = eng.topk_batch([[2]], 7)[0]
    wd, ws = exhaustive_topk(idx, [[2]], 7)[0]
    assert np.array_equal(gd, wd) and np.array_equal(gs, ws)
    # duplicate terms score double and stay identical to the oracle
    gd2, gs2 = eng.topk_batch([[2, 2]], 7)[0]
    assert np.array_equal(gd2, gd)
    assert np.allclose(gs2, 2 * gs)


def test_scores_sorted_and_tie_broken_by_docid():
    idx, lists, _ = _mk_index(41)
    rng = np.random.default_rng(0)
    queries = [[int(t) for t in q] for q in make_queries(rng, len(lists), 8, 2)]
    for gd, gs in TopKEngine(idx).topk_batch(queries, 20):
        assert (np.diff(gs) <= 0).all()
        ties = np.flatnonzero(np.diff(gs) == 0)
        assert (gd[ties + 1] > gd[ties]).all()


def test_index_freq_stream_roundtrip():
    idx, lists, freqs = _mk_index(51)
    for t in range(len(lists)):
        assert np.array_equal(idx.decode_list_freqs(t), freqs[t])
    assert idx.has_freqs
    assert idx.n_docs_real == int(np.count_nonzero(idx.doc_lens))
    dl = np.zeros(len(idx.doc_lens), np.int64)
    for seq, tf in zip(lists, freqs):
        np.add.at(dl, seq, tf)
    assert np.array_equal(idx.doc_lens, dl)


def test_engine_requires_freq_stream():
    rng = np.random.default_rng(0)
    lists, _ = make_ranked_corpus(rng, n_lists=3, min_len=60, max_len=300)
    idx = build_partitioned_index(lists, "optimal")  # no freqs
    with pytest.raises(ValueError, match="ranked sidecar"):
        TopKEngine(idx)


def test_uniform_strategy_also_ranked():
    """The ranked sidecar rides any partitioning strategy."""
    rng = np.random.default_rng(9)
    lists, freqs = make_ranked_corpus(rng, n_lists=4, min_len=80, max_len=700)
    for strategy in ("uniform", "single"):
        idx = build_partitioned_index(lists, strategy, freqs=freqs)
        queries = [[0, 1], [2, 3], [0, 3]]
        want = exhaustive_topk(idx, queries, 5)
        got = TopKEngine(idx).topk_batch(queries, 5)
        for (gd, gs), (wd, ws) in zip(got, want):
            assert np.array_equal(gd, wd), strategy
            assert np.array_equal(gs, ws), strategy


# ---------------------------------------------------------------------------
# membership pass: per-lane search == one global search of the flat mirror
# ---------------------------------------------------------------------------

def _global_membership(eng, specs):
    """Reference: every (term, doc) pair of the batch materialised, ONE
    searchsorted over the whole flat mirror, masked by the next lane's
    start.  Per query: (member [T, D], pos [T, D], UB [D])."""
    a, core = eng.arena, eng.core
    out = []
    for terms, mult, docs in specs:
        T, D = len(terms), len(docs)
        t_rep, d_til = np.repeat(terms, D), np.tile(docs, T)
        pos = np.searchsorted(core.flat_keys, d_til + t_rep * a.stride, "left")
        member = (core.flat_vals[pos] == d_til) & (
            pos < core.lane_end[t_rep + 1]
        )
        row = np.minimum(pos, a.n_blocks * BLOCK_VALS - 1) >> 7
        mem, pos = member.reshape(T, D), pos.reshape(T, D)
        ub = (
            mult[:, None]
            * np.where(mem, eng.bounds[row.reshape(T, D)], 0.0)
        ).sum(axis=0)
        out.append((mem, pos, ub))
    return out


@pytest.fixture(scope="module")
def multicodec_ranked():
    """Bitvector (gap-1 run), EF (clustered), VByte (sparse) partitions, a
    list mixing all three, and a one-posting list; lengths that are not
    multiples of 128 leave padding lanes at every list's end."""
    rng = np.random.default_rng(7)
    dense = np.arange(5_000, 9_000, dtype=np.int64)
    clustered = 20_000 + np.cumsum(
        rng.choice([1, 2, 6, 10, 20, 30], size=1_500)
    ).astype(np.int64)
    sparse = 100 + np.cumsum(rng.integers(65, 128, size=700)).astype(np.int64)
    mixed = np.unique(np.concatenate([dense[::3], clustered[::2], sparse[::4]]))
    lists = [dense, clustered, sparse, mixed, np.array([7_777], np.int64)]
    idx = build_partitioned_index(
        lists, "optimal", freqs=make_freqs(rng, lists), codecs="auto"
    )
    tags = set(np.asarray(idx.tags).tolist())
    assert {TAG_BITVECTOR, TAG_EF, TAG_VBYTE} <= tags
    return idx, lists


def _spec(terms, docs, mult=None):
    terms = np.asarray(terms, np.int64)
    mult = np.ones(len(terms)) if mult is None else np.asarray(mult, np.float64)
    return terms, mult, np.unique(np.asarray(docs, np.int64))


def _membership_cases(lists):
    dense, clustered, sparse, mixed, one = lists
    rng = np.random.default_rng(11)
    edges = [
        0, dense[0] - 1, dense[-1] + 1, clustered[0] - 1, clustered[-1] + 1,
        sparse[0] - 1, sparse[-1] + 1, mixed[0] - 1, mixed[-1] + 1,
    ]
    between = [sparse[10] + 1, sparse[300] - 1, clustered[40] + 1, mixed[7] + 1]
    hits = [dense[0], dense[-1], clustered[0], clustered[-1], sparse[0],
            sparse[-1], mixed[0], mixed[-1]]
    union = np.concatenate(lists)
    return {
        "one_posting": [
            _spec([4], [one[0] - 1, one[0], one[0] + 1]),
            _spec([3, 4], [one[0], mixed[0], mixed[100], 12_345], [2, 1]),
        ],
        "outside_and_between": [
            _spec([0, 1, 2, 3], edges + between + hits, [1, 2, 1, 3]),
            _spec([2], between + [sparse[-1] + 10_000]),
        ],
        "term_without_candidates": [
            _spec([0, 2, 4], clustered[::50]),  # no candidate in 0, 2, 4
            _spec([1, 3], []),  # no candidates at all
            _spec([1], clustered[:5]),
        ],
        "mixed_batch": [
            _spec(
                np.unique(rng.choice(5, size=int(n), replace=False)),
                np.concatenate([
                    rng.choice(union, size=200),
                    rng.integers(0, int(union.max()) + 500, size=200),
                ]),
            )
            for n in rng.integers(1, 5, size=6)
        ],
    }


@pytest.mark.parametrize("resident", ["mirror", "kernel"])
@pytest.mark.parametrize(
    "case",
    ["one_posting", "outside_and_between", "term_without_candidates",
     "mixed_batch"],
)
def test_membership_matches_global_search(multicodec_ranked, case, resident):
    """Searching each term's own lane gives the global search's member
    mask, member positions and block-max UBs, bit for bit."""
    idx, lists = multicodec_ranked
    eng = TopKEngine(idx, backend="numpy", resident=resident,
                     codec_policy="auto")
    eng._flat_init()
    specs = _membership_cases(lists)[case]
    pos, cuts, mems, ubs = eng._membership(specs, need_ub=True)
    _, _, mems_n, ubs_n = eng._membership(specs, need_ub=False)
    want = _global_membership(eng, specs)
    assert cuts[-1] == len(pos) == sum(len(t) * len(d) for t, _, d in specs)
    assert eng.stats["membership_pairs"] == 2 * cuts[-1]
    n_members = 0
    for i, (w_mem, w_pos, w_ub) in enumerate(want):
        T, D = w_mem.shape
        got_pos = pos[cuts[i] : cuts[i + 1]].reshape(T, D)
        assert np.array_equal(mems[i], w_mem), (case, i)
        assert np.array_equal(mems_n[i], w_mem), (case, i)
        assert np.array_equal(got_pos[w_mem], w_pos[w_mem]), (case, i)
        assert ubs[i].dtype == np.float64 and ubs_n[i] is None
        assert np.array_equal(ubs[i].view(np.int64), w_ub.view(np.int64))
        n_members += int(w_mem.sum())
    assert n_members > 0
