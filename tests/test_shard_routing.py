"""Shard routing edge cases for the sharded arena (ISSUE-4 tentpole).

The contract under test: a ``ShardedArena`` serves EXACTLY what the
unsharded arena serves -- 1-shard sharding is bit-identical on every
backend, cursors route to the right shard whatever the list-hash layout
(including shards no list hashes to), duplicate (term, probe) grouping
composes with routing, and the int32 probe clip at 2^31 survives the
host-side shard merge.  The multi-device ``shard_map`` placement runs in a
subprocess (device count is process-global).
"""

import json
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.index import build_partitioned_index
from repro.core.query_engine import QueryEngine
from repro.core.shard import ShardedArena, shard_of_list
from repro.data.postings import make_corpus, make_freqs, make_queries


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(23)
    return make_corpus(rng, n_lists=7, min_len=300, max_len=2_500,
                       mean_dense_gap=2.13, frac_dense=0.8)


@pytest.fixture(scope="module")
def index(corpus):
    return build_partitioned_index(corpus, "optimal")


@pytest.fixture(scope="module")
def ranked_index(corpus):
    rng = np.random.default_rng(24)
    return build_partitioned_index(
        corpus, "optimal", freqs=make_freqs(rng, corpus)
    )


def _cursors(rng, corpus, n=400):
    """Cursor batch hammering boundaries: members, gaps, far out of range."""
    terms = rng.integers(0, len(corpus), n)
    probes = rng.integers(0, 4_000_000, n)
    for i in range(0, n, 7):  # exact members sprinkled in
        seq = corpus[int(terms[i])]
        probes[i] = seq[rng.integers(0, len(seq))]
    return terms, probes


def test_hash_routing_is_stable_and_total():
    lists = np.arange(1000, dtype=np.int64)
    assert np.array_equal(shard_of_list(lists, 1), np.zeros(1000, np.int64))
    for n_shards in (2, 3, 8):
        owner = shard_of_list(lists, n_shards)
        assert owner.min() >= 0 and owner.max() < n_shards
        # deterministic (pure function of the id -- no routing table)
        assert np.array_equal(owner, shard_of_list(lists, n_shards))
        # splitmix spreads consecutive ids instead of striping them
        assert len(np.unique(owner[:16])) > 1


def test_explicit_mesh_shard_axis_must_match(index):
    """A user-supplied mesh must have a 'shard' AXIS of exactly n_shards
    (total device count multiplying out to n_shards is not enough -- the
    [S, ...] stacking splits dim 0 over that axis specifically)."""
    import jax

    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("shard",))
    with pytest.raises(ValueError, match="shard"):
        ShardedArena.build(index.arena, 2, mesh=mesh)
    with pytest.raises(ValueError, match="shard"):
        mesh2 = jax.sharding.Mesh(
            np.asarray(jax.devices()[:1]).reshape(1, 1), ("a", "b")
        )
        ShardedArena.build(index.arena, 1, mesh=mesh2)
    # exact 1:1 mesh is accepted
    assert ShardedArena.build(index.arena, 1, mesh=mesh).mesh is mesh


def test_mesh_path_releases_host_slices(index, corpus):
    """After the stacked device placement, the per-shard host slices are
    released (they fed the stacking and nothing else on the mesh path)."""
    rng = np.random.default_rng(4)
    terms, probes = _cursors(rng, corpus, 100)
    eng = QueryEngine(index, backend="ref", shards=1)
    want = QueryEngine(index, backend="numpy").search_batch(terms, probes)
    got = eng.search_batch(terms, probes)
    assert np.array_equal(got[0], want[0])
    assert eng._smap_fn is not None
    assert eng.sharded._shards is None  # host slices freed post-placement
    # ...and a later explicit access rebuilds them on demand
    assert eng.sharded.shards[0].n_blocks == index.arena.n_blocks


def test_one_shard_slice_reproduces_global_arena(index):
    a = index.arena
    sa = ShardedArena.build(a, 1, mesh=None)
    sub = sa.shards[0]
    assert np.array_equal(sub.block_keys, a.block_keys)
    assert np.array_equal(sub.block_base, a.block_base)
    assert np.array_equal(sub.lens, a.lens[: a.n_blocks])
    assert np.array_equal(sub.data, a.data[: a.n_blocks])
    assert np.array_equal(sub.lane_valid, a.lane_valid)
    assert np.array_equal(sub.list_blk_offsets, a.list_blk_offsets)
    assert np.array_equal(sub.first_blk, a.first_blk)
    assert np.array_equal(sub.part_list, a.part_list)
    assert sub.stride == a.stride and sub.n_blocks == a.n_blocks


@pytest.mark.parametrize("backend", ["numpy", "ref", "pallas"])
def test_one_shard_bit_identical_query(index, corpus, backend):
    """ISSUE-4 acceptance: 1-shard == unsharded, bit for bit, all backends
    (on the single CPU device this exercises the real shard_map dispatch
    for the device backends -- the mesh has one device, one shard)."""
    rng = np.random.default_rng(5)
    terms, probes = _cursors(rng, corpus)
    base = QueryEngine(index, backend=backend)
    eng = QueryEngine(index, backend=backend, shards=1)
    bv, br = base.search_batch(terms, probes)
    v, r = eng.search_batch(terms, probes)
    assert np.array_equal(v, bv)
    assert np.array_equal(r, br)
    assert np.array_equal(
        eng.member_batch(terms, probes), base.member_batch(terms, probes)
    )
    queries = [[0, 1], [2, 3, 4], [5], [6, 0], []]
    for q, g in zip(queries, eng.intersect_batch(queries)):
        assert np.array_equal(g, index.intersect_scalar(q)), q
    if backend in ("ref", "pallas"):
        assert eng._smap_fn is not None  # the shard_map path actually ran


@pytest.mark.parametrize("backend", ["numpy", "ref"])
@pytest.mark.parametrize("n_shards", [2, 3, 5])
def test_multi_shard_matches_unsharded(index, corpus, backend, n_shards):
    rng = np.random.default_rng(6)
    terms, probes = _cursors(rng, corpus)
    base = QueryEngine(index, backend="numpy")
    eng = QueryEngine(index, backend=backend, shards=n_shards)
    bv, br = base.search_batch(terms, probes)
    v, r = eng.search_batch(terms, probes)
    assert np.array_equal(v, bv)
    assert np.array_equal(r, br)
    queries = [[int(t) for t in q]
               for q in make_queries(rng, len(corpus), 8, 2)]
    for q, g in zip(queries, eng.intersect_batch(queries)):
        assert np.array_equal(g, index.intersect_scalar(q)), (n_shards, q)
    # the routed host path (per-shard EngineCores + scatter merge) is the
    # reference the device routing is tested against -- exact as well
    v2, r2, p2 = eng._fused_sharded(terms, probes)
    assert np.array_equal(np.where(p2, -1, v2), bv)
    assert np.array_equal(np.where(p2, -1, r2), br)


@pytest.mark.parametrize("backend", ["numpy", "ref"])
def test_empty_shard_is_served_around(index, corpus, backend):
    """More shards than lists: some shards own nothing.  They must be valid
    degenerate sub-arenas and never perturb routing or results."""
    n_shards = 16  # 7 lists -> pigeonhole guarantees empty shards
    eng = QueryEngine(index, backend=backend, shards=n_shards)
    sa = eng.sharded
    empty = [s for s in range(n_shards) if len(sa.lists_of[s]) == 0]
    assert empty, "expected at least one empty shard"
    for s in empty:
        assert sa.shards[s].n_blocks == 0
        assert np.array_equal(sa.shards[s].list_blk_offsets, [0])
    # every list is owned exactly once
    assert sorted(int(t) for f in sa.lists_of for t in f) == list(
        range(len(corpus))
    )
    rng = np.random.default_rng(7)
    terms, probes = _cursors(rng, corpus, 200)
    base = QueryEngine(index, backend="numpy")
    v, r = eng.search_batch(terms, probes)
    bv, br = base.search_batch(terms, probes)
    assert np.array_equal(v, bv)
    assert np.array_equal(r, br)
    # force the routed path as well: cursors only ever land on non-empty
    # shards, and the scatter merge fills every slot
    v2, r2, p2 = eng._fused_sharded(terms, probes)
    assert np.array_equal(np.where(p2, -1, v2), bv)
    assert np.array_equal(np.where(p2, -1, r2), br)


@pytest.mark.parametrize("n_shards", [1, 3])
def test_duplicate_grouping_across_shard_boundaries(index, corpus, n_shards):
    """Grouping runs BEFORE routing, so duplicate (term, probe) cursors
    collapse across the whole batch even when the duplicates' terms hash to
    different shards; grouped and ungrouped dispatches stay bit-identical."""
    rng = np.random.default_rng(8)
    base_t = rng.integers(0, len(corpus), 40)
    base_p = rng.integers(0, 3_000, 40)
    terms = np.tile(base_t, 8)
    probes = np.tile(base_p, 8)
    # duplicates span >1 shard (trivially true for n_shards=1)
    owners = np.unique(shard_of_list(np.unique(base_t), n_shards))
    assert n_shards == 1 or len(owners) > 1
    grouped = QueryEngine(index, backend="ref", shards=n_shards)
    plain = QueryEngine(index, backend="ref", shards=n_shards, group=False)
    want = QueryEngine(index, backend="numpy").search_batch(terms, probes)
    for eng, expect_grouped in ((grouped, True), (plain, False)):
        v, r = eng.search_batch(terms, probes)
        assert np.array_equal(v, want[0])
        assert np.array_equal(r, want[1])
        assert (eng.stats["grouped_cursors"] > 0) == expect_grouped


@pytest.mark.parametrize("backend", ["numpy", "ref"])
def test_probe_clip_2_31_survives_shard_merge(backend):
    """The int32 staging clip (probes >= 2^31 resolve past-the-end, huge
    negatives clip to probe 0) must hold through routing AND the host-side
    scatter merge -- per shard the clip uses the same global stride."""
    lists = [np.arange(0, 4_000, 3, dtype=np.int64),
             np.arange(1, 5_000, 2, dtype=np.int64),
             np.arange(2, 6_000, 5, dtype=np.int64)]
    idx = build_partitioned_index(lists, "optimal")
    probes = np.array([
        2**31 - 1, 2**31, 2**31 + 1, 2**40, -2**33,
        0, int(lists[0][-1]),
    ])
    terms = np.zeros(len(probes), np.int64)
    for n_shards in (1, 2, 3):
        engine = QueryEngine(idx, backend=backend, shards=n_shards)
        got = engine.next_geq_batch(terms, probes)
        assert (got[:4] == -1).all(), n_shards   # >= 2^31: past the end
        assert got[4] == 0                       # negative clips to probe 0
        assert got[5] == 0 and got[6] == lists[0][-1]
        member = engine.member_batch(terms, probes)
        assert not member[:4].any()
        assert member[5] and member[6]
        # the clip must hold on the ROUTED path too (per-shard staging)
        v, _, p = engine._fused_sharded(terms, probes)
        assert np.array_equal(np.where(p, -1, v), got), n_shards


@pytest.mark.parametrize("backend", ["numpy", "ref"])
@pytest.mark.parametrize("n_shards", [1, 4])
def test_ranked_sharded_identity(ranked_index, corpus, backend, n_shards):
    """TopKEngine over a sharded arena: identical top-k (docIDs AND scores)
    and identical point-lookup contributions, 1-shard and multi-shard."""
    from repro.ranked.topk_engine import TopKEngine

    rng = np.random.default_rng(9)
    queries = [[int(t) for t in q]
               for ar in (2, 3)
               for q in make_queries(rng, len(corpus), 4, ar)]
    base = TopKEngine(ranked_index, backend="numpy", seed_blocks=2)
    want = base.topk_batch(queries, 10)
    eng = TopKEngine(ranked_index, backend=backend, seed_blocks=2,
                     shards=n_shards)
    got = eng.topk_batch(queries, 10)
    for q, (gd, gs), (wd, ws) in zip(queries, got, want):
        assert np.array_equal(gd, wd), (backend, n_shards, q)
        assert np.array_equal(gs, ws), (backend, n_shards, q)
    terms = rng.integers(0, len(corpus), 300)
    docs = rng.integers(-5, 4_000_000, 300)
    assert np.array_equal(
        eng.contributions(terms, docs), base.contributions(terms, docs)
    )
    if backend == "ref" and n_shards == 1:
        assert eng._smap_fn is not None  # shard_map bm25 dispatch ran


@pytest.mark.slow
def test_shard_map_multidevice_subprocess():
    """The real multi-device placement: 8 forced host devices, shards
    served one-per-device under shard_map, results identical to the
    unsharded engine (device count is process-global, hence subprocess)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, json
        sys.path.insert(0, "src")
        import numpy as np
        import jax
        from repro.core.index import build_partitioned_index
        from repro.core.query_engine import QueryEngine
        from repro.ranked.topk_engine import TopKEngine
        from repro.data.postings import make_corpus, make_freqs, make_queries

        rng = np.random.default_rng(1)
        corpus = make_corpus(rng, n_lists=9, min_len=200, max_len=2000,
                             mean_dense_gap=2.13, frac_dense=0.8)
        freqs = make_freqs(rng, corpus)
        idx = build_partitioned_index(corpus, "optimal", freqs=freqs)
        terms = rng.integers(0, 9, 400)
        probes = rng.integers(0, 3_000_000, 400)
        base = QueryEngine(idx, backend="numpy")
        bv, br = base.search_batch(terms, probes)
        ok = {"devices": len(jax.devices())}
        for S in (2, 4, 8):
            e = QueryEngine(idx, backend="ref", shards=S)
            assert e.sharded.mesh is not None
            assert e.sharded.mesh.devices.size == S
            v, r = e.search_batch(terms, probes)
            assert e._smap_fn is not None, "shard_map path not taken"
            ok[f"q{S}"] = bool(
                np.array_equal(v, bv) and np.array_equal(r, br)
            )
        queries = [[int(t) for t in q] for q in make_queries(rng, 9, 6, 2)]
        bt = TopKEngine(idx, backend="numpy", seed_blocks=2)
        want = bt.topk_batch(queries, 10)
        ct = rng.integers(0, 9, 300)
        cd = rng.integers(-5, 3_000_000, 300)
        cw = bt.contributions(ct, cd)
        for S in (2, 4):
            e = TopKEngine(idx, backend="ref", seed_blocks=2, shards=S)
            got = e.topk_batch(queries, 10)
            same = all(
                np.array_equal(gd, wd) and np.array_equal(gs, ws)
                for (gd, gs), (wd, ws) in zip(got, want)
            )
            c = e.contributions(ct, cd)
            assert e._smap_fn is not None, "bm25 shard_map path not taken"
            ok[f"r{S}"] = bool(same and np.array_equal(c, cw))
        for S in (2, 4):
            # kernel residency: the Block-Max pruning itself runs as a
            # shard_map dispatch (ShardMapPivot) over the device mesh
            e = TopKEngine(idx, backend="ref", seed_blocks=2, shards=S,
                           resident="kernel")
            got = e.topk_batch(queries, 10)
            same = all(
                np.array_equal(gd, wd) and np.array_equal(gs, ws)
                for (gd, gs), (wd, ws) in zip(got, want)
            )
            assert e._smap_pivot is not None, "pivot shard_map not taken"
            ok[f"rk{S}"] = bool(same)
        print(json.dumps(ok))
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=pathlib.Path(__file__).parent.parent, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 8
    assert all(
        res[k] for k in ("q2", "q4", "q8", "r2", "r4", "rk2", "rk4")
    ), res
