"""Fault-tolerant sharded serving (ISSUE-7 tentpole).

The recovery contract of DESIGN.md §11, end to end: the arena survives a
checkpoint round-trip bit-exactly (with the paper's own OptVB codec
packing its monotone sidecars), one shard's sub-arena restores from a
GLOBAL checkpoint onto a *different* shard count / replica factor, the
``replicas=R`` routing fails a dead primary over to a live replica, the
``ShardFaultInjector`` fires from the REAL dispatch boundaries (host
loops in-band; the shard_map boundary in the subprocess lane), and
``ResilientEngine`` keeps the answers bit-identical to the no-fault run
whenever any live copy of the data exists -- degrading to exactly the
no-fault answers of the live-restricted queries when none does.
"""

import json
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core.arena_ckpt import (
    arena_to_tree,
    restore_arena,
    restore_shard,
    save_arena,
    tree_to_arena,
)
from repro.core.index import build_partitioned_index
from repro.core.query_engine import QueryEngine
from repro.core.shard import (
    ShardedArena,
    ShardsUnavailable,
    replica_owners,
    shard_of_list,
)
from repro.data.postings import make_corpus, make_freqs, make_queries
from repro import obs
from repro.distributed.resilient import (
    DEAD,
    HEALTHY,
    RECOVERING,
    SUSPECT,
    ResilientEngine,
    ShardFailure,
    ShardFaultInjector,
)
from repro.ranked.topk_engine import TopKEngine

N_LISTS = 7


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(77)
    return make_corpus(rng, n_lists=N_LISTS, min_len=300, max_len=2_500,
                       mean_dense_gap=2.13, frac_dense=0.8)


@pytest.fixture(scope="module")
def index(corpus):
    return build_partitioned_index(corpus, "optimal")


@pytest.fixture(scope="module")
def ranked_index(corpus):
    rng = np.random.default_rng(78)
    return build_partitioned_index(
        corpus, "optimal", freqs=make_freqs(rng, corpus)
    )


@pytest.fixture(scope="module")
def queries():
    rng = np.random.default_rng(79)
    return [
        [int(t) for t in q]
        for q in make_queries(rng, N_LISTS, 24, 2)
    ]


def _arena_fields(a):
    out = {
        k: getattr(a, k)
        for k in ("lens", "data", "block_base", "block_keys", "lane_valid",
                  "part_of_block", "first_blk", "n_blk", "sizes", "bases",
                  "part_list", "list_blk_offsets")
    }
    out["stride"] = np.int64(a.stride)
    out["n_blocks"] = np.int64(a.n_blocks)
    if a.ranked is not None:
        r = a.ranked
        out.update(
            freq_lens=r.freq_lens, freq_data=r.freq_data, norm_q=r.norm_q,
            block_max_q=r.block_max_q, bound_scale=np.float32(r.bound_scale),
            idf=r.idf, list_ub=r.list_ub, kmin=np.float32(r.kmin),
            kstep=np.float32(r.kstep), norm_table=r.norm_table,
            bm25_k1=np.float64(r.params.k1), bm25_b=np.float64(r.params.b),
        )
    return out


def _assert_same_arena(a, b):
    fa, fb = _arena_fields(a), _arena_fields(b)
    assert fa.keys() == fb.keys()
    for k in fa:
        assert np.array_equal(np.asarray(fa[k]), np.asarray(fb[k])), k


def _serve_chunks(res, queries, batch=6):
    out, degraded_q = [], 0
    for i in range(0, len(queries), batch):
        chunk = queries[i : i + batch]
        got, info = res.intersect_batch(chunk)
        out.extend(got)
        if info.degraded:
            miss = set(info.missing_lists.tolist())
            degraded_q += sum(1 for q in chunk if any(t in miss for t in q))
    return out, degraded_q


# ----------------------------------------------------------------------
# arena checkpoint layout
# ----------------------------------------------------------------------
@pytest.mark.parametrize("ranked", [False, True])
def test_arena_tree_roundtrip(index, ranked_index, ranked):
    arena = (ranked_index if ranked else index).arena
    back = tree_to_arena(arena_to_tree(arena))
    assert (back.ranked is not None) == ranked
    _assert_same_arena(arena, back)


def test_arena_checkpoint_uses_optvb_codec(tmp_path, index):
    """The monotone sidecars must land OptVB-packed (the paper's codec
    compressing its own index metadata), not as raw int64 rows."""
    m = CheckpointManager(tmp_path, async_save=False)
    save_arena(m, index.arena, step=3)
    leaves = m.manifest(3)["leaves"]
    tree = arena_to_tree(index.arena)
    keys = sorted(tree.keys())  # dict treedef flattens by sorted keys
    codec_of = {keys[leaf["i"]]: leaf["codec"] for leaf in leaves}
    assert codec_of["block_keys"] == "optvb"
    assert codec_of["first_blk"] == "optvb"
    assert codec_of["list_blk_offsets"] == "optvb"
    assert codec_of["data"] == "raw"
    back, got = restore_arena(m)
    assert got == 3
    _assert_same_arena(index.arena, back)


def test_restore_arena_ranked_roundtrip(tmp_path, ranked_index):
    m = CheckpointManager(tmp_path, async_save=False)
    save_arena(m, ranked_index.arena)
    back, _ = restore_arena(m)
    assert back.ranked is not None
    _assert_same_arena(ranked_index.arena, back)


@pytest.mark.parametrize("n_shards,replicas", [(2, 1), (5, 2), (3, 3)])
def test_restore_shard_is_elastic(tmp_path, index, n_shards, replicas):
    """One shard restored from a GLOBAL checkpoint equals the same shard
    of a FRESH sharding at any (shard count, replica factor) -- the
    serving analog of restore-to-new-mesh."""
    m = CheckpointManager(tmp_path, async_save=False)
    save_arena(m, index.arena)
    sa = ShardedArena.build(index.arena, n_shards, mesh=None,
                            replicas=replicas)
    for s in range(n_shards):
        sub, _ = restore_shard(m, s, n_shards, replicas=replicas)
        _assert_same_arena(sa.shards[s], sub)


def test_restore_shard_skips_corrupt_step(tmp_path, index):
    m = CheckpointManager(tmp_path, async_save=False, keep=4)
    save_arena(m, index.arena, step=1)
    save_arena(m, index.arena, step=2)
    npz = tmp_path / "step_0000000002" / "arrays.npz"
    npz.write_bytes(npz.read_bytes()[: 40])  # truncate the newest step
    sub, got = restore_shard(m, 0, 2)
    assert got == 1
    sa = ShardedArena.build(index.arena, 2, mesh=None)
    _assert_same_arena(sa.shards[0], sub)
    with pytest.raises(Exception):
        restore_shard(m, 0, 2, step=2)  # explicit step: no fallback


def test_restore_decodes_keys_past_int32(tmp_path, corpus):
    """Packed leaves decode on the host in int64: a value past 2^31 round-
    trips, and so does a shard whose block keys (list x stride + docID)
    pass 2^31 -- the Gov2-shaped arena's case."""
    from repro.checkpoint.manager import (
        pack_sorted_int_array,
        unpack_sorted_int_array,
    )

    arr = np.array([1, 2**31 + 7, 2**40], np.int64)
    back = unpack_sorted_int_array(pack_sorted_int_array(arr))
    assert back.dtype == np.int64 and np.array_equal(back, arr)
    spread = [np.asarray(c, np.int64) * 40_000 for c in corpus]
    idx = build_partitioned_index(spread, "optimal")
    assert int(idx.arena.block_keys.max()) > 2**31
    m = CheckpointManager(tmp_path, async_save=False)
    save_arena(m, idx.arena)
    sa = ShardedArena.build(idx.arena, 2, mesh=None, replicas=2)
    for s in range(2):
        sub, _ = restore_shard(m, s, 2, replicas=2)
        assert int(sub.block_keys.max()) > 2**31
        _assert_same_arena(sa.shards[s], sub)



def test_async_restore_readmits_at_a_later_batch(tmp_path, index, queries,
                                                monkeypatch):
    """An asynchronous restore re-admits its shard at a later batch
    boundary however soon it ends: here it has ended before the batch that
    lost the shard retries, and that batch is still served without it."""
    import threading

    start = threading.Thread.start

    def start_and_finish(self):
        start(self)
        self.join()

    monkeypatch.setattr(threading.Thread, "start", start_and_finish)
    want = QueryEngine(index, backend="numpy").intersect_batch(queries[:12])
    res = ResilientEngine(
        QueryEngine(index, backend="numpy", shards=3, replicas=2,
                    shard_mesh=None),
        injector=ShardFaultInjector(at_batches=(1,), shards=(0,)),
        manager=CheckpointManager(tmp_path, async_save=False),
        backoff_s=1e-4, recover_async=True,
    )
    res.checkpoint()
    got = []
    for i, dead_after in enumerate((False, True, False)):
        out, info = res.intersect_batch(queries[4 * i: 4 * i + 4])
        got += out
        assert bool(res.sa.dead[0]) == dead_after
        assert bool(info.failed_shards) == (i == 1) and not info.degraded
    assert all(np.array_equal(g, w) for g, w in zip(got, want))
    assert res.stats["recoveries"] == 1 and res.health == [HEALTHY] * 3

# ----------------------------------------------------------------------
# replica routing
# ----------------------------------------------------------------------
def test_replica_owner_layout():
    n = 100
    owner_r = replica_owners(n, 4, 3)
    assert owner_r.shape == (3, n)
    assert np.array_equal(owner_r[0], shard_of_list(np.arange(n), 4))
    for r in range(3):
        assert np.array_equal(owner_r[r], (owner_r[0] + r) % 4)
    # replicas land on r distinct shards per list
    assert all(len(set(owner_r[:, t])) == 3 for t in range(n))


def test_route_failover_prefers_primary(index):
    sa = ShardedArena.build(index.arena, 3, mesh=None, replicas=2)
    terms = np.arange(N_LISTS, dtype=np.int64)
    owner0, local0, served0 = sa.route(terms)
    assert served0.all()
    assert np.array_equal(owner0, sa.owner[terms])  # no-fault: primary
    victim = int(sa.owner[0])
    sa.dead[victim] = True
    owner1, local1, served1 = sa.route(terms)
    assert served1.all()
    moved = sa.owner[terms] == victim
    assert moved.any()
    assert np.array_equal(owner1[moved], (sa.owner[terms][moved] + 1) % 3)
    assert np.array_equal(owner1[~moved], owner0[~moved])  # others unmoved
    # the replica's local slot indexes the same global list
    for t, s, lt in zip(terms, owner1, local1):
        rows = np.flatnonzero((sa.owner_r == s).any(axis=0))
        assert rows[lt] == t
    sa.dead[:] = True
    _, _, served2 = sa.route(terms)
    assert not served2.any()
    assert np.array_equal(sa.unserved_lists(), terms)
    with pytest.raises(ShardsUnavailable):
        sa.route_one(0)


@pytest.mark.parametrize("backend", ["numpy", "ref"])
def test_replicated_engine_identity_no_faults(index, backend, queries):
    plain = QueryEngine(index, backend="numpy")
    eng = QueryEngine(index, backend=backend, shards=3, replicas=2,
                      shard_mesh=None)
    rng = np.random.default_rng(5)
    terms = rng.integers(0, N_LISTS, 200)
    probes = rng.integers(0, 4_000_000, 200)
    bv, br = plain.search_batch(terms, probes)
    v, r = eng.search_batch(terms, probes)
    assert np.array_equal(v, bv) and np.array_equal(r, br)
    for g, w in zip(eng.intersect_batch(queries),
                    plain.intersect_batch(queries)):
        assert np.array_equal(g, w)


# ----------------------------------------------------------------------
# fault injector
# ----------------------------------------------------------------------
def test_injector_deterministic_schedule():
    inj = ShardFaultInjector(at_batches=(1, 3), shards=(2, 0))
    dead_per_batch = []
    for _ in range(5):
        inj.begin_batch()
        dead_per_batch.append(sorted(inj.dead))
    assert dead_per_batch == [[], [2], [2], [0, 2], [0, 2]]
    assert inj.fired == 2
    with pytest.raises(ShardFailure) as ei:
        inj.check(2)
    assert ei.value.shard == 2
    inj.check(1)  # live shard passes
    with pytest.raises(ShardFailure):
        inj.check_shards(np.array([[1, 0]]))
    inj.revive(0)
    inj.revive(2)
    inj.check_shards(np.array([0, 1, 2]))


def test_injector_probability_is_seeded():
    def schedule(seed):
        inj = ShardFaultInjector(probability=0.5, seed=seed,
                                 shards=(0, 1, 2), transient=True)
        fires = []
        for _ in range(64):
            inj.begin_batch()
            fires.append(sorted(inj.dead))
        return fires, inj.fired

    a, fired_a = schedule(11)
    b, fired_b = schedule(11)
    assert a == b and fired_a == fired_b  # same seed replays exactly
    assert 0 < fired_a < 64  # actually probabilistic
    c, _ = schedule(12)
    assert a != c
    # transient: each batch starts clean, so at most one dead at a time
    assert all(len(d) <= 1 for d in a)


def test_inband_raise_from_host_loop(index):
    """A dead shard raises ShardFailure from the engine's own per-shard
    dispatch (EngineCore.fused_search), not from a wrapper mock."""
    inj = ShardFaultInjector()
    eng = QueryEngine(index, backend="ref", shards=3, shard_mesh=None,
                      fault_injector=inj)
    rng = np.random.default_rng(6)
    terms = rng.integers(0, N_LISTS, 64)
    probes = rng.integers(0, 4_000_000, 64)
    eng.search_batch(terms, probes)  # warm: all shards serve
    victim = int(eng.sharded.owner[int(terms[0])])
    inj.dead.add(victim)
    with pytest.raises(ShardFailure) as ei:
        eng.search_batch(terms, probes)
    assert ei.value.shard == victim


def test_resilient_needs_sharded_engine(index):
    with pytest.raises(ValueError, match="shard"):
        ResilientEngine(QueryEngine(index, backend="numpy"))


# ----------------------------------------------------------------------
# ResilientEngine: failover / degradation / recovery
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["numpy", "ref"])
def test_replica_failover_bit_identical(index, backend, queries):
    plain = QueryEngine(index, backend="numpy")
    want = plain.intersect_batch(queries)
    res = ResilientEngine(
        QueryEngine(index, backend=backend, shards=3, replicas=2,
                    shard_mesh=None),
        injector=ShardFaultInjector(at_batches=(1,), shards=(0,)),
        backoff_s=1e-4,
    )
    got, degraded_q = _serve_chunks(res, queries)
    assert degraded_q == 0
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    assert DEAD in res.health
    assert res.stats["failovers"] >= 1
    assert res.stats["dead_events"] == 1
    assert not res.sa.unserved_lists().size  # replicas cover everything


@pytest.mark.parametrize("backend", ["numpy", "ref"])
def test_topk_replica_failover_bit_identical(ranked_index, backend, queries):
    plain = TopKEngine(ranked_index, backend="numpy", seed_blocks=2)
    want = plain.topk_batch(queries, 10)
    res = ResilientEngine(
        TopKEngine(ranked_index, backend=backend, seed_blocks=2, shards=3,
                   replicas=2, shard_mesh=None),
        injector=ShardFaultInjector(at_batches=(1,), shards=(1,)),
        backoff_s=1e-4,
    )
    got_all = []
    for i in range(0, len(queries), 6):
        got, info = res.topk_batch(queries[i : i + 6], 10)
        assert not info.degraded
        got_all.extend(got)
    for (gd, gs), (wd, ws) in zip(got_all, want):
        assert np.array_equal(gd, wd) and np.array_equal(gs, ws)
    assert res.stats["failovers"] >= 1


def test_transient_fault_retries_then_heals(index, queries):
    """A blip is absorbed by backoff-retry: the shard goes SUSPECT, the
    retry succeeds, and health returns to HEALTHY without a dead_event.
    (A one-shot blip clears on first contact -- ``transient=True`` alone
    clears at the next BATCH, which is slower than the in-batch retry.)"""

    class OneShotBlip(ShardFaultInjector):
        def check(self, shard):
            try:
                super().check(shard)
            except ShardFailure:
                self.dead.discard(int(shard))  # gone by the retry
                raise

    plain = QueryEngine(index, backend="numpy")
    want = plain.intersect_batch(queries)
    res = ResilientEngine(
        QueryEngine(index, backend="numpy", shards=3, shard_mesh=None),
        injector=OneShotBlip(at_batches=(1,), shards=(0,)),
        backoff_s=1e-4,
    )
    got, degraded_q = _serve_chunks(res, queries)
    assert degraded_q == 0
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    assert res.stats["retries"] >= 1
    assert res.stats["dead_events"] == 0
    assert res.health == [HEALTHY] * 3


def test_degraded_equals_restricted_no_fault_answers(index, queries):
    plain = QueryEngine(index, backend="numpy")
    want = plain.intersect_batch(queries)
    res = ResilientEngine(
        QueryEngine(index, backend="numpy", shards=3, shard_mesh=None),
        injector=ShardFaultInjector(at_batches=(1,), shards=(0,)),
        backoff_s=1e-4,
    )
    got, degraded_q = _serve_chunks(res, queries)
    missing = set(res.sa.unserved_lists().tolist())
    assert missing and degraded_q > 0
    restricted = plain.intersect_batch(
        [[t for t in q if t not in missing] for q in queries]
    )
    for i, (g, w, r) in enumerate(zip(got, want, restricted)):
        # pre-fault batches match the full answers; later ones the
        # live-restricted answers
        assert np.array_equal(g, w) or np.array_equal(g, r), i
    assert res.stats["degraded_batches"] >= 1
    # NextGEQ wrapper: unserved cursors pinned at -1, rest exact
    rng = np.random.default_rng(7)
    terms = rng.integers(0, N_LISTS, 80)
    probes = rng.integers(0, 4_000_000, 80)
    v, r, info = res.search_batch(terms, probes)
    hit = np.isin(terms, np.asarray(sorted(missing)))
    assert info.degraded
    assert set(info.missing_lists.tolist()) <= missing
    assert (v[hit] == -1).all() and (r[hit] == -1).all()
    bv, br = plain.search_batch(terms[~hit], probes[~hit])
    assert np.array_equal(v[~hit], bv) and np.array_equal(r[~hit], br)


@pytest.mark.parametrize("recover_async", [False, True])
def test_checkpoint_recovery_bit_identical(tmp_path, index, queries,
                                           recover_async):
    plain = QueryEngine(index, backend="numpy")
    want = plain.intersect_batch(queries)
    res = ResilientEngine(
        QueryEngine(index, backend="numpy", shards=3, shard_mesh=None),
        injector=ShardFaultInjector(at_batches=(1,), shards=(0,)),
        manager=CheckpointManager(tmp_path, async_save=False),
        backoff_s=1e-4,
        recover_async=recover_async,
    )
    res.checkpoint()
    got, degraded_q = _serve_chunks(res, queries)
    if recover_async:
        # drain the background restore, then one more served batch
        # re-admits the shard
        res.wait_recovered()
        extra, _ = _serve_chunks(res, queries[:6])
        for g, w in zip(extra, want[:6]):
            assert np.array_equal(g, w)
    else:
        assert degraded_q == 0  # sync restore re-admits within the batch
        for g, w in zip(got, want):
            assert np.array_equal(g, w)
    assert res.stats["recoveries"] == 1
    assert res.health == [HEALTHY] * 3
    assert not res.sa.dead.any()
    assert np.isfinite(res.recovery_p99_s())
    summary = res.health_summary()
    assert summary["health"] == [HEALTHY] * 3
    assert summary["recoveries"] == 1
    # recovered serving keeps working on fresh traffic
    rng = np.random.default_rng(8)
    terms = rng.integers(0, N_LISTS, 60)
    probes = rng.integers(0, 4_000_000, 60)
    v, r, info = res.search_batch(terms, probes)
    assert not info.degraded
    bv, br = plain.search_batch(terms, probes)
    assert np.array_equal(v, bv) and np.array_equal(r, br)


# ----------------------------------------------------------------------
# observability: the health lifecycle as emitted events (ISSUE-8)
# ----------------------------------------------------------------------
@pytest.fixture
def armed_obs():
    was = obs.enabled()
    obs.enable(True)
    obs.reset()
    yield
    obs.reset()
    obs.enable(was)


def _transitions(shard: int) -> list[tuple[str, str]]:
    return [
        (e["src"], e["dst"])
        for e in obs.events()
        if e["name"] == "health_transition" and e["shard"] == shard
    ]


def test_health_lifecycle_emitted_as_obs_events(tmp_path, index, queries,
                                                armed_obs):
    """The DESIGN §11 trajectory, reconstructed from the obs layer alone:
    the trace ring carries the ordered HEALTHY -> SUSPECT -> DEAD ->
    RECOVERING -> HEALTHY transitions and the registry snapshot carries
    the matching counters + recovery/failover latency histograms."""
    res = ResilientEngine(
        QueryEngine(index, backend="numpy", shards=3, shard_mesh=None),
        injector=ShardFaultInjector(at_batches=(1,), shards=(0,)),
        manager=CheckpointManager(tmp_path, async_save=False),
        backoff_s=1e-4,
    )
    res.checkpoint()
    _, degraded_q = _serve_chunks(res, queries)
    assert degraded_q == 0
    seq = _transitions(0)
    assert seq == [
        (HEALTHY, SUSPECT), (SUSPECT, DEAD),
        (DEAD, RECOVERING), (RECOVERING, HEALTHY),
    ]
    assert all(_transitions(s) == [] for s in (1, 2))  # bystanders quiet
    snap = obs.snapshot(events=False)
    c = snap["counters"]
    for src, dst in seq:
        key = (f'resilient_health_transitions'
               f'{{dst="{dst}",shard="0",src="{src}"}}')
        assert c[key] == 1, key
    # CounterDict keeps the dict API AND mirrors into the registry
    assert c["resilient_recoveries"] == res.stats["recoveries"] == 1
    assert c["resilient_dead_events"] == res.stats["dead_events"] == 1
    assert c["resilient_failovers"] == res.stats["failovers"] >= 1
    h = snap["histograms"]
    assert h['resilient_recovery_ms{shard="0"}']["count"] == 1
    assert h['resilient_recovery_ms{shard="0"}']["max"] < 30_000  # ms
    assert h["resilient_failover_ms"]["count"] >= 1


def test_degraded_serving_counted_lifecycle_stops_at_dead(index, queries,
                                                          armed_obs):
    """No replicas, no checkpoint: answers degrade (counted per missing
    list) and the victim's lifecycle ends at DEAD -- no recovery events
    may appear when there is nothing to recover from."""
    res = ResilientEngine(
        QueryEngine(index, backend="numpy", shards=3, shard_mesh=None),
        injector=ShardFaultInjector(at_batches=(1,), shards=(0,)),
        backoff_s=1e-4,
    )
    _, degraded_q = _serve_chunks(res, queries)
    assert degraded_q > 0
    assert _transitions(0) == [(HEALTHY, SUSPECT), (SUSPECT, DEAD)]
    snap = obs.snapshot(events=False)
    assert snap["counters"]["resilient_degraded_answers"] >= 1
    assert "resilient_recovery_ms{shard=\"0\"}" not in snap["histograms"]


def test_topk_mesh_failover_scores_on_shards_subprocess():
    """Kernel-resident top-10 on a 4-device mesh (4 shards x 2 replicas,
    svb): rows score on their shards' devices, and the answers equal
    ``exhaustive_topk`` exactly before a kill, while shard 1 is dead and
    evicted from its device (the dispatches run over the other three, on
    programs loaded at the eviction), and after its restored buffers are
    re-admitted on device 1."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys, json, tempfile
        sys.path.insert(0, "src")
        import numpy as np
        import jax
        from repro.checkpoint import CheckpointManager
        from repro.core.index import build_partitioned_index
        from repro.data.postings import make_corpus, make_freqs
        from repro.distributed.resilient import ResilientEngine, ShardFaultInjector
        from repro.ranked.bm25 import exhaustive_topk
        from repro.ranked.topk_engine import TopKEngine

        rng = np.random.default_rng(3)
        corpus = make_corpus(rng, n_lists=12, min_len=200, max_len=3000,
                             mean_dense_gap=2.13, frac_dense=0.8)
        idx = build_partitioned_index(corpus, "optimal", freqs=make_freqs(rng, corpus),
                                      codecs="svb")
        queries = [[int(t) for t in rng.choice(12, size=int(rng.integers(1, 4)),
                                               replace=False)] for _ in range(24)]
        want = exhaustive_topk(idx, queries, 10)

        def same(got, qs):
            w = [want[queries.index(q)] for q in qs]
            return all(np.array_equal(gd, wd) and np.array_equal(gs, ws)
                       for (gd, gs), (wd, ws) in zip(got, w))

        eng = TopKEngine(idx, backend="ref", shards=4, replicas=2, resident="kernel",
                         codec_policy="svb", seed_blocks=2)
        sa = eng.sharded
        dev1 = sa.mesh.devices.reshape(-1)[1]
        ok = {"mesh": sa.mesh is not None and sa.mesh.devices.size == 4}
        with tempfile.TemporaryDirectory() as d:
            res = ResilientEngine(eng,
                                  injector=ShardFaultInjector(at_batches=(1,), shards=(1,)),
                                  manager=CheckpointManager(d, async_save=False),
                                  recover_async=True, backoff_s=1e-4)
            res.checkpoint()
            got, info = res.topk_batch(queries[:8], 10)
            ok["before"] = same(got, queries[:8]) and not info.failed_shards
            old = [sh.data for a in sa._dev["arena"].values()
                   for sh in a.addressable_shards if sh.device == dev1]
            ok["old_on_dev1"] = len(old) > 0 and not any(b.is_deleted() for b in old)
            eng._scache_rows = np.zeros(0, np.int64)   # force re-scoring while dead
            eng._scache = np.zeros((0, 128), np.float32)
            got, info = res.topk_batch(queries[:8], 10)
            ok["during"] = (same(got, queries[:8]) and set(info.failed_shards) == {1}
                            and not info.degraded and sa.live_shards() == (0, 2, 3))
            # the three live shards' programs loaded at the eviction, for every
            # bucket the same batch can stage on them
            ok["warmed"] = all(fn._ran.get((0, 2, 3), set()) <= fn._warmed.get((0, 2, 3), set())
                               for fn in res._dispatchers())
            ok["deleted"] = all(b.is_deleted() for b in old)
            ok["no_dev1"] = not any(sh.device == dev1 for a in sa._dev["arena"].values()
                                    for sh in a.addressable_shards)
            res.wait_recovered(60)
            eng._scache_rows = np.zeros(0, np.int64)
            eng._scache = np.zeros((0, 128), np.float32)
            got, info = res.topk_batch(queries[16:], 10)
            ok["after"] = same(got, queries[16:]) and sa.live_shards() == (0, 1, 2, 3)
            new = [sh.data for a in sa._dev["arena"].values()
                   for sh in a.addressable_shards if sh.device == dev1]
            ok["new_on_dev1"] = len(new) == len(old) and not any(b.is_deleted() for b in new)
            ok["health"] = res.health == ["HEALTHY"] * 4
        per = [eng.stats[f"scored_rows_shard{s}"] for s in range(4)]
        ok["shards_scored"] = sum(n > 0 for n in per)
        ok["per"] = per
        print(json.dumps(ok))
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=pathlib.Path(__file__).parent.parent, timeout=90,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res.pop("shards_scored") > 1, res
    per = res.pop("per")
    assert all(res.values()), (res, per)


@pytest.mark.slow
def test_shard_map_faults_multidevice_subprocess():
    """The mesh path: 8 forced host devices, the injector firing from the
    shard_map dispatch boundary itself, replica failover + checkpoint
    recovery bit-identical under the real placement."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, json, tempfile
        sys.path.insert(0, "src")
        import numpy as np
        import jax
        from repro.checkpoint import CheckpointManager
        from repro.core.index import build_partitioned_index
        from repro.core.query_engine import QueryEngine
        from repro.data.postings import make_corpus, make_queries
        from repro.distributed.resilient import (
            ResilientEngine, ShardFailure, ShardFaultInjector,
        )

        rng = np.random.default_rng(2)
        corpus = make_corpus(rng, n_lists=9, min_len=200, max_len=2000,
                             mean_dense_gap=2.13, frac_dense=0.8)
        idx = build_partitioned_index(corpus, "optimal")
        queries = [[int(t) for t in q]
                   for q in make_queries(rng, 9, 18, 2)]
        plain = QueryEngine(idx, backend="numpy")
        want = plain.intersect_batch(queries)

        def serve(res, batch=6):
            out = []
            for i in range(0, len(queries), batch):
                got, info = res.intersect_batch(queries[i:i + batch])
                assert not info.degraded
                out.extend(got)
            return out

        ok = {"devices": len(jax.devices())}

        # in-band: the shard_map dispatch boundary itself raises
        inj = ShardFaultInjector()
        eng = QueryEngine(idx, backend="ref", shards=4, replicas=2,
                          fault_injector=inj)
        assert eng.sharded.mesh is not None
        terms = rng.integers(0, 9, 120)
        probes = rng.integers(0, 3_000_000, 120)
        eng.search_batch(terms, probes)
        assert eng._smap_fn is not None, "shard_map path not taken"
        inj.dead.add(0)
        try:
            eng.search_batch(terms, probes)
            ok["inband"] = False
        except ShardFailure as e:
            ok["inband"] = e.shard == 0
        inj.dead.clear()

        # replica failover under the mesh placement
        res = ResilientEngine(
            QueryEngine(idx, backend="ref", shards=4, replicas=2),
            injector=ShardFaultInjector(at_batches=(1,), shards=(0,)),
            backoff_s=1e-4,
        )
        got = serve(res)
        ok["failover"] = bool(
            res.stats["failovers"] >= 1
            and all(np.array_equal(g, w) for g, w in zip(got, want))
        )

        # checkpoint recovery under the mesh placement
        with tempfile.TemporaryDirectory() as d:
            res = ResilientEngine(
                QueryEngine(idx, backend="ref", shards=4),
                injector=ShardFaultInjector(at_batches=(1,), shards=(1,)),
                manager=CheckpointManager(d, async_save=False),
                backoff_s=1e-4,
            )
            res.checkpoint()
            got = serve(res)
            ok["recovery"] = bool(
                res.stats["recoveries"] == 1
                and all(np.array_equal(g, w) for g, w in zip(got, want))
            )
        print(json.dumps(ok))
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=pathlib.Path(__file__).parent.parent, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    ok = json.loads(out.stdout.strip().splitlines()[-1])
    assert ok["devices"] == 8
    assert ok["inband"] and ok["failover"] and ok["recovery"], ok
