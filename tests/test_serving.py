"""Continuous-batching serving loop: batch former + async server (§13).

The :class:`BatchFormer` is pure and clock-free, so every wave-formation
edge case runs against a hand-rolled clock -- no sleeps, no flakes:
empty-queue drain, deadline expiry mid-wave, single-query waves, pow2
bucket reuse across waves, EDF ordering, linger/ready semantics, and the
backpressure/shedding boundary.  The :class:`AsyncTopKServer` integration
tests then check the one property the serving layer must never break:
results through the loop are bit-identical to a direct
``engine.topk_batch`` call.
"""

import asyncio
import math

import numpy as np
import pytest

from repro.core.index import build_partitioned_index
from repro.data.postings import make_ranked_corpus
from repro.ranked.topk_engine import TopKEngine
from repro.serving import AsyncTopKServer, BatchFormer, QueueFull
from repro.serving.batcher import pow2_wave


# ---------------------------------------------------------------------------
# batch former (pure, hand-rolled clock)
# ---------------------------------------------------------------------------

def test_pow2_wave_buckets():
    assert [pow2_wave(n, 64) for n in (0, 1, 2, 3, 4, 5, 63, 64, 65)] == [
        1, 1, 2, 4, 4, 8, 64, 64, 64,
    ]
    # cap need not be a power of two: over-cap waves bucket to exactly cap
    assert pow2_wave(7, 6) == 6


def test_empty_queue_drain_is_noop():
    f = BatchFormer()
    assert f.depth == 0 and not f.ready(0.0)
    assert f.take(0.0) == ([], [], 0)
    assert f.stats["waves"] == 0
    assert f.linger_remaining(0.0) == math.inf


def test_single_query_wave_fires_on_linger():
    f = BatchFormer(max_batch=8, max_delay_s=1.0)
    f.push([1], now=10.0)
    assert not f.ready(10.5)                # mid-linger: keep coalescing
    assert f.linger_remaining(10.5) == pytest.approx(0.5)
    assert f.ready(11.0)                    # linger elapsed
    batch, expired, bucket = f.take(11.0)
    assert [r.query for r in batch] == [[1]] and not expired
    assert bucket == 1                      # single-query wave: bucket 1
    assert f.depth == 0 and f.stats["waves"] == 1


def test_full_batch_fires_immediately():
    f = BatchFormer(max_batch=2, max_delay_s=1e9)
    f.push([1], now=0.0)
    assert not f.ready(0.0)
    f.push([2], now=0.0)
    assert f.ready(0.0) and f.linger_remaining(0.0) == 0.0
    batch, _, bucket = f.take(0.0)
    assert len(batch) == 2 and bucket == 2
    assert f.stats["full_waves"] == 1


def test_edf_pop_order_breaks_ties_fifo():
    f = BatchFormer(max_batch=4, max_delay_s=0.0)
    f.push(["lax"], now=0.0, deadline=100.0)
    f.push(["tight"], now=0.0, deadline=5.0)
    f.push(["tie-a"], now=0.0, deadline=7.0)
    f.push(["tie-b"], now=0.0, deadline=7.0)
    batch, _, _ = f.take(1.0)
    assert [r.query[0] for r in batch] == ["tight", "tie-a", "tie-b", "lax"]


def test_imminent_deadline_forces_wave():
    f = BatchFormer(max_batch=64, max_delay_s=1e9)
    f.push([1], now=0.0, deadline=2.0)
    assert not f.ready(1.0)
    # waiting past the earliest deadline could only expire it: fire now
    assert f.ready(2.0)
    assert f.linger_remaining(1.5) == pytest.approx(0.5)


def test_deadline_expiry_mid_wave_frees_slots():
    """Expired requests pop out of the wave WITHOUT consuming batch
    slots -- an overloaded queue drains more than max_batch per take."""
    f = BatchFormer(max_batch=2, max_delay_s=0.0)
    f.push(["dead-1"], now=0.0, deadline=1.0)
    f.push(["dead-2"], now=0.0, deadline=1.5)
    f.push(["live-1"], now=0.0, deadline=100.0)
    f.push(["live-2"], now=0.0, deadline=100.0)
    batch, expired, bucket = f.take(2.0)
    assert [r.query[0] for r in expired] == ["dead-1", "dead-2"]
    assert [r.query[0] for r in batch] == ["live-1", "live-2"]
    assert bucket == 2 and f.depth == 0
    assert f.stats["expired"] == 2 and f.stats["waves"] == 1


def test_all_expired_take_is_not_a_wave():
    f = BatchFormer(max_batch=4)
    f.push([1], now=0.0, deadline=1.0)
    batch, expired, bucket = f.take(5.0)
    assert batch == [] and len(expired) == 1 and bucket == 0
    assert f.stats["waves"] == 0
    # queue emptied: linger anchor resets
    assert f.linger_remaining(5.0) == math.inf


def test_bucket_reuse_across_waves():
    f = BatchFormer(max_batch=16, max_delay_s=0.0)
    for n in (3, 5, 4, 2, 6):               # occupancies 3,5,4,2,6
        for i in range(n):
            f.push([i], now=0.0)
        f.take(1.0)
    # buckets: 4, 8, 4(hit), 2, 8(hit) -> 2 hits over 5 waves
    assert f.stats["waves"] == 5
    assert f.stats["bucket_hits"] == 2


def test_push_refuses_beyond_max_queue():
    f = BatchFormer(max_queue=2)
    assert f.push([1], now=0.0) is not None
    assert f.push([2], now=0.0) is not None
    assert f.full and f.push([3], now=0.0) is None
    assert f.stats == {**f.stats, "admitted": 2, "refused": 1}


def test_linger_restarts_when_requests_remain():
    f = BatchFormer(max_batch=2, max_delay_s=1.0)
    for i in range(3):
        f.push([i], now=0.0)
    f.take(5.0)                             # pops 2, one remains
    assert f.depth == 1
    # the leftover's linger window restarts at the wave, not at admission
    assert not f.ready(5.5)
    assert f.linger_remaining(5.5) == pytest.approx(0.5)
    assert f.ready(6.0)


# ---------------------------------------------------------------------------
# async server over a real engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    rng = np.random.default_rng(31)
    lists, freqs = make_ranked_corpus(
        rng, n_lists=6, min_len=80, max_len=1_000,
        mean_dense_gap=2.13, frac_dense=0.8,
    )
    idx = build_partitioned_index(lists, "optimal", freqs=freqs)
    return TopKEngine(idx, backend="numpy", resident="kernel")


def _queries(engine, rng, n):
    nl = len(engine.index.list_sizes)
    return [rng.integers(0, nl, rng.integers(1, 4)).tolist()
            for _ in range(n)]


def test_server_results_identical_to_direct_batch(engine):
    queries = _queries(engine, np.random.default_rng(5), 23)
    want = engine.topk_batch(queries, 10)

    async def drive():
        async with AsyncTopKServer(
            engine, k=10, max_batch=8, max_delay_s=1e-3
        ) as server:
            return await asyncio.gather(
                *(server.submit(q) for q in queries)
            ), server

    results, server = asyncio.run(drive())
    for res, (wd, ws) in zip(results, want):
        assert not res.expired
        assert np.array_equal(res.docs, wd)
        assert np.array_equal(res.scores, ws)
        assert res.latency_s == res.wait_s + res.service_s >= 0.0
    assert server.stats["served"] == len(queries)
    assert server.former.depth == 0       # close() drained everything
    # waves were pow2-padded: occupancies 23 -> buckets sum >= served
    assert server.stats["padded_queries"] >= 0
    assert server.former.stats["waves"] >= 1


def test_server_expires_past_deadline_requests(engine):
    """A request admitted with an already-tiny deadline resolves as
    EXPIRED (empty arrays, engine never ran for it) once a wave forms."""
    queries = _queries(engine, np.random.default_rng(9), 4)

    async def drive():
        server = AsyncTopKServer(engine, k=10, max_batch=4,
                                 max_delay_s=0.0)
        async with server:
            dead = asyncio.ensure_future(
                server.submit(queries[0], deadline_s=-1.0)
            )
            live = await asyncio.gather(
                *(server.submit(q) for q in queries[1:])
            )
            return await dead, live, server

    dead, live, server = asyncio.run(drive())
    assert dead.expired and len(dead.docs) == 0 and dead.service_s == 0.0
    assert all(not r.expired for r in live)
    assert server.stats["expired"] == 1
    assert server.stats["served"] == len(queries) - 1


def test_try_submit_sheds_when_queue_full(engine):
    async def drive():
        server = AsyncTopKServer(engine, k=10, max_batch=2, max_queue=2,
                                 max_delay_s=1e9)
        # no serve_forever task: the queue cannot drain, so the third
        # admission must shed
        a = asyncio.ensure_future(server.try_submit([0]))
        b = asyncio.ensure_future(server.try_submit([1]))
        await asyncio.sleep(0)
        with pytest.raises(QueueFull):
            await server.try_submit([2])
        assert server.stats["shed"] == 1
        await server.drain()
        return await asyncio.gather(a, b), server

    (ra, rb), server = asyncio.run(drive())
    assert not ra.expired and not rb.expired
    assert server.former.stats["refused"] == 1


def test_submit_backpressure_waits_for_space(engine):
    """submit() on a full queue WAITS (closed-loop self-throttling) and
    completes once the serving loop frees space."""
    async def drive():
        async with AsyncTopKServer(
            engine, k=10, max_batch=2, max_queue=2, max_delay_s=0.0
        ) as server:
            out = await asyncio.gather(
                *(server.submit([i % 3]) for i in range(7))
            )
            return out, server

    out, server = asyncio.run(drive())
    assert len(out) == 7 and all(not r.expired for r in out)
    assert server.stats["served"] == 7
    assert server.stats["backpressure_waits"] >= 1
    assert server.former.stats["refused"] >= 1


def test_drain_ignores_linger(engine):
    """drain() fires waves immediately even though the linger window has
    not elapsed -- shutdown never waits out max_delay_s."""
    async def drive():
        server = AsyncTopKServer(engine, k=10, max_batch=64,
                                 max_delay_s=1e9)
        fut = asyncio.ensure_future(server.submit([0, 1]))
        await asyncio.sleep(0)
        assert server.former.depth == 1
        await server.drain()
        return await fut, server

    res, server = asyncio.run(drive())
    assert not res.expired and server.former.depth == 0


@pytest.mark.parametrize("replicas", [2, 1])
def test_server_serves_resilient_engine_through_a_kill(engine, replicas):
    """A ``ResilientEngine`` behind the server: shard 1 dies at the second
    wave.  With a replica every answer equals ``exhaustive_topk`` and the
    wave that lost the shard counts as a failover; with none, the waves
    after the kill that ask for a lost list are degraded, marked so, and
    answer the queries restricted to the live lists exactly."""
    from repro.distributed.resilient import ResilientEngine, ShardFaultInjector
    from repro.ranked.bm25 import exhaustive_topk

    sharded = TopKEngine(engine.index, backend="numpy", resident="kernel",
                         shards=2, replicas=replicas)
    res = ResilientEngine(
        sharded, injector=ShardFaultInjector(at_batches=(1,), shards=(1,)),
        backoff_s=1e-4,
    )
    queries = _queries(engine, np.random.default_rng(9), 23)

    async def drive():
        async with AsyncTopKServer(
            res, k=10, max_batch=4, max_delay_s=1e-3
        ) as server:
            out = []
            for i in range(0, len(queries), 4):  # one wave at a time
                out += await asyncio.gather(
                    *(server.submit(q) for q in queries[i:i + 4]))
            return out, server

    results, server = asyncio.run(drive())
    lost = set(res.sa.unserved_lists().tolist())
    live = [[t for t in q if t not in lost] for q in queries]
    for r, (wd, ws) in zip(results, exhaustive_topk(engine.index, live, 10)):
        assert not r.expired
        assert np.array_equal(r.docs, wd) and np.array_equal(r.scores, ws)
    assert server.stats["failover_waves"] == 1
    degraded = [r.degraded for r in results]
    if replicas == 2:
        assert not lost and not any(degraded)
        assert server.stats["degraded_waves"] == 0
    else:
        # a wave is degraded from the kill on where it asks for a lost list
        hit = [i >= 4 and any(lost.intersection(q) for q in
                              queries[i - i % 4: i - i % 4 + 4])
               for i in range(len(queries))]
        assert lost and any(hit) and degraded == hit
        assert server.stats["degraded_waves"] == sum(hit[::4])
