"""Index serving: the paper's own application as a batched query service.

  PYTHONPATH=src python -m repro.launch.serve --n-lists 64 --queries 512
  PYTHONPATH=src python -m repro.launch.serve --ranked --topk 10

Builds an optimally-partitioned VByte index over a synthetic clustered
corpus, then serves boolean-AND queries through the batched
``repro.core.query_engine.QueryEngine``.  The default path is the FUSED
device-resident pipeline (one locate searchsorted + the decode_search
kernel over the block arena, jitted end-to-end on ``ref``/``pallas``
backends); ``--no-fused`` selects the PR-1 partition-LRU engine instead.
Reports space vs. the un-partitioned baseline, throughput, and per-batch
latency percentiles.  ``--compare-scalar`` also times the per-query NextGEQ
loop and verifies the batched results against it.

``--ranked`` serves RANKED BM25 top-k instead (DESIGN.md §5): the corpus
gains a clustered term-frequency stream, the arena its freq blocks and
block-max sidecar, and queries run through the Block-Max MaxScore/WAND
``repro.ranked.TopKEngine``.  ``--compare-scalar`` then verifies every
batch against the exhaustive-scoring oracle (identical top-k, ties by
docID) and reports the speedup.  ``--resident kernel`` drops the host
impact mirror and runs the Block-Max pruning through the
``blockmax_pivot`` kernel over resident bound tiles (DESIGN.md §9) --
same top-k, HBM-resident configuration.

``--shards N`` list-hash-partitions the arena into N shards (DESIGN.md §6)
and routes every cursor batch per shard: one device per shard under
``shard_map`` when the process has enough jax devices, a host-side loop of
per-shard engines otherwise.  Results are identical to unsharded serving
-- the merge is a pure scatter at the result boundary.

``--replicas R`` places every list on R shards, and ``--faults`` /
``--fault-prob`` inject shard deaths at the dispatch boundary
(DESIGN.md §11): serving then runs through ``ResilientEngine`` -- retry
with backoff, replica failover, degradation to live lists -- and reports
availability, degraded fraction, and recovery times.  ``--recover``
checkpoints the arena up front so DEAD shards restore from it and
re-admit.

``--loop`` (requires ``--ranked``) serves through the CONTINUOUS-BATCHING
async engine instead of fixed batches (``repro.serving``, DESIGN.md §13):
requests arrive on an asyncio loop at ``--offered-qps`` (Poisson) for
``--duration`` seconds, a deadline-aware batch former coalesces them into
pow2-bucketed waves (``--batch`` caps the wave, ``--max-delay-ms`` bounds
the linger, ``--deadline-ms`` sets the per-request SLO, ``--max-queue``
the backpressure bound), and the report adds sustained QPS, wave
occupancy, queue depth, deadline misses, and end-to-end latency
p50/p99/p99.9.  Operator runbook: docs/serving.md.

``--codec {auto,svb,ef}`` selects the arena codec policy (DESIGN.md §14):
``auto`` lets the optimal partitioner pick VByte / Elias-Fano / bitvector
per partition by exact encoded size, ``svb`` keeps the legacy
VByte/bitvector arena, ``ef`` prefers Elias-Fano wherever a block is
eligible.  ``--config FILE`` loads a ``repro.api.EngineConfig`` JSON as
the base engine configuration; explicit flags override its fields.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro import obs
from repro.api import EngineConfig, make_query_engine, make_topk_engine
from repro.core import build_partitioned_index, build_unpartitioned_index
from repro.core.query_engine import QueryEngine
from repro.data.postings import make_corpus, make_freqs, make_queries
from repro.launch.compile_cache import enable_compile_cache

# the one shared percentile implementation (DESIGN.md §12) -- formerly a
# local helper here plus per-bench copies
_percentile = obs.Histogram.percentile_of


def _latency_line(lat: list[float], per_q: list[float]) -> str:
    return (f"p50 {_percentile(lat, 50)*1e3:.2f} ms  "
            f"p90 {_percentile(lat, 90)*1e3:.2f} ms  "
            f"p99 {_percentile(lat, 99)*1e3:.2f} ms  "
            f"p99.9 {_percentile(lat, 99.9)*1e3:.2f} ms  "
            f"(per-query p50 {_percentile(per_q, 50)*1e3:.3f} ms)")


def serve_batches(
    engine: QueryEngine, queries: list[list[int]], batch: int
) -> tuple[list[np.ndarray], list[float]]:
    """Run all queries through the engine in batches; returns (results,
    per-batch wall latencies in seconds)."""
    results: list[np.ndarray] = []
    latencies: list[float] = []
    for i in range(0, len(queries), batch):
        chunk = queries[i : i + batch]
        with obs.timer("serve_batch_ms", path="boolean_and") as t:
            results.extend(engine.intersect_batch(chunk))
        latencies.append(t.elapsed_s)
    return results, latencies


def _print_shard_layout(engine) -> None:
    sa = engine.sharded
    if sa is None:
        return
    sizes = [len(f) for f in sa.lists_of]
    mode = (
        f"shard_map over {sa.mesh.devices.size} devices"
        if sa.mesh is not None else "host loop (too few devices for a mesh)"
    )
    # sizes from ROUTING METADATA only: forcing sa.shards here would
    # materialize the per-shard arena slices even on backends (numpy)
    # that never route -- exactly what ShardedArena keeps lazy
    lbo = engine.arena.list_blk_offsets
    blocks = [int((lbo[f + 1] - lbo[f]).sum()) for f in sa.lists_of]
    per_blk = engine.arena.nbytes() / max(engine.arena.n_blocks, 1)
    print(f"[serve] shards: {sa.n_shards} ({mode}); lists/shard {sizes}; "
          f"~MB/shard {[round(b * per_blk / 1e6, 1) for b in blocks]}")


def _make_resilient(args, engine):
    """Wrap the engine for fault-injected serving, or None without
    --faults/--fault-prob.  The checkpoint tempdir (with --recover) lives
    for the process -- real deployments point CheckpointManager at
    durable storage instead."""
    if not args.faults and args.fault_prob == 0.0:
        return None
    if args.shards is None:
        raise SystemExit("--faults/--fault-prob require --shards")
    from repro.distributed.resilient import ResilientEngine, ShardFaultInjector

    at = tuple(int(b) for b in args.faults.split(",")) if args.faults else ()
    injector = ShardFaultInjector(
        at_batches=at, probability=args.fault_prob, seed=args.seed,
        shards=tuple(range(args.shards)),
    )
    manager = None
    if args.recover:
        import tempfile

        from repro.checkpoint import CheckpointManager

        manager = CheckpointManager(
            tempfile.mkdtemp(prefix="arena-ckpt-"), async_save=False
        )
    res = ResilientEngine(engine, injector=injector, manager=manager)
    if manager is not None:
        res.checkpoint()
    return res


def serve_resilient(res, queries, batch: int, topk: int | None = None):
    """Serve all queries through a ResilientEngine; returns (results,
    latencies, n_degraded_queries)."""
    results: list = []
    lat: list[float] = []
    degraded_q = 0
    for i in range(0, len(queries), batch):
        chunk = queries[i : i + batch]
        with obs.timer("serve_batch_ms", path="resilient") as t:
            if topk is None:
                out, info = res.intersect_batch(chunk)
            else:
                out, info = res.topk_batch(chunk, topk)
        lat.append(t.elapsed_s)
        results.extend(out)
        if info.degraded:
            miss = set(info.missing_lists.tolist())
            degraded_q += sum(
                1 for q in chunk if any(int(t) in miss for t in q)
            )
    return results, lat, degraded_q


def _print_fault_summary(res, n_queries: int, degraded_q: int) -> None:
    stats = res.stats
    avail = (n_queries - degraded_q) / max(n_queries, 1)
    p99 = res.recovery_p99_s()
    rec = f"{p99 * 1e3:.1f} ms" if p99 == p99 else "n/a"
    print(f"[serve] faults: availability {avail:.4f} "
          f"({n_queries - degraded_q}/{n_queries} exact, "
          f"{degraded_q} degraded), failures {stats['failures']}, "
          f"retries {stats['retries']}, failovers {stats['failovers']}, "
          f"recoveries {stats['recoveries']} (p99 {rec})")
    print(f"[serve] shard health: {res.health}")


def serve_loop(args, engine, queries) -> None:
    """The --loop endpoint: open-loop Poisson arrivals through the
    continuous-batching ``AsyncTopKServer`` (DESIGN.md §13)."""
    import asyncio

    from repro.serving import AsyncTopKServer, QueueFull

    server = AsyncTopKServer(
        engine,
        k=args.topk,
        max_batch=args.batch,
        max_queue=args.max_queue,
        max_delay_s=args.max_delay_ms / 1e3,
        default_deadline_s=(
            args.deadline_ms / 1e3 if args.deadline_ms else float("inf")
        ),
    )

    async def drive():
        rng = np.random.default_rng(args.seed + 1)
        results: list = []
        t0 = obs.now()

        async def client(q):
            try:
                results.append(await server.try_submit(q))
            except QueueFull:
                pass  # counted in server.stats["shed"]

        async with server:
            tasks = []
            deadline = t0 + args.duration
            i = 0
            while obs.now() < deadline:
                tasks.append(asyncio.ensure_future(
                    client(queries[i % len(queries)])
                ))
                i += 1
                # Poisson arrivals at the offered rate
                await asyncio.sleep(rng.exponential(1.0 / args.offered_qps))
            await asyncio.gather(*tasks)
        return results, obs.now() - t0

    results, wall = asyncio.run(drive())
    ok = [r for r in results if not r.expired]
    lat = [r.latency_s for r in ok]
    waits = [r.wait_s for r in ok]
    st, fst = server.stats, server.former.stats
    print(f"[serve] loop: offered {args.offered_qps:,.0f} q/s for "
          f"{args.duration:.1f}s -> sustained {len(ok)/wall:,.0f} q/s "
          f"({len(ok)} served, {st['expired']} expired, {st['shed']} shed, "
          f"{st['late']} late)")
    if lat:
        print(f"[serve] loop latency: "
              f"p50 {_percentile(lat, 50)*1e3:.2f} ms  "
              f"p99 {_percentile(lat, 99)*1e3:.2f} ms  "
              f"p99.9 {_percentile(lat, 99.9)*1e3:.2f} ms  "
              f"(queue-wait p50 {_percentile(waits, 50)*1e3:.3f} ms)")
    waves = max(fst["waves"], 1)
    print(f"[serve] loop waves: {fst['waves']} "
          f"({fst['full_waves']} full, "
          f"occupancy {st['served']/(waves*args.batch):.2f}, "
          f"bucket reuse {fst['bucket_hits']}/{fst['waves']}, "
          f"{st['padded_queries']} padded)")
    print(f"[serve] engine stats: {engine.stats}")


def serve_ranked(args, rng, corpus) -> None:
    """The --ranked endpoint: batched BM25 top-k over the freq arena."""
    from repro.ranked.bm25 import exhaustive_topk

    freqs = make_freqs(rng, corpus)
    t0 = obs.now()
    idx = build_partitioned_index(
        corpus, "optimal", freqs=freqs, codecs=args.cfg.codec_policy
    )
    # includes the freq transcode + block-max sidecar
    arena = idx.arena_for(args.cfg.codec_policy)
    t_build = obs.now() - t0
    print(f"[serve] ranked index: {idx.bits_per_int():.2f} bpi docIDs + "
          f"{idx.freq_payload.size * 8 / max(int(idx.list_sizes.sum()), 1):.2f} "
          f"bpi freqs; arena {arena.nbytes() / 1e6:.1f} MB "
          f"(build {t_build:.1f}s)")

    queries = [
        [int(t) for t in q]
        for q in make_queries(rng, args.n_lists, args.queries, args.arity)
    ]
    engine = make_topk_engine(idx, args.cfg)
    _print_shard_layout(engine)
    engine.topk_batch(queries[: args.batch], args.topk)  # warm mirror + jit
    if args.loop:
        serve_loop(args, engine, queries)
        return
    resilient = _make_resilient(args, engine)

    t0 = obs.now()
    if resilient is not None:
        results, lat, degraded_q = serve_resilient(
            resilient, queries, args.batch, topk=args.topk
        )
    else:
        results, lat = [], []
        for i in range(0, len(queries), args.batch):
            with obs.timer("serve_batch_ms", path="ranked") as bt:
                results.extend(
                    engine.topk_batch(queries[i : i + args.batch], args.topk)
                )
            lat.append(bt.elapsed_s)
    wall = obs.now() - t0
    sizes = [len(queries[i : i + args.batch])
             for i in range(0, len(queries), args.batch)]
    per_q = [l / max(s, 1) for l, s in zip(lat, sizes)]
    print(f"[serve] ranked top-{args.topk} ({engine.backend}/"
          f"{engine.resident}, batch={args.batch}): "
          f"{len(queries)/wall:,.0f} q/s, "
          f"{wall/len(queries)*1e3:.3f} ms/query avg")
    print(f"[serve] batch latency: {_latency_line(lat, per_q)}")
    print(f"[serve] engine stats: {engine.stats}")
    if resilient is not None:
        _print_fault_summary(resilient, len(queries), degraded_q)
        return  # degraded batches must not be verified against the oracle

    if args.compare_scalar:
        n_check = min(len(queries), 64)
        t0 = obs.now()
        want = exhaustive_topk(idx, queries[:n_check], args.topk)
        dt = obs.now() - t0
        for q, (gd, gs), (wd, ws) in zip(queries, results, want):
            assert np.array_equal(gd, wd) and np.array_equal(gs, ws), q
        speedup = (dt / n_check) / (wall / len(queries))
        print(f"[serve] exhaustive oracle: {dt/n_check*1e3:.2f} ms/query "
              f"over {n_check} queries -> block-max speedup {speedup:.1f}x, "
              f"identical top-k")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-lists", type=int, default=64)
    ap.add_argument("--min-len", type=int, default=1_000)
    ap.add_argument("--max-len", type=int, default=100_000)
    ap.add_argument("--queries", type=int, default=512)
    ap.add_argument("--arity", type=int, default=2)
    ap.add_argument("--batch", type=int, default=64)
    # engine flags default to None so a --config file is not clobbered by
    # argparse defaults: EngineConfig.from_args only overrides fields the
    # caller actually set, and main() rebinds the resolved values onto args
    ap.add_argument("--backend", default=None,
                    choices=["auto", "numpy", "ref", "pallas"])
    ap.add_argument("--no-fused", dest="fused", action="store_false",
                    default=None,
                    help="serve through the PR-1 partition-LRU engine "
                         "instead of the fused device pipeline")
    ap.add_argument("--codec", default=None, choices=["auto", "svb", "ef"],
                    help="arena codec policy (DESIGN.md §14): 'auto' lets "
                         "the partitioner pick VByte/Elias-Fano/bitvector "
                         "per partition by encoded size, 'svb' keeps the "
                         "legacy VByte/bitvector arena, 'ef' prefers "
                         "Elias-Fano wherever a block is eligible")
    ap.add_argument("--config", default=None, metavar="PATH",
                    help="EngineConfig JSON file (repro.api) supplying the "
                         "engine options; explicit flags override its "
                         "fields")
    ap.add_argument("--ranked", action="store_true",
                    help="serve BM25 top-k through the Block-Max engine "
                         "instead of boolean AND")
    ap.add_argument("--topk", type=int, default=10,
                    help="k for --ranked serving")
    ap.add_argument("--resident", default=None,
                    choices=["auto", "mirror", "kernel"],
                    help="ranked residency: 'mirror' prunes on the host "
                         "impact mirror; 'kernel' keeps only compressed "
                         "blocks + bound tiles resident and runs the "
                         "Block-Max pruning through the blockmax_pivot "
                         "kernel (DESIGN.md §9); 'auto' picks kernel on "
                         "a real accelerator")
    ap.add_argument("--shards", type=int, default=None,
                    help="list-hash-partition the arena into N shards "
                         "(DESIGN.md §6): shard_map over a device mesh "
                         "when possible, host-side shard loop otherwise")
    ap.add_argument("--replicas", type=int, default=None,
                    help="place every list on R shards (DESIGN.md §11); "
                         "routing prefers the primary, replicas carry its "
                         "lists bit-identically when it dies")
    ap.add_argument("--faults", default=None,
                    help="comma-separated batch indices at which a shard "
                         "dies (e.g. '2,5'); serves through the "
                         "ResilientEngine health state machine")
    ap.add_argument("--fault-prob", type=float, default=0.0,
                    help="per-batch shard-death probability (seeded by "
                         "--seed), instead of/alongside --faults")
    ap.add_argument("--recover", action="store_true",
                    help="checkpoint the arena up front (OptVB-packed "
                         "sidecars) and restore DEAD shards' sub-arenas "
                         "from it, re-admitting them")
    ap.add_argument("--loop", action="store_true",
                    help="serve through the continuous-batching async "
                         "engine (repro.serving, requires --ranked): "
                         "Poisson arrivals at --offered-qps for "
                         "--duration seconds, deadline-aware waves")
    ap.add_argument("--offered-qps", type=float, default=2_000.0,
                    help="open-loop arrival rate for --loop (Poisson)")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="seconds of --loop arrivals before draining")
    ap.add_argument("--max-delay-ms", type=float, default=2.0,
                    help="batch-former linger: a partial wave fires after "
                         "this long (latency floor vs occupancy trade)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request SLO for --loop; requests past it "
                         "are expired unserved (0 = no deadline)")
    ap.add_argument("--max-queue", type=int, default=1_024,
                    help="bounded request queue for --loop: admissions "
                         "beyond it shed (backpressure bound)")
    ap.add_argument("--compare-scalar", action="store_true",
                    help="also time the per-query NextGEQ loop (or, with "
                         "--ranked, the exhaustive-scoring oracle) and "
                         "verify the batched results against it")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="arm the obs layer and serve the live metrics "
                         "registry over HTTP: /metrics (Prometheus text) "
                         "and /metrics.json (JSON snapshot); 0 binds an "
                         "ephemeral port")
    ap.add_argument("--metrics-dump", default=None, metavar="PATH",
                    help="arm the obs layer and write the JSON metrics "
                         "snapshot to PATH at exit")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    # resolve flags + --config file into the one EngineConfig, then rebind
    # the resolved values so the rest of the driver reads them from args
    args.cfg = EngineConfig.from_args(args)
    args.backend = args.cfg.backend
    args.fused = args.cfg.fused
    args.resident = args.cfg.resident
    args.shards = args.cfg.shards
    args.replicas = args.cfg.replicas
    if args.shards is not None and not args.fused and not args.ranked:
        # the ranked engine has no fused= knob; only boolean-AND serving
        # needs the fused pipeline for sharding
        ap.error("--shards requires the fused engine (drop --no-fused)")
    if args.loop and not args.ranked:
        ap.error("--loop serves ranked top-k; add --ranked")
    if args.loop and (args.faults or args.fault_prob):
        ap.error("--loop and fault injection are separate lanes; "
                 "drop --faults/--fault-prob")

    enable_compile_cache()
    server = None
    if args.metrics_port is not None or args.metrics_dump:
        obs.enable()
    if args.metrics_port is not None:
        server = obs.MetricsServer(args.metrics_port)
        print(f"[serve] metrics: http://127.0.0.1:{server.port}/metrics "
              f"(Prometheus) and /metrics.json")
    try:
        _serve(args)
    finally:
        if args.metrics_dump:
            obs.write_snapshot(args.metrics_dump)
            print(f"[serve] metrics snapshot -> {args.metrics_dump}")
        if server is not None:
            server.close()


def _serve(args) -> None:
    rng = np.random.default_rng(args.seed)
    t0 = obs.now()
    corpus = make_corpus(
        rng, n_lists=args.n_lists, min_len=args.min_len, max_len=args.max_len
    )
    n_postings = sum(len(l) for l in corpus)
    print(f"[serve] corpus: {args.n_lists} lists, {n_postings:,} postings "
          f"({obs.now()-t0:.1f}s)")

    if args.ranked:
        serve_ranked(args, rng, corpus)
        return

    t0 = obs.now()
    idx = build_partitioned_index(
        corpus, "optimal", codecs=args.cfg.codec_policy
    )
    t_build = obs.now() - t0
    base = build_unpartitioned_index(corpus)
    print(f"[serve] space: optimal {idx.bits_per_int():.2f} bpi vs "
          f"un-partitioned {base.bits_per_int():.2f} bpi "
          f"({base.bits_per_int()/idx.bits_per_int():.2f}x); "
          f"build {n_postings/max(t_build,1e-9)/1e6:.1f} M ints/s")

    queries = [
        [int(t) for t in q]
        for q in make_queries(rng, args.n_lists, args.queries, args.arity)
    ]
    engine = make_query_engine(idx, args.cfg)
    _print_shard_layout(engine)
    # warm-up batch: triggers the one-time arena transcode + jit on device
    engine.intersect_batch(queries[: args.batch])
    resilient = _make_resilient(args, engine)

    t0 = obs.now()
    if resilient is not None:
        results, lat, degraded_q = serve_resilient(resilient, queries, args.batch)
    else:
        results, lat = serve_batches(engine, queries, args.batch)
    wall = obs.now() - t0
    n_results = sum(r.size for r in results)
    sizes = [len(queries[i : i + args.batch])
             for i in range(0, len(queries), args.batch)]
    per_q = [l / max(s, 1) for l, s in zip(lat, sizes)]
    path = "fused" if engine.fused else "partition-lru"
    print(f"[serve] batched AND ({engine.backend}/{path}, batch={args.batch}): "
          f"{len(queries)/wall:,.0f} q/s, "
          f"{wall/len(queries)*1e3:.3f} ms/query avg, "
          f"{n_results:,} results total")
    print(f"[serve] batch latency: {_latency_line(lat, per_q)}")
    print(f"[serve] engine stats: {engine.stats}")
    if resilient is not None:
        _print_fault_summary(resilient, len(queries), degraded_q)
        return  # degraded batches must not be verified against the oracle

    if args.compare_scalar:
        n_check = min(len(queries), 128)
        t0 = obs.now()
        scalar = [idx.intersect_scalar(q) for q in queries[:n_check]]
        dt = obs.now() - t0
        for q, got, want in zip(queries[:n_check], results[:n_check], scalar):
            assert np.array_equal(got, want), f"mismatch on query {q}"
        speedup = (dt / n_check) / (wall / len(queries))
        print(f"[serve] scalar loop: {dt/n_check*1e3:.2f} ms/query over "
              f"{n_check} queries -> batched speedup {speedup:.1f}x, "
              f"results identical")


if __name__ == "__main__":
    main()
