#!/usr/bin/env python3
"""On-chip smoke test: the served index's main path once, at a real size.

    python3 chip_smoke.py              # one TPU chip
    python3 chip_smoke.py --chips 4    # the sharded path, on four chips

One chip: a ranked multi-codec index of about 100M postings over 2,048
Zipf-sized lists is generated from ``--seed``, built, uploaded, and served
through the entry points a user calls -- boolean AND through
``make_query_engine``, BM25 top-10 through ``make_topk_engine`` and a few
hundred requests through ``AsyncTopKServer``.  Every answer is checked
against plain references: the generated lists and ``intersect_scalar`` for
AND, ``exhaustive_topk`` for top-k, direct ``topk_batch`` for the serving
loop.  ``--chips 4`` runs only the sharded path: four shards under one
``shard_map`` over four devices, two replicas per list, one shard killed
mid-run, checked against the same references.

Each phase prints one line.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failure exits non-zero without it, and so does a run where JAX finds
no TPU.  Everything happens in this one process, since a chip belongs to
one process at a time.  The JAX compile cache is ``JAX_COMPILATION_CACHE_DIR``
when set, ``<checkout>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import argparse
import asyncio
import functools
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

K = 10
BATCH = 64
N_LISTS = 2_048
QUERIES = 256  # AND and top-10 queries, half 2-term, half 3-term
SCALAR_SAMPLE = 16  # AND queries also checked against intersect_scalar
REQUESTS = 384  # requests sent through AsyncTopKServer
SERVE_TIMEOUT_S = 300.0


class SmokeFailure(Exception):
    """A check failed: the run exits non-zero and prints no result."""


def phase(name: str, **fields) -> None:
    print(f"[{name}] " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def check_device(chips: int):
    """Phase 1: a TPU, the compiled Pallas backend and kernel residency."""
    if os.environ.get("REPRO_BACKEND"):
        raise SmokeFailure("REPRO_BACKEND is set: the smoke checks the default backend")
    import jax

    from repro.kernels.vbyte_decode.ops import default_backend, default_interpret
    from repro.ranked.topk_engine import default_resident

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"no TPU found: JAX reports platform {devs[0].platform!r}")
    if len(devs) != chips:
        raise SmokeFailure(f"--chips {chips} but JAX reports {len(devs)} devices")
    backend, interpret, resident = default_backend(), default_interpret(), default_resident()
    phase(
        "device", platform=devs[0].platform, kind=repr(devs[0].device_kind),
        count=len(devs), backend=backend, interpret=interpret, resident=resident,
    )
    if (backend, interpret, resident) != ("pallas", False, "kernel"):
        raise SmokeFailure("the default path is not compiled Pallas with kernel residency")
    return devs


def require_compiled(engine, what: str) -> None:
    """The engine must run compiled Pallas kernels (kernel residency for
    the top-k engine), not a host or interpreted fallback."""
    mode = (engine.backend, engine.interpret, getattr(engine, "resident", "kernel"))
    if mode != ("pallas", False, "kernel"):
        raise SmokeFailure(f"{what} runs backend/interpret/resident = {mode}")


def zipf_lengths(n_lists: int, total: int, max_len: int) -> np.ndarray:
    """Rank-frequency Zipf lengths ``max_len * r^-s``, s bisected so that
    they sum to about ``total``."""
    r = np.arange(1, n_lists + 1, dtype=np.float64)
    lo, hi = 0.0, 8.0
    for _ in range(60):
        s = (lo + hi) / 2
        if (max_len * r**-s).sum() > total:
            lo = s
        else:
            hi = s
    return np.maximum(max_len * r**-hi, 1).astype(np.int64)


def build(args, rng):
    """Phase 2: generate, build and upload the index."""
    from repro.core import build_partitioned_index
    from repro.data.postings import make_freqs, make_posting_list

    t0 = time.perf_counter()
    # the longest list holds 4% of the postings: 4M of the default 100M
    lens = rng.permutation(zipf_lengths(N_LISTS, args.postings, args.postings // 25))
    lists = [make_posting_list(rng, int(n)) for n in lens]
    freqs = make_freqs(rng, lists)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    idx = build_partitioned_index(lists, "optimal", freqs=freqs, codecs="auto")
    del freqs
    arena = idx.arena_for(args.codec)
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    import jax

    dev = [*vars(arena.dev).values(), *vars(arena.ranked.dev).values()]
    jax.block_until_ready(dev)
    t_up = time.perf_counter() - t0
    key_span = idx.n_lists * arena.stride
    phase(
        "build", postings=int(lens.sum()), lists=idx.n_lists,
        max_list=int(lens.max()), bpi=f"{idx.bits_per_int():.3f}",
        codec=args.codec, multi_codec=arena.block_codec is not None,
        device_bytes=sum(int(a.nbytes) for a in dev), gen_s=f"{t_gen:.1f}",
        build_s=f"{t_build:.1f}", upload_s=f"{t_up:.1f}",
        n_lists_x_stride=key_span, past_2_31=key_span >= 2**31,
    )
    if key_span < 2**31:
        raise SmokeFailure("n_lists * stride stays below 2^31: not a real-size key space")
    return idx, lists


def make_query_sets(rng, n_lists: int, n: int) -> list[list[int]]:
    from repro.data.postings import make_queries

    return [
        [int(t) for t in q]
        for ar in (2, 3)
        for q in make_queries(rng, n_lists, n // 2, ar)
    ]


def check_and(engine, idx, lists, queries, name: str) -> None:
    """AND answers vs the generated lists and ``intersect_scalar``."""
    t0 = time.perf_counter()
    got = []
    for i in range(0, len(queries), BATCH):
        got += engine.intersect_batch(queries[i : i + BATCH])
    t_serve = time.perf_counter() - t0
    for q, g in zip(queries, got):
        want = functools.reduce(np.intersect1d, [lists[t] for t in q])
        if not np.array_equal(g, want):
            raise SmokeFailure(f"{name}: AND {q} differs from the generated lists")
    for q, g in zip(queries[:SCALAR_SAMPLE], got[:SCALAR_SAMPLE]):
        if not np.array_equal(g, idx.intersect_scalar(q)):
            raise SmokeFailure(f"{name}: AND {q} differs from intersect_scalar")
    phase(
        name, queries=len(queries), results=sum(len(g) for g in got),
        serve_s=f"{t_serve:.2f}", scalar_checked=min(SCALAR_SAMPLE, len(queries)),
        identical=True,
    )


def compare_topk(got, want, what: str) -> None:
    for i, ((gd, gs), (wd, ws)) in enumerate(zip(got, want)):
        if not (np.array_equal(gd, wd) and np.array_equal(gs, ws)):
            diff = np.asarray(gs, np.float64)[: len(ws)] - np.asarray(ws, np.float64)[: len(gs)]
            raise SmokeFailure(
                f"{what}: query {i} differs (docs equal: {np.array_equal(gd, wd)}, "
                f"max score diff {np.abs(diff).max() if diff.size else 'n/a'})"
            )


def check_topk(engine, idx, queries, name: str):
    """Top-10 answers vs ``exhaustive_topk``; returns the answers."""
    from repro.ranked.bm25 import exhaustive_topk

    t0 = time.perf_counter()
    got = []
    for i in range(0, len(queries), BATCH):
        got += engine.topk_batch(queries[i : i + BATCH], K)
    t_serve = time.perf_counter() - t0
    compare_topk(got, exhaustive_topk(idx, queries, K), name)
    phase(
        name, queries=len(queries), k=K, backend=engine.backend,
        interpret=engine.interpret, resident=engine.resident,
        serve_s=f"{t_serve:.2f}", identical=True,
    )
    return got


def check_serving(engine, rng, queries) -> None:
    """Phase 5: requests through ``AsyncTopKServer`` vs direct ``topk_batch``,
    under a wall-clock limit so that a wave that raises fails the run."""
    from repro.serving import AsyncTopKServer

    picks = rng.integers(0, len(queries), REQUESTS)
    server = AsyncTopKServer(engine, k=K, max_batch=BATCH)

    async def drive():
        async with server:
            return await asyncio.gather(*(server.submit(queries[i]) for i in picks))

    t0 = time.perf_counter()
    try:
        results = asyncio.run(asyncio.wait_for(drive(), SERVE_TIMEOUT_S))
    except asyncio.TimeoutError:
        raise SmokeFailure(f"serving: no answer within {SERVE_TIMEOUT_S:.0f} s") from None
    t_serve = time.perf_counter() - t0
    direct = engine.topk_batch(queries, K)
    compare_topk(
        [(r.docs, r.scores) for r in results], [direct[i] for i in picks], "serving"
    )
    if any(r.expired for r in results):
        raise SmokeFailure("serving: a request expired")
    phase(
        "serving", requests=REQUESTS, served=server.stats["served"],
        waves_padded=server.stats["padded_queries"], wall_s=f"{t_serve:.2f}",
        identical_to_topk_batch=True,
    )


def one_chip(args, rng) -> None:
    from repro.api import make_query_engine, make_topk_engine

    idx, lists = build(args, rng)
    queries = make_query_sets(rng, idx.n_lists, QUERIES)
    qe = make_query_engine(idx)
    require_compiled(qe, "the AND engine")
    check_and(qe, idx, lists, queries, "and")
    te = make_topk_engine(idx)
    require_compiled(te, "the top-k engine")
    check_topk(te, idx, queries, "topk")
    check_serving(te, rng, queries)


def shard_bytes(arrays) -> dict:
    """Bytes of the given sharded arrays held by each device id."""
    per = {}
    for arr in arrays:
        for sh in arr.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + int(sh.data.nbytes)
    return per


def four_chips(args, rng, devs) -> None:
    """The sharded path only: shard_map over four devices + one failover."""
    from repro.api import EngineConfig, make_query_engine, make_topk_engine
    from repro.distributed.resilient import ResilientEngine, ShardFaultInjector

    idx, lists = build(args, rng)
    queries = make_query_sets(rng, idx.n_lists, QUERIES)
    cfg = EngineConfig(codec_policy=args.codec, shards=len(devs), replicas=2)
    qe = make_query_engine(idx, cfg)
    te = make_topk_engine(idx, cfg)
    for eng in (qe, te):
        require_compiled(eng, "a sharded engine")
        if eng.sharded.mesh is None:
            raise SmokeFailure("no shard mesh: the shards would run as a host loop")
    phase(
        "shards", shards=len(devs), replicas=2, codec=args.codec,
        mesh=dict(qe.sharded.mesh.shape), path="shard_map",
    )
    check_and(qe, idx, lists, queries, "sharded_and")
    got = check_topk(te, idx, queries, "sharded_topk")
    per = shard_bytes([
        *qe.sharded.stacked_dev().values(),
        *te.sharded.stacked_dev().values(),
        *te.sharded.stacked_pivot_dev().values(),
    ])
    phase(
        "residency", **{f"dev{d}_bytes": b for d, b in sorted(per.items())},
        **{f"dev{d.id}_in_use": (d.memory_stats() or {}).get("bytes_in_use")
           for d in devs},
    )
    if len(per) != len(devs) or min(per.values()) <= 0:
        raise SmokeFailure(f"shard bytes are not on all {len(devs)} devices: {per}")
    victim = len(devs) - 1
    res = ResilientEngine(
        te, injector=ShardFaultInjector(at_batches=(1,), shards=(victim,))
    )
    after, step = [], max(1, len(queries) // 4)  # the shard dies in batch 1
    for i in range(0, len(queries), step):
        out, info = res.topk_batch(queries[i : i + step], K)
        if info.degraded:
            raise SmokeFailure("failover: a batch was answered degraded")
        after += out
    # ``got`` is identical to exhaustive_topk (checked above)
    compare_topk(after, got, "failover vs exhaustive_topk")
    phase(
        "failover", killed_shard=victim, failovers=res.stats["failovers"],
        health=",".join(res.health), identical=True,
    )
    if res.stats["failovers"] < 1:
        raise SmokeFailure("failover: the killed shard never failed over")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded path, one shard per chip")
    ap.add_argument("--postings", type=int, default=100_000_000,
                    help="corpus size of the generated index")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    # shard_map stacks one codec; the single-chip index serves multi-codec
    args.codec = "svb" if args.chips > 1 else "auto"
    try:
        import jax

        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the system ({e}); run from a checkout",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    log = CompileLog()
    t0 = time.perf_counter()
    try:
        devs = check_device(args.chips)
        rng = np.random.default_rng(args.seed)
        if args.chips == 1:
            one_chip(args, rng)
        else:
            four_chips(args, rng, devs)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = devs[0].memory_stats() or {}
    phase(
        "report", compile_s=f"{log.seconds:.1f}", cache_dir=cache_dir,
        cache_hits=log.hits, cache_misses=log.misses,
        bytes_in_use=stats.get("bytes_in_use"),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        wall_s=f"{time.perf_counter() - t0:.1f}",
    )
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
