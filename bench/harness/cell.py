"""One run of one cell: set-up, a measured window, the check, the metrics.

Set-up generates the configuration's corpus from the seed, builds and
uploads the index through the system's own entry points, makes the engine
the traffic asks for and warms it up on traffic of the cell's own mix from
a separate stream of the seed.  The window then drives the timed path:

* ``loop: open`` -- requests arrive on a Poisson schedule and go through
  ``AsyncTopKServer.try_submit``; each is timed from when it was due, and
  ``qps`` counts the answers completed inside the window;
* ``loop: closed`` -- a query log is replayed whole, in batches, through
  ``QueryEngine.intersect_batch`` until a replay ends past the window's
  seconds.

After the window the device's peak memory is read, the system's state is
dropped, and a sample of the answers (drawn from the seed, with the
heaviest query in it) is compared with the plain reference.

A configuration with a ``deployment`` block (see ``spec``) builds both
engines sharded over that many chips, through the same entry points.  A
traffic with ``faults`` serves through a ``ResilientEngine`` over an arena
checkpoint written in set-up; warm-up kills and restores each fault's
shard once, the window kills it at its ``at_s``, and the check adds the
failover answers, the degraded ones and the shards left unrecovered.

Streams of the run's seed: 0 list-id order, 1 warm-up, 2 window, 3 check
sample, 4 failover check sample (the corpus's contents and the query sets
come from the configuration's and the traffic's own seeds; see ``corpus``
and ``traffic``).
"""

from __future__ import annotations

import asyncio
import contextlib
import gc
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from harness import corpus as corpus_mod
from harness import reference, spec, trace as trace_mod, traffic as traffic_mod

# requests due in the window may be answered this long after it closes;
# later than that they count as never answered
GRACE_S = 60.0
WINDOW_SPAN = trace_mod.WINDOW


class BenchFailure(Exception):
    """The run cannot measure: it exits non-zero and prints no result."""


def say(tag: str, **fields) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          file=sys.stderr, flush=True)


class CompileLog:
    """Backend compiles and persistent-cache hits and misses, from JAX's
    own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def loaded(self) -> int:
        """Programs compiled or read from the persistent cache so far (JAX
        reports a backend compile for both)."""
        return self.compiles

    def snapshot(self) -> dict:
        return dict(compile_s=round(self.seconds, 3), compiles=self.compiles,
                    cache_hits=self.hits, cache_misses=self.misses)


def keep_every_program(cache_dir: str) -> str:
    """JAX writes to its persistent cache only programs that took a second
    or more to compile; the engines compile dozens that take less, so the
    benchmark keeps them all, whichever directory the cache is in."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


def rng_stream(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, stream])


@dataclass
class Run:
    """What a metric reader sees of one run."""

    operation: str
    n_queries: int = 0
    # open loop, per request, seconds from the window's start
    due: np.ndarray = None
    admit: np.ndarray = None
    wait_s: np.ndarray = None       # the server's admission -> wave formation
    latency: np.ndarray = None      # due -> answer (the wait, if never answered)
    stats: dict = field(default_factory=dict)     # engine stats over the window
    counters: dict = field(default_factory=dict)  # repro.obs counters over it
    bits_per_int: float = float("nan")
    trace: trace_mod.TraceSummary = None
    # traffic with faults: see ``window_faults``
    failover: dict = None


@dataclass
class Setup:
    cell: spec.Cell
    seed: int
    lists: list
    freqs: list
    rank_to_list: np.ndarray
    index: object
    engine: object
    postings: int
    phases: dict
    resilient: object = None  # the ResilientEngine, where the traffic kills shards


def check_device(chips: int):
    """A TPU with at least ``chips`` devices and the compiled Pallas path
    with kernel residency, or BenchFailure."""
    import jax

    from repro.kernels.vbyte_decode.ops import default_backend, default_interpret
    from repro.ranked.topk_engine import default_resident

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchFailure(f"no TPU found: JAX reports platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchFailure(f"the cell needs {chips} chips, JAX reports {len(devs)}")
    mode = (default_backend(), default_interpret(), default_resident())
    if mode != ("pallas", False, "kernel"):
        raise BenchFailure(f"the default path is {mode}, not compiled Pallas "
                           "with kernel residency")
    return devs


def require_compiled(engine, require_chip: bool) -> None:
    mode = (engine.backend, engine.interpret, getattr(engine, "resident", "kernel"))
    if require_chip and mode != ("pallas", False, "kernel"):
        raise BenchFailure(f"the engine runs backend/interpret/resident = {mode}")


def require_mesh(engine, layout, require_chip: bool) -> None:
    """A chip run of a sharded layout serves its shards from one mesh, one
    chip to a shard, or raises BenchFailure."""
    if layout is None or not require_chip:
        return
    mesh = engine.sharded.mesh
    axis = None if mesh is None else dict(mesh.shape).get("shard")
    if axis != layout["shards"]:
        raise BenchFailure(
            f"the configuration lays the index out in {layout['shards']} "
            f"shards, one to a chip, but the engine put them on no mesh with a "
            f"'shard' axis of {layout['shards']} devices (mesh: {mesh}): the "
            "shards would run as a host loop on one chip")


def make_resilient(s: Setup, directory: str) -> dict:
    """Wrap the engine in a ``ResilientEngine`` whose injector kills the
    traffic's shards in order, and write the arena checkpoint that
    recovery restores from into ``directory``."""
    from repro.checkpoint import CheckpointManager
    from repro.distributed.resilient import ResilientEngine, ShardFaultInjector

    t0 = time.perf_counter()
    s.resilient = ResilientEngine(
        s.engine,
        injector=ShardFaultInjector(shards=[f["shard"] for f in s.cell.faults]),
        manager=CheckpointManager(directory, keep=1, async_save=False),
        recover_async=True,
    )
    s.resilient.checkpoint()
    return {"checkpoint_s": time.perf_counter() - t0}


class Waves:
    """The harness's view of a ``ResilientEngine``: each call's results go
    on to the caller, and its ``ServeInfo`` is kept with the wave's start
    and end in seconds from ``t0`` and the shards routed around after it.

    ``at_s``: when, from ``t0``, the loop driving the waves calls ``arm``,
    which kills the injector's next shard at the next wave.  ``keep(i,
    query, answer)`` sees every answer of a wave served with a shard
    failed or dead, numbered from 0.  The engine's counters as they stand
    at construction are the baseline of ``window_faults``."""

    def __init__(self, resilient, at_s=(), keep=None, clock=time.perf_counter):
        self.res, self.at_s, self.keep, self.clock = (
            resilient, list(at_s), keep, clock)
        self.t0 = clock()
        self.records: list = []
        self.armed: list = []
        self.kept = 0
        self.fired0 = resilient.injector.fired
        self.stats0 = dict(resilient.stats)
        self.recovered0 = len(resilient.stats["recovery_s"])

    def arm(self) -> None:
        inj = self.res.injector
        inj.at_batches.add(inj.batch + 1)
        self.armed.append(self.clock() - self.t0)

    def _call(self, fn, queries, *args):
        t0 = self.clock() - self.t0
        results, info = fn(queries, *args)
        rec = {"t0": t0, "t1": self.clock() - self.t0,
               "failed_shards": [int(x) for x in info.failed_shards],
               "retries": int(info.retries), "degraded": bool(info.degraded),
               "dead_shards": [int(x) for x in np.flatnonzero(self.res.sa.dead)],
               "answers": sum(1 for q in queries if len(q))}
        self.records.append(rec)
        if self.keep is not None and (rec["failed_shards"] or rec["dead_shards"]):
            for q, a in zip(queries, results):
                if len(q):
                    self.keep(self.kept, q, a)
                    self.kept += 1
        return results

    def topk_batch(self, queries, k):
        return self._call(self.res.topk_batch, queries, k)

    def intersect_batch(self, queries):
        return self._call(self.res.intersect_batch, queries)


def _secs(xs) -> str:
    return ",".join(str(round(x, 3)) for x in xs) or "-"


def window_faults(waves: Waves, cell: spec.Cell) -> dict:
    """What the window's faults did, once its answers are in and the
    restores have had ``GRACE_S`` to finish: faults scheduled and fired,
    every wave's record, each kill with the start of the wave that found
    the shard dead and of the first wave served after it was re-admitted
    (seconds from the window's start), the engine's counters over the
    window, each restore's seconds from death to re-admission, the answers
    of degraded waves, the shards left unhealthy, and the engine's health.
    Raises BenchFailure where fewer faults fired than were scheduled."""
    from repro.distributed.resilient import HEALTHY

    res = waves.res
    res.wait_recovered(GRACE_S)
    scheduled, fired = len(cell.faults), res.injector.fired - waves.fired0
    kills, down = [], set()
    for w in waves.records:
        now = set(w["dead_shards"])
        kills += [{"shard": sh, "killed_at": w["t0"], "readmitted_at": None}
                  for sh in sorted(now - down)]
        for k in kills:
            if k["readmitted_at"] is None and k["shard"] in down - now:
                k["readmitted_at"] = w["t0"]
        down = now
    stats = {key: res.stats[key] - waves.stats0[key] for key in
             ("failovers", "retries", "degraded_batches", "dead_events",
              "recoveries")}
    out = dict(scheduled=scheduled, fired=fired, armed_at=list(waves.armed),
               waves=waves.records, kills=kills, **stats,
               recovery_s=list(res.stats["recovery_s"][waves.recovered0:]),
               degraded_answers=sum(w["answers"] for w in waves.records
                                    if w["degraded"]),
               unrecovered_shards=sum(h != HEALTHY for h in res.health),
               health=res.health_summary())
    say("failover", scheduled=scheduled, fired=fired, armed_at=_secs(waves.armed),
        killed_at=_secs(k["killed_at"] for k in kills),
        readmitted_at=_secs(k["readmitted_at"] for k in kills
                            if k["readmitted_at"] is not None),
        failover_waves=sum(1 for w in waves.records
                           if w["failed_shards"] or w["dead_shards"]),
        **stats, degraded_answers=out["degraded_answers"],
        recovery_s=_secs(out["recovery_s"]), health=",".join(res.health))
    if fired < scheduled:
        raise BenchFailure(f"{fired} of the traffic's {scheduled} faults fired "
                           "in the window: the run measured no failover")
    return out


def device_bytes() -> int:
    """Bytes of every array the process holds on its devices."""
    import jax

    gc.collect()
    return sum(int(a.nbytes) for a in jax.live_arrays())


def codec_mix(index) -> dict:
    """Partitions and share of postings per docID codec."""
    from repro.core.index import TAG_BITVECTOR, TAG_EF, TAG_VBYTE

    out = {}
    total = max(int(index.sizes.sum()), 1)
    for tag, name in ((TAG_VBYTE, "vbyte"), (TAG_BITVECTOR, "bitvector"),
                      (TAG_EF, "ef")):
        m = index.tags == tag
        out[f"{name}_partitions"] = int(m.sum())
        out[f"{name}_postings_share"] = round(int(index.sizes[m].sum()) / total, 4)
    return out


def setup(cell: spec.Cell, seed: int, log: CompileLog, require_chip: bool) -> Setup:
    """Generate, build, upload, make the engine; warm-up is separate."""
    import jax

    from repro.api import EngineConfig, make_query_engine, make_topk_engine
    from repro.core import build_partitioned_index

    cfg, tr = cell.config, cell.traffic
    phases = {}
    t0 = time.perf_counter()
    lists, freqs, order = corpus_mod.make_corpus(rng_stream(seed, 0), cfg["corpus"])
    rank_to_list = np.argsort(order)
    postings = int(sum(len(x) for x in lists))
    phases["generate_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    codecs = cfg["index"]["codecs"]
    index = build_partitioned_index(lists, cfg["index"]["partitioning"],
                                    freqs=freqs, codecs=codecs)
    arena = index.arena_for(codecs)
    phases["build_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    resident = [*vars(arena.dev).values()]
    if arena.ranked is not None:
        resident += [*vars(arena.ranked.dev).values()]
    jax.block_until_ready(resident)
    phases["upload_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    before = log.snapshot()
    config = None if cell.layout is None else EngineConfig(
        shards=cell.layout["shards"], replicas=cell.layout["replicas"],
        codec_policy=codecs)
    if tr["operation"] == "topk":
        engine = make_topk_engine(index, config)
        require_compiled(engine, require_chip)
        require_mesh(engine, cell.layout, require_chip)
        engine.topk_batch([[int(rank_to_list[-1])]], int(tr["k"]))
    elif tr["operation"] == "and":
        engine = make_query_engine(index, config)
        require_compiled(engine, require_chip)
        require_mesh(engine, cell.layout, require_chip)
        engine.intersect_batch([[int(rank_to_list[-1]), int(rank_to_list[-2])]])
    else:
        raise spec.SpecError(f"unknown operation {tr['operation']!r}")
    phases["engine_init_s"] = time.perf_counter() - t0
    phases["engine_init_compiles"] = log.snapshot()["compiles"] - before["compiles"]
    return Setup(cell, seed, lists, freqs, rank_to_list, index, engine,
                 postings, phases)


def serve(engine, queries, k: int, op: str):
    if op == "topk":
        return engine.topk_batch(queries, k)
    return engine.intersect_batch(queries)


def warm_up(s: Setup, log: CompileLog) -> dict:
    """Warm the engine on the mix's own traffic from the seed's warm-up
    stream: rounds of every wave size the window can form (full batches for
    a closed loop) until a round loads no new program, then, for an open
    loop, segments of open-loop traffic at the cell's rate until one loads
    no new program.  The engine's shapes follow the data, so a segment
    forms the window's own kinds of waves; it also fills the score cache.
    Where the traffic kills shards, each kill is then rehearsed once
    (``rehearse_faults``)."""
    import jax

    tr = s.cell.traffic
    front = s.engine if s.resilient is None else Waves(s.resilient)
    wu = tr["warmup"]
    op, k = tr["operation"], int(tr.get("k", 0))
    rng = rng_stream(s.seed, 1)
    sizes = []
    b = int(tr["batch"])
    while b >= 1:
        sizes.append(b)
        b //= 2
    if tr["loop"] == "closed":
        sizes = [int(tr["batch"])] * len(sizes)
    log_q = traffic_mod.query_log(rng, tr, s.rank_to_list,
                                  sum(sizes) * int(wu["max_rounds"]), 1)
    t0 = time.perf_counter()
    rounds, pos = 0, 0
    while rounds < int(wu["max_rounds"]):
        before = log.loaded()
        wave_s = []
        for b in sizes:
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.warmup"):
                serve(front, log_q[pos: pos + b], k, op)
            wave_s.append(f"{b}:{time.perf_counter() - t1:.3f}")
            pos += b
        rounds += 1
        say("warmup", round=rounds, loaded=log.loaded() - before,
            wave_s=",".join(wave_s))
        if rounds >= int(wu["min_rounds"]) and log.loaded() == before:
            break
    segments = 0
    if tr["loop"] == "open":
        rate, seg_s = float(tr["rate_qps"]), float(wu["segment_s"])
        while segments < int(wu["max_segments"]):
            n = max(int(round(rate * seg_s)), 1)
            queries = traffic_mod.query_log(rng, tr, s.rank_to_list, n,
                                            100 + segments)
            due = traffic_mod.poisson_due(rng, rate, n)
            before = log.loaded()
            waves = None if s.resilient is None else Waves(s.resilient)
            with jax.profiler.TraceAnnotation("bench.warmup"):
                _, answer, _, _, t_close = open_loop(s, queries, due, seg_s,
                                                     waves=waves)
            segments += 1
            pos += n
            lat = np.where(np.isfinite(answer), answer - due, t_close - due)
            say("warmup", segment=segments, loaded=log.loaded() - before,
                p95_ms=round(nearest_rank(lat, 0.95) * 1e3, 3))
            if segments >= int(wu["min_segments"]) and log.loaded() == before:
                break
    out = dict(warmup_s=time.perf_counter() - t0, warmup_rounds=rounds,
               warmup_segments=segments, warmup_queries=pos)
    if s.resilient is not None:
        out.update(rehearse_faults(s, log, sizes, rng))
        out["warmup_s"] = time.perf_counter() - t0
    return out


def rehearse_faults(s: Setup, log: CompileLog, sizes: list, rng) -> dict:
    """Each of the traffic's kills once, before the window: the shard dies
    at the first wave of a round of every wave size, the round fails over,
    the restore is awaited, and a second round re-admits the shard at its
    first wave.  The failover waves' programs load here, not in the window,
    and the injector's victims come round to the first again."""
    import jax

    from repro.distributed.resilient import HEALTHY

    tr, res = s.cell.traffic, s.resilient
    op, k = tr["operation"], int(tr.get("k", 0))
    t0 = time.perf_counter()
    for i, f in enumerate(s.cell.faults):
        log_q = traffic_mod.query_log(rng, tr, s.rank_to_list, 2 * sum(sizes),
                                      200 + i)
        waves = Waves(res)
        before = log.loaded()
        waves.arm()
        pos = 0
        for round_ in range(2):
            if round_:
                res.wait_recovered(GRACE_S)
            for b in sizes:
                with jax.profiler.TraceAnnotation("bench.warmup"):
                    serve(waves, log_q[pos: pos + b], k, op)
                pos += b
        failover = sum(1 for w in waves.records
                       if w["failed_shards"] or w["dead_shards"])
        say("warmup", fault=i + 1, shard=f["shard"], loaded=log.loaded() - before,
            failover_waves=failover, health=",".join(res.health))
        if failover == 0 or any(h != HEALTHY for h in res.health):
            raise BenchFailure(
                f"rehearsing the kill of shard {f['shard']}: {failover} waves "
                f"failed over, health after the restore {res.health}")
    return {"fault_rehearsal_s": time.perf_counter() - t0}


class _Annotated:
    """The engine as the server sees it, with a host span around each call."""

    def __init__(self, engine):
        self._engine = engine

    def topk_batch(self, queries, k):
        import jax

        with jax.profiler.TraceAnnotation("bench.topk_batch"):
            return self._engine.topk_batch(queries, k)


def open_loop(s: Setup, queries, due, seconds: float, clock=time.perf_counter,
              waves: Waves | None = None):
    """Drive ``AsyncTopKServer`` with requests due at ``due`` (s from the
    window's start).  Returns (admit, answer, wait_s, results, t_close):
    times in seconds from the window's start, NaN where never answered.
    With ``waves`` the server serves through it, and the loop arms its
    kills at their times."""
    import jax

    from repro.serving import AsyncTopKServer
    from repro.serving.loop import QueueFull

    tr = s.cell.traffic
    n = len(queries)
    admit = np.full(n, np.nan)
    answer = np.full(n, np.nan)
    wait_s = np.full(n, np.nan)
    results: list = [None] * n

    async def drive():
        # the queue holds every request: an overloaded window builds a
        # backlog that drains after the close, and sheds nothing
        server = AsyncTopKServer(
            _Annotated(s.engine if waves is None else waves), k=int(tr["k"]),
            max_batch=int(tr["batch"]),
            max_queue=max(n, 1), max_delay_s=float(tr["linger_ms"]) * 1e-3,
        )
        take = server.former.take

        def take_annotated(now):
            with jax.profiler.TraceAnnotation("bench.wave_form"):
                return take(now)

        server.former.take = take_annotated
        async with server:
            t0 = clock()
            timers = []
            if waves is not None:
                waves.t0 = t0
                loop = asyncio.get_running_loop()
                timers = [loop.call_later(max(t0 + at - clock(), 0.0), waves.arm)
                          for at in waves.at_s]

            async def one(i):
                admit[i] = clock() - t0
                try:
                    res = await server.try_submit(queries[i])
                except QueueFull:
                    return
                if not res.expired:
                    answer[i] = clock() - t0
                    wait_s[i] = res.wait_s
                    results[i] = res

            tasks = []
            for i in range(n):
                delay = t0 + due[i] - clock()
                if delay > 0:
                    await asyncio.sleep(delay)
                tasks.append(asyncio.ensure_future(one(i)))
            rest = max(t0 + seconds + GRACE_S - clock(), 0.0)
            done, pending = await asyncio.wait(tasks, timeout=rest)
            for t in [*pending, *timers]:
                t.cancel()
            for t in done:
                t.result()  # a wave that raised fails the run
            return clock() - t0

    t_close = asyncio.run(drive())
    return admit, answer, wait_s, results, t_close


def closed_loop(s: Setup, log_q, seconds: float, keep, clock=time.perf_counter,
                waves: Waves | None = None):
    """Replay the whole of ``log_q`` in batches, again and again, until a
    replay ends at or after ``seconds``: every window does whole replays, so
    every seed's window does the same work.  ``keep(i, answer)`` sees every
    answer.  With ``waves`` the batches go through it, and its kills are
    armed between batches.  Returns (queries served, window seconds)."""
    import jax

    b = int(s.cell.traffic["batch"])
    engine = s.engine if waves is None else waves
    kills = [] if waves is None else list(waves.at_s)
    served = 0
    t0 = clock()
    if waves is not None:
        waves.t0 = t0
    while served == 0 or clock() - t0 < seconds:
        for start in range(0, len(log_q), b):
            while kills and clock() - t0 >= kills[0]:
                kills.pop(0)
                waves.arm()
            with jax.profiler.TraceAnnotation("bench.intersect_batch"):
                out = engine.intersect_batch(log_q[start: start + b])
            for j, a in enumerate(out):
                keep(served + j, a)
            served += len(out)
    return served, clock() - t0


class Reservoir:
    """A uniform sample of ``size`` answers drawn from the seed, plus the
    answer of the heaviest query (most postings in its lists)."""

    def __init__(self, rng, size: int, weight):
        self.rng, self.size, self.weight = rng, size, weight
        self.items: dict = {}
        self.slots: list = []
        self.heavy = (-1, None, None)

    def __call__(self, i: int, query, answer) -> None:
        w = self.weight(query)
        if w > self.heavy[0]:
            self.heavy = (w, i, (query, answer))
        if len(self.slots) < self.size:
            self.slots.append(i)
            self.items[i] = (query, answer)
            return
        j = int(self.rng.integers(0, i + 1))
        if j < self.size:
            del self.items[self.slots[j]]
            self.slots[j] = i
            self.items[i] = (query, answer)

    def sample(self) -> dict:
        out = dict(self.items)
        if self.heavy[1] is not None:
            out[self.heavy[1]] = self.heavy[2]
        return out


def completed_rate(answer, seconds: float) -> float:
    """Answers completed inside the window (answer times in seconds from
    its start, NaN where none came) over the window's seconds."""
    return int(np.sum(np.asarray(answer) <= seconds)) / float(seconds)


def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank: the smallest value with at least a
    share q of the values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(math.ceil(q * len(v)) - 1, 0)])


def run(root: Path, workload: str, seed: int, seconds: float, traced: bool,
        t_start: float, require_chip: bool = True) -> dict:
    """One run; returns the result object the command prints last."""
    from repro import obs

    armed = obs.enabled()
    try:
        with contextlib.ExitStack() as scratch:
            return _run(root, workload, seed, seconds, traced, t_start,
                        require_chip, scratch)
    finally:
        obs.enable(armed)


def _run(root, workload, seed, seconds, traced, t_start, require_chip,
         scratch: contextlib.ExitStack) -> dict:
    import jax

    from repro import obs
    from repro.launch.compile_cache import enable_compile_cache

    cell = spec.resolve(root, workload)
    if require_chip:
        devs = check_device(cell.chips)
        cache_dir = keep_every_program(enable_compile_cache())
    else:
        devs, cache_dir = jax.devices(), None
    log = CompileLog()
    tr = cell.traffic
    op = tr["operation"]
    if traced:
        obs.enable()  # the engines count score-cache hits only when armed
    s = setup(cell, seed, log, require_chip)
    phases = dict(s.phases)
    if cell.faults:
        phases.update(make_resilient(s, scratch.enter_context(
            tempfile.TemporaryDirectory(prefix="bench-ckpt-"))))
    phases.update(warm_up(s, log))
    bits = 8.0 * device_bytes() / s.postings
    setup_s = time.perf_counter() - t_start
    say("setup", setup_s=round(setup_s, 3),
        **{k: round(v, 3) if isinstance(v, float) else v for k, v in phases.items()},
        **log.snapshot(), cache_dir=cache_dir)
    say("index", postings=s.postings, lists=len(s.lists),
        bits_per_int=round(s.index.bits_per_int(), 4),
        device_bits_per_posting=round(bits, 4), **codec_mix(s.index))

    rw = rng_stream(seed, 2)
    stats0 = dict(getattr(s.engine, "stats", {}))
    hits = obs.counter("ranked_score_cache_rows", kind="hit")
    miss = obs.counter("ranked_score_cache_rows", kind="miss")
    c0 = (hits.value, miss.value)
    loaded0, compiles0, hits0 = log.loaded(), log.compiles, log.hits
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    record = Run(op, bits_per_int=s.index.bits_per_int())
    k = int(tr.get("k", 0))
    lists, freqs = s.lists, s.freqs
    res = Reservoir(rng_stream(seed, 3), int(tr["check_sample"]),
                    lambda q: sum(len(lists[t]) for t in q))
    waves = failover_sample = None
    if s.resilient is not None:
        failover_sample = Reservoir(rng_stream(seed, 4),
                                    int(tr["check_sample"]) // 4, res.weight)
        waves = Waves(s.resilient, keep=failover_sample,
                      at_s=[f["at_s"] for f in cell.faults if f["at_s"] < seconds])
    if tr["loop"] == "open":
        n = max(int(round(float(tr["rate_qps"]) * seconds)), 1)
        queries = traffic_mod.query_log(rw, tr, s.rank_to_list, n, 2)
        due = traffic_mod.poisson_due(rw, float(tr["rate_qps"]), n)
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            admit, answer, wait_s, results, t_close = open_loop(
                s, queries, due, seconds, waves=waves)
        answered = np.isfinite(answer)
        latency = np.where(answered, answer - due, t_close - due)
        record.due, record.admit, record.wait_s = due, admit, wait_s
        record.latency = latency
        record.n_queries = n
        attempted, failed = n, int((~answered).sum())
        e2e = {
            "p95_ms": nearest_rank(latency, 0.95) * 1e3,
            "qps": completed_rate(answer, seconds),
        }
        for i in range(n):
            if results[i] is not None:
                res(i, queries[i], (results[i].docs, results[i].scores))
        say("window", requests=n, answered=int(answered.sum()), seconds=seconds,
            qps=round(e2e["qps"], 3),
            t_close=round(t_close, 3), p50_ms=round(nearest_rank(latency, 0.5) * 1e3, 3),
            p95_ms=round(e2e["p95_ms"], 3),
            admit_lag_p95_ms=round(nearest_rank(admit - due, 0.95) * 1e3, 3))
    else:
        log_q = traffic_mod.query_log(rw, tr, s.rank_to_list,
                                      int(tr["log_queries"]), 2)
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            served, window_s = closed_loop(
                s, log_q, seconds,
                lambda i, a: res(i, log_q[i % len(log_q)], a), waves=waves)
        record.n_queries = attempted = served
        failed = 0
        e2e = {"qps": served / window_s}
        say("window", queries=served, seconds=round(window_s, 3),
            qps=round(e2e["qps"], 3), replays=served // len(log_q))
    if traced:
        jax.profiler.stop_trace()
    say("compiles_in_window", loaded=log.loaded() - loaded0,
        from_cache=log.hits - hits0,
        compiled=(log.compiles - compiles0) - (log.hits - hits0))
    if waves is not None:
        record.failover = window_faults(waves, cell)
    e2e["device_bits_per_posting"] = bits
    e2e["build_mpostings_s"] = s.postings / 1e6 / (
        phases["build_s"] + phases["upload_s"])
    e2e["setup_s"] = setup_s
    stats1 = dict(getattr(s.engine, "stats", {}))
    record.stats = {key: stats1[key] - stats0.get(key, 0) for key in stats1
                    if isinstance(stats1[key], (int, float))}
    record.counters = {"score_cache_hit": hits.value - c0[0],
                       "score_cache_miss": miss.value - c0[1]}

    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    mem = [m for m in mem if m is not None]
    if len(mem) > 1:
        say("memory", peak_bytes_per_chip=",".join(str(m) for m in mem))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": max(mem) if mem else 0}
    sample = res.sample()
    failover = None if waves is None else dict(
        sample=failover_sample.sample(), **record.failover)
    del s, waves  # drop the system's state before the reference runs
    gc.collect()

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.check"):
        checks = check(cell, lists, freqs, sample, k, failed, failover)
    extra = {} if failover is None else {"failover_sampled": len(failover["sample"])}
    say("check", sampled=len(sample), **extra,
        check_s=round(time.perf_counter() - t0, 3))

    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values())
           and len(sample) > 0
           and (failover is None or len(failover["sample"]) > 0),
           "attempted": int(attempted), "failed": int(failed)}
    if traced:
        devices, host = trace_mod.load(trace_dir)
        record.trace = trace_mod.reduce(devices, host)
        device["busy_s"] = record.trace.busy_s
        device["window_s"] = record.trace.window_s
        metrics = {}
        for m in cell.per_layer:
            v = spec.load_reader(root, m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": record.trace.top_ops(),
                            "idle_gaps": record.trace.top_gaps()}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        out["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    out["checks"] = checks
    for name, c in checks.items():
        print(f"[limit] {name}={c['value']} limit={c['limit']}", file=sys.stderr)
    return out


def check(cell: spec.Cell, lists, freqs, sample: dict, k: int, failed: int,
          failover: dict | None = None) -> dict:
    """The numbers ``correct`` is decided on, each beside its limit.  Where
    the traffic kills shards, ``failover`` is the window's failover report
    with a ``sample`` of the answers of waves served with a shard failed
    or dead; those answers, the degraded ones and the shards left
    unrecovered are compared too."""
    if cell.traffic["operation"] == "topk":
        bm = cell.config["scoring"]
        ref = reference.BM25(lists, freqs, k1=bm["k1"], b=bm["b"],
                             levels=bm["norm_levels"])

        def is_wrong(q, got):
            return not reference.same_topk(got, ref.topk(q, k))
    else:
        def is_wrong(q, got):
            return not reference.same_and(got, reference.intersect(lists, q))

    wrong = sum(is_wrong(q, got) for q, got in sample.values())
    out = {
        "wrong_answers": {"value": wrong, "limit": 0},
        "unanswered": {"value": int(failed), "limit": 0},
    }
    if failover is not None:
        out["failover_wrong_answers"] = {
            "value": sum(is_wrong(q, got) for q, got in failover["sample"].values()),
            "limit": 0}
        out["degraded_answers"] = {"value": failover["degraded_answers"],
                                   "limit": 0}
        out["unrecovered_shards"] = {"value": failover["unrecovered_shards"],
                                     "limit": 0}
    return out
