"""Continuous-batching async top-k serving loop (DESIGN.md §13).

One asyncio task (``serve_forever``) alternates between two states:

* **forming** -- wait until the :class:`BatchFormer` says a wave should
  fire (full batch, linger timeout, or an imminent deadline), admitting
  requests the whole time;
* **serving** -- pop the wave, pad it to its pow2 bucket with empty
  queries (trace-shape reuse across waves), and run ONE
  ``TopKEngine.topk_batch`` call.  Admission continues while the engine
  runs -- the next wave forms from everything that arrived meanwhile,
  which is what makes the loop *continuous* batching rather than
  fixed-size batching.

Backpressure: the queue is bounded.  ``submit`` AWAITS space (the
caller's send loop slows to the service rate -- closed-loop clients
self-throttle), ``try_submit`` raises :class:`QueueFull` instead (open-
loop producers shed).  Both outcomes are counted.

Every wave publishes through ``repro.obs`` (armed or not -- the gauges
are cheap): queue depth, wave occupancy, wave latency, per-request
end-to-end latency, deadline misses.  Metric names and units:
docs/metrics.md.  Operator tuning: docs/serving.md.
"""

from __future__ import annotations

import asyncio
import math
import time
from dataclasses import dataclass

import numpy as np

from repro import obs
from repro.distributed.resilient import ServeInfo
from repro.serving.batcher import BatchFormer


class QueueFull(RuntimeError):
    """try_submit refused: the request queue is at max_queue."""


@dataclass
class ServeResult:
    """One request's outcome.  ``expired`` results carry empty doc/score
    arrays: the deadline passed before a wave served the request, so the
    engine never ran for it."""

    docs: np.ndarray
    scores: np.ndarray
    expired: bool
    wait_s: float     # admission -> wave formation
    service_s: float  # wave formation -> result (0.0 when expired)
    # served from live lists only: the wave's ``ServeInfo.degraded``
    degraded: bool = False

    @property
    def latency_s(self) -> float:
        return self.wait_s + self.service_s


_EMPTY = (np.zeros(0, np.int64), np.zeros(0, np.float64))


class AsyncTopKServer:
    """Continuous-batching front for a ``TopKEngine``, or for a
    ``ResilientEngine`` over one: its ``(results, ServeInfo)`` pair is taken
    apart, waves with a failed shard count as ``failover_waves`` and
    degraded ones as ``degraded_waves``, and a degraded wave's results are
    marked ``degraded``.

    Parameters mirror the ``launch.serve --loop`` flags (docs/serving.md):
    ``max_batch`` wave cap, ``max_queue`` backpressure bound,
    ``max_delay_s`` linger, ``default_deadline_s`` per-request SLO
    (math.inf = none).  ``clock`` is injectable for tests."""

    def __init__(
        self,
        engine,
        k: int = 10,
        max_batch: int = 64,
        max_queue: int = 1_024,
        max_delay_s: float = 2e-3,
        default_deadline_s: float = math.inf,
        pad_waves: bool = True,
        clock=time.monotonic,
    ):
        self.engine = engine
        self.k = int(k)
        self.former = BatchFormer(
            max_batch=max_batch, max_queue=max_queue, max_delay_s=max_delay_s
        )
        self.default_deadline_s = float(default_deadline_s)
        self.pad_waves = bool(pad_waves)
        self.clock = clock
        self.stats = {
            "served": 0,
            "expired": 0,
            "late": 0,
            "shed": 0,
            "backpressure_waits": 0,
            "padded_queries": 0,
            "failover_waves": 0,
            "degraded_waves": 0,
        }
        self._wake = asyncio.Event()
        self._space = asyncio.Event()
        self._space.set()
        self._closed = False
        self._task: asyncio.Task | None = None

    # ---- client side ------------------------------------------------
    def _admit(self, query, deadline_s: float | None):
        now = self.clock()
        ttl = self.default_deadline_s if deadline_s is None else deadline_s
        fut = asyncio.get_running_loop().create_future()
        req = self.former.push(
            list(query), now, deadline=now + ttl, payload=fut
        )
        if req is not None:
            self._wake.set()
        return req, fut

    async def submit(self, query, deadline_s: float | None = None):
        """Admit one query and await its :class:`ServeResult`.  When the
        queue is full, WAIT for space (backpressure: the submitter runs
        at the service rate)."""
        while True:
            req, fut = self._admit(query, deadline_s)
            if req is not None:
                return await fut
            self.stats["backpressure_waits"] += 1
            obs.count("serve_backpressure_waits")
            self._space.clear()
            await self._space.wait()

    async def try_submit(self, query, deadline_s: float | None = None):
        """Admit or raise :class:`QueueFull` (open-loop shedding)."""
        req, fut = self._admit(query, deadline_s)
        if req is None:
            self.stats["shed"] += 1
            obs.count("serve_requests", kind="shed")
            raise QueueFull(f"queue at max_queue={self.former.max_queue}")
        return await fut

    # ---- serving loop -----------------------------------------------
    def _resolve(self, req, result: ServeResult) -> None:
        fut = req.payload
        if not fut.done():
            fut.set_result(result)
        obs.observe("serve_request_ms", result.latency_s * 1e3)
        obs.count(
            "serve_requests", kind="expired" if result.expired else "done"
        )

    def _run_wave(self) -> bool:
        """Form and serve one wave; False when the queue was idle."""
        with obs.span("serve.wave"):
            t_form = self.clock()
            batch, expired, bucket = self.former.take(t_form)
            if self.former.depth < self.former.max_queue:
                self._space.set()
            for req in expired:
                self.stats["expired"] += 1
                obs.count("serve_deadline_misses", kind="expired")
                self._resolve(req, ServeResult(
                    *_EMPTY, expired=True,
                    wait_s=t_form - req.enqueued, service_s=0.0,
                ))
            if not batch:
                return False
            queries = [req.query for req in batch]
            if self.pad_waves and bucket > len(batch):
                self.stats["padded_queries"] += bucket - len(batch)
                queries += [[] for _ in range(bucket - len(batch))]
            obs.observe("serve_wave_occupancy", len(batch) / max(bucket, 1))
            with obs.timer("serve_wave_ms", engine="topk"):
                outs = self.engine.topk_batch(queries, self.k)
            t_done = self.clock()
            degraded = False
            if isinstance(outs, tuple) and isinstance(outs[1], ServeInfo):
                outs, info = outs
                degraded = bool(info.degraded)
                self.stats["failover_waves"] += bool(info.failed_shards)
                self.stats["degraded_waves"] += degraded
            for req, (docs, scores) in zip(batch, outs):
                self.stats["served"] += 1
                if req.deadline < t_done:
                    self.stats["late"] += 1
                    obs.count("serve_deadline_misses", kind="late")
                self._resolve(req, ServeResult(
                    docs, scores, expired=False,
                    wait_s=t_form - req.enqueued,
                    service_s=t_done - t_form, degraded=degraded,
                ))
            obs.set_gauge("serve_queue_depth", self.former.depth)
            return True

    async def serve_forever(self) -> None:
        """Run waves until :meth:`close`.  Between waves the loop yields
        to admissions; idle it sleeps on the wake event."""
        while not self._closed:
            now = self.clock()
            if self.former.ready(now):
                self._run_wave()
                await asyncio.sleep(0)  # let submitters enqueue/resolve
                continue
            linger = self.former.linger_remaining(now)
            self._wake.clear()
            if self.former.depth:
                # half-formed wave: sleep out the linger window, but wake
                # early if admissions could complete the batch
                try:
                    await asyncio.wait_for(self._wake.wait(), timeout=linger)
                except asyncio.TimeoutError:
                    pass
            else:
                obs.set_gauge("serve_queue_depth", 0)
                await self._wake.wait()

    # ---- lifecycle --------------------------------------------------
    async def __aenter__(self):
        self._task = asyncio.ensure_future(self.serve_forever())
        return self

    async def __aexit__(self, *exc) -> None:
        await self.close()

    async def drain(self) -> None:
        """Serve until the queue is empty (pending futures resolved).
        Fires waves immediately -- draining does not honor the linger."""
        while self.former.depth:
            self._run_wave()
            await asyncio.sleep(0)

    async def close(self) -> None:
        """Drain outstanding requests, then stop ``serve_forever``."""
        await self.drain()
        self._closed = True
        self._wake.set()
        if self._task is not None:
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
