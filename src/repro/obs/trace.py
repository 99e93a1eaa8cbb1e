"""Span tracing: nested context managers over a ring-buffered trace log.

``span("decode_search")`` is the workhorse: when the layer is armed it
records a {name, start, wall duration, nesting depth, thread} event into
a bounded ring and observes the duration into the ``span_ms`` histogram
(labelled by span name).  When disarmed, ``span()`` returns a shared
no-op singleton -- no allocation, no clock read, no lock.

An armed span is also a host span on the ``jax.profiler`` timeline,
named ``repro.<name>``: a profiler trace of an armed run shows the
program's phases on the same clock as the device's operations.  Spans
never wait on the device, so instrumentation adds no host sync (the
sync_audit ratchet stays flat).

``now()`` is the sanctioned raw clock for code that needs a timestamp
across scopes; the ``obs-timers`` idiom-lint rule steers the rest of
``src/repro`` here instead of bare ``time.perf_counter()``.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque

from . import metrics as _m

__all__ = [
    "NULL_SPAN",
    "Span",
    "Timer",
    "clear",
    "event",
    "events",
    "now",
    "span",
    "timer",
]

TRACE_CAPACITY = 4096
_RING: deque = deque(maxlen=TRACE_CAPACITY)
_EPOCH = time.perf_counter()
_TLS = threading.local()
_TRACE_ANNOTATION = None  # jax.profiler.TraceAnnotation, imported on first use


def now() -> float:
    """Monotonic wall clock (seconds); the lint-blessed perf_counter alias."""
    return time.perf_counter()


def events() -> list:
    """Snapshot of the trace ring, oldest first."""
    return list(_RING)


def clear() -> None:
    _RING.clear()


def event(name: str, **fields) -> None:
    """Record a discrete event (health transition, failover, ...) iff armed."""
    if _m.enabled():
        rec = {"kind": "event", "name": name, "t_s": now() - _EPOCH}
        rec.update(fields)
        _RING.append(rec)


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named ``repro.<name>`` (a plain
    no-op context where jax is not importable); jax is imported on the
    first armed span, once."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = lambda _name: contextlib.nullcontext()  # noqa: E731
        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION("repro." + name)


class Span:
    """Armed span: wall time into the ring and ``span_ms``, and a profiler
    host span over the same stretch."""

    __slots__ = ("name", "labels", "_t0", "_depth", "_annot")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = labels

    def __enter__(self):
        depth = getattr(_TLS, "depth", 0)
        _TLS.depth = depth + 1
        self._depth = depth
        self._annot = _annotation(self.name)
        self._annot.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self._annot.__exit__(exc_type, exc, tb)
        _TLS.depth = self._depth
        dur_ms = (t1 - self._t0) * 1e3
        rec = {
            "kind": "span",
            "name": self.name,
            "start_s": self._t0 - _EPOCH,
            "dur_ms": dur_ms,
            "depth": self._depth,
            "thread": threading.current_thread().name,
        }
        if self.labels:
            rec.update(self.labels)
        _RING.append(rec)
        labels = {"span": self.name, **self.labels}
        _m.REGISTRY.histogram("span_ms", **labels).observe(dur_ms)
        return False


class _NullSpan:
    """Disarmed singleton: every method is a constant no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NULL_SPAN = _NullSpan()


def span(name: str, **labels):
    """Open a trace span; returns the shared no-op singleton when disarmed."""
    if _m.enabled():
        return Span(name, labels)
    return NULL_SPAN


class Timer:
    """Always measures wall time (``.elapsed_s``); records the sample into
    the registry histogram only when the layer is armed.  For call sites
    that need the elapsed time regardless (serve.py latency lines)."""

    __slots__ = ("name", "labels", "elapsed_s", "_t0")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = labels
        self.elapsed_s = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.elapsed_s = time.perf_counter() - self._t0
        if _m.enabled():
            _m.REGISTRY.histogram(self.name, **self.labels).observe(
                self.elapsed_s * 1e3
            )
        return False


def timer(name: str, **labels) -> Timer:
    """Wall-clock timer; histogram names take a ``_ms`` suffix by convention."""
    return Timer(name, labels)
