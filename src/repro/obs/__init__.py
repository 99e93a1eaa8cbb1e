"""repro.obs -- unified metrics / tracing / profiling layer.

Off by default; arm with ``REPRO_OBS=1`` or ``obs.enable()``.  See
DESIGN.md §12 for the metric-naming contract and the no-sync invariant.

Quick tour::

    from repro import obs

    obs.enable()
    obs.count("engine_cache_hits", backend="ref")
    with obs.span("decode_search", path="ranked"):
        ...
    with obs.timer("serve_batch_ms") as t:
        ...
    print(t.elapsed_s, obs.histogram("serve_batch_ms").percentile(99))
    print(obs.render_prometheus())
"""

from .metrics import (
    REGISTRY,
    Counter,
    CounterDict,
    Gauge,
    Histogram,
    Registry,
    count,
    counter,
    enable,
    enabled,
    gauge,
    histogram,
    observe,
    set_gauge,
)
from .metrics import reset as _reset_metrics
from .trace import (
    NULL_SPAN,
    Span,
    Timer,
    event,
    events,
    now,
    span,
    timer,
)
from .trace import clear as clear_trace
from .export import diff, render_prometheus, snapshot, write_snapshot
from .server import MetricsServer

__all__ = [
    "REGISTRY",
    "Counter",
    "CounterDict",
    "Gauge",
    "Histogram",
    "MetricsServer",
    "NULL_SPAN",
    "Registry",
    "Span",
    "Timer",
    "clear_trace",
    "count",
    "counter",
    "diff",
    "enable",
    "enabled",
    "event",
    "events",
    "gauge",
    "histogram",
    "now",
    "observe",
    "render_prometheus",
    "reset",
    "set_gauge",
    "snapshot",
    "span",
    "timer",
    "write_snapshot",
]


def reset() -> None:
    """Drop all metrics and the trace ring (tests / benches)."""
    _reset_metrics()
    clear_trace()
