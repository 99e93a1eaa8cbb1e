"""Reduction of a profiler trace to the benchmark's device numbers.

A traced run records one ``jax.profiler`` trace around its measured window.
The window itself is a host span named ``bench.window``; the harness puts
further ``bench.*`` spans around each call into a layer (wave formation,
``topk_batch``, ``intersect_batch``).  From the trace:

* busy time of a device: the union of the intervals in which an operation
  ran on it, clipped to the window; its idle share is 1 - busy / window;
* time of a kernel: the summed durations of the device events of that
  name inside the window;
* idle gaps: each stretch of the window in which the device ran nothing,
  named after the innermost ``bench.*`` host span that covers its middle
  (``host.none`` where no span does: the loop was waiting for requests).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

WINDOW = "bench.window"
NO_SPAN = "host.none"
# the device line that holds one event per operation the cores execute;
# "Async XLA Ops" (copy-start / copy-done spans of DMAs in flight) is not
# counted as busy
OP_LINES = ("XLA Ops",)


@dataclass
class Interval:
    start: float  # seconds, on the trace's clock
    end: float
    name: str


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                                   # mean over devices
    op_s: dict = field(default_factory=dict)        # op name -> s, all devices
    gap_s: dict = field(default_factory=dict)       # host span -> idle s
    n_devices: int = 1

    def kernel_s(self, names) -> float:
        """Summed time of the device ops with one of these base names
        (a trailing ``.<n>`` or ``_<n>`` numbering is ignored)."""
        want = set(names)
        return sum(s for op, s in self.op_s.items() if base_name(op) in want)

    def top_ops(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.op_s.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.gap_s.items()),
                      key=lambda kv: -kv[1])[:n]


def base_name(op: str) -> str:
    return re.sub(r"([._]\d+)+$", "", op)


def op_name(event_name: str) -> str:
    """An XLA op event is named by its HLO text (``%name.3 = f32[..]
    custom-call(...)``): keep the instruction's name (``name.3``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def merge(intervals: list[Interval], lo: float, hi: float) -> list[tuple]:
    """Union of the intervals clipped to [lo, hi], as sorted (start, end)."""
    out: list[list[float]] = []
    for iv in sorted(intervals, key=lambda i: i.start):
        s, e = max(iv.start, lo), min(iv.end, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def innermost(spans: list[Interval], starts: list[float], t: float) -> str:
    """Name of the latest-starting span that covers t; ``spans`` sorted by
    start, ``starts`` their starts.  The spans of one thread nest, so a
    short walk back from the last span started by t finds it."""
    i = bisect.bisect_right(starts, t) - 1
    for sp in spans[max(i - 16, 0): i + 1][::-1]:
        if sp.end >= t:
            return sp.name
    return NO_SPAN


def reduce(devices: list[list[Interval]], host: list[Interval]) -> TraceSummary:
    """Busy, per-op and idle-gap times of each device over the host span
    ``bench.window``."""
    windows = [h for h in host if h.name == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    lo, hi = windows[0].start, windows[0].end
    spans = sorted((h for h in host if h.name != WINDOW and h.end > lo
                    and h.start < hi), key=lambda h: h.start)
    starts = [h.start for h in spans]
    busy_total, ops, gaps = 0.0, {}, {}
    for events in devices:
        busy = merge(events, lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for ev in events:
            d = min(ev.end, hi) - max(ev.start, lo)
            if d > 0:
                ops[ev.name] = ops.get(ev.name, 0.0) + d
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                who = innermost(spans, starts, (s + e) / 2)
                gaps[who] = gaps.get(who, 0.0) + (e - s)
    n = max(len(devices), 1)
    return TraceSummary(hi - lo, busy_total / n, ops, gaps, len(devices))


def load(trace_dir: str) -> tuple[list[list[Interval]], list[Interval]]:
    """(per-device op events, host ``bench.*`` spans) of the one trace
    written under ``trace_dir``."""
    import jax

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace file under {trace_dir}, found {files}")
    data = jax.profiler.ProfileData.from_file(files[0])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[n] for n in OP_LINES if n in lines), None)
            if line is None:
                continue
            devices.append([
                Interval(ev.start_ns * 1e-9, ev.end_ns * 1e-9, op_name(ev.name))
                for ev in line.events
            ])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [
                    Interval(ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                    for ev in line.events if ev.name.startswith("bench.")
                ]
    return devices, host
