"""Idle device time by the program phase the host was in.

An armed ``repro.obs`` span is also a host span named ``repro.<name>`` on
the profiler's timeline.  This pass reads the trace of a traced run and
splits every stretch of the window in which the device ran nothing at each
boundary of those spans and of JAX's own compile events (counted together
as ``jit.compile``).  Each piece goes to the innermost span open over it
(``self``) and to that span and every span enclosing it (``inclusive``);
a compile event counts as the innermost span while it is open.  No
midpoint rule: in the top-10 cell one idle stretch lasts a whole wave, and
the phases inside it are what the pass is for.

It reads the host events of the thread that opened ``bench.window`` only,
and leaves ``trace.py``'s reduction and what it reports as they are.  A
metric reader finds the trace of its own run with ``for_run``: a traced
run writes one trace under a ``bench-trace-`` directory in the temporary
directory and removes it once the readers have run.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
import tempfile
from dataclasses import dataclass, field

from harness.trace import OP_LINES, WINDOW, Interval, merge, op_name

PREFIX = "repro."
COMPILE = "jit.compile"
NO_SPAN = "program.none"
# JAX's events on the calling thread while it lowers a program, compiles it
# or loads it from the persistent cache.  A TPU v5e trace names lowering
# (``lower_sharding_computation``) and compiling (``backend_compile_and_load``)
# but not the cache's read and write: those show only as the rest of the
# jitted call that lowered, so a ``PjitFunction(...)`` call that holds a
# lowering counts whole.
LOWER = "lower_sharding_computation"
JIT_CALL = "PjitFunction("
COMPILE_EVENT = re.compile(LOWER + r"|compil|deserializ", re.IGNORECASE)
TRACE_PREFIX = "bench-trace-"


@dataclass
class ProgramGaps:
    window_s: float
    self_s: dict = field(default_factory=dict)   # span -> idle s, innermost
    incl_s: dict = field(default_factory=dict)   # span -> idle s, enclosing too
    seen: set = field(default_factory=set)       # span names in the window
    n_devices: int = 1

    def share(self, names, absent=None):
        """Idle seconds inclusive under any of ``names``, % of the window
        (mean over devices); ``absent`` where none of them ran in it."""
        if not self.seen.intersection(names):
            return absent
        return 100.0 * sum(self.incl_s.get(n, 0.0) for n in names) / self.window_s

    def top_self(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.self_s.items()),
                      key=lambda kv: -kv[1])[:n]


def is_compile(name: str) -> bool:
    return COMPILE_EVENT.search(name) is not None


def program_spans(events: list[Interval]) -> list[Interval]:
    """The ``repro.*`` spans among one thread's host events, and its compile
    events as ``jit.compile``: the events ``is_compile`` names, and each
    jitted call that lowered a program inside it."""
    lowers = sorted(ev.start for ev in events if ev.name == LOWER)
    out = []
    for ev in events:
        if ev.name.startswith(PREFIX):
            out.append(ev)
        elif is_compile(ev.name) or (
                ev.name.startswith(JIT_CALL)
                and bisect.bisect_left(lowers, ev.start)
                < bisect.bisect_right(lowers, ev.end)):
            out.append(Interval(ev.start, ev.end, COMPILE))
    return out


def depths(spans: list[Interval]) -> list[float]:
    """Nesting depth of each span of one thread (spans sorted by start, the
    enclosing one first); a compile event is innermost while it is open."""
    out, stack = [], []
    for sp in spans:
        while stack and stack[-1] <= sp.start:
            stack.pop()
        out.append(float("inf") if sp.name == COMPILE else float(len(stack)))
        if sp.name != COMPILE:
            stack.append(sp.end)
    return out


def _idle_before(idle: list[tuple], starts: list[float], cum: list[float],
                 t: float) -> float:
    """Idle seconds of one device in [window start, t]."""
    i = bisect.bisect_right(starts, t) - 1
    if i < 0:
        return 0.0
    s, e = idle[i]
    return cum[i] + min(t, e) - s


def attribute(devices: list[list[Interval]], spans: list[Interval], lo: float,
              hi: float) -> ProgramGaps:
    """Idle time of the devices over [lo, hi] by the program spans that were
    open, piece by piece (see the module's docstring); the mean over the
    devices.  ``spans`` are one thread's, so they nest."""
    spans = sorted((s for s in spans if s.end > lo and s.start < hi),
                   key=lambda s: (s.start, -s.end))
    depth = depths(spans)
    curves = []
    for events in devices:
        busy = merge(events, lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        cum, acc = [], 0.0
        for s, e in idle:
            cum.append(acc)
            acc += e - s
        curves.append((idle, [s for s, _ in idle], cum))
    out = ProgramGaps(hi - lo, seen={s.name for s in spans},
                      n_devices=len(devices))
    if not curves:
        return out
    bounds = []
    for i, sp in enumerate(spans):
        bounds.append((max(sp.start, lo), 1, i))
        bounds.append((min(sp.end, hi), 0, i))
    bounds.sort()
    bounds.append((hi, 0, -1))
    active: set = set()
    t0 = lo
    for t, opens, i in bounds:
        if t > t0:
            idle = sum(_idle_before(*c, t) - _idle_before(*c, t0)
                       for c in curves) / len(curves)
            if idle > 0:
                if active:
                    inner = max(active, key=lambda j: (depth[j], spans[j].start))
                    who = spans[inner].name
                    for name in {spans[j].name for j in active}:
                        out.incl_s[name] = out.incl_s.get(name, 0.0) + idle
                else:
                    who = NO_SPAN
                out.self_s[who] = out.self_s.get(who, 0.0) + idle
            t0 = t
        if i < 0:
            break
        if opens:
            active.add(i)
        else:
            active.discard(i)
    return out


def load(path: str):
    """(per-device op events, (window start, end), program and compile
    spans of the window's thread) of one trace file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, window, spans = [], None, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[n] for n in OP_LINES if n in lines), None)
            if line is not None:
                devices.append([
                    Interval(ev.start_ns * 1e-9, ev.end_ns * 1e-9, op_name(ev.name))
                    for ev in line.events
                ])
        elif plane.name.startswith("/host:") and window is None:
            for line in plane.lines:
                events = list(line.events)
                win = next((ev for ev in events if ev.name == WINDOW), None)
                if win is None:
                    continue
                window = (win.start_ns * 1e-9, win.end_ns * 1e-9)
                spans = program_spans([
                    Interval(ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                    for ev in events
                ])
                break
    return devices, window, spans


_LAST: dict = {}  # the trace file read last: (path, mtime, size) -> ProgramGaps


def of_file(path: str) -> ProgramGaps | None:
    """The attribution of one trace file (None: it holds no window).  Every
    reader of a run asks for the same file, so it is read once; its largest
    self entries go to standard error, beside ``breakdown.idle_gaps``."""
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _LAST:
        devices, window, spans = load(path)
        got = None if window is None else attribute(devices, spans, *window)
        _LAST.clear()
        _LAST[key] = got
        if got is not None and got.n_devices:
            print("[program_gaps] " + " ".join(
                f"{k}={v:.6f}" for k, v in got.top_self()), file=sys.stderr,
                flush=True)
    return _LAST[key]


def for_run(run) -> ProgramGaps | None:
    """The attribution of the run's own trace: the newest trace under the
    temporary directory whose window is the one ``run.trace`` measured.
    None where the run was not traced, no device ran, or the program
    opened no ``repro.`` span (it predates them, or it was not armed)."""
    if run.trace is None:
        return None
    pattern = os.path.join(tempfile.gettempdir(), TRACE_PREFIX + "*", "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        try:
            got = of_file(path)
        except (OSError, ValueError):
            continue
        if got is None or abs(got.window_s - run.trace.window_s) > 1e-6:
            continue
        if not got.n_devices or not any(n.startswith(PREFIX) for n in got.seen):
            return None
        return got
    return None
