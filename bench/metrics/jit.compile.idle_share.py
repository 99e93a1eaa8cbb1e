"""Idle device time while JAX lowered, compiled or loaded from its cache a
program inside the traced window, % of the window: the idle pieces under
its compile events and under each jitted call that lowered a program
(``bench/harness/program_trace.py``, ``program_spans``), a compile counting
as the innermost span.  0 in a window that loaded no program; None where
the program opened no ``repro.`` span to read."""

from harness import program_trace


def read(run):
    g = program_trace.for_run(run)
    return None if g is None else g.share([program_trace.COMPILE], absent=0.0)
