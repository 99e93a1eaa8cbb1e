"""Idle device time while the boolean engine gathered each query's seed
candidates (its shortest list, from the host mirror), % of the traced
window: the idle pieces under ``repro.gather``
(``bench/harness/program_trace.py``).  Read in the cells whose operation is
``and``."""

from harness import program_trace


def read(run):
    if run.operation != "and":
        return None
    g = program_trace.for_run(run)
    return None if g is None else g.share(["repro.gather"])
