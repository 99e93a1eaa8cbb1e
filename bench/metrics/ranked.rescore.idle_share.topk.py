"""Idle device time while the ranked engine rescored its candidates (two
rounds: theta raise, then the docs whose bound clears it), % of the traced
window: the idle pieces under ``repro.rescore``, inclusive
(``bench/harness/program_trace.py``).  Read in the cells whose operation is
``topk``."""

from harness import program_trace


def read(run):
    if run.operation != "topk":
        return None
    g = program_trace.for_run(run)
    return None if g is None else g.share(["repro.rescore"])
