"""Idle device time in the ranked engine's membership pass (every (term,
doc) pair of a wave looked up in the host flat mirror with one
searchsorted, and the per-doc block-max bound), from seed and rescore
alike, % of the traced window: the idle pieces under ``repro.membership``
(``bench/harness/program_trace.py``).  Read in the cells whose operation is
``topk``."""

from harness import program_trace


def read(run):
    if run.operation != "topk":
        return None
    g = program_trace.for_run(run)
    return None if g is None else g.share(["repro.membership"])
