"""Idle device time while the boolean engine filtered candidates by
membership, term by term, % of the traced window: the idle pieces under
``repro.member_filter``, inclusive of staging, dispatch and fetch inside it
(``bench/harness/program_trace.py``).  Read in the cells whose operation is
``and``."""

from harness import program_trace


def read(run):
    if run.operation != "and":
        return None
    g = program_trace.for_run(run)
    return None if g is None else g.share(["repro.member_filter"])
