"""Idle device time in the boolean engine's host staging, % of the traced
window: the idle pieces under ``repro.group_cursors`` (duplicate cursors
grouped), ``repro.codec_split`` (the host searchsorted that buckets
cursors by codec) and ``repro.stage`` (cursors padded to their bucket)
(``bench/harness/program_trace.py``).  Read in the cells whose operation is
``and``."""

from harness import program_trace

SPANS = ("repro.group_cursors", "repro.codec_split", "repro.stage")


def read(run):
    if run.operation != "and":
        return None
    g = program_trace.for_run(run)
    return None if g is None else g.share(SPANS)
