"""Idle device time while the serving thread evicted a killed shard's
buffers from its chip and installed its restored ones, % of the traced
window: the idle pieces under ``repro.resilient.evict`` and
``repro.resilient.admit``, inclusive (``bench/harness/program_trace.py``).
The restore and upload run on their own thread and are not counted.  Read
in the cells whose traffic kills shards."""

from harness import program_trace

SPANS = ("repro.resilient.evict", "repro.resilient.admit")


def read(run):
    if run.failover is None:
        return None
    g = program_trace.for_run(run)
    return None if g is None else g.share(SPANS)
