"""Idle device time while the ranked engine scored rows on their shards'
chips, % of the traced window: the idle pieces under ``repro.shard_score``
(routing each row to a live owner shard, one ``shard_map`` dispatch of
``bm25_score_blocks``, the merge), inclusive
(``bench/harness/program_trace.py``).  Read in the cells whose operation is
``topk``."""

from harness import program_trace


def read(run):
    if run.operation != "topk":
        return None
    g = program_trace.for_run(run)
    return None if g is None else g.share(["repro.shard_score"])
