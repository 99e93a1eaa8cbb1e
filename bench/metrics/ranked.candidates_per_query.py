"""Candidate documents the ranked engine re-scored per query over the
window (its ``candidates`` counter over the queries served)."""


def read(run):
    if run.operation != "topk" or not run.n_queries or "candidates" not in run.stats:
        return None
    return run.stats["candidates"] / run.n_queries
