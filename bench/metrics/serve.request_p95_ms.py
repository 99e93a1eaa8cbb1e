"""95th percentile (nearest rank) of request latency, ms, over every
request due in the window, timed from when it was due to its answer (open
loop).  The client's tail; it has no bound while the ranked engine still
traces and compiles programs for new bucket shapes inside the window."""

from harness.cell import nearest_rank


def read(run):
    if run.latency is None or not len(run.latency):
        return None
    return nearest_rank(run.latency, 0.95) * 1e3
