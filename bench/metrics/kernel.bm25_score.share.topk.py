"""Summed device time of the ``bm25_score`` kernels (``bm25_score_blocks``,
which also scores the kept slots of the fused pivot-and-score round, and
``bm25_score_probe_blocks``) over the traced window, % of the window (all
chips used, over their number).  Read in the cells whose operation is
``topk``."""

KERNELS = ("bm25_score_blocks", "bm25_score_probe_blocks")


def read(run):
    if run.trace is None or run.operation != "topk" or run.trace.window_s <= 0:
        return None
    s = run.trace.kernel_s(KERNELS)
    if s <= 0:
        return None
    return 100.0 * s / run.trace.n_devices / run.trace.window_s
