"""Summed device time of ``bm25_score_blocks``, which scores the ranked
engine's rows on their shards' chips, over the traced window, % of the
window (all chips used, over their number).  Read in the cells whose
operation is ``topk``."""

KERNELS = ("bm25_score_blocks",)


def read(run):
    if run.trace is None or run.operation != "topk" or run.trace.window_s <= 0:
        return None
    s = run.trace.kernel_s(KERNELS)
    if s <= 0:
        return None
    return 100.0 * s / run.trace.n_devices / run.trace.window_s
