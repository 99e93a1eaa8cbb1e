"""Summed device time of the ``pivot_select_blocks`` kernel (the block-max
pivot selection, alone or as the first half of the fused pivot-and-score
round) over the traced window, % of the window (all chips used, over their
number).  Read in the cells whose operation is ``topk``."""

KERNELS = ("pivot_select_blocks",)


def read(run):
    if run.trace is None or run.operation != "topk" or run.trace.window_s <= 0:
        return None
    s = run.trace.kernel_s(KERNELS)
    if s <= 0:
        return None
    return 100.0 * s / run.trace.n_devices / run.trace.window_s
