"""Summed device time of the ``vbyte_decode`` kernel family's events over the
traced window, % of the window (all chips used, over their number).
Read in the cells whose operation is ``and``."""

KERNELS = ("decode_blocks", "decode_search_blocks")


def read(run):
    if run.trace is None or run.operation != "and" or run.trace.window_s <= 0:
        return None
    s = run.trace.kernel_s(KERNELS)
    if s <= 0:
        return None
    return 100.0 * s / run.trace.n_devices / run.trace.window_s
