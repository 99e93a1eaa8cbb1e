"""Share of the traced window in which no operation ran on the device, %
(1 - busy / window, busy the union of device-op intervals; mean over the
chips used).  Read in the cells whose operation is ``topk``."""


def read(run):
    if run.trace is None or run.operation != "topk" or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
