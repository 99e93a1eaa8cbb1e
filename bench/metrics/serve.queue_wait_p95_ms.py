"""95th percentile of the server's own wait, admission to wave formation,
ms (``ServeResult.wait_s``): the time a request spends in the batch
former."""

import numpy as np

from harness.cell import nearest_rank


def read(run):
    if run.wait_s is None:
        return None
    w = run.wait_s[np.isfinite(run.wait_s)]
    return nearest_rank(w, 0.95) * 1e3 if len(w) else None
