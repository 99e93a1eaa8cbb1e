"""95th percentile of admission time minus due time, ms (open loop).

A wave runs inside the serving loop's event loop, so requests that fall due
during a wave are admitted only when it ends: this is how long waves hold
admissions back."""

import numpy as np

from harness.cell import nearest_rank


def read(run):
    if run.due is None:
        return None
    lag = run.admit - run.due
    lag = lag[np.isfinite(lag)]
    return nearest_rank(lag, 0.95) * 1e3 if len(lag) else None
