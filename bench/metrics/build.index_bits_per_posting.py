"""Bits per posting of the partitioned index as encoded on the host
(``bits_per_int()``): the source paper's space."""

import math


def read(run):
    return None if math.isnan(run.bits_per_int) else run.bits_per_int
