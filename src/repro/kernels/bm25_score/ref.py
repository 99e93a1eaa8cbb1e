"""Pure-jnp oracle for the fused BM25 scoring kernels (DESIGN.md §5)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.bm25_score.kernel import div_rn
from repro.kernels.vbyte_decode.ref import decode_blocks_ref


def score_rows_ref(flens, fdata, norms, idf_rows, table, k1p1):
    """All-lane BM25 scores of gathered freq rows, float32 contract order.

    flens: [nr, 128] int32; fdata: [nr, 512] uint8 (tf - 1 blocks); norms:
    [nr, 128] int32 codes; idf_rows: [nr] float32; table: [256] float32
    norm dequant table; k1p1: float32 scalar.  Returns [nr, 128] float32
    (padding lanes garbage).  The norm is GATHERED from the table, never
    recomputed -- see ``repro.ranked.bm25.norm_table`` -- and the quotient
    is ``div_rn``'s correctly rounded one on every platform.
    """
    tf = (decode_blocks_ref(flens, fdata) + 1).astype(jnp.float32)
    k_hat = table[norms]
    return idf_rows[:, None] * div_rn(tf * k1p1, tf + k_hat)


def score_probe_ref(
    lens, data, flens, fdata, norms, bases, probes, idf_rows, table, k1p1
):
    """jnp oracle of ``bm25_score_probe_blocks``: per-row contribution of the
    lane whose docID equals the probe (0.0 when absent)."""
    gaps = decode_blocks_ref(lens, data)
    vals = bases[:, None] + jnp.cumsum(gaps + 1, axis=1)
    scores = score_rows_ref(flens, fdata, norms, idf_rows, table, k1p1)
    return jnp.sum(
        jnp.where(vals == probes[:, None], scores, jnp.float32(0.0)), axis=1
    )
