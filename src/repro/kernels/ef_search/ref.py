"""Pure-jnp oracle for the fused Elias-Fano NextGEQ kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ef_search.kernel import _I32_MAX, EF_HI_BITS, EF_HI_WORDS
from repro.kernels.vbyte_decode.kernel import BLOCK_VALS


def _ef_search_rows(lo, hi_words, lbits, base, probe):
    """[nr,128] i32 lows + [nr,24] i32 high words + [nr,1] i32 scalars
    -> [nr,1] (value, rank), derived over a [nr, 24, 16] bit cube (the
    kernel derives the same counts over 16 lane-aligned bit planes)."""
    rows = lo.shape[0]
    shift = jax.lax.broadcasted_iota(jnp.int32, (rows, EF_HI_WORDS, 16), 2)
    # the inclusive one counts over the 384-bit stream, built
    # hierarchically -- a length-16 scan within each word plus a length-24
    # word-prefix scan -- instead of one length-384 scan, and kept in
    # int8/int16 (the [BM,24,16] intermediates dominate memory traffic on
    # big cursor waves; every count fits: inner <= 16, oc <= 128).  The
    # zero counts are never materialized: zc_j = j+1 - oc_j, so
    # ``zc_j <= b``  <=>  ``oc_j >= j+1-b``.
    bits = ((hi_words[:, :, None] >> shift) & 1).astype(jnp.int8)
    inner_oc = jnp.cumsum(bits, axis=2)  # within-word one counts
    wo = inner_oc[:, :, 15:16].astype(jnp.int16)  # ones per word
    oc = jnp.cumsum(wo, axis=1) - wo + inner_oc  # inclusive one counts
    pos1 = (
        jax.lax.broadcasted_iota(jnp.int16, (rows, EF_HI_WORDS, 16), 1) * 16
        + shift.astype(jnp.int16) + 1
    )  # j + 1 over the flat 384-bit stream
    rp = jnp.clip(probe - base - 1, 0, None)  # rebased probe, >= 0
    hp = rp >> lbits
    lp = rp & ((1 << lbits) - 1)
    # hp clamps to 384 before the int16 narrowing: zc <= 256, so every
    # b >= 256 already counts all 384 positions -- identical sums, and the
    # hp > 255 rows are overridden by ``big`` below anyway
    hp3 = jnp.minimum(hp, EF_HI_BITS)[:, :, None].astype(jnp.int16)
    # count_lt = #lanes with high < hp; count_le = #lanes with high <= hp
    z_lt = jnp.sum(oc >= pos1 - (hp3 - 1), axis=(1, 2), dtype=jnp.int32)[:, None]
    z_le = jnp.sum(oc >= pos1 - hp3, axis=(1, 2), dtype=jnp.int32)[:, None]
    big = hp > 255  # beyond the tile's high range: every lane is below
    count_lt = jnp.where(hp <= 0, 0, z_lt - (hp - 1))
    count_lt = jnp.where(big, BLOCK_VALS, count_lt)
    count_le = jnp.where(big, BLOCK_VALS, z_le - hp)
    lane = jax.lax.broadcasted_iota(jnp.int32, lo.shape, 1)
    mid = jnp.sum(
        ((lane >= count_lt) & (lane < count_le) & (lo < lp)).astype(
            jnp.int32
        ),
        axis=1,
        keepdims=True,
    )
    rank = jnp.where(big, BLOCK_VALS, count_lt + mid)
    rc = jnp.minimum(rank, BLOCK_VALS - 1)
    sel = jnp.sum(
        oc <= rc[:, :, None].astype(jnp.int16), axis=(1, 2), dtype=jnp.int32
    )[:, None]
    high_r = sel - rc
    low_r = jnp.sum(jnp.where(lane == rc, lo, 0), axis=1, keepdims=True)
    value = base + 1 + ((high_r << lbits) | low_r)
    value = jnp.where(rank >= BLOCK_VALS, _I32_MAX, value)
    return value, rank


def ef_search_ref(lo_rows, hi_rows, lbits_rows, bases, probes):
    """jnp oracle of the fused EF NextGEQ kernel (DESIGN.md §14).

    lo_rows: [nr, 128] int32 low bits; hi_rows: [nr, 24] int32 16-bit
    high-stream words; lbits_rows / bases / probes: [nr] int32 -- gathered
    EF tiles, one per cursor.  Returns (value [nr] int32, rank [nr]
    int32): the smallest in-block value >= probe (2^31-1 if none) and the
    count of block values < probe -- ``decode_search_ref``'s contract.
    """
    value, rank = _ef_search_rows(
        lo_rows.astype(jnp.int32),
        hi_rows.astype(jnp.int32),
        lbits_rows.astype(jnp.int32)[:, None],
        bases.astype(jnp.int32)[:, None],
        probes.astype(jnp.int32)[:, None],
    )
    return value[:, 0], rank[:, 0]
