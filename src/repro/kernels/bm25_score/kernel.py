"""Pallas TPU kernels: fused freq decode + BM25 scoring (DESIGN.md §5).

Second kernel family over the block arena.  The ranked sidecar stores term
frequencies as a PARALLEL Stream-VByte block stream (``freq_lens`` /
``freq_data``, lane-aligned with the docID blocks) plus an 8-bit length-norm
code per lane, so scoring a block is: decode the freq tile with the same
one-hot-MXU-matmul trick as ``vbyte_decode`` (``_decode_tile`` is reused
verbatim), dequantize the norm code, and evaluate the float32 BM25 contract
of ``repro.ranked.bm25`` on the VPU:

    score = idf * (tf * (k1 + 1)) / (tf + K_hat)

with the quotient taken by ``div_rn``, exact where the chip's divide is
not.  The norm dequantization MUST be a GATHER from the 256-entry f32 table of
``repro.ranked.bm25.norm_table`` -- expressed as one-hot matmuls (``table
@ [code == c]``, one per tile row) so it runs on the MXU with no per-lane
control flow, and so the kernel reproduces the numpy contract BIT-EXACTLY.
The table is split into three bf16 parts (hi + mid + lo == table exactly),
each gathered by its own matmul and summed back in that order: every
product is exact and every output sums one nonzero product, so the gather
is exact whatever precision the MXU gives f32 operands.  Do NOT
"simplify" it into the arithmetic ``kmin + kstep * q`` form: in-graph that
mul+add gets FMA-contracted by XLA and drifts 1 ulp off the oracle,
breaking the cross-backend bit-identity the top-k engine relies on.

Two kernels:

  * ``bm25_score_blocks``       -- all 128 lane scores of gathered rows (the
    exhaustive / seeding path; callers mask padding lanes).
  * ``bm25_score_probe_blocks`` -- the WAND "check" op: ALSO decodes the
    docID tile, rebuilds absolute docIDs in-register, and emits per row only
    the contribution of the lane whose docID == probe (0.0 when the probe is
    absent).  Neither decoded postings nor per-lane scores touch HBM.

Per-row scalars ride int32 / float32 meta tiles (lanes named below), kept
128-wide for tiling like ``decode_search_blocks``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vbyte_decode.kernel import (
    BLOCK_BYTES,
    BLOCK_VALS,
    BM,
    META_BASE,
    META_PROBE,
    _decode_tile,
    lane_cumsum,
)

# float32 meta lanes (per gathered row)
FMETA_IDF = 0    # idf of the row's owning list
FMETA_K1P1 = 1   # k1 + 1

NORM_LEVELS = 256


def div_rn(num, den):
    """``num / den`` for positive normal f32 with a normal quotient,
    rounded to nearest even -- the IEEE quotient numpy computes.

    The TPU's f32 divide is not correctly rounded (1 ulp off the numpy
    contract on real scores), so the 24-bit mantissas are divided exactly
    by restoring division in int32 -- 24 quotient bits plus a guard bit,
    the remainder as sticky bit -- and rounded by hand.  Integer ops and
    bitcasts only: identical inside a Pallas kernel and in plain XLA.
    """
    nb = jax.lax.bitcast_convert_type(num, jnp.int32)
    db = jax.lax.bitcast_convert_type(den, jnp.int32)
    mn = (nb & 0x7FFFFF) | 0x800000
    md = (db & 0x7FFFFF) | 0x800000
    exp = ((nb >> 23) & 0xFF) - ((db >> 23) & 0xFF) + 127
    below = mn < md  # quotient mantissa < 1: take one more dividend bit
    rem = jnp.where(below, mn << 1, mn)
    exp = jnp.where(below, exp - 1, exp)
    q = jnp.zeros_like(rem)
    for _ in range(25):
        bit = (rem >= md).astype(jnp.int32)
        q = (q << 1) | bit
        rem = (rem - bit * md) << 1
    mant = q >> 1
    mant = mant + ((q & 1) & (jnp.where(rem != 0, 1, 0) | (mant & 1)))
    carry = mant >> 24  # rounding carried into a 25th bit
    bits = ((exp + carry) << 23) | ((mant >> carry) & 0x7FFFFF)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _gather_table(table_f32, norm_i32):
    """k_hat[r, i] = table[norm[r, i]]: [BM,256] f32 table (rows identical)
    + [BM,128] i32 codes -> [BM,128] f32, bit-exact (module docstring)."""
    hi = table_f32.astype(jnp.bfloat16)
    r1 = table_f32 - hi.astype(jnp.float32)
    mid = r1.astype(jnp.bfloat16)
    lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (NORM_LEVELS, BLOCK_VALS), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (BM, BLOCK_VALS), 0)
    k_hat = jnp.zeros((BM, BLOCK_VALS), jnp.float32)
    for r in range(BM):
        sel = (c_iota == norm_i32[r : r + 1, :]).astype(jnp.bfloat16)
        parts = [
            jnp.dot(t, sel, preferred_element_type=jnp.float32)
            for t in (hi, mid, lo)
        ]
        k_hat = jnp.where(row == r, (parts[0] + parts[1]) + parts[2], k_hat)
    return k_hat


def _score_tile(flens, fdata, norm_i32, table_f32, fmeta):
    """[BM,128] freq tile + norm codes + [BM,256] table -> [BM,128] scores."""
    tf = (_decode_tile(flens, fdata) + 1).astype(jnp.float32)
    k1p1 = fmeta[:, FMETA_K1P1 : FMETA_K1P1 + 1]
    idf_t = fmeta[:, FMETA_IDF : FMETA_IDF + 1]
    k_hat = _gather_table(table_f32, norm_i32)
    return idf_t * div_rn(tf * k1p1, tf + k_hat)


def _score_kernel(flens_ref, fdata_ref, norm_ref, table_ref, fmeta_ref,
                  out_ref):
    out_ref[...] = _score_tile(
        flens_ref[...], fdata_ref[...], norm_ref[...], table_ref[...],
        fmeta_ref[...],
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def bm25_score_blocks(
    flens: jnp.ndarray, fdata: jnp.ndarray, norms: jnp.ndarray,
    table: jnp.ndarray, fmeta: jnp.ndarray, interpret: bool = True,
):
    """All-lane BM25 scores of gathered freq rows.

    flens: [nr, 128] int32; fdata: [nr, 512] uint8 (freq blocks, tf - 1);
    norms: [nr, 128] int32 (8-bit codes widened); table: [BM, 256] float32
    (the norm dequant table, broadcast over sublanes); fmeta: [nr, 128]
    float32 carrying FMETA_* lanes per row.  Returns [nr, 128] float32
    scores; padding lanes score garbage -- callers mask with ``lane_valid``.
    """
    nr = flens.shape[0]
    assert nr % BM == 0, f"rows must be a multiple of {BM}"
    grid = (nr // BM,)
    return pl.pallas_call(
        _score_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0)),
            pl.BlockSpec((BM, BLOCK_BYTES), lambda i: (i, 0)),
            pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0)),
            pl.BlockSpec((BM, NORM_LEVELS), lambda i: (0, 0)),
            pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nr, BLOCK_VALS), jnp.float32),
        interpret=interpret,
    )(flens, fdata, norms, table, fmeta)


def _score_probe_kernel(
    lens_ref, data_ref, flens_ref, fdata_ref, norm_ref, table_ref, meta_ref,
    fmeta_ref, out_ref,
):
    gaps = _decode_tile(lens_ref[...], data_ref[...])
    base = meta_ref[:, META_BASE : META_BASE + 1]
    probe = meta_ref[:, META_PROBE : META_PROBE + 1]
    vals = base + lane_cumsum(gaps + 1)
    scores = _score_tile(
        flens_ref[...], fdata_ref[...], norm_ref[...], table_ref[...],
        fmeta_ref[...],
    )
    # docIDs are strictly increasing within the row: at most one lane matches
    contrib = jnp.sum(
        jnp.where(vals == probe, scores, jnp.float32(0.0)),
        axis=1, keepdims=True,
    )
    lane = jax.lax.broadcasted_iota(jnp.int32, (BM, BLOCK_VALS), 1)
    out_ref[...] = jnp.where(lane == 0, contrib, jnp.float32(0.0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def bm25_score_probe_blocks(
    lens: jnp.ndarray, data: jnp.ndarray, flens: jnp.ndarray,
    fdata: jnp.ndarray, norms: jnp.ndarray, table: jnp.ndarray,
    meta: jnp.ndarray, fmeta: jnp.ndarray, interpret: bool = True,
):
    """Fused decode(docIDs + freqs) + BM25 + probe match over gathered rows.

    lens/data: the docID blocks of the gathered rows; flens/fdata their
    parallel freq blocks; norms their [nr, 128] int32 norm codes; table the
    [BM, 256] float32 norm dequant table; meta the int32 tile of
    ``decode_search_blocks`` (lane META_BASE = block_base, lane META_PROBE =
    probe); fmeta the float32 FMETA_* tile.

    Returns [nr, 128] float32: lane 0 = the BM25 contribution of the row's
    lane whose docID equals the probe, 0.0 when the probe is absent from the
    row.  Callers locate rows with ``block_keys`` exactly as for NextGEQ, so
    a probe <= the row's endpoint either matches a real lane or misses;
    padding lanes ascend past the endpoint and can never match.
    """
    nr = lens.shape[0]
    assert nr % BM == 0, f"rows must be a multiple of {BM}"
    grid = (nr // BM,)
    spec_v = pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0))
    spec_b = pl.BlockSpec((BM, BLOCK_BYTES), lambda i: (i, 0))
    spec_t = pl.BlockSpec((BM, NORM_LEVELS), lambda i: (0, 0))
    return pl.pallas_call(
        _score_probe_kernel,
        grid=grid,
        in_specs=[spec_v, spec_b, spec_v, spec_b, spec_v, spec_t, spec_v,
                  spec_v],
        out_specs=spec_v,
        out_shape=jax.ShapeDtypeStruct((nr, BLOCK_VALS), jnp.float32),
        interpret=interpret,
    )(lens, data, flens, fdata, norms, table, meta, fmeta)
