"""Pallas TPU kernels: block Stream-VByte decode, plain and fused-with-search.

TPU adaptation of Masked-VByte / Stream-VByte (DESIGN.md §3): the x86 decoder
uses PSHUFB byte shuffles; TPUs have no byte-shuffle unit, so the
variable-length gather is re-expressed as a ONE-HOT MATMUL on the MXU:

    byte_j(i) = sum_d  data[d + j] * [d == start(i)]

with ``start`` the in-block exclusive prefix sum of the 2-bit lengths.  Per
row of a tile, one 2-D matmul of the four byte-shifted copies of the data
tile against the row's [512, 128] one-hot gathers all four bytes of every
value; shift-or then reconstructs the integers.  The operands are bytes and
0/1 selectors, exact in bf16, and every output sums ONE nonzero product, so
the gather is exact at the MXU's native precision.

Layout (produced by ops.pack_blocks): 128 values/block, data padded to 512
bytes/block, so each grid step streams an (BM, 512) uint8 tile and an
(BM, 128) int32 lens tile through VMEM (~5 KB/block -- far below VMEM).

Two kernels share the decode tile:

  * ``decode_blocks``       -- decode to values in HBM (the PR-1 path).
  * ``decode_search_blocks``-- the FUSED query kernel (DESIGN.md §4): decode
    a tile of gathered blocks, rebuild absolute docIDs in-register
    (``block_base + cumsum(gap+1)``), compare against each row's probe and
    emit only (next_geq_value, in_block_rank) per row.  Decoded values never
    touch HBM; the output is 2 useful lanes per 128-value block.

Every kernel of the package is written in the subset Mosaic (the TPU
compiler behind Pallas) lowers: uint8 tiles widen through int32 before any
float cast, prefix sums are the shift-add scan ``lane_cumsum``, and one-hot
gathers are 2-D matmuls, one per tile row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_VALS = 128
BLOCK_BYTES = 512
BM = 8  # blocks per grid step: (8, 512) u8 + (8, 128) i32 tiles

# decode_search_blocks meta lanes: [:, META_BASE] = block_base of the row,
# [:, META_PROBE] = probe; remaining lanes ignored (kept 128-wide for tiling)
META_BASE = 0
META_PROBE = 1
_I32_MAX = 2**31 - 1  # python int: jnp constants would be captured by pallas


def lane_cumsum(x):
    """Inclusive prefix sum of a 2-D int32 tile along its lanes.

    A log-step shift-add scan over lane rotations: Mosaic has no lowering
    for ``cumsum``.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = 1
    while s < x.shape[1]:
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0)
        s *= 2
    return x


def _decode_tile(lens, data):
    """[BM,128] i32 lens + [BM,512] u8 bytes -> [BM,128] i32 values."""
    starts = lane_cumsum(lens) - lens  # [BM, 128]
    d = data.astype(jnp.int32)
    # byte j of value i sits at data[start(i) + j]: stack the tile shifted
    # left by j lanes (j = 0..3) so one matmul per row gathers all 4 bytes
    shifted = jnp.concatenate(
        [d] + [pltpu.roll(d, BLOCK_BYTES - j, 1) for j in range(1, 4)], axis=0
    ).astype(jnp.float32).astype(jnp.bfloat16)  # [4*BM, 512]
    d_iota = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_BYTES, BLOCK_VALS), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (BM, BLOCK_VALS), 0)
    out = jnp.zeros((BM, BLOCK_VALS), jnp.int32)
    for r in range(BM):
        sel = (d_iota == starts[r : r + 1, :]).astype(jnp.bfloat16)
        # MXU gather: [4*BM, 512] @ [512, 128]; row j*BM + r holds byte j
        got = jnp.dot(
            shifted, sel, preferred_element_type=jnp.float32
        ).astype(jnp.int32)
        val = jnp.zeros((BM, BLOCK_VALS), jnp.int32)
        for j in range(4):
            byte = got[j * BM : (j + 1) * BM]
            val = val | jnp.where(lens > j, byte << (8 * j), 0)
        out = jnp.where(row == r, val, out)
    return out


def _decode_kernel(lens_ref, data_ref, out_ref):
    out_ref[...] = _decode_tile(lens_ref[...], data_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_blocks(lens: jnp.ndarray, data: jnp.ndarray, interpret: bool = True):
    """lens: [nb, 128] int32; data: [nb, 512] uint8 -> [nb, 128] int32."""
    nb = lens.shape[0]
    assert nb % BM == 0, f"nb must be a multiple of {BM}"
    grid = (nb // BM,)
    return pl.pallas_call(
        _decode_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0)),
            pl.BlockSpec((BM, BLOCK_BYTES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, BLOCK_VALS), jnp.int32),
        interpret=interpret,
    )(lens, data)


def _search_kernel(lens_ref, data_ref, meta_ref, out_ref):
    gaps = _decode_tile(lens_ref[...], data_ref[...])
    base = meta_ref[:, META_BASE : META_BASE + 1]    # [BM, 1]
    probe = meta_ref[:, META_PROBE : META_PROBE + 1]  # [BM, 1]
    # absolute docIDs of the row, ascending (padding lanes keep ascending)
    vals = base + lane_cumsum(gaps + 1)
    below = vals < probe
    value = jnp.min(
        jnp.where(below, _I32_MAX, vals), axis=1, keepdims=True
    )
    rank = jnp.sum(below.astype(jnp.int32), axis=1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, (BM, BLOCK_VALS), 1)
    out_ref[...] = jnp.where(
        lane == 0, value, jnp.where(lane == 1, rank, 0)
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_search_blocks(
    lens: jnp.ndarray, data: jnp.ndarray, meta: jnp.ndarray,
    interpret: bool = True,
):
    """Fused decode + in-register NextGEQ over gathered block rows.

    lens: [nr, 128] int32; data: [nr, 512] uint8 -- one GATHERED arena row
    per cursor (the block ``locate`` found).  meta: [nr, 128] int32 carrying
    per row: lane META_BASE = block_base, lane META_PROBE = probe.

    Returns [nr, 128] int32: lane 0 = smallest value >= probe within the row
    (2^31-1 if none), lane 1 = count of row values < probe (0..128).  The
    caller guarantees probe <= the row's partition endpoint, so lane 0 is
    always a real (non-padding) value and lane 1 a real rank.
    """
    nr = lens.shape[0]
    assert nr % BM == 0, f"rows must be a multiple of {BM}"
    grid = (nr // BM,)
    return pl.pallas_call(
        _search_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0)),
            pl.BlockSpec((BM, BLOCK_BYTES), lambda i: (i, 0)),
            pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nr, BLOCK_VALS), jnp.int32),
        interpret=interpret,
    )(lens, data, meta)
