"""Pallas TPU kernel: Block-Max WAND pivot selection over bound tiles (§9).

Third kernel family over the block arena.  The ranked sidecar stores one
u8 quantized score upper bound per block (``block_max_q``); Block-Max
WAND/MaxScore pruning asks, per (query, term) and against the current
threshold theta, WHICH blocks of the term's posting list can still hold a
top-k document.  Until this kernel that question ran on the host, block by
block, against the decoded flat mirror, and every pruning round synced the
device.

The kernel answers it entirely in-register.  The host reduces the float
admissibility envelope -- theta, the per-term multiplicities, the
range-aligned co-candidate bounds, and the proportional-share floor -- to
ONE u8 code per BLOCK (the minimal admissible bound code ``qmin``; see
``ops.qmin_for``, computed in float64 so the integer test below is exactly
the host's float test), and the kernel then, per gathered chunk row of up
to 128 consecutive blocks:

  * keeps the lanes (blocks) with ``block_max_q >= qmin[lane]``,
  * COMPACTS the kept lane indices to the front of the row (the candidate
    block list) with the same one-hot MXU matmul trick as the decoders: an
    inclusive scan of the keep mask counts the kept lanes up to each lane,
    and slot s receives the number of lanes whose count is <= s -- the
    lane of the (s+1)-th kept block -- with no per-lane control flow,
  * emits the WAND pivot lane (lowest lane attaining the max surviving
    bound) and that max bound code.

Everything is int32 arithmetic plus 0/1 matmuls whose sums are <= 128
(exact in bf16 operands with f32 accumulation), so all three backends (this kernel, the jnp ref, the numpy
mirror) are bit-identical by construction -- no FMA/rounding hazards.

Layout mirrors ``bm25_score``: the qmin codes ride a full [nr, 128] int32
tile (one code per lane, parallel to the bound tile -- broadcasting a new
theta to the device is re-staging these integer tiles), per-row scalars
ride an int32 meta tile, and the outputs are two [nr, 128] int32 tiles
(the compacted lane list, -1 padded, and an aux tile with count/pivot/maxq
in its first lanes), all kept 128-wide for tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vbyte_decode.kernel import BLOCK_VALS, BM, lane_cumsum

# int32 meta lanes (per gathered chunk row)
PMETA_NBLK = 0  # number of valid lanes (blocks) in the chunk

# aux output lanes (per row)
AUX_COUNT = 0  # how many blocks survived
AUX_PIVOT = 1  # pivot lane: lowest lane with the max surviving bound (-1)
AUX_MAXQ = 2  # that max surviving bound code (-1 when none survived)

# block_max_q is u8, so 256 is one past every representable bound code:
# qmin == QMIN_NONE prunes the lane unconditionally
QMIN_NONE = 256

_I32_MAX = 2**31 - 1  # python int: jnp constants would be captured by pallas


def _pivot_tile(qb, qmin, nblk):
    """[BM,128] i32 bound + qmin tiles, per-row nblk -> pivot selection.

    Returns (compact [BM,128], count [BM,1], pivot [BM,1], maxq [BM,1]):
    compact holds the kept lane indices ascending, -1 past the count.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (BM, BLOCK_VALS), 1)
    keep = (qb >= qmin) & (lane < nblk)
    keep_i = keep.astype(jnp.int32)
    count = jnp.sum(keep_i, axis=1, keepdims=True)
    kept_upto = lane_cumsum(keep_i)  # kept lanes in [0, l]
    # one-hot MXU compaction, one row at a time: ones @ [slot, lane] 0/1
    # with the lane axis contracted counts, per slot s, the lanes whose
    # kept count is <= s; sums <= 128, exact in bf16 with f32 accumulation
    slot = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_VALS, BLOCK_VALS), 0)
    ones = jnp.ones((BM, BLOCK_VALS), jnp.bfloat16)
    row = jax.lax.broadcasted_iota(jnp.int32, (BM, BLOCK_VALS), 0)
    compact = jnp.zeros((BM, BLOCK_VALS), jnp.int32)
    for r in range(BM):
        below = (kept_upto[r : r + 1, :] <= slot).astype(jnp.bfloat16)
        got = jax.lax.dot_general(
            ones, below, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)
        compact = jnp.where(row == r, got, compact)
    compact = jnp.where(lane < count, compact, -1)
    maxq = jnp.max(jnp.where(keep, qb, -1), axis=1, keepdims=True)
    pivot = jnp.min(
        jnp.where(keep & (qb == maxq), lane, _I32_MAX), axis=1, keepdims=True
    )
    pivot = jnp.where(count > 0, pivot, -1)
    return compact, count, pivot, maxq


def _pivot_kernel(qb_ref, qmin_ref, meta_ref, out_ref, aux_ref):
    nblk = meta_ref[:, PMETA_NBLK : PMETA_NBLK + 1]
    compact, count, pivot, maxq = _pivot_tile(qb_ref[...], qmin_ref[...], nblk)
    out_ref[...] = compact
    lane = jax.lax.broadcasted_iota(jnp.int32, (BM, BLOCK_VALS), 1)
    aux_ref[...] = jnp.where(
        lane == AUX_COUNT,
        count,
        jnp.where(lane == AUX_PIVOT, pivot, jnp.where(lane == AUX_MAXQ, maxq, 0)),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def pivot_select_blocks(
    qb: jnp.ndarray, qmin: jnp.ndarray, meta: jnp.ndarray, interpret: bool = True
):
    """Fused keep-test + compaction + pivot over gathered bound chunks.

    qb: [nr, 128] int32 -- ``block_max_q`` of up to 128 consecutive blocks
    per row (one gathered chunk of one (query, term); garbage past the
    row's PMETA_NBLK lanes).  qmin: [nr, 128] int32 -- the minimal
    admissible bound code per lane (QMIN_NONE prunes a lane outright).
    meta: [nr, 128] int32 carrying per row: lane PMETA_NBLK = the number
    of valid lanes.

    Returns (out, aux), both [nr, 128] int32.  ``out`` lists the kept lane
    indices compacted ascending (-1 past the count); ``aux`` lane AUX_COUNT
    = kept count, lane AUX_PIVOT = the WAND pivot lane (lowest lane with
    the maximal surviving bound; -1 when nothing survived), lane AUX_MAXQ =
    that maximal bound code (-1 when nothing survived).
    """
    nr = qb.shape[0]
    assert nr % BM == 0, f"rows must be a multiple of {BM}"
    grid = (nr // BM,)
    spec_v = pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0))
    return pl.pallas_call(
        _pivot_kernel,
        grid=grid,
        in_specs=[spec_v, spec_v, spec_v],
        out_specs=[spec_v, spec_v],
        out_shape=[
            jax.ShapeDtypeStruct((nr, BLOCK_VALS), jnp.int32),
            jax.ShapeDtypeStruct((nr, BLOCK_VALS), jnp.int32),
        ],
        interpret=interpret,
    )(qb, qmin, meta)
